"""Batched serving driver: prefill a batch of prompts, decode N tokens.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --smoke \
        --batch 4 --prompt-len 64 --gen 16

Timing protocol: the prefill and the decode step are jitted and
AOT-compiled *before* the clock starts (the same
``lower().compile()`` pattern as ``launch/train.py``), and prefill and
decode throughput are reported separately — a single end-to-end figure
with compilation inside the window mostly measures XLA, not the model.

``--continuous N`` drives ``serve.ContinuousBatcher`` instead: N requests
through ``--batch`` cache slots with admissions between decode steps.
Adding ``--disaggregated`` swaps in ``serve.DisaggregatedBatcher`` — the
prefill front-end feeds the decode loop via cache-row handoffs (token
outputs are identical; the prefill/handoff counters are printed).  A
measured decode run can feed the calibration decode-bandwidth table via
``calibration.measured_decode_eff`` (printed for the local device).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch, smoke_config
from repro.core import calibration, memtrace
from repro.core.devices import DEVICE_TYPES
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_params
from repro.serve import (ContinuousBatcher, DisaggregatedBatcher,
                         ServeRequest, prefill, serve_step)


def _build_compiled(cfg, params, prompt, cache_len):
    """Jit + AOT-compile the prefill and decode-step executables (warm-up
    happens here, outside any timing window)."""
    batch_map = {"tokens": prompt}
    if cfg.num_modal_tokens:
        b = prompt.shape[0]
        batch_map["modal_embeds"] = jnp.zeros(
            (b, cfg.num_modal_tokens, cfg.d_model), jnp.bfloat16)
    prefill_jit = jax.jit(lambda p, bm: prefill(cfg, p, bm, cache_len))
    prefill_c = prefill_jit.lower(params, batch_map).compile()
    logits, cache = prefill_c(params, batch_map)
    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    decode_jit = jax.jit(
        lambda p, t, c, pos: serve_step(cfg, p, t, c, pos))
    pos0 = jnp.int32(prompt.shape[1] + cfg.num_modal_tokens)
    decode_c = decode_jit.lower(params, tok, cache, pos0).compile()
    return batch_map, prefill_c, decode_c


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="prompt batch (or cache slots with --continuous)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--continuous", type=int, default=0, metavar="N",
                    help="serve N requests through the continuous batcher")
    ap.add_argument("--disaggregated", action="store_true",
                    help="with --continuous: split prefill front-end from"
                         " the decode loop (DisaggregatedBatcher)")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    cache_len = args.prompt_len + cfg.num_modal_tokens + args.gen

    if args.continuous:
        prompts = jax.random.randint(
            key, (args.continuous, args.prompt_len), 0, cfg.vocab_size,
            jnp.int32)
        batcher_cls = (DisaggregatedBatcher if args.disaggregated
                       else ContinuousBatcher)
        cb = batcher_cls(cfg, params, slots=args.batch, cache_len=cache_len)
        cb.submit(ServeRequest(0, prompts[0], args.gen))
        cb.step()                           # warm-up: compile prefill+decode
        t0 = time.time()
        for i in range(1, args.continuous):
            cb.submit(ServeRequest(i, prompts[i], args.gen))
        out = cb.run()
        dt = time.time() - t0
        n_tok = sum(len(v) for v in out.values())
        mode = "disaggregated" if args.disaggregated else "continuous"
        print(f"arch={cfg.name} {mode}: {len(out)} requests,"
              f" {n_tok} tokens via {cb.decode_steps} steps x"
              f" {args.batch} slots in {dt:.2f}s ({n_tok / dt:.1f} tok/s)")
        if args.disaggregated:
            print(f"prefill front-end: {cb.prefills} prefills,"
                  f" {cb.handoffs} cache-row handoffs to the decode loop")
        print("sample:", out[0][:12])
        return out

    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size, jnp.int32)
    batch_map, prefill_c, decode_c = _build_compiled(cfg, params, prompt,
                                                     cache_len)
    t0 = time.time()
    logits, cache = prefill_c(params, batch_map)
    logits.block_until_ready()
    dt_prefill = time.time() - t0
    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    toks = [tok]
    pos = prompt.shape[1] + cfg.num_modal_tokens
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, cache = decode_c(params, tok, cache, jnp.int32(pos + i))
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        toks.append(tok)
    tok.block_until_ready()
    dt_decode = time.time() - t0
    toks = jnp.concatenate(toks, axis=1)

    prefill_tok_s = args.batch * args.prompt_len / max(dt_prefill, 1e-9)
    decode_tok_s = args.batch * max(args.gen - 1, 1) / max(dt_decode, 1e-9)
    print(f"arch={cfg.name} generated {toks.shape}: prefill"
          f" {args.batch}x{args.prompt_len} in {dt_prefill:.3f}s"
          f" ({prefill_tok_s:.1f} tok/s), decode {args.gen - 1} steps in"
          f" {dt_decode:.3f}s ({decode_tok_s:.1f} tok/s)")
    dt_name = memtrace.device_type_for(jax.devices()[0].device_kind)
    if dt_name == memtrace.ANY_DEVICE:
        print(f"device kind {jax.devices()[0].device_kind!r} is not in the"
              f" catalog: no calibration entry derived")
    else:
        dev = DEVICE_TYPES[dt_name]
        eff = calibration.measured_decode_eff(
            decode_tok_s, cfg, args.batch, cache_len, 1, 1, dev)
        print(f"decode-bandwidth efficiency {eff:.3f} of {dt_name}"
              f" peak (calibration.enable_decode table entry)")
        pf_eff = calibration.measured_prefill_eff(prefill_tok_s, cfg, 1, dev)
        print(f"prefill MFU {pf_eff:.3f} of {dt_name} peak"
              f" (prefill-pool rate model input)")
    print("sample:", toks[0, :12].tolist())
    return toks


if __name__ == "__main__":
    main()
