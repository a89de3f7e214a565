"""Chip sweep of the flash attention kernels' (block_q, block_k).

    python benchmarks/attention_tiling.py [--backward] \
        [--shapes gpt2-350m ...] [--blocks 128 256 512 1024] \
        [--pairs 1024x1024 512x1024 ...] [--calls 8] [--out sweep.json]

For each model width and each (block_q, block_k) pair it compiles the
forward kernel (or, with ``--backward``, the two backward kernels), runs it
``--calls`` times under the JAX profiler, and reports the kernels' device
time a call (the summed durations of their events on the device's ``XLA
Ops`` line over the calls) beside the host's wall time a call (transposes
and padding included).  ``--pairs`` gives the pairs instead of every
square of ``--blocks``.  With ``--backward`` each row also splits the time
by kernel and gives each gradient's largest error against the VJP of the
chunked ref taken in float32 (``grad_err``, over the gradient's largest
magnitude), beside the same error of the chunked ref's own bf16 VJP
(``ref_bf16_err``, once a width) and that VJP's wall time a call.  Pairs
the chip's compiler refuses are reported as such.  It needs a TPU: on any
other backend it exits with status 4.
"""
from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention, flash_attention_bwd
from repro.models.attention import chunked_attention

#: (q shape, kv shape, window) of the callers' full-sequence attention:
#: the gpt2-350m train micro-batch, llama3.2-3b (GQA 24/8), an MLA width
#: (q/k carry the rope part), starcoder2 on one chip (window 4096) and
#: starcoder2 on one chip of a model axis of 2 (12 query heads to 1)
SHAPES = {
    "gpt2-350m": ((8, 1024, 16, 64), (8, 1024, 16, 64), 0),
    "llama3.2-3b": ((4, 1024, 24, 128), (4, 1024, 8, 128), 0),
    "mla-d192": ((2, 1024, 16, 192), (2, 1024, 16, 192), 0),
    "starcoder2-3b": ((1, 8192, 24, 128), (1, 8192, 2, 128), 4096),
    "starcoder2-3b-tp2": ((1, 8192, 12, 128), (1, 8192, 1, 128), 4096),
}
FORWARD = ("flash_attention_fwd",)
BACKWARD = ("flash_attention_bwd_dq", "flash_attention_bwd_dkv")


def kernel_ns(trace_dir: str, kernels) -> dict:
    """Summed device time of each kernel's events in the newest trace."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    total = dict.fromkeys(kernels, 0)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                name = e.name.lstrip("%").split(" ")[0].split(".")[0]
                if name in total:
                    total[name] += e.duration_ns
    return total


def _timed(fn, args, calls, trace_root, kernels):
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    wall = (time.perf_counter() - t0) / calls
    trace_dir = tempfile.mkdtemp(dir=trace_root)
    with jax.profiler.trace(trace_dir):
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
    ns = kernel_ns(trace_dir, kernels)
    row = {"kernel_ms": sum(ns.values()) / calls / 1e6,
           "wall_ms": wall * 1e3}
    if len(kernels) > 1:
        row.update({f"{k}_ms": v / calls / 1e6 for k, v in ns.items()})
    return row


def _inputs(qs, ks):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(keys[0], qs, jnp.bfloat16),
            jax.random.normal(keys[1], ks, jnp.bfloat16),
            jax.random.normal(keys[2], ks, jnp.bfloat16),
            jax.random.normal(keys[3], qs, jnp.bfloat16))


def _rel_err(got, want) -> list[float]:
    return [float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                  / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30))
            for g, w in zip(got, want)]


def reference(qs, ks, window, calls):
    """The chunked ref's VJP in float32 (the yardstick), and the error and
    wall time a call of its bf16 VJP (the backward the kernels replace)."""
    q, k, v, do = _inputs(qs, ks)
    attn = functools.partial(chunked_attention, causal=True, window=window)

    @jax.jit
    def vjp(q, k, v, do):
        return jax.vjp(attn, q, k, v)[1](do)
    want = vjp(*(x.astype(jnp.float32) for x in (q, k, v, do)))
    got = vjp(q, k, v, do)
    t0 = time.perf_counter()
    for _ in range(calls):
        got = vjp(q, k, v, do)
    jax.block_until_ready(got)
    return want, {"ref_bf16_err": _rel_err(got, want),
                  "ref_bf16_wall_ms": (time.perf_counter() - t0) / calls
                  * 1e3}


def measure(qs, ks, window, bq, bk, calls, trace_root, want=None):
    q, k, v, do = _inputs(qs, ks)
    if want is None:
        fn = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, block_q=bq, block_k=bk))
        args, kernels = (q, k, v), FORWARD
    else:
        o = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window))(q, k, v)
        fn = jax.jit(lambda q, k, v, o, do: flash_attention_bwd(
            q, k, v, o, do, causal=True, window=window, block_q=bq,
            block_k=bk))
        args, kernels = (q, k, v, o, do), BACKWARD
    try:
        got = jax.block_until_ready(fn(*args))     # compile and warm
    except Exception as e:                          # noqa: BLE001
        return {"error": str(e).splitlines()[0][:200]}
    row = _timed(fn, args, calls, trace_root, kernels)
    if want is not None:
        row["grad_err"] = _rel_err(got, want)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backward", action="store_true")
    ap.add_argument("--shapes", nargs="+", default=sorted(SHAPES),
                    choices=sorted(SHAPES))
    ap.add_argument("--blocks", nargs="+", type=int,
                    default=[128, 256, 512, 1024])
    ap.add_argument("--pairs", nargs="+", default=None)
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("attention_tiling: no TPU", file=sys.stderr)
        return 4
    pairs = ([tuple(int(b) for b in p.split("x")) for p in args.pairs]
             if args.pairs else
             [(bq, bk) for bq in args.blocks for bk in args.blocks])
    dev = jax.devices()[0]
    rows = []
    with tempfile.TemporaryDirectory() as trace_root:
        for name in args.shapes:
            qs, ks, window = SHAPES[name]
            want = None
            if args.backward:
                want, ref = reference(qs, ks, window, args.calls)
                print(json.dumps({"shape": name, **ref}), flush=True)
                rows.append({"shape": name, **ref})
            for bq, bk in pairs:
                r = measure(qs, ks, window, bq, bk, args.calls, trace_root,
                            want)
                row = {"shape": name, "block_q": bq, "block_k": bk, **r}
                rows.append(row)
                print(json.dumps(row), flush=True)
            del want
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "rows": rows}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
