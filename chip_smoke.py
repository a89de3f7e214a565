"""Bring-up smoke: the train and serve paths on one TPU chip at full width.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one host with a 2x2 mesh of chips

With no option it runs, in this one process and in order:

  a. device check: the platform is a TPU the planning catalog knows, and
     every dispatched op resolves to its Pallas kernel;
  b. kernel check: each Pallas kernel once at real model widths, against
     its jnp reference;
  c. train: ``repro.launch.train`` on gpt2-350m (24 layers, d_model 1024,
     batch 8 x seq 1024, ZeRO-1) for 20 steps, with MARP's predicted peak
     bytes beside the device's measured peak;
  d. serve: ``repro.launch.serve`` on llama3.2-3b (28 layers, d_model
     3072, GQA 24/8): prefill a 4 x 128 prompt batch, decode 16 tokens.

``--four-chips`` runs only the mesh comparison: gpt2-350m at the same
global batch and seed on a 2x2 (data x model) mesh and on a one-chip mesh,
and the two loss series must agree.

Weights are random from a fixed seed.  The last line of standard output is
one JSON object, ``{"ok": true, "device": {...}}``; any failure exits
non-zero before it.  No child process is started: a process that has
touched JAX holds the chip.  ``JAX_COMPILATION_CACHE_DIR`` places the
compile cache (default: ``.jax_cache`` in the checkout).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import TrainConfig  # noqa: E402
from repro.configs.registry import get_arch  # noqa: E402
from repro.core import memory_model as mm  # noqa: E402
from repro.core import memtrace  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.kernels.adam_update import adam_ref, adam_update_fused  # noqa: E402
from repro.kernels.flash_attention import attention_ref, flash_attention  # noqa: E402
from repro.kernels.flash_decode import (flash_decode_gqa,  # noqa: E402
                                        flash_decode_mla, ref as fd_ref)
from repro.kernels.ssd_scan import ssd_ref, ssd_scan  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_plan_mesh  # noqa: E402

#: bf16 kernel-vs-ref tolerance (atol = rtol), as in tests/test_dispatch.py
TOL = 2e-2
TRAIN_ARGV = ["--arch", "gpt2-350m", "--batch", "8", "--seq", "1024",
              "--steps", "20", "--log-every", "1"]
SERVE_ARGV = ["--arch", "llama3.2-3b", "--batch", "4", "--prompt-len", "128",
              "--gen", "16"]
#: the 2x2-vs-one-chip comparison: steps run on each mesh, and the largest
#: loss difference allowed between them (the meshes reduce in different
#: orders in bf16, so the series agree to rounding, not bit for bit)
MESH_STEPS = 6
MESH_LOSS_TOL = 0.05


class SmokeFailure(RuntimeError):
    pass


def check_device(min_count: int) -> dict:
    """Phase a.  Returns the device record for the last line."""
    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind!r}"
          f" count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX found platform {d0.platform!r}")
    dev_type = memtrace.device_type_for(d0.device_kind)
    print(f"catalog device type: {dev_type}")
    if dev_type == memtrace.ANY_DEVICE:
        raise SmokeFailure(f"device kind {d0.device_kind!r} is not in the"
                           f" planning catalog")
    if len(devs) < min_count:
        raise SmokeFailure(f"need {min_count} chips, found {len(devs)}")
    if os.environ.get(dispatch.ENV_VAR):
        raise SmokeFailure(f"{dispatch.ENV_VAR} is set; the smoke checks"
                           f" the default resolution")
    for op in dispatch.ops():
        impl = dispatch.resolve(op)[0]
        print(f"dispatch.resolve({op!r}) -> {impl}")
        if impl != "pallas":
            raise SmokeFailure(f"{op} resolves to {impl}, not pallas")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def _compare(name: str, got, want) -> bool:
    failed = []
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        if g.shape != w.shape:
            failed.append(f"shape {g.shape} != {w.shape}")
            continue
        diff = np.abs(g - w)
        print(f"kernel {name}: shape {g.shape} max|kernel-ref|"
              f" {float(diff.max())!r}", flush=True)
        if not np.isfinite(g).all():
            failed.append("non-finite output")
        elif not (diff <= TOL + TOL * np.abs(w)).all():
            failed.append(f"max diff {float(diff.max())!r} above tolerance")
    for f in failed:
        print(f"kernel {name}: FAILED: {f}", flush=True)
    return not failed


def check_kernels() -> bool:
    """Phase b: every Pallas kernel at real widths against its ref.  Each
    ref takes the kernel's bf16 inputs upcast to f32 and runs at the
    highest matmul precision, so that it is the reference and not a second
    bf16 approximation."""
    key = iter(jax.random.split(jax.random.PRNGKey(0), 64))
    bf = jnp.bfloat16

    def normal(shape, dtype=bf, scale=1.0):
        return (jax.random.normal(next(key), shape, jnp.float32)
                * scale).astype(dtype)

    def ref(fn, *args, **kw):
        args = [a.astype(jnp.float32) if a.dtype == bf else a for a in args]
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *a: fn(*a, **kw))(*args)

    ok = True
    # flash attention: gpt2-350m train shape, llama3.2-3b prefill shape
    for name, qs, ks in [("flash_attention gpt2-350m", (4, 1024, 16, 64),
                          (4, 1024, 16, 64)),
                         ("flash_attention llama3.2-3b", (4, 128, 24, 128),
                          (4, 128, 8, 128))]:
        q, k, v = normal(qs), normal(ks), normal(ks)
        got = jax.jit(flash_attention)(q, k, v)
        ok &= _compare(name, got, ref(attention_ref, q, k, v))
    # split-KV decode: llama3.2-3b GQA and deepseek-v2 MLA latent, at the
    # serve smoke's 144-row cache (not a block multiple) and at 2048
    for S in (144, 2048):
        q = normal((4, 1, 24, 128))
        kc, vc = normal((4, S, 8, 128)), normal((4, S, 8, 128))
        valid = jnp.arange(S)[None, :] < jnp.array([[S], [S - 7], [100],
                                                     [1]])
        got = jax.jit(flash_decode_gqa)(q, kc, vc, valid)
        ok &= _compare(f"flash_decode_gqa llama3.2-3b S={S}", got,
                       ref(fd_ref.gqa_decode_ref, q, kc, vc, valid))
        args = (normal((4, 128, 512)), normal((4, 128, 64)),
                normal((4, S, 512)), normal((4, S, 64)), valid)
        denom = (128 + 64) ** 0.5
        got = jax.jit(lambda *a: flash_decode_mla(*a, denom=denom))(*args)
        ok &= _compare(f"flash_decode_mla deepseek-v2 S={S}", got,
                       ref(fd_ref.mla_decode_ref, *args, denom=denom))
    # SSD scan: mamba2-130m (24 heads of 64, state 128), batch 4 x seq 1024
    b, s, h, p, n = 4, 1024, 24, 64, 128
    x, dt_raw = normal((b, s, h, p)), normal((b, s, h), scale=0.5)
    A_log = normal((h,), jnp.float32, 0.3)
    B, C = normal((b, s, n)), normal((b, s, n))
    D, dtb = normal((h,), jnp.float32), jnp.full((h,), 0.1, jnp.float32)
    got = jax.jit(ssd_scan)(x, dt_raw, A_log, B, C, D, dtb)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dtb)
    ok &= _compare("ssd_scan mamba2-130m", got,
                   ref(ssd_ref, x, dt, -jnp.exp(A_log), B, C, D))
    # fused Adam over 2^24 fp32 parameters
    g, m, mp = (normal((1 << 24,), jnp.float32) for _ in range(3))
    v = jnp.abs(normal((1 << 24,), jnp.float32)) * 0.01
    kw = dict(lr=1e-3, beta1=0.9, beta2=0.95, eps=1e-8, wd=0.1, c1=0.5,
              c2=0.2)
    got = jax.jit(lambda *a: adam_update_fused(*a, **kw))(g, m, v, mp)
    ok &= _compare("adam_update_fused 2^24", got, ref(adam_ref, g, m, v, mp,
                                                      **kw))
    return bool(ok)


def run_train() -> bool:
    """Phase c: the train entry point at full gpt2-350m width."""
    losses = train.main(TRAIN_ARGV)
    print("train losses:", json.dumps([float(x) for x in losses]))
    ok = len(losses) == 20 and bool(np.isfinite(losses).all())
    cfg = get_arch("gpt2-350m")
    pred = mm.exact_peak_bytes(cfg, 8, 1024, 1, 1, zero=1, microbatch=0)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"train peak bytes: device peak_bytes_in_use {peak!r},"
          f" MARP exact_peak_bytes {pred!r}", flush=True)
    return ok


def run_serve() -> bool:
    """Phase d: the serve entry point at full llama3.2-3b width."""
    toks = serve.main(SERVE_ARGV)
    vocab = get_arch("llama3.2-3b").vocab_size
    ok = (toks.shape == (4, 16) and bool((toks >= 0).all())
          and bool((toks < vocab).all()))
    print(f"serve tokens: shape {tuple(toks.shape)} in-vocab {ok}")
    return ok


def compare_meshes() -> bool:
    """--four-chips: gpt2-350m on a 2x2 mesh and on one chip, same global
    batch and seed, in this process."""
    cfg = get_arch("gpt2-350m")
    tc = TrainConfig(global_batch=8, seq_len=1024, learning_rate=3e-4,
                     steps=MESH_STEPS, warmup_steps=1, zero=1)
    series = {}
    for name, mesh in [("2x2", make_plan_mesh(2, 2)),
                       ("1x1", make_plan_mesh(1, 1, jax.devices()[:1]))]:
        print(f"mesh {name}: {dict(mesh.shape)} on devices"
              f" {[d.id for d in mesh.devices.flat]}", flush=True)
        series[name], _ = train.run(cfg, tc, mesh, log_every=1)
        print(f"mesh {name} losses:", json.dumps(series[name]), flush=True)
        gc.collect()
    diff = max(abs(a - b) for a, b in zip(series["2x2"], series["1x1"]))
    print(f"mesh loss max|2x2-1x1| over {MESH_STEPS} steps {diff!r}"
          f" (tolerance {MESH_LOSS_TOL})")
    return bool(np.isfinite(series["2x2"]).all()) and diff <= MESH_LOSS_TOL


def _phase(name: str, fn) -> bool:
    print(f"=== phase {name}", flush=True)
    t0 = time.time()
    try:
        ok = fn()
    except Exception:  # noqa: BLE001 — reported, and fails the run below
        traceback.print_exc()
        ok = False
    print(f"=== phase {name}: {'ok' if ok else 'FAILED'}"
          f" ({time.time() - t0:.1f}s wall)", flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 2x2-mesh vs one-chip loss comparison")
    args = ap.parse_args(argv)
    use_compile_cache()
    try:
        device = check_device(4 if args.four_chips else 1)
    except SmokeFailure as e:
        print(f"device check failed: {e}", file=sys.stderr)
        return 1
    if args.four_chips:
        phases = [("mesh 2x2 vs 1x1", compare_meshes)]
    else:
        phases = [("kernels", check_kernels), ("train", run_train),
                  ("serve", run_serve)]
    results = [_phase(name, fn) for name, fn in phases]
    if not all(results):
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
