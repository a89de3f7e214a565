"""Chip sweep of the flash attention forward kernel's (block_q, block_k).

    python benchmarks/attention_tiling.py [--shapes gpt2-350m ...] \
        [--blocks 128 256 512 1024] [--calls 8] [--out sweep.json]

For each model width and each (block_q, block_k) pair it compiles the
kernel, runs it ``--calls`` times under the JAX profiler, and reports the
kernel's device time a call (the summed durations of the
``flash_attention_fwd`` events on the device's ``XLA Ops`` line over the
calls) beside the host's wall time a call (transposes and padding
included).  Pairs the chip's compiler refuses are reported as such.  It
needs a TPU: on any other backend it exits with status 4.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention

#: (q shape, kv shape, window) of the callers' full-sequence attention:
#: the gpt2-350m train micro-batch, llama3.2-3b (GQA 24/8), an MLA width
#: (q/k carry the rope part) and starcoder2 on one chip (window 4096)
SHAPES = {
    "gpt2-350m": ((8, 1024, 16, 64), (8, 1024, 16, 64), 0),
    "llama3.2-3b": ((4, 1024, 24, 128), (4, 1024, 8, 128), 0),
    "mla-d192": ((2, 1024, 16, 192), (2, 1024, 16, 192), 0),
    "starcoder2-3b": ((1, 8192, 24, 128), (1, 8192, 2, 128), 4096),
}
KERNEL = "flash_attention_fwd"


def kernel_ns(trace_dir: str) -> int:
    """Summed device time of the kernel's events in the newest trace."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    total = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                total += sum(e.duration_ns for e in line.events
                             if e.name.lstrip("%").startswith(KERNEL))
    return total


def measure(qs, ks, window, bq, bk, calls, trace_root):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], qs, jnp.bfloat16)
    k = jax.random.normal(keys[1], ks, jnp.bfloat16)
    v = jax.random.normal(keys[2], ks, jnp.bfloat16)
    fn = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=bq, block_k=bk))
    try:
        fn(q, k, v).block_until_ready()             # compile and warm
    except Exception as e:                          # noqa: BLE001
        return {"error": str(e).splitlines()[0][:200]}
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(q, k, v)
    out.block_until_ready()
    wall = (time.perf_counter() - t0) / calls
    trace_dir = tempfile.mkdtemp(dir=trace_root)
    with jax.profiler.trace(trace_dir):
        for _ in range(calls):
            out = fn(q, k, v)
        out.block_until_ready()
    return {"kernel_ms": kernel_ns(trace_dir) / calls / 1e6,
            "wall_ms": wall * 1e3}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=sorted(SHAPES),
                    choices=sorted(SHAPES))
    ap.add_argument("--blocks", nargs="+", type=int,
                    default=[128, 256, 512, 1024])
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("attention_tiling: no TPU", file=sys.stderr)
        return 4
    dev = jax.devices()[0]
    rows = []
    with tempfile.TemporaryDirectory() as trace_root:
        for name in args.shapes:
            qs, ks, window = SHAPES[name]
            for bq in args.blocks:
                for bk in args.blocks:
                    r = measure(qs, ks, window, bq, bk, args.calls,
                                trace_root)
                    row = {"shape": name, "block_q": bq, "block_k": bk, **r}
                    rows.append(row)
                    print(json.dumps(row), flush=True)
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "rows": rows}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
