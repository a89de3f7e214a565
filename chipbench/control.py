"""Readings that set the upper ends of a cell's limits.  Not run by the
benchmark's own runs.

    python chipbench/control.py --workload <name> --seeds 1,2,3

For each seed, from the benchmark's weights and the seed's first batches,
at the cell's own sizes:

- ``control``: the reference computed with fp8 matrix products (the step
  below the configuration's bfloat16), compared with the float32
  reference as the program is;
- ``half_batch``: the reference with the second half of every batch left
  out (the mean taken over the rest), compared the same way.

Two faults read the same on every seed and need no run: a step that hands
back its state unchanged reads 1 on ``grad_gap`` and ``update_gap`` (no
first moment, no change), and an update doubled where the optimizer writes
it reads 1 on ``update_gap`` at that layer.

One JSON line per seed and reading goes to standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

import compare  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import weights  # noqa: E402
from traffic import TokenStream  # noqa: E402


def readings(cell: spec.Cell, seed: int, kinds=("control", "half_batch")):
    """``{kind: {number: (value, where)}}`` for one seed."""
    m, t = dict(cell.config["model"]), cell.traffic
    cfg = harness.ModelConfig(**m)
    key = weights.seed_key(seed)
    init = harness.reference_weights(cfg, cell.family,
                                     jax.devices()[:cell.chips])
    stream = TokenStream(t, cfg.vocab_size, seed)
    batches = [next(stream)["tokens"] for _ in range(t["check_steps"])]

    def read(**kw):
        return reference.readings(m, t, init, key, batches,
                                  family=cell.family, **kw)

    ref = read()
    out = {}
    for kind in kinds:
        got = read(precision="fp8") if kind == "control" else read(fault=kind)
        out[kind] = compare.numbers(got, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    harness.use_compile_cache()
    cell = spec.load_cell(args.workload)
    harness.check_device(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind, nums in readings(cell, seed).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": kind,
                              **{k: v for k, (v, _) in nums.items()},
                              "at": {k: w for k, (_, w) in nums.items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
