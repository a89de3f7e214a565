"""Runs on four host devices for ``test_split.py``, one case a process:
the device count is fixed when JAX starts.

    python chipbench/tests/split.py reference
    python chipbench/tests/split.py sound|stale_state|half_batch|... DIR

``reference``: the tiny-dense reference's readings with its state split
over four devices and on one, and how each leaf of the split weights
lies on the devices.  Otherwise one harness run of the tiny 2x2 cell in
a checkout-like ``DIR``, the program's step broken underneath unless the
case is ``sound``.  One JSON line goes to standard output.
"""
from __future__ import annotations

import json
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import tiny  # noqa: E402
from tiny import jax  # noqa: E402

import harness  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import weights  # noqa: E402
from traffic import TokenStream  # noqa: E402

SEED = 3000000007


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.abs(b)))


def split_reference():
    """Readings split over four devices against one; the elements of each
    leaf on each device."""
    with open(os.path.join(tiny.DATA, "tiny-dense.json")) as f:
        m = json.load(f)["model"]
    with open(os.path.join(tiny.DATA, "tiny.2x2.json")) as f:
        t = json.load(f)
    family = spec.reference_family("dense")
    cfg = harness.ModelConfig(**m)
    key = weights.seed_key(SEED)
    stream = TokenStream(t, cfg.vocab_size, SEED)
    batches = [next(stream)["tokens"] for _ in range(t["check_steps"])]
    out, read = {}, {}
    for n in (1, 4):
        make = harness.reference_weights(cfg, family, jax.devices()[:n])
        read[n] = reference.readings(m, t, make, key, batches,
                                     family=family)
        if n == 4:
            p = reference.to_f32(make(key))
            out["leaves"] = {
                name: {"shape": list(x.shape),
                       "on_device": sorted(int(np.prod(s.data.shape))
                                           for s in x.addressable_shards),
                       "devices": len(x.sharding.device_set)}
                for name, x in zip(reference._names(p), jax.tree.leaves(p))}
    one, four = read[1], read[4]
    out["loss"] = _rel(four["loss"], one["loss"])
    for k in ("grad_norms", "change_norms"):
        out[k] = max(_rel(four[k][n], one[k][n]) for n in one[k])
    return out


def harness_run(case: str, root: str):
    if case != "sound":
        harness.build_train_step = tiny.broken(case)
    out = tiny.run(root, "tiny-dense.train.2x2")
    return {"correct": out["correct"], "checks": out["checks"],
            "devices": out["device"]["count"]}


def main(argv) -> int:
    case = argv[0]
    out = (split_reference() if case == "reference"
           else harness_run(case, argv[1]))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
