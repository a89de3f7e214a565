"""Runs on four host devices for ``test_unsynced.py``, one case a process:
one harness run of the tiny 2x2 cell in a checkout-like ``DIR``, sound
or with the data replicas' gradient exchange left out.

    python chipbench/tests/unsynced.py sound|unsynced DIR

``unsynced``: each data replica applies the mean gradient of its own rows
alone.  Under ZeRO-1 a replica updates the shard of the optimizer state
it owns and hands its parameters out from it, so the step's new state is,
shard by shard, the state that the owner's rows alone give.  The program's
own step computes each replica's state from a batch that holds that
replica's rows twice (the same mean); the shards are then put together by
owner.  One JSON line goes to standard output.
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
from tiny import jax, jnp  # noqa: E402

import harness  # noqa: E402

#: the program's own step builder, before a case replaces it
REAL_BUILD = harness.build_train_step


def replica_rows(batch: int, n_micro: int, n_data: int, r: int):
    """The rows data replica ``r`` holds: each micro-step takes the next
    ``batch / n_micro`` rows, split evenly over the replicas."""
    mb = batch // n_micro
    per = mb // n_data
    return np.concatenate([np.arange(i * mb + r * per, i * mb + (r + 1) * per)
                           for i in range(n_micro)])


def owners(spec, shape, mesh):
    """For each axis position of a leaf split over ``data``: the replica
    that owns it, shaped to broadcast; None if no axis is."""
    for ax, e in enumerate(spec):
        names = e if isinstance(e, tuple) else (e,)
        if "data" not in names:
            continue
        sizes = [mesh.shape[n] for n in names]
        blocks = int(np.prod(sizes))
        coord = np.unravel_index(np.arange(blocks), sizes)[names.index("data")]
        own = np.repeat(coord, shape[ax] // blocks)
        return own.reshape([-1 if i == ax else 1 for i in range(len(shape))])
    return None


def unsynced_build(cfg, tc, mesh, batch, seq, *, jit=False):
    from jax.sharding import PartitionSpec as P
    from repro.train import state_specs
    real, n_micro = REAL_BUILD(cfg, tc, mesh, batch, seq, jit=False)
    n_data = mesh.shape["data"]
    rows = [replica_rows(batch, n_micro, n_data, r) for r in range(n_data)]

    def own_twice(b, r):
        """Each micro-step's rows: replica ``r``'s, once for each
        replica."""
        idx = rows[r].reshape(n_micro, -1)
        idx = np.concatenate([np.tile(i, n_data) for i in idx])
        return jax.tree.map(lambda x: x[idx], b)

    def step(state, b):
        specs = state_specs(cfg, tc, mesh, state)["opt"]
        outs = [real(state, own_twice(b, r)) for r in range(n_data)]
        news = [o[0] for o in outs]

        def pick(spec, *leaves):
            own = owners(spec, leaves[0].shape, mesh)
            if own is None:
                return leaves[0]
            out = leaves[0]
            for r in range(1, n_data):
                out = jnp.where(own == r, leaves[r], out)
            return out

        opt = {k: jax.tree.map(pick, specs[k], *[n["opt"][k] for n in news],
                               is_leaf=lambda x: isinstance(x, P))
               for k in ("master", "m", "v")}
        params = jax.tree.map(lambda mp, p: mp.astype(p.dtype),
                              opt["master"], news[0]["params"])
        met = {k: sum(o[1][k] for o in outs) / n_data for k in outs[0][1]}
        return {"params": params, "opt": opt, "step": news[0]["step"]}, met

    return jax.jit(step), n_micro


def main(argv) -> int:
    case, root = argv
    if case == "unsynced":
        harness.build_train_step = unsynced_build
    out = tiny.run(root, "tiny-dense.train.2x2")
    print(json.dumps({"correct": out["correct"], "checks": out["checks"],
                      "devices": out["device"]["count"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
