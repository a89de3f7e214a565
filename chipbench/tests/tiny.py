"""Tiny cells for the CPU tests: a checkout-like directory with the test's
cells added as files and entries, one harness run of a cell with the chip
check skipped, and the program's timed step broken underneath.

The cells' limits are in ``data/``: the benchmark's own were set at full
size on the chip.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import spec  # noqa: E402

#: workload -> (configuration, traffic, chips); each has its files in
#: ``data/``: ``<config>.json``, ``<traffic>.json``, ``<workload>.json``
CELLS = {
    "tiny-dense.train": ("tiny-dense", "tiny", 1),
    "tiny-dense.train.2x2": ("tiny-dense", "tiny.2x2", 4),
}


def make_root(path) -> None:
    """The benchmark's files under ``path``, the tiny cells added."""
    base = os.path.join(path, "chipbench")
    shutil.copytree(BENCH, base, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, (cfg, traffic, chips) in CELLS.items():
        shutil.copy(os.path.join(DATA, cfg + ".json"),
                    os.path.join(base, "configs"))
        shutil.copy(os.path.join(DATA, traffic + ".json"),
                    os.path.join(base, "traffic"))
        shutil.copy(os.path.join(DATA, name + ".json"),
                    os.path.join(base, "cells"))
        bench["workloads"].append({"name": name, "config": cfg,
                                   "traffic": traffic, "chips": chips,
                                   "why": "CPU test"})
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def load(root, name) -> spec.Cell:
    return spec.load_cell(name, root=str(root),
                          base=os.path.join(str(root), "chipbench"))


def run(root, name, seed=7):
    """One harness run of the cell on this process's devices."""
    import harness
    return harness.run(load(root, name), seed, 0.5, False, t_start=0.0,
                       devices=jax.devices(),
                       peaks=spec.peaks_for("TPU v5 lite"),
                       log=lambda s: None)


BROKEN = ("stale_state", "half_batch", "altered_update")


def broken(kind):
    """``build_train_step`` with the timed step broken underneath."""
    import harness
    real_build = harness.build_train_step

    def build(cfg, tc, mesh, batch, seq, *, jit=False):
        real, n_micro = real_build(cfg, tc, mesh, batch, seq, jit=False)
        if kind == "stale_state":
            def step(state, b):
                return state, real(state, b)[1]
        elif kind == "half_batch":
            def step(state, b):
                half = jax.tree.map(lambda x: jnp.concatenate(
                    [x[:batch // 2]] * 2), b)
                return real(state, half)
        elif kind == "altered_update":
            def step(state, b):
                new, met = real(state, b)
                leaf = new["opt"]["master"]["blocks"]["sub0"]["mixer"]
                old = state["opt"]["master"]["blocks"]["sub0"]["mixer"]["wq"]
                leaf["wq"] = leaf["wq"].at[0].set(2 * leaf["wq"][0] - old[0])
                return new, met
        return jax.jit(step), n_micro
    return build
