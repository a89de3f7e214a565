"""A 2x2 cell whose data replicas do not exchange gradients fails
``correct``; the sound run passes.  Each case runs ``unsynced.py`` in a
process of its own, with four host devices.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_unsynced.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import tiny
import unsynced

SCRIPT = os.path.join(tiny.HERE, "unsynced.py")


def test_replica_rows_follow_the_micro_steps():
    """8 rows, 2 micro-steps of 4, 2 replicas: replica 0 holds rows 0, 1
    of the first micro-step and 4, 5 of the second."""
    assert unsynced.replica_rows(8, 2, 2, 0).tolist() == [0, 1, 4, 5]
    assert unsynced.replica_rows(8, 2, 2, 1).tolist() == [2, 3, 6, 7]


@pytest.mark.parametrize("case", ("sound", "unsynced"))
def test_a_2x2_cell_without_the_gradient_exchange_fails(tmp_path, case):
    tiny.make_root(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, SCRIPT, case, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["correct"] == (case == "sound"), out["checks"]
    if case == "unsynced":
        # the gradient of half the rows: the update's norms move with it
        assert out["checks"]["grad_gap"]["value"] > \
            out["checks"]["grad_gap"]["limit"] or \
            out["checks"]["update_gap"]["value"] > \
            out["checks"]["update_gap"]["limit"], out["checks"]
    assert np.isfinite(out["checks"]["loss_gap"]["value"])
