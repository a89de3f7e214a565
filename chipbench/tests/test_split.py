"""The reference with its state split over four host devices reads as it
does on one, and the harness decides ``correct`` on a 2x2 cell as it does
on one chip.  Each case runs ``split.py`` in a process of its own, with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_split.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import tiny

SCRIPT = os.path.join(tiny.HERE, "split.py")


def _split(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, SCRIPT, *args], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def split_reference():
    return _split("reference")


def test_split_reference_reads_as_on_one_device(split_reference):
    for k in ("loss", "grad_norms", "change_norms"):
        assert split_reference[k] <= 1e-5, (k, split_reference[k])


def test_each_leaf_is_split_along_an_axis_four_divides(split_reference):
    """A leaf with an axis (past the layer axis under ``blocks``) that 4
    divides holds a quarter of its elements on each device; the others
    are whole on each."""
    leaves = split_reference["leaves"]
    assert len(leaves) == 10
    for name, leaf in leaves.items():
        shape, lead = leaf["shape"], name.startswith("blocks/")
        whole = 1
        for n in shape:
            whole *= n
        assert leaf["devices"] == 4, name
        if any(n % 4 == 0 for n in shape[lead:]):
            assert leaf["on_device"] == [whole // 4] * 4, (name, leaf)
        else:
            assert leaf["on_device"] == [whole] * 4, (name, leaf)


@pytest.mark.parametrize("case", ("sound",) + tiny.BROKEN)
def test_a_2x2_cell_is_checked(tmp_path, case):
    tiny.make_root(tmp_path)
    out = _split(case, str(tmp_path))
    assert out["devices"] == 4
    assert out["correct"] == (case == "sound"), out["checks"]
