"""CPU tests of the chip benchmark: the trace reduction, the work counts,
finding files by name, refusing a machine with no known TPU, and the
check that decides ``correct`` (the control and the planted faults fail
it; a sound run passes).

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

The end-to-end cases run a two-layer dense model through the harness on
the CPU (the chip check skipped), with limits of their own in ``data/``
(see ``tiny.py``): the cells' limits were set at full size on the chip.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")
sys.path.insert(0, BENCH)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import compare  # noqa: E402
import devtrace  # noqa: E402
import spec  # noqa: E402
import tiny  # noqa: E402
import weights  # noqa: E402
import work  # noqa: E402

TINY = "tiny-dense.train"


# ---------------------------------------------------------------- trace --

def _ev(name, start, dur, **kw):
    return {"name": name, "start": start, "dur": dur, **kw}


def test_busy_and_idle_by_hand():
    tr = devtrace.Trace(
        ops={0: [_ev("fusion.1", 0, 30), _ev("fusion.2", 20, 30),
                 _ev("fusion.3", 40, 30), _ev("_kernel", 80, 10)],
             1: [_ev("fusion.1", 0, 100)]},
        host=[], window=(0, 100))
    # chip 0: union [0, 70] + [80, 90] = 80; chip 1: 100
    assert devtrace.busy_s(tr) == pytest.approx(90e-9)
    assert devtrace.idle_share(tr) == pytest.approx(0.1)
    picked = devtrace.select(tr, {"match": {"name": "^_kernel$"}})
    assert [e["start"] for e in picked] == [80]


def test_reduction_of_a_recorded_chip_trace():
    """A slice of a trace recorded on a TPU v5e: the reduction agrees with
    a direct sum over its events."""
    tr = devtrace.read_saved(os.path.join(DATA, "trace_v5e_slice.json.gz"))
    lo, hi = tr.window
    ev = tr.ops[0]
    inside = [(max(e["start"], lo), min(e["start"] + e["dur"], hi))
              for e in ev if e["start"] < hi and e["start"] + e["dur"] > lo]
    # brute force union at 1 ns resolution is too slow; merge by sorting
    inside.sort()
    busy, end = 0.0, lo
    for a, b in inside:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert devtrace.busy_s(tr) == pytest.approx(busy / 1e9, rel=1e-9)
    share = devtrace.idle_share(tr)
    assert 0.0 <= share < 1.0
    top = devtrace.top_ops(tr)
    assert len(top) <= 10 and top == sorted(top, key=lambda x: -x[1])
    for kernel in ("flash_attention", "adam_update"):
        sel = spec.kernel_events(kernel)
        assert devtrace.select(tr, sel), kernel


# --------------------------------------------------------------- counts --

def test_attention_band_by_hand():
    assert work.band_pairs(4) == 10                  # 1 + 2 + 3 + 4
    assert work.band_pairs(6, window=2) == 11        # 1 + 2 + 2 + 2 + 2 + 2
    assert work.band_pairs(4, window=8) == 10
    w = work.attention_fwd(b=2, s=4, heads=3, kv_heads=1, hd=8)
    assert w["flops"] == 4 * 8 * 10 * 2 * 3          # QK and PV over the band
    assert w["bytes"] == 2 * 2 * 4 * 8 * (2 * 3 + 2 * 1)


def test_adam_and_roofline_by_hand():
    assert work.adam(1000)["flops"] == 10000
    assert work.adam(1000)["bytes"] == 30000
    peaks = {"bf16_flops": 100.0, "hbm_bw": 10.0}
    assert work.roofline_s({"flops": 200.0, "bytes": 10.0}, peaks) == 2.0
    assert work.roofline_s({"flops": 100.0, "bytes": 50.0}, peaks) == 5.0


def test_model_flops_by_hand():
    m = {"family": "dense", "tie_embeddings": True, "vocab_size": 10,
         "d_model": 4, "num_layers": 2, "num_heads": 2, "num_kv_heads": 2,
         "head_dim": 2, "sliding_window": 0}
    t = {"global_batch": 1, "seq_len": 4}
    attn = 3 * 2 * 4 * 2 * 10 * 1 * 2
    assert work.model_flops_per_step(m, t, 100) == 6 * 100 * 4 + attn
    m["tie_embeddings"] = False
    assert work.model_flops_per_step(m, t, 140) == 6 * 100 * 4 + attn


# ------------------------------------------------------------- by name --

@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-like directory: the benchmark's files, the test's tiny
    cells added as files and entries, nothing else edited."""
    tiny.make_root(tmp_path)
    return tmp_path


def test_files_dropped_in_are_found_by_name(tiny_root):
    base = str(tiny_root / "chipbench")
    (tiny_root / "chipbench" / "metrics" / "answer.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "answer", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "setup_s",
                               "workloads": [TINY]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(TINY, root=str(tiny_root), base=base)
    assert cell.config["model"]["num_layers"] == 2
    assert cell.family.__file__ == os.path.join(base, "references",
                                                "dense.py")
    assert cell.traffic["seq_len"] == 128
    assert "answer" in [m["name"] for m in cell.per_layer]
    assert spec.metric_reader("answer", base=base)(None) == 42.0
    other = spec.load_cell("gpt2-350m.train.s1024", root=str(tiny_root),
                           base=base)
    assert "answer" not in [m["name"] for m in other.per_layer]
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell", root=str(tiny_root), base=base)


def _add_cell(root, name, model):
    """A workload ``name`` of configuration ``model`` on the tiny traffic,
    added as files and an entry."""
    base = root / "chipbench"
    cfg = name.split(".")[0]
    (base / "configs" / (cfg + ".json")).write_text(json.dumps(
        {"name": cfg, "model": model}))
    shutil.copy(os.path.join(DATA, TINY + ".json"),
                base / "cells" / (name + ".json"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": cfg,
                               "traffic": "tiny", "chips": 1,
                               "why": "CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(base)


SSM_FAMILY = """
import jax.numpy as jnp

def _a_log(key, shape, dtype):
    n = jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)
    return jnp.log(jnp.broadcast_to(n, shape)).astype(dtype)

INIT = {"A_log": _a_log, "D": "ones", "dt_bias": "zeros",
        "conv_x_b": "zeros", "conv_bc_b": "zeros", "norm": "ones"}

def loss(m, ein, params, tokens):
    raise NotImplementedError
"""


def test_a_family_dropped_in_is_found_by_name(tiny_root):
    """A configuration of a family the benchmark has not met needs only
    ``references/<family>.py``; its ``INIT`` fills the vector leaves that
    the default rules do not cover, and without it they are refused."""
    import harness
    from repro.configs.registry import smoke_config
    cfg = smoke_config("mamba2-130m")
    assert cfg.family == "ssm"
    base = _add_cell(tiny_root, "tiny-ssm.train",
                     {k: getattr(cfg, k) for k in cfg.__dataclass_fields__})
    (tiny_root / "chipbench" / "references" / "ssm.py").write_text(
        SSM_FAMILY)
    cell = spec.load_cell("tiny-ssm.train", root=str(tiny_root), base=base)
    assert cell.family.__file__ == os.path.join(base, "references",
                                                "ssm.py")
    assert harness.ModelConfig(**cell.config["model"]) == cfg
    key = weights.seed_key(3000000019)
    mixer = weights.make_params(cfg, key, cell.family.INIT)[
        "blocks"]["sub0"]["mixer"]
    n = cfg.n_ssm_heads
    assert (mixer["A_log"] == jnp.log(jnp.arange(1.0, n + 1))).all()
    assert (mixer["D"] == 1).all() and (mixer["norm"] == 1).all()
    for name in ("dt_bias", "conv_x_b", "conv_bc_b"):
        assert (mixer[name] == 0).all(), name
    assert mixer["in_zx"].std() > 0
    with pytest.raises(ValueError, match="no initialiser for vector leaf"):
        weights.make_params(cfg, key)


def test_a_family_with_no_file_fails_when_the_cell_is_loaded(tiny_root):
    model = json.load(open(os.path.join(DATA, "tiny-dense.json")))["model"]
    base = _add_cell(tiny_root, "tiny-moe.train", dict(model, family="moe"))
    with pytest.raises(spec.SpecError, match=os.path.join(
            base, "references", "moe.py")):
        spec.load_cell("tiny-moe.train", root=str(tiny_root), base=base)


def test_every_cell_of_the_benchmark_has_its_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert set(cell.limits) == {"loss_gap", "grad_gap", "update_gap"} \
            or set(cell.limits) == {"grad_gap", "update_gap"}
        family = cell.config["model"]["family"]
        assert cell.family.__file__ == os.path.join(BENCH, "references",
                                                    family + ".py")
        assert callable(cell.family.loss)
        for m in cell.per_layer:
            spec.metric_reader(m["name"])


# --------------------------------------------------------------- device --

class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_refuses_without_a_tpu(monkeypatch):
    import harness
    with pytest.raises(harness.NoChip, match="no TPU"):
        harness.check_device(1)
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu", "TPU v99")])
    with pytest.raises(harness.NoChip, match="not in peaks.json"):
        harness.check_device(1)
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu", "TPU v5 lite")])
    with pytest.raises(harness.NoChip, match="needs 4 chips"):
        harness.check_device(4)
    assert harness.check_device(1)["bf16_flops"] == 197e12


def test_command_prints_no_result_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "chipbench/run.py", "--workload",
           "gpt2-350m.train.s1024", "--seed", "3000000001", "--seconds", "1"]
    r = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0 and r.stdout == ""
    # a directory with only BENCHMARK.json and the benchmark: no program
    shutil.copytree(BENCH, tmp_path / "chipbench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0 and r.stdout == ""


# ---------------------------------------------------------------- check --

def test_sound_run_is_correct(tiny_root):
    out = tiny.run(tiny_root, TINY)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"train_tokens_per_s", "peak_hbm_gib",
                                   "setup_s"}


@pytest.mark.parametrize("kind", tiny.BROKEN)
def test_broken_step_is_not_correct(tiny_root, monkeypatch, kind):
    import harness
    monkeypatch.setattr(harness, "build_train_step", tiny.broken(kind))
    out = tiny.run(tiny_root, TINY)
    assert not out["correct"], out["checks"]


def test_control_fails_the_limits(tiny_root):
    """The reference in fp8, put in the program's place, fails one of the
    numbers; the same comparison passes the reference itself."""
    import control
    cell = spec.load_cell(TINY, root=str(tiny_root),
                          base=str(tiny_root / "chipbench"))
    got = control.readings(cell, 7, kinds=("control",))["control"]
    ok, checks = compare.verdict(got, cell.limits)
    assert not ok, checks
