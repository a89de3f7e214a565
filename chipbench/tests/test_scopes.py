"""CPU tests of the benchmark's phase and scope readers: ``scopes.py``
and the six metrics that read it (``forward_ms``, ``recompute_ms``,
``backward_ms``, ``optimizer_ms``, ``attention_ms``,
``compiles_in_window``).

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
DATA = os.path.join(HERE, "data")
sys.path.insert(0, BENCH)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import devtrace  # noqa: E402
import scopes  # noqa: E402
import spec  # noqa: E402

FWD = "jit(step)/while/body/jvp(model)/while/body/closed_call/"
BWD = "jit(step)/while/body/transpose(jvp(model))/while/body/closed_call/"


@pytest.mark.parametrize("op_name,phase", [
    (FWD + "attention/flash_attention_fwd/pallas_call", "forward"),
    ("jit(step)/while/body/jvp(model)/dot_general", "forward"),
    (BWD + "checkpoint/rematted_computation/attention/dot_general",
     "recompute"),
    (BWD + "checkpoint/attention/jvp()/exp", "backward"),
    ("jit(step)/while/body/grad_accum/add", "backward"),
    ("jit(step)/optimizer/adam_update_leaf/pallas_call", "optimizer"),
    ("jit(step)/add", None),
    ("", None),
    ("jit(step)/jvp(modelx)/add", None),
])
def test_phase_by_hand(op_name, phase):
    assert scopes.phase(op_name) == phase


def _scoped(*ops, spans=()):
    return scopes.Scoped(ops={0: [{"start": a, "dur": d, "op_name": n}
                                  for a, d, n in ops]}, spans=list(spans))


def test_readers_by_hand():
    """Self time per step of each phase and scope; a while loop's body
    counts once; spans outside the window do not count."""
    loop = "jit(step)/while/body/jvp(model)/while"
    sc = _scoped((0, 40, loop), (0, 30, FWD + "attention/x"),
                 (40, 20, BWD + "checkpoint/rematted_computation/attention/y"),
                 (60, 30, BWD + "checkpoint/mlp/z"),
                 (90, 10, "jit(step)/optimizer/w"), (100, 10, "jit(step)/q"),
                 spans=[{"name": "repro/train_step", "start": s, "dur": 5,
                         "stats": {"step": str(s), "compiled": c}}
                        for s, c in ((-10, "1"), (1, "0"), (50, "1"))])
    win = (0, 120)
    got = {p: scopes.phase_ms(sc, win, 2, p) for p in scopes.PHASES}
    assert got == pytest.approx({"forward": 40e-6 / 2, "recompute": 20e-6 / 2,
                                 "backward": 30e-6 / 2,
                                 "optimizer": 10e-6 / 2})
    assert scopes.scope_ms(sc, win, 2, "attention") == pytest.approx(25e-6)
    assert scopes.scope_ms(sc, win, 2, "ssd") is None
    assert scopes.compiles_in_window(sc, win) == 1


def test_readers_read_nothing_without_the_programs_names():
    """A program without scopes and spans (the one before them) reads
    None everywhere, never 0."""
    sc = _scoped((0, 40, "jit(step)/while/body/dot_general"), (40, 5, ""))
    win = (0, 50)
    assert all(scopes.phase_ms(sc, win, 1, p) is None for p in scopes.PHASES)
    assert scopes.scope_ms(sc, win, 1, "attention") is None
    assert scopes.compiles_in_window(sc, win) is None
    assert scopes.compiles_in_window(scopes.Scoped(), win) is None


def test_op_names_and_spans_of_a_cpu_trace(tmp_path):
    """The trace's own copy of the optimized HLO maps each instruction to
    its ``op_name``, scopes and transform markers included; the program's
    host spans come with their stats."""
    from jax.profiler import ProfileData, TraceAnnotation

    def loss(w, x):
        with jax.named_scope("model"):
            return jnp.tanh(x @ w).sum()

    def step(w, x):
        g = jax.grad(loss)(w, x)
        with jax.named_scope("optimizer"):
            return w - 0.1 * g
    f = jax.jit(step)
    w, x = jnp.ones((16, 16)), jnp.ones((4, 16))
    f(w, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("repro/train_step", step=3) as s:
            out = f(w, x)
            s.set_metadata(compiled=0)
        out.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = [os.path.join(d, n) for d, _, ns in os.walk(tmp_path)
             for n in ns if n.endswith(".xplane.pb")]
    names = scopes.hlo_op_names(open(path, "rb").read())["jit_step"]
    found = {scopes.phase(n) for n in names.values()}
    assert {"forward", "backward", "optimizer"} <= found, names
    # every instruction the CPU ran in the step maps to a name
    ran = {dict(e.stats)["hlo_op"] for p in ProfileData.from_file(path).planes
           for ln in p.lines for e in ln.events
           if dict(e.stats).get("hlo_module") == "jit_step"}
    assert ran and ran <= set(names)
    sc = scopes.load(path)
    assert [(sp["name"], sp["stats"]) for sp in sc.spans] == [
        ("repro/train_step", {"step": "3", "compiled": "0"})]


def test_readers_on_a_recorded_chip_trace(monkeypatch):
    """150 ms of a traced gpt2-350m step on a TPU v5e, each operation
    with the ``op_name`` its instruction has in the trace's HLO: every
    reader reads it, the four phases claim all but 5% of busy time, and
    the ``attention`` scope holds at least the kernel the old selector
    picks, which now carries its own name."""
    import gzip
    from types import SimpleNamespace
    with gzip.open(os.path.join(DATA, "scopes_v5e_slice.json.gz"), "rt") as f:
        d = json.load(f)
    sc, window = scopes.Scoped.from_json(d["scoped"]), tuple(d["window"])
    tr = devtrace.Trace(ops=sc.ops, window=window)
    run = SimpleNamespace(trace=tr, steps=1)
    monkeypatch.setattr(scopes, "of", lambda run: sc)
    got = {n: spec.metric_reader(n)(run) for n in (
        "forward_ms", "recompute_ms", "backward_ms", "optimizer_ms",
        "attention_ms", "compiles_in_window")}
    assert None not in got.values(), got
    busy_ms = devtrace.busy_s(tr) * 1e3
    phases = sum(got[p + "_ms"] for p in scopes.PHASES)
    assert 0.95 * busy_ms <= phases <= busy_ms * (1 + 1e-9)
    kernel = devtrace.select(tr, spec.kernel_events("flash_attention"))
    assert kernel and all(e["name"].startswith("%flash_attention_fwd.")
                          and "attention" in e["op_name"].split("/")
                          for e in kernel)
    assert got["attention_ms"] >= sum(e["dur"] for e in kernel) / 1e6
    assert got["compiles_in_window"] == 0
