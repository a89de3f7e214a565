"""CPU tests of the exchange readers, on hand-made events: ``exchange_ms``
and ``collective_exposed_share`` (``collectives.py``), and
``attention_bwd_ms`` (``scopes.py``).

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import collectives  # noqa: E402
import devtrace  # noqa: E402
import scopes  # noqa: E402
import spec  # noqa: E402

T = "bf16[8,128]{1,0:T(8,128)(2,1)}"


def op(name, code, start, dur, operand="%p.1"):
    return {"name": f"%{name} = {T} {code}({T} {operand})",
            "start": start, "dur": dur}


def run_of(ops, window, steps=1):
    """A run whose trace holds ``ops`` (chip -> events)."""
    return SimpleNamespace(trace=devtrace.Trace(ops=ops, window=window),
                           steps=steps)


def read(name, run):
    return spec.metric_reader(name)(run)


@pytest.mark.parametrize("event,want", [
    (op("all-reduce.3", "all-reduce", 0, 1), ("all-reduce", "")),
    (op("all-gather-start.1", "all-gather-start", 0, 1),
     ("all-gather", "start")),
    (op("collective-permute-done", "collective-permute-done", 0, 1,
        "%collective-permute-start"), ("collective-permute", "done")),
    (op("reduce-scatter.2", "reduce-scatter", 0, 1), ("reduce-scatter", "")),
    (op("all-to-all.5", "all-to-all", 0, 1), ("all-to-all", "")),
    (op("fusion.7", "fusion", 0, 1), (None, "")),
    (op("copy-start.8", "copy-start", 0, 1), (None, "")),
    (op("all-reduce-fusion.1", "fusion", 0, 1), (None, "")),
])
def test_kind_by_the_opcode(event, want):
    assert collectives.kind(event) == want


def test_a_collective_overlapped_by_compute_is_not_exposed():
    """Ops on a chip's line run one after another, so the transfer hides
    behind compute only in its async form: from the start's end to the
    done's begin the fusion runs, and only the halves' own 2 ns each are
    exposed."""
    ops = {0: [op("all-gather-start.1", "all-gather-start", 10, 2),
               op("fusion.1", "fusion", 12, 80),
               op("all-gather-done.1", "all-gather-done", 92, 2,
                  "%all-gather-start.1")]}
    run = run_of(ops, (0, 200))
    assert read("collective_exposed_share", run) == pytest.approx(2.0)
    assert read("exchange_ms", run) == pytest.approx(4e-6)


def test_an_exposed_collective_counts_where_nothing_else_runs():
    """On chip 0 the all-gather runs 40 of its 50 ns alone; on chip 1 it
    runs alone throughout: (40 + 50) / 2 of a 200 ns window."""
    ops = {0: [op("fusion.1", "fusion", 0, 110),
               op("all-gather.1", "all-gather", 100, 50)],
           1: [op("all-gather.1", "all-gather", 100, 50)]}
    run = run_of(ops, (0, 200), steps=2)
    assert read("collective_exposed_share", run) == pytest.approx(22.5)
    assert read("exchange_ms", run) == pytest.approx(50e-6 / 2)


def test_an_async_pair_covers_start_to_done():
    """The transfer runs between the halves: from the start's begin (10)
    to the done's end (60), less the compute between (20 to 40); the
    exchange's self time is the two halves' own events."""
    ops = {0: [op("all-reduce-start.2", "all-reduce-start", 10, 5),
               op("fusion.3", "fusion", 20, 20),
               op("all-reduce-done.2", "all-reduce-done", 50, 10,
                  "%all-reduce-start.2")]}
    run = run_of(ops, (0, 100))
    assert collectives.spans(ops[0]) == [(10, 60)]
    assert read("collective_exposed_share", run) == pytest.approx(30.0)
    assert read("exchange_ms", run) == pytest.approx(15e-6)


def test_a_done_pairs_with_the_start_it_names():
    ops = [op("all-gather-start.1", "all-gather-start", 0, 2),
           op("all-gather-start.2", "all-gather-start", 5, 2),
           op("all-gather-done.1", "all-gather-done", 10, 2,
              "%all-gather-start.1"),
           op("all-gather-done.2", "all-gather-done", 20, 2,
              "%all-gather-start.2")]
    assert sorted(collectives.spans(ops)) == [(0, 12), (5, 22)]


def test_a_loop_does_not_hide_the_collective_inside_it():
    """The ``while`` covers its body: only leaf operations count as other
    work, so the all-reduce inside the loop is exposed."""
    ops = {0: [op("while.1", "while", 0, 100),
               op("fusion.1", "fusion", 0, 50),
               op("all-reduce.1", "all-reduce", 50, 50)]}
    run = run_of(ops, (0, 100))
    assert read("collective_exposed_share", run) == pytest.approx(50.0)
    assert read("exchange_ms", run) == pytest.approx(50e-6)


def test_the_window_clips_and_one_chip_reads_none():
    """Time outside the window does not count; a trace without
    collectives (one chip) reads None, never 0."""
    ops = {0: [op("all-reduce.1", "all-reduce", 90, 20)]}
    assert read("collective_exposed_share", run_of(ops, (0, 100))) \
        == pytest.approx(10.0)
    alone = run_of({0: [op("fusion.1", "fusion", 0, 50)]}, (0, 100))
    assert read("collective_exposed_share", alone) is None
    assert read("exchange_ms", alone) is None


def test_attention_bwd_ms_reads_its_scope(monkeypatch):
    """The backward's scope nests in ``attention``; a program without it
    (the one before the name) reads None."""
    bwd = ("jit(step)/while/body/transpose(jvp(model))/while/body/"
           "checkpoint/attention/shard_map/attention_bwd/")
    sc = scopes.Scoped(ops={0: [
        {"start": 0, "dur": 30, "op_name": bwd + "jvp()/exp"},
        {"start": 30, "dur": 10, "op_name": bwd + "dot_general"},
        {"start": 40, "dur": 20, "op_name":
         "jit(step)/while/body/jvp(model)/attention/shard_map/pallas_call"},
    ]})
    run = run_of(sc.ops, (0, 100), steps=2)
    monkeypatch.setattr(scopes, "of", lambda run: sc)
    assert read("attention_bwd_ms", run) == pytest.approx(20e-6)
    assert scopes.scope_ms(sc, (0, 100), 2, "attention") \
        == pytest.approx(30e-6)
    before = scopes.Scoped(ops={0: [
        {"start": 0, "dur": 30, "op_name": bwd.replace("attention_bwd/", "")
         + "exp"}]})
    monkeypatch.setattr(scopes, "of", lambda run: before)
    assert read("attention_bwd_ms", run) is None
