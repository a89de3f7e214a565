"""Fractional-GPU packing (PR 10): slice accounting safety and the
train/serve colocation path.

The core contract under test: a device's allocated slice bytes never
exceed its capacity, across arbitrary interleavings of exclusive grants,
slice grants, frees, and cluster churn — checked by a hypothesis property
and a deterministic fuzz twin driving the same op interpreter, with the
pool's own ``_debug_check_slices`` full-scan cross-check run after every
op.  On top sit placement-query units (harvest select/find, the
histogram's necessary-condition bound) and an end-to-end colocated mixed
simulation with misprediction noise that must stay repeat-OOM-free.
"""
import random

import pytest

from repro.cluster.schedulers import FrenzyScheduler, OpportunisticScheduler
from repro.cluster.simulator import simulate
from repro.core.has import ClusterPool, Grant, Node
from repro.core.marp import ResourcePlan

from hypothesis import given, settings, strategies as st

GB = 1024 ** 3


def _mixed_cluster():
    return ([Node(f"a{i}", "A100-80G", 80 * GB, 4, 4) for i in range(3)]
            + [Node(f"v{i}", "v5e", 16 * GB, 8, 8) for i in range(3)])


def _plan(device_type="A100-80G", n=1, slice_bytes=0, mem=10 * GB):
    return ResourcePlan(n_devices=n, min_mem=mem, d=n, t=1,
                        device_type=device_type, pred_bytes=float(mem),
                        score=1.0, zero=1, slice_bytes=slice_bytes)


# ------------------------------------------------------------ op interpreter

def _drive(ops):
    """Interpret a list of ints as pool ops (exclusive grant / slice grant
    / free / node leave / node join) against a mixed pool, shadowing every
    open device's used bytes in a plain dict and cross-checking the
    incremental indexes after each op.  Shared by the hypothesis property
    and the deterministic fuzz twin, so a CI failure in either reproduces
    in the other from the same op list."""
    pool = ClusterPool(_mixed_cluster())
    pool.enable_slicing()
    live = []                               # applied grants
    used = {}                               # (node_id, dev) -> tenant bytes
    joined = 0

    def check():
        pool._debug_check_slices()
        for node_id, devs in pool._open.items():
            n = pool.nodes[node_id]
            for dev, (u, tenants) in devs.items():
                # THE invariant: allocated slice bytes never exceed the
                # device's capacity, and match the shadow model exactly
                assert 0 < u <= n.mem, (node_id, dev, u, n.mem)
                assert tenants > 0
                assert used.get((node_id, dev), 0) == u

    for x in ops:
        op, r = x % 5, x // 5
        if op == 0:                         # exclusive grant (train job)
            cands = [n for n in pool.nodes.values() if n.idle > 0]
            if not cands:
                continue
            n = cands[r % len(cands)]
            g = Grant(n.node_id, 1 + r % n.idle, 1 + r % n.mem)
            pool.apply([g])
            live.append(g)
            for dev in g.devs:
                used[(n.node_id, dev)] = g.nbytes
        elif op == 1:                       # slice grant (harvester)
            nbytes = 1 + r % (2 * GB)
            g = None
            for dt in ("A100-80G", "v5e"):
                hit = pool._slice_best_fit(dt, nbytes)
                if hit is not None:          # slack entry (free,pos,dev,nid)
                    g = Grant(hit[3], 1, nbytes, exclusive=False,
                              devs=(hit[2],))
                    break
            if g is None:                   # idle-device fallback
                cands = [n for n in pool.nodes.values()
                         if n.idle > 0 and n.mem >= nbytes]
                if not cands:
                    continue
                g = Grant(cands[r % len(cands)].node_id, 1, nbytes,
                          exclusive=False)
            pool.apply([g])
            live.append(g)
            for dev in g.devs:
                used[(g.node_id, dev)] = (used.get((g.node_id, dev), 0)
                                          + g.nbytes)
        elif op == 2:                       # free
            if not live:
                continue
            g = live.pop(r % len(live))
            pool.release([g])
            for dev in g.devs:
                used[(g.node_id, dev)] -= g.nbytes
                if not used[(g.node_id, dev)]:
                    del used[(g.node_id, dev)]
        elif op == 3:                       # node leave (must be drained)
            cands = [n for n in pool.nodes.values()
                     if n.idle == n.total and not pool._open.get(n.node_id)]
            if len(cands) <= 1:             # keep the pool non-empty
                continue
            pool.remove_node(cands[r % len(cands)].node_id)
        else:                               # node join
            joined += 1
            pool.add_node(Node(f"j{joined}", "A100-80G", 80 * GB, 4, 4))
        check()

    for g in live:                          # drain: everything releases
        pool.release([g])
    assert not pool._open and pool.total_slack == 0
    assert pool.total_idle == sum(n.total for n in pool.nodes.values())
    for dt, v in pool.idle_bytes_by_type.items():
        assert v == sum(n.idle * n.mem for n in pool.nodes.values()
                        if n.device_type == dt)
    pool._debug_check_slices()


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=2 ** 63 - 1),
                max_size=60))
def test_slice_bytes_never_exceed_capacity_property(ops):
    _drive(ops)


def test_slice_bytes_never_exceed_capacity_fuzz():
    """Deterministic twin of the hypothesis property (runs even without
    hypothesis installed; same interpreter, fixed seeds)."""
    for seed in range(25):
        rng = random.Random(1000 + seed)
        _drive([rng.getrandbits(63) for _ in range(80)])


# ------------------------------------------------------------- query units

def test_grant_iterates_as_legacy_pair():
    # every `for nid, k in placements` consumer sees (node, whole devices):
    # slices report k=0 so they add no whole-device weight anywhere
    assert list(Grant("n1", 2, 5)) == ["n1", 2]
    assert list(Grant("n1", 1, 5, exclusive=False)) == ["n1", 0]


def test_harvest_slice_rides_exclusive_grants_slack():
    pool = ClusterPool([Node("n1", "A100-80G", 80 * GB, 4, 4)])
    pool.enable_slicing()
    excl = Grant("n1", 4, 30 * GB)          # all devices, 50 GB slack each
    pool.apply([excl])
    assert pool.total_idle == 0 and pool.total_slack == 4 * 50 * GB

    plan = _plan(slice_bytes=10 * GB)
    # whole-device admission is impossible; harvest admission is not
    assert pool.select_plan([plan]) is None
    assert pool.select_plan([plan], harvest=True) is plan
    (g,) = pool.find_placements(plan, harvest=True)
    assert isinstance(g, Grant) and not g.exclusive
    assert g.nbytes == 10 * GB and g.devs[0] in excl.devs
    pool.apply([g])
    assert pool.total_slack == 3 * 50 * GB + 40 * GB
    pool.release([g])
    pool.release([excl])
    assert pool.total_idle == 4 and pool.total_slack == 0


def test_slack_may_fit_is_necessary_condition():
    pool = ClusterPool([Node("n1", "A100-80G", 80 * GB, 2, 2)])
    pool.enable_slicing()
    assert not pool.slack_may_fit("A100-80G", 1)        # nothing open
    pool.apply([Grant("n1", 1, 30 * GB)])               # 50 GB slack
    # exact fits are always admitted by the histogram bound...
    assert pool.slack_may_fit("A100-80G", 40 * GB)
    assert pool._slice_best_fit("A100-80G", 40 * GB) is not None
    # ...and anything the exact query can place passes the bound (the
    # converse may not hold: the pow2 bound is allowed to overestimate)
    assert pool._slice_best_fit("A100-80G", 64 * GB) is None
    assert not pool.slack_may_fit("A100-80G", 64 * GB)
    assert pool.slack_may_fit("A100-80G", 50 * GB)      # exact boundary


def test_slice_best_fit_prefers_tightest_slack():
    pool = ClusterPool([Node("n1", "A100-80G", 80 * GB, 2, 2)])
    pool.enable_slicing()
    g1 = Grant("n1", 1, 70 * GB)            # 10 GB slack
    g2 = Grant("n1", 1, 40 * GB)            # 40 GB slack
    pool.apply([g1])
    pool.apply([g2])
    # best fit: the 10 GB hole wins for a 5 GB ask
    hit = pool._slice_best_fit("A100-80G", 5 * GB)
    assert (hit[3], hit[2]) == ("n1", g1.devs[0])
    hit = pool._slice_best_fit("A100-80G", 20 * GB)
    assert (hit[3], hit[2]) == ("n1", g2.devs[0])


def test_whole_device_pool_untouched_without_slicing():
    # a never-enabled pool carries zeroed slice state and rejects grants
    pool = ClusterPool(_mixed_cluster())
    assert not pool.slicing and pool.total_slack == 0
    with pytest.raises(AssertionError):
        pool.apply([Grant("a0", 1, GB)])


def test_colocate_requires_slicing_scheduler():
    # snapshot schedulers count whole devices on a private clone; the
    # engine must reject colocation for them instead of dropping budgets
    with pytest.raises(AssertionError):
        simulate([], _mixed_cluster(), OpportunisticScheduler(),
                 charge_overhead=False, colocate=True)


def test_remove_node_refuses_open_devices():
    pool = ClusterPool(_mixed_cluster())
    pool.enable_slicing()
    g = Grant("a0", 1, GB, exclusive=False)
    pool.apply([g])
    with pytest.raises(AssertionError):
        pool.remove_node("a0")
    pool.release([g])
    pool.remove_node("a0")


# --------------------------------------------------------------- end-to-end

def _mixed_workload(types, n_train=15, n_serve=8, n_ft=8, seed=5,
                    horizon=3600.0):
    from repro.cluster.traces import (finetune_workload, new_workload,
                                      serve_workload)
    tjobs = new_workload(n_train, types, seed=seed)
    sjobs, revs = serve_workload(n_serve, types, seed=seed, horizon=horizon,
                                 start_id=100_000)
    fjobs = finetune_workload(n_ft, types, seed=seed, start_id=200_000)
    jobs = sorted(tjobs + sjobs + fjobs, key=lambda j: (j.arrival, j.job_id))
    return jobs, revs


def test_colocated_mixed_sim_finishes_and_scales_more():
    import copy
    nodes = ([Node(f"a{i}", "A100-80G", 80 * GB, 4, 4) for i in range(8)]
             + [Node(f"v{i}", "v5e", 16 * GB, 8, 8) for i in range(8)])
    types = sorted({n.device_type for n in nodes})
    jobs, revs = _mixed_workload(types)
    coloc = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                     FrenzyScheduler(), charge_overhead=False,
                     rate_events=list(revs), colocate=True)
    whole = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                     FrenzyScheduler(), charge_overhead=False,
                     rate_events=list(revs))
    assert coloc.unfinished == 0 and whole.unfinished == 0
    assert coloc.ooms == 0
    # colocation's point: harvested slack fits extra serve replicas
    assert coloc.scale_ups >= whole.scale_ups


def test_colocated_sim_no_repeat_oom_with_feedback():
    """The no-repeat-OOM invariant (PR 4) carries over to slices: with the
    feedback plane on, colocated jobs that OOM against their slice budget
    never re-die on the same (device, shape) class — corrected peaks grow
    ``slice_bytes`` on requeue exactly as they grow ``min_mem``."""
    import copy
    from benchmarks.oom_resilience import count_repeat_ooms
    from repro.core import memtrace
    from repro.core.marp import predict_plans_shared
    from repro.cluster.traces import misprediction_oracle
    nodes = ([Node(f"a{i}", "A100-80G", 80 * GB, 4, 4) for i in range(8)]
             + [Node(f"v{i}", "v5e", 16 * GB, 8, 8) for i in range(8)])
    types = sorted({n.device_type for n in nodes})
    jobs, revs = _mixed_workload(types, seed=9)
    memtrace.enable()
    try:
        res = simulate(copy.deepcopy(jobs), nodes, FrenzyScheduler(),
                       charge_overhead=False, rate_events=list(revs),
                       colocate=True,
                       oom_check_fn=misprediction_oracle(severity=0.6,
                                                         frac=0.3, seed=3),
                       replan_fn=lambda j: predict_plans_shared(
                           j.cfg, j.global_batch, j.seq_len,
                           device_types=tuple(types), max_devices=64))
        assert count_repeat_ooms(res) == 0
        assert res.oom_failures == 0 and res.unfinished == 0
    finally:
        memtrace.disable()
        memtrace.reset()
        memtrace.seed_from_experiments()


def test_colocated_stream_run_matches_list_run():
    """The streamed-trace path (serve_stream + rate_events_iter satellite)
    reaches the same colocated end state as the materialized path."""
    import copy
    from repro.cluster.simulator import simulate_stream
    from repro.cluster.traces import serve_stream, serve_workload
    nodes = ([Node(f"a{i}", "A100-80G", 80 * GB, 4, 4) for i in range(4)]
             + [Node(f"v{i}", "v5e", 16 * GB, 8, 8) for i in range(4)])
    types = sorted({n.device_type for n in nodes})
    jobs, revs = serve_workload(10, types, seed=7, horizon=3600.0)
    r1 = simulate(jobs, copy.deepcopy(nodes), FrenzyScheduler(),
                  charge_overhead=False, rate_events=revs, colocate=True)
    sj, sr = serve_stream(10, types, seed=7, horizon=3600.0)
    r2 = simulate_stream(sj, copy.deepcopy(nodes), FrenzyScheduler(),
                         charge_overhead=False, rate_events=sr,
                         colocate=True)
    assert (len(r1.finished), r1.unfinished, r1.makespan, r1.scale_ups) \
        == (r2.n_finished, r2.unfinished, r2.makespan, r2.scale_ups)


def test_rate_events_iter_bit_identical_to_list_form():
    from repro.cluster.traces import rate_events_iter, serve_workload
    types = ("A100-80G", "v5e")
    _, revs = serve_workload(12, types, seed=3, horizon=7200.0, start_id=50)
    got = list(rate_events_iter(12, types, seed=3, horizon=7200.0,
                                start_id=50))
    assert got == sorted(revs, key=lambda e: (e.time, e.job_id))
    assert all(a.time <= b.time for a, b in zip(got, got[1:]))
