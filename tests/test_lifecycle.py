"""Unified lifecycle engine: live-path restart policy, dynamic cluster
availability (node_join/node_leave), elastic reallocation, and the
ClusterPool churn-index invariants (ISSUE 2)."""
import copy
import random

import pytest

from repro.cluster.schedulers import FrenzyScheduler
from repro.cluster.simulator import SimJob, SimResult, simulate
from repro.cluster.traces import churn_schedule, scale_workload, spot_schedule
from repro.core.has import ClusterPool, Node
from repro.core.lifecycle import (ClusterEvent, HASAdmission, Job,
                                  LifecycleEngine, NODE_JOIN, NODE_LEAVE,
                                  RESCHEDULE, fifo_order)
from repro.core.marp import ResourcePlan
from repro.core.orchestrator import Orchestrator, make_cluster, \
    PAPER_SIM_CLUSTER
from hypothesis import given, settings, strategies as st

GB = 1024 ** 3


def _plan(n, mem_gb=8, d=None, t=1, dtype="X"):
    return ResourcePlan(n_devices=n, min_mem=mem_gb * GB, d=d or n, t=t,
                        device_type=dtype, pred_bytes=float(mem_gb * GB),
                        score=1.0 / n)


def _nodes(spec):
    """spec: [(node_id, dtype, total), ...] with 40 GB devices."""
    return [Node(nid, dt, 40 * GB, total, total) for nid, dt, total in spec]


# --------------------------------------------------------------------------
# live path: Orchestrator.release -> FIFO restart of queued jobs

def test_release_restarts_queued_fifo():
    """Three 4-device jobs on a 4-device cluster: strict FIFO restarts."""
    orch = Orchestrator(_nodes([("a", "X", 4)]))
    jobs = [orch.submit([_plan(4)]) for _ in range(3)]
    assert [j.state for j in jobs] == ["running", "queued", "queued"]
    orch.release(jobs[0].job_id)
    assert [j.state for j in jobs] == ["done", "running", "queued"]
    orch.release(jobs[1].job_id)
    assert [j.state for j in jobs] == ["done", "done", "running"]
    orch.release(jobs[2].job_id)
    assert all(j.state == "done" for j in jobs)
    assert orch.idle_devices() == 4


def test_release_backfills_smaller_job_over_blocked_head():
    """A release that cannot restart the queue head still starts a later
    job that fits (backfill, matching the seed's try-every-queued loop)."""
    orch = Orchestrator(_nodes([("a", "X", 4)]))
    big = orch.submit([_plan(4)])
    blocked = orch.submit([_plan(3)])
    small = orch.submit([_plan(1)])
    assert (big.state, blocked.state, small.state) == \
        ("running", "queued", "queued")
    # free 4: head (3 devices) starts, then small (1 device) backfills
    orch.release(big.job_id)
    assert (blocked.state, small.state) == ("running", "running")
    assert orch.idle_devices() == 0


def test_release_of_non_running_job_is_noop():
    orch = Orchestrator(_nodes([("a", "X", 2)]))
    j1 = orch.submit([_plan(2)])
    j2 = orch.submit([_plan(2)])
    orch.release(j2.job_id)               # queued, not running
    assert j2.state == "queued"
    orch.release(j1.job_id)
    orch.release(j1.job_id)               # double release: no-op
    assert j2.state == "running"
    assert orch.idle_devices() == 0


def test_try_start_single_job_semantics():
    orch = Orchestrator(_nodes([("a", "X", 2)]))
    j1 = orch.submit([_plan(2)])
    j2 = orch.submit([_plan(2)])
    assert not orch.try_start(j2)         # no capacity
    assert not orch.try_start(j1)         # already running
    orch.release(j1.job_id)
    assert j2.state == "running"          # restarted by release
    assert j2.allocation is not None
    assert j2.allocation.plan.n_devices == 2


# --------------------------------------------------------------------------
# live path: node churn through the orchestrator

def test_orchestrator_node_leave_preempts_and_requeues():
    orch = Orchestrator(_nodes([("a", "X", 2), ("b", "X", 2)]))
    job = orch.submit([_plan(2)])
    assert job.state == "running"
    (victim_node, _), = job.allocation.placements
    victims = orch.node_leave(victim_node)
    assert victims == [job]
    # the surviving node has 2 idle devices, so the preempted job restarts
    assert job.state == "running"
    assert job.preemptions == 1
    assert all(nid != victim_node for nid, _ in job.placements)
    assert victim_node not in orch.nodes
    assert len(orch.nodes) == 1


def test_orchestrator_node_join_restarts_queued():
    orch = Orchestrator(_nodes([("a", "X", 1)]))
    job = orch.submit([_plan(2)])
    assert job.state == "queued"
    orch.node_join(Node("b", "X", 40 * GB, 4, 4))
    assert job.state == "running"
    assert orch.idle_devices() == 3
    # departed node returning: leave then rejoin by id
    orch.node_leave("b")
    assert job.state == "queued"          # "a" alone cannot host it
    assert job.preemptions == 1
    back = orch.node_join(node_id="b")
    assert back is not None and "b" in orch.nodes
    assert job.state == "running"         # rejoin restarted it


def test_node_leave_unknown_and_rejoin_unknown_are_noops():
    orch = Orchestrator(_nodes([("a", "X", 2)]))
    assert orch.node_leave("nope") == []
    assert orch.node_join(node_id="nope") is None


# --------------------------------------------------------------------------
# ClusterPool index invariants across node_join/node_leave

def _pool_consistent(pool):
    """Brute-force recount of every index the pool maintains."""
    assert pool.total_idle == sum(n.idle for n in pool.nodes.values())
    for (dt, mem), bucket in pool._buckets.items():
        members = [n for n in pool.nodes.values()
                   if n.device_type == dt and n.mem == mem]
        assert bucket.idle_sum == sum(n.idle for n in members)
        assert sorted(bucket.entries) == bucket.entries
        assert [e[2] for e in bucket.entries] == \
            [n.node_id for n in sorted(
                (n for n in members if n.idle > 0),
                key=lambda n: (-n.idle, pool._pos[n.node_id]))]


def test_pool_join_leave_index_invariants_random():
    """Seeded-random property: arbitrary take/free/add/remove sequences keep
    the per-class index in sync with a brute-force recount (runs with or
    without hypothesis installed)."""
    rng = random.Random(7)
    pool = ClusterPool([Node(f"n{i}", rng.choice(["X", "Y"]),
                             rng.choice([16, 40]) * GB, tot := rng.randint(1, 8),
                             tot) for i in range(8)])
    spare = [Node(f"s{i}", rng.choice(["X", "Y"]),
                  rng.choice([16, 40]) * GB, tot := rng.randint(1, 8), tot)
             for i in range(8)]
    removed = []
    for step in range(2000):
        op = rng.random()
        ids = list(pool.nodes)
        if op < 0.35 and ids:
            n = pool.nodes[rng.choice(ids)]
            if n.idle > 0:
                pool.take(n.node_id, rng.randint(1, n.idle))
        elif op < 0.7 and ids:
            n = pool.nodes[rng.choice(ids)]
            if n.idle < n.total:
                pool.free(n.node_id, rng.randint(1, n.total - n.idle))
        elif op < 0.85:
            src = spare or removed
            if src:
                n = src.pop(rng.randrange(len(src)))
                n.idle = n.total
                pool.add_node(n)
        elif ids:
            n = pool.nodes[rng.choice(ids)]
            if n.idle == n.total:         # engine contract: drained first
                removed.append(pool.remove_node(n.node_id))
        if step % 50 == 0:
            _pool_consistent(pool)
    _pool_consistent(pool)


def test_remove_node_asserts_on_busy_node():
    pool = ClusterPool(_nodes([("a", "X", 4)]))
    pool.take("a", 1)
    with pytest.raises(AssertionError):
        pool.remove_node("a")
    pool.free("a", 1)
    n = pool.remove_node("a")
    assert n.node_id == "a" and not pool.nodes and pool.total_idle == 0


def test_rejoining_node_goes_to_back_of_fifo_tiebreak():
    """A node that leaves and rejoins loses its FIFO seniority: within a
    class, equal-idle nodes order by insertion position."""
    pool = ClusterPool(_nodes([("a", "X", 4), ("b", "X", 4)]))
    n = pool.remove_node("a")
    pool.add_node(n)
    plan = _plan(4, mem_gb=8, dtype="X")
    # both fit exactly; "b" is now senior
    assert pool.find_placements(plan) == (("b", 4),)


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 7),
                          st.integers(1, 8)), min_size=1, max_size=120))
def test_pool_join_leave_index_invariants_property(ops):
    """Property-style (hypothesis): ops = (op, node_idx, k) sequences."""
    pool = ClusterPool([Node(f"n{i}", "XY"[i % 2], (16 + 24 * (i % 3)) * GB,
                             4, 4) for i in range(4)])
    offline = {}
    for op, idx, k in ops:
        nid = f"n{idx % 8}"
        node = pool.nodes.get(nid)
        if op == 0 and node is not None and node.idle > 0:
            pool.take(nid, 1 + k % node.idle)
        elif op == 1 and node is not None and node.idle < node.total:
            pool.free(nid, 1 + k % (node.total - node.idle))
        elif op == 2 and node is not None and node.idle == node.total:
            offline[nid] = pool.remove_node(nid)
        elif op == 3 and node is None and nid in offline:
            n = offline.pop(nid)
            n.idle = n.total
            pool.add_node(n)
        _pool_consistent(pool)


# --------------------------------------------------------------------------
# sim path: churn + elasticity behaviour

@pytest.fixture(scope="module")
def small_world():
    nodes = make_cluster(PAPER_SIM_CLUSTER)
    types = sorted({n.device_type for n in nodes})
    jobs = scale_workload(40, types, seed=11)
    return nodes, jobs


def test_simulate_under_churn_completes_all_jobs(small_world):
    nodes, jobs = small_world
    probe = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                     FrenzyScheduler(), charge_overhead=False)
    events = churn_schedule(nodes, horizon=probe.makespan, churn_frac=0.3,
                            seed=3)
    assert events, "churn schedule must produce events"
    res = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                   FrenzyScheduler(), charge_overhead=False,
                   cluster_events=events, elastic=False)
    assert res.unfinished == 0
    assert all(j.finish_time >= j.start_time >= j.arrival for j in res.jobs)
    # requeued jobs kept their identity and progress accounting
    for j in res.jobs:
        assert j.samples_done == pytest.approx(j.total_samples)


def test_simulate_spot_waves_complete_all_jobs(small_world):
    nodes, jobs = small_world
    probe = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                     FrenzyScheduler(), charge_overhead=False)
    events = spot_schedule(nodes, horizon=probe.makespan, n_waves=3,
                           wave_frac=0.34, seed=5)
    res = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                   FrenzyScheduler(), charge_overhead=False,
                   cluster_events=events, elastic=True)
    assert res.unfinished == 0


def test_capacity_never_exceeded_under_churn(small_world):
    """The node-availability property: between leave and rejoin, a node
    hosts nothing; allocations never exceed capacity anywhere."""
    nodes, jobs = small_world
    probe = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                     FrenzyScheduler(), charge_overhead=False)
    events = churn_schedule(nodes, horizon=probe.makespan, churn_frac=0.5,
                            seed=9)
    run_nodes = copy.deepcopy(nodes)
    res = simulate(copy.deepcopy(jobs), run_nodes, FrenzyScheduler(),
                   charge_overhead=False, cluster_events=events, elastic=True)
    totals = {n.node_id: n.total for n in nodes}
    # final idle state must balance: every placement released
    for n in run_nodes:
        assert 0 <= n.idle <= n.total
    assert res.preemptions >= 0
    for j in res.jobs:
        for nid, k in j.placements:
            assert 0 < k <= totals[nid]


def test_elastic_migration_improves_jct_under_contention():
    """Jobs admitted on a lower-ranked plan migrate up when capacity frees:
    elastic avg JCT must beat (or match) non-elastic on a contended trace,
    and must actually migrate."""
    nodes = make_cluster(PAPER_SIM_CLUSTER)
    types = sorted({n.device_type for n in nodes})
    jobs = scale_workload(60, types, seed=21, mean_interarrival=0.2,
                          mean_minutes=30.0)
    r0 = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                  FrenzyScheduler(), charge_overhead=False, elastic=False)
    r1 = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                  FrenzyScheduler(), charge_overhead=False, elastic=True)
    assert r1.migrations > 0
    assert r1.avg_jct <= r0.avg_jct
    assert r1.unfinished == 0


def test_static_nonelastic_run_bit_identical_with_elastic_flag_machinery():
    """elastic=False + no cluster events is the golden static path: the
    engine with all churn machinery present must reproduce itself exactly
    (determinism guard for the epoch/progress plumbing)."""
    nodes = make_cluster(PAPER_SIM_CLUSTER)
    types = sorted({n.device_type for n in nodes})
    jobs = scale_workload(30, types, seed=31)
    r1 = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                  FrenzyScheduler(), charge_overhead=False)
    r2 = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                  FrenzyScheduler(), charge_overhead=False,
                  cluster_events=(), elastic=False)
    for a, b in zip(r1.jobs, r2.jobs):
        assert (a.placements, a.start_time, a.finish_time, a.rate) == \
            (b.placements, b.start_time, b.finish_time, b.rate)


def test_migration_charges_checkpoint_cost():
    """A migrated job's predicted finish includes save+restore time: its
    progress accounting must never exceed total work, and migration count
    is reflected on the job."""
    nodes = make_cluster(PAPER_SIM_CLUSTER)
    types = sorted({n.device_type for n in nodes})
    jobs = scale_workload(60, types, seed=21, mean_interarrival=0.2,
                          mean_minutes=30.0)
    res = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                   FrenzyScheduler(), charge_overhead=False, elastic=True)
    migrated = [j for j in res.jobs if j.migrations > 0]
    assert migrated
    for j in migrated:
        assert j.finish_time > j.start_time
        assert j.samples_done == pytest.approx(j.total_samples)


def test_preempted_jobs_get_remaining_work_priority():
    """fifo_order puts preempted jobs first, least remaining work ahead."""
    fresh = Job(job_id=1, arrival=0.0, total_samples=100)
    nearly_done = Job(job_id=2, arrival=5.0, total_samples=100)
    nearly_done.preemptions = 1
    nearly_done.samples_done = 90.0
    barely_started = Job(job_id=3, arrival=1.0, total_samples=100)
    barely_started.preemptions = 1
    barely_started.samples_done = 10.0
    order = fifo_order([fresh, barely_started, nearly_done])
    assert [j.job_id for j in order] == [2, 3, 1]


def test_reschedule_event_triggers_admission():
    """The typed `reschedule` event re-runs admission mid-trace."""
    nodes = _nodes([("a", "RTX6000x", 4)])
    # build a direct engine run with a manual rate model (no MARP needed)
    job = Job(job_id=0, arrival=0.0, total_samples=10,
              plans=(_plan(2, mem_gb=8, dtype="RTX6000x"),))
    engine = LifecycleEngine(nodes, HASAdmission(),
                             rate_fn=lambda j, p, d, t: 1.0, reset=True)
    engine.run([job], [ClusterEvent(time=0.5, kind=RESCHEDULE)])
    assert job.state == "done"
    assert job.finish_time == pytest.approx(10.0)


def test_engine_counters_survive_in_simresult(small_world):
    nodes, jobs = small_world
    probe = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                     FrenzyScheduler(), charge_overhead=False)
    events = churn_schedule(nodes, horizon=probe.makespan, churn_frac=0.5,
                            seed=13)
    res = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                   FrenzyScheduler(), charge_overhead=False,
                   cluster_events=events, elastic=True)
    assert isinstance(res, SimResult)
    assert res.preemptions == sum(j.preemptions for j in res.jobs)
    assert res.migrations == sum(j.migrations for j in res.jobs)
