"""Failure plane (PR 8): crash-faults, Young–Daly checkpointing, backoff.

Covers the checkpoint-durability contract (crashes only keep progress up
to the last durable cycle boundary; graceful departures lose nothing),
the deterministic backoff/budget state machine, partial serve-replica
failures, the streaming failure trace discipline, and the
reliability-aware planning model.  The fault-free bit-identity guarantee
lives in ``test_golden_equivalence.py``.
"""
import copy
import math
import random

import pytest

from hypothesis import given, settings, strategies as st

from repro.ckpt.checkpoint import (checkpoint_seconds, migration_seconds,
                                   state_bytes)
from repro.cluster import traces
from repro.cluster.simulator import job_rate, simulate
from repro.configs.registry import ARCHS
from repro.core import reliability
from repro.core.devices import DEVICE_TYPES
from repro.core.lifecycle import (ClusterEvent, HASAdmission, Job,
                                  LifecycleEngine, NODE_FAIL, NODE_JOIN,
                                  NODE_LEAVE)
from repro.core.orchestrator import make_cluster


def _cluster(n_nodes=4, devices=8, device_type="v5e"):
    return make_cluster([(n_nodes, devices, device_type)])


def _train_job(job_id=0, cfg_name="gpt2-350m", total=10_000.0, **kw):
    from repro.core.marp import predict_plans
    cfg = ARCHS[cfg_name]
    return Job(job_id=job_id, cfg=cfg, global_batch=32, seq_len=1024,
               total_samples=total,
               plans=tuple(predict_plans(cfg, 32, 1024,
                                         device_types=["v5e"])), **kw)


def _engine(nodes, live=False, **kw):
    engine = LifecycleEngine(nodes, HASAdmission(), reset=True, **kw)
    if not live:
        pool_nodes = engine.pool.nodes
        engine.rate_fn = lambda job, placements, d, t: \
            job_rate(job, placements, pool_nodes, d, t)
    return engine


# ------------------------------------------------------- rollback contract

def test_crash_rolls_back_to_last_durable_cycle():
    """With a fixed interval, a crash keeps exactly k = floor(dt/(tau+C))
    cycles of effective-rate progress and loses the partial cycle."""
    nodes = _cluster(2)
    tau = 100.0
    engine = _engine(nodes, ckpt_policy="fixed", ckpt_fixed_interval_s=tau)
    job = _train_job(total=1e12)            # never finishes in-window
    engine.submit_job(job, now=0.0)
    assert job.state == "running"
    cost = job.ckpt_cost_s
    assert cost == pytest.approx(checkpoint_seconds(job.cfg))
    assert 0.0 < cost < tau
    assert job._ckpt_tau == tau
    eff = job.rate                          # already save-stall discounted
    victim = job.placements[0][0]
    t_fail = 1000.0
    engine.node_fail(victim, now=t_fail)
    cycle = tau + cost
    k = int(t_fail // cycle)
    assert k >= 1
    assert job.samples_done == pytest.approx(k * cycle * eff)
    assert job.lost_work_s == pytest.approx(t_fail - k * cycle)
    assert engine.lost_work_s == job.lost_work_s
    assert job.ckpt_overhead_s == pytest.approx(k * cost)
    assert job.restarts.get("crash") == 1
    assert engine.crash_count == 1 and engine.node_fail_count == 1
    assert engine.failure_log == [
        (t_fail, victim, job.job_id, pytest.approx(t_fail - k * cycle))]


def test_no_checkpoint_crash_loses_everything_since_start():
    nodes = _cluster(2)
    engine = _engine(nodes)                 # no ckpt policy
    job = _train_job(total=1e12)
    engine.submit_job(job, now=0.0)
    assert job._ckpt_tau == 0.0 and job.ckpt_cost_s == 0.0
    t_fail = 777.0
    engine.node_fail(job.placements[0][0], now=t_fail)
    assert job.samples_done == 0.0          # all progress rolled back
    assert job.lost_work_s == pytest.approx(t_fail)
    assert job.ckpt_overhead_s == 0.0


def test_node_leave_stays_graceful_zero_lost_work():
    """The pre-existing contract is untouched: a graceful departure
    checkpoints on the way out — full accrual, nothing lost."""
    nodes = _cluster(2)
    engine = _engine(nodes)
    job = _train_job(total=1e12)
    engine.submit_job(job, now=0.0)
    eff = job.rate
    engine.node_leave(job.placements[0][0], now=500.0)
    assert job.lost_work_s == 0.0
    assert engine.lost_work_s == 0.0
    assert job.samples_done == pytest.approx(500.0 * eff)
    assert "crash" not in job.restarts


def test_young_daly_interval_from_placement_mtbf():
    """tau = sqrt(2*C*M_agg) with M_agg the placement's aggregate MTBF
    (per-device MTBF over total devices), and the rate discounted by
    tau/(tau+C)."""
    nodes = _cluster(2)
    engine = _engine(nodes, ckpt_policy="young_daly")
    job = _train_job(total=1e12)
    engine.submit_job(job, now=0.0)
    assert job.state == "running"
    n_devs = sum(k for _, k in job.placements)
    mtbf = DEVICE_TYPES["v5e"].mtbf_s / n_devs
    cost = checkpoint_seconds(job.cfg)
    want_tau = math.sqrt(2.0 * cost * mtbf)
    assert job._ckpt_tau == pytest.approx(want_tau)
    assert job.ckpt_cost_s == pytest.approx(cost)
    raw = job_rate(job, job.placements, engine.pool.nodes,
                   job.plan.d, job.plan.t)
    assert job.rate == pytest.approx(raw * want_tau / (want_tau + cost))
    assert job.rate < raw                   # the save stall is priced in


def test_per_job_interval_override_beats_policy():
    nodes = _cluster(2)
    engine = _engine(nodes, ckpt_policy="young_daly")
    job = _train_job(total=1e12, ckpt_interval_s=42.0)
    engine.submit_job(job, now=0.0)
    assert job._ckpt_tau == pytest.approx(
        max(42.0, checkpoint_seconds(job.cfg)))


def test_lora_finetune_checkpoints_near_free():
    cfg = ARCHS["gpt2-7b"]
    full = checkpoint_seconds(cfg)
    lora = checkpoint_seconds(cfg, lora_rank=16)
    assert lora < full / 100.0
    assert full == pytest.approx(state_bytes(cfg) / (16 * 2 ** 30))
    # a save is the write half of a full migrate (save + restore)
    assert full == pytest.approx(migration_seconds(cfg) / 2.0)


# ------------------------------------------------- backoff + restart budget

def test_backoff_deterministic_and_escalating():
    nodes = _cluster(2)
    engine = _engine(nodes, restart_backoff_s=10.0)
    job = _train_job()
    delays = []
    for n in range(1, 5):
        job.restarts = {"crash": n}
        delays.append(engine._backoff_delay(job))
    # same (job, attempt) -> same delay
    job.restarts = {"crash": 1}
    assert engine._backoff_delay(job) == delays[0]
    # exponential escalation with bounded jitter
    for n, d in enumerate(delays, start=1):
        base = 10.0 * 2.0 ** (n - 1)
        assert base <= d <= base * 1.25
    # different jobs fan out (deterministic jitter differs)
    other = _train_job(job_id=99)
    other.restarts = {"crash": 1}
    assert engine._backoff_delay(other) != delays[0]
    # disabled backoff is exactly zero (hot-loop baseline)
    cold = _engine(_cluster(1))
    assert cold._backoff_delay(job) == 0.0


def test_crash_restart_completes_through_backoff():
    """Crash -> backoff -> restart -> finish: the job completes once the
    node pool recovers, with preemption priority and the restore charge."""
    nodes = _cluster(1)
    engine = _engine(nodes, ckpt_policy="fixed", ckpt_fixed_interval_s=60.0,
                     restart_backoff_s=30.0)
    job = _train_job(total=50_000.0)        # ~650 s of work: spans the fail
    nid = nodes[0].node_id
    events = [ClusterEvent(time=200.0, kind=NODE_FAIL, node_id=nid),
              ClusterEvent(time=300.0, kind=NODE_JOIN, node_id=nid)]
    engine.run([job], events)
    assert job.state == "done"
    assert job.restarts == {"crash": 1}
    assert job.preemptions == 1
    assert job.finish_time > 300.0          # waited out backoff + rejoin
    assert engine.crash_count == 1
    assert engine.crash_failures == 0
    assert engine.failure_log and engine.failure_log[0][2] == job.job_id
    assert job.samples_done == pytest.approx(50_000.0)


def test_combined_restart_budget_across_causes():
    """The ledger is shared: crashes alone exhaust a ``max_restarts``
    budget and the job is abandoned (counted in ``crash_failures``), and
    a pre-spent OOM budget leaves less room for crashes."""
    nodes = _cluster(1)
    engine = _engine(nodes, max_restarts=1, restart_backoff_s=0.0)
    job = _train_job(total=1e12)
    nid = nodes[0].node_id
    events = []
    for i in range(3):                      # fail/rejoin cycles
        t = 100.0 * (i + 1)
        events.append(ClusterEvent(time=t, kind=NODE_FAIL, node_id=nid))
        events.append(ClusterEvent(time=t + 10.0, kind=NODE_JOIN,
                                   node_id=nid))
    engine.run([job], events)
    assert job.state == "failed"
    assert job.total_restarts == 2          # budget 1 -> fails on restart 2
    assert engine.crash_failures == 1
    # pre-spent OOM budget: one crash tips the same budget over
    nodes2 = _cluster(1)
    engine2 = _engine(nodes2, max_restarts=1)
    job2 = _train_job(total=1e12)
    job2.restarts = {"oom": 1}
    engine2.submit_job(job2, now=0.0)
    engine2.node_fail(nodes2[0].node_id, now=100.0)
    assert job2.state == "failed"
    assert job2.total_restarts == 2
    assert job2.ooms == 1                   # the property reads the ledger


def test_ooms_property_backed_by_ledger():
    job = Job(job_id=1)
    assert job.ooms == 0 and job.total_restarts == 0
    job.record_restart("oom")
    job.record_restart("crash")
    job.record_restart("oom")
    assert job.ooms == 2
    assert job.total_restarts == 3
    assert job.restarts == {"oom": 2, "crash": 1}


# ------------------------------------------------------- serve replica loss

def _serve_job(job_id=0, replicas=4):
    from repro.core.marp import default_serve_slo, predict_serve_plans
    cfg = ARCHS["gpt2-350m"]
    plans = tuple(predict_serve_plans(cfg, 8, 2048, device_types=["v5e"]))
    return Job(job_id=job_id, cfg=cfg, kind="serve", global_batch=8,
               seq_len=2048, total_samples=100_000.0, plans=plans,
               autoscale=False, static_replicas=replicas,
               request_rate=100.0,
               slo_p95_s=default_serve_slo(cfg, plans[0], 8, 2048))


def test_node_fail_partial_serve_loss_survives_and_refills():
    nodes = _cluster(4, devices=2)
    engine = _engine(nodes, live=True)      # live path: sync scaling
    job = _serve_job(replicas=4)
    engine.submit_job(job, now=0.0)
    assert job.state == "running" and job.serve_replicas == 4
    hosts = [{nid for nid, _ in rep} for rep in job.replica_placements]
    spread = hosts[-1] - hosts[0]
    assert spread, "replicas should span nodes on a 2-device/node fleet"
    victim = sorted(spread)[0]
    before = job.serve_replicas
    crashed = engine.node_fail(victim, now=1000.0)
    assert crashed == []                    # job survived degraded
    assert job.state == "running"
    assert 0 < job.serve_replicas < before
    assert job.replica_fails > 0
    assert engine.replica_fail_count == job.replica_fails
    assert all(nid != victim for nid, _ in job.placements)
    # the SLO ledger closed the pre-fault segment at the fault
    assert job.slo_total_s >= 1000.0 - 1e-6
    assert "crash" not in job.restarts
    # recovery rides the normal scale path once capacity returns
    engine.node_join(node_id=victim, now=1100.0)
    assert job.serve_replicas == before


def test_node_fail_whole_serve_group_crashes():
    nodes = _cluster(1)
    engine = _engine(nodes, live=True)
    job = _serve_job(replicas=2)
    engine.submit_job(job, now=0.0)
    assert job.state == "running"
    crashed = engine.node_fail(nodes[0].node_id, now=500.0)
    assert crashed == [job]
    assert job.restarts.get("crash") == 1
    assert job.serve_replicas == 0 and job.replica_placements == []
    assert job.lost_work_s == 0.0           # serve progress never rolls back
    assert job.slo_total_s >= 500.0 - 1e-6  # outage honestly on the ledger


# ----------------------------------------------------------- failure traces

def test_failure_schedule_iter_matches_list_and_is_ordered():
    nodes = make_cluster([(6, 8, "v5e"), (4, 8, "RTX3090")])
    kw = dict(horizon=50_000.0, seed=7, mtbf_scale=0.01)
    listed = traces.failure_schedule(nodes, **kw)
    streamed = list(traces.failure_schedule_iter(nodes, **kw))
    assert listed == streamed               # streaming-rng discipline
    assert listed, "trace should contain failures at this scale"
    times = [e.time for e in listed]
    assert times == sorted(times)           # nondecreasing for _pull
    # every fail is paired with a later rejoin of the same node
    open_fails = {}
    for ev in listed:
        if ev.kind == NODE_FAIL:
            assert ev.node_id not in open_fails
            open_fails[ev.node_id] = ev.time
        else:
            assert ev.kind == NODE_JOIN
            assert ev.node_id in open_fails
            assert ev.time >= open_fails.pop(ev.node_id)
    assert not open_fails                   # capacity always returns


def test_failure_schedule_mtbf_scale_and_device_hazard():
    """A flakier fleet fails more; consumer cards (lower catalog MTBF)
    fail more often than TPU pods at the same scale."""
    tpu = make_cluster([(8, 8, "v5e")])
    rtx = make_cluster([(8, 8, "RTX3090")])

    def n_fails(nodes, scale):
        return sum(1 for e in traces.failure_schedule(
            nodes, horizon=200_000.0, seed=3, mtbf_scale=scale)
            if e.kind == NODE_FAIL)

    assert n_fails(tpu, 0.01) > n_fails(tpu, 0.1)
    assert n_fails(rtx, 0.05) > n_fails(tpu, 0.05)


def test_spot_schedule_crash_flag_same_draws_abrupt_kind():
    nodes = make_cluster([(10, 8, "v5e")])
    kw = dict(horizon=10_000.0, n_waves=3, wave_frac=0.2, seed=11)
    graceful = traces.spot_schedule(nodes, **kw)
    abrupt = traces.spot_schedule(nodes, crash=True, **kw)
    assert len(graceful) == len(abrupt)

    def key(evs):
        return sorted((e.time, e.node_id) for e in evs)

    assert key(graceful) == key(abrupt)     # identical rng draws
    assert {e.kind for e in graceful} == {NODE_LEAVE, NODE_JOIN}
    assert {e.kind for e in abrupt} == {NODE_FAIL, NODE_JOIN}


# -------------------------------------------------- reliability-aware MARP

def test_expected_goodput_monotone_in_devices_and_mtbf():
    cfg = ARCHS["gpt2-7b"]
    reliability.reset()
    try:
        reliability.enable(mtbf_scale=0.001)
        g8 = reliability.expected_goodput(cfg, "v5e", 8)
        g64 = reliability.expected_goodput(cfg, "v5e", 64)
        g512 = reliability.expected_goodput(cfg, "v5e", 512)
        assert 1.0 > g8 > g64 > g512 >= reliability.MIN_GOODPUT
        # LoRA checkpoints are near-free -> near-perfect goodput
        assert reliability.expected_goodput(cfg, "v5e", 64, lora_rank=16) \
            > g64
    finally:
        reliability.reset()


def test_reliability_discount_can_reorder_plans():
    """The planning claim: with reliability priced, device-hungry plans on
    a flaky fleet are discounted and the ranking shifts."""
    from repro.core.marp import predict_plans
    cfg = ARCHS["gpt2-7b"]
    kw = dict(device_types=["v5e", "RTX3090"], max_devices=512)
    reliability.reset()
    base = predict_plans(cfg, 256, 1024, **kw)
    try:
        # 1e-3 keeps small plans near-perfect while big ones pay dearly
        # (a harsher scale floors *every* plan at MIN_GOODPUT, which
        # preserves the ordering — the discount must differentiate)
        reliability.enable(mtbf_scale=1e-3)
        flaky = predict_plans(cfg, 256, 1024, **kw)
        assert [(p.device_type, p.d, p.t) for p in flaky] \
            != [(p.device_type, p.d, p.t) for p in base]
    finally:
        reliability.reset()
    assert predict_plans(cfg, 256, 1024, **kw) == base


# ------------------------------------------------- O(victims) index (S1)

def test_node_jobs_index_refcounts_stay_consistent():
    """The refcounted node->jobs index must mirror placements exactly
    through serve scale churn, crashes, and restarts."""
    nodes = _cluster(3)
    engine = _engine(nodes, live=True)
    serve = _serve_job(job_id=0, replicas=3)
    train = _train_job(job_id=1, total=1e12)
    engine.submit_job(serve, now=0.0)
    engine.submit_job(train, now=0.0)

    def check():
        want = {}
        for job in engine.jobs.values():
            for nid, _ in job.placements:
                per = want.setdefault(nid, {})
                per[job.job_id] = per.get(job.job_id, 0) + 1
        got = {nid: dict(per) for nid, per in engine._node_jobs.items()
               if per}
        assert got == want

    check()
    engine._scale_to(serve, 1, 2000.0)      # scale down
    check()
    engine._scale_to(serve, 3, 3000.0)      # scale back up
    check()
    engine.node_fail(nodes[0].node_id, now=4000.0)
    check()
    engine.node_join(node_id=nodes[0].node_id, now=5000.0)
    check()


# ---------------------------------------------- progress monotonicity (S3)

class _MonotoneEngine(LifecycleEngine):
    """Asserts samples_done is monotone non-decreasing and bounded by
    total_samples across every accrual path (graceful, crash, finish):
    a crash withholds the un-checkpointed tail, it never claws back
    progress that was already durably credited."""

    def _observe(self, job):
        last = getattr(job, "_last_seen_done", 0.0)
        assert job.samples_done >= last - 1e-9, \
            f"progress went backwards: {job.samples_done} < {last}"
        assert job.samples_done <= job.total_samples + 1e-9
        job._last_seen_done = job.samples_done

    def _accrue(self, job, now):
        super()._accrue(job, now)
        self._observe(job)

    def _accrue_crash(self, job, now):
        lost = super()._accrue_crash(job, now)
        self._observe(job)
        return lost

    def _finish(self, job, now):
        super()._finish(job, now)
        self._observe(job)


def _fuzz_failure_run(seed: int) -> None:
    rng = random.Random(seed)
    nodes = make_cluster([(rng.randint(2, 4), 8, "v5e")])
    engine = _MonotoneEngine(
        nodes, HASAdmission(), reset=True,
        ckpt_policy=rng.choice([None, "young_daly", "fixed"]),
        ckpt_fixed_interval_s=rng.choice([30.0, 300.0]),
        restart_backoff_s=rng.choice([0.0, 20.0]),
        max_restarts=rng.choice([1, 3, 8]))
    pool_nodes = engine.pool.nodes
    engine.rate_fn = lambda job, placements, d, t: \
        job_rate(job, placements, pool_nodes, d, t)
    jobs = [_train_job(job_id=i, total=rng.uniform(100.0, 20_000.0))
            for i in range(rng.randint(1, 4))]
    for job in jobs:
        job.arrival = rng.uniform(0.0, 50.0)
    events = []
    t = 0.0
    for _ in range(rng.randint(1, 8)):      # arbitrary fail/leave/join mix
        t += rng.uniform(10.0, 500.0)
        nid = rng.choice(nodes).node_id
        kind = rng.choice([NODE_FAIL, NODE_FAIL, NODE_LEAVE])
        events.append(ClusterEvent(time=t, kind=kind, node_id=nid))
        events.append(ClusterEvent(time=t + rng.uniform(1.0, 200.0),
                                   kind=NODE_JOIN, node_id=nid))
    events.sort(key=lambda e: (e.time, e.kind, e.node_id))
    engine.run(jobs, events)
    for job in jobs:
        assert job.samples_done <= job.total_samples + 1e-9
        if job.state == "done":
            assert job.samples_done == pytest.approx(job.total_samples)


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_progress_monotone_under_failures_property(seed):
    _fuzz_failure_run(seed)


def test_progress_monotone_under_failures_deterministic():
    """Deterministic twin of the hypothesis property (the container may
    not ship hypothesis): fixed seed sweep over the same fuzz body."""
    for seed in range(25):
        _fuzz_failure_run(seed)


# ------------------------------------------------- riding bugfix coverage

def test_bench_baseline_key_orders_suffixed_runs_last():
    """Lexicographic glob order puts BENCH_x.json after BENCH_x.2.json
    ('j' > '2'), silently pinning the gate to a stale baseline — the
    chronological key must rank same-day suffixed runs newest."""
    from benchmarks.compare import _baseline_key
    names = ["BENCH_20260808.json", "BENCH_20260808.3.json",
             "BENCH_20260731.json", "BENCH_20260808.2.json"]
    assert sorted(names, key=_baseline_key) == [
        "BENCH_20260731.json", "BENCH_20260808.json",
        "BENCH_20260808.2.json", "BENCH_20260808.3.json"]
    assert sorted(names)[-1] != "BENCH_20260808.3.json"  # the bug


# ------------------------------------------------------------- end-to-end

def test_young_daly_beats_no_checkpoint_on_goodput():
    """The benchmark's core claim, in miniature: under a contended fault
    trace, Young–Daly checkpointing preserves more durable work than no
    checkpointing."""
    nodes = make_cluster([(8, 8, "v5e")])
    jobs = traces.scale_workload(120, ["v5e"], seed=2,
                                 mean_interarrival=3.0, mean_minutes=30.0)
    base = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                    HASAdmission(), charge_overhead=False)
    fails = traces.failure_schedule(nodes, horizon=base.makespan, seed=5,
                                    mtbf_scale=0.01)
    assert any(e.kind == NODE_FAIL for e in fails)
    none = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                    HASAdmission(), charge_overhead=False,
                    cluster_events=list(fails))
    yd = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                  HASAdmission(), charge_overhead=False,
                  cluster_events=list(fails), ckpt_policy="young_daly",
                  restart_backoff_s=15.0)
    assert none.crashes > 0 and yd.crashes > 0
    assert yd.goodput > none.goodput
    assert yd.lost_work_s < none.lost_work_s
    assert yd.ckpt_overhead_s > 0.0
    # telemetry is additive: fault-free runs never accrue any of it
    assert base.lost_work_s == 0.0 and base.ckpt_overhead_s == 0.0
    assert base.goodput == pytest.approx(1.0)
