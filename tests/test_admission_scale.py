"""PR 7 guards: incremental sharded admission must be *decision-identical*
to the PR 2 list-scan pass, the streaming run path must match the
materialized one, and the new queue containers must agree with their
naive references.

``_ScanAdmission`` below is the verbatim pre-shard ``HASAdmission.schedule``
body (list scan over ``fifo_order`` with the id(plans) no-fit dedupe) —
every golden test runs both schedulers over deep-copied traces and asserts
per-job outcomes and ``SimResult`` accounting are bit-identical across
plain, churn+elastic, OOM, and serve scenarios.
"""
import copy
import random

import pytest

from hypothesis import given, settings, strategies as st

from repro.cluster import traces
from repro.cluster.schedulers import FrenzyScheduler
from repro.cluster.simulator import simulate, simulate_stream
from repro.core import lifecycle, memtrace
from repro.core.has import ClusterPool, Node
from repro.core.lifecycle import (AdmissionQueue, Scheduler, SortedIdDict,
                                  SortedIdSet, _AdmissionShard, _fifo_key,
                                  _record_plan, fifo_order)
from repro.core.marp import ResourcePlan, predict_plans_shared
from repro.core.orchestrator import make_cluster

TYPES = ("RTX2080Ti", "A100-40G", "RTX6000")
CLUSTER_SPEC = [(6, 8, "RTX2080Ti"), (4, 8, "A100-40G"), (2, 4, "RTX6000")]


class _ScanAdmission(Scheduler):
    """The PR 2 admission pass, verbatim: full ``fifo_order`` list scan
    with the id(plans) no-fit dedupe.  ``admits_single`` stays False, so
    the engine runs this full pass on every (gate-open) arrival — the
    pre-PR control flow."""
    name = "scan-has"
    applies_to_pool = True

    def schedule(self, queued, state):
        pool = state
        select_plan = pool.select_plan
        find_placements = pool.find_placements
        out = []
        no_fit = set()
        for job in fifo_order(queued):
            plans_key = id(job.plans)
            if plans_key in no_fit:
                continue
            plan = select_plan(job.plans)
            if plan is None:
                no_fit.add(plans_key)
                continue
            placements = find_placements(plan)
            if placements is None:
                continue
            pool.apply(placements)
            _record_plan(job, plan, placements)
            out.append((job, placements, plan.d, plan.t))
        return out


def _job_state(j):
    return (j.job_id, j.state, j.start_time, j.finish_time,
            tuple(j.placements), j.plan_rank, j.preemptions, j.migrations,
            j.ooms, j.samples_done)


def _run_both(jobs, **kw):
    """Simulate the same trace under sharded and scan admission; assert
    bit-identical outcomes; return the sharded result."""
    a = simulate(copy.deepcopy(jobs), make_cluster(list(CLUSTER_SPEC)),
                 FrenzyScheduler(), charge_overhead=False,
                 **copy.deepcopy(kw))
    b = simulate(copy.deepcopy(jobs), make_cluster(list(CLUSTER_SPEC)),
                 _ScanAdmission(), charge_overhead=False,
                 **copy.deepcopy(kw))
    sa = sorted(map(_job_state, a.jobs))
    sb = sorted(map(_job_state, b.jobs))
    assert sa == sb
    for f in ("sched_calls", "makespan", "preemptions", "migrations",
              "unfinished", "ooms", "oom_failures", "scale_ups",
              "scale_downs"):
        assert getattr(a, f) == getattr(b, f), f
    return a


def test_golden_plain_trace():
    jobs = traces.scale_workload(300, TYPES, seed=11, mean_interarrival=0.5,
                                 mean_minutes=3.0)
    res = _run_both(jobs)
    assert res.unfinished == 0


def test_golden_churn_elastic_trace():
    jobs = list(traces.mixed_scale_workload_iter(150, 80, TYPES, seed=5,
                                                 mean_interarrival=0.5,
                                                 mean_minutes=3.0))
    nodes = make_cluster(list(CLUSTER_SPEC))
    horizon = max(j.arrival for j in jobs) + 600.0
    churn = traces.churn_schedule(nodes, horizon=horizon, churn_frac=0.3,
                                  seed=5)
    res = _run_both(jobs, cluster_events=churn, elastic=True)
    assert res.preemptions > 0              # the churn actually bit


def test_golden_oom_trace():
    memtrace.reset()

    def replan(job):
        return predict_plans_shared(job.cfg, job.global_batch, job.seq_len,
                                    device_types=TYPES, max_devices=64)

    jobs = traces.scale_workload(150, TYPES, seed=23, mean_interarrival=0.5,
                                 mean_minutes=3.0)
    oracle = traces.misprediction_oracle(severity=0.6, frac=0.3, seed=23)
    res = _run_both(jobs, oom_check_fn=oracle, replan_fn=replan)
    memtrace.reset()
    assert res.ooms > 0                     # the oracle actually bit


def test_golden_serve_trace():
    train = traces.scale_workload(60, TYPES, seed=9, mean_interarrival=2.0,
                                  mean_minutes=5.0)
    serve, rates = traces.serve_workload(6, TYPES, horizon=1800.0, seed=9,
                                         start_id=len(train))
    jobs = train + serve
    res = _run_both(jobs, rate_events=rates)
    assert res.scale_ups > 0                # the autoscaler actually ran


# ------------------------------------------------------- streaming run path

def test_stream_matches_list_sim():
    jobs = traces.scale_workload(400, TYPES, seed=7)
    a = simulate(copy.deepcopy(jobs), make_cluster(list(CLUSTER_SPEC)),
                 FrenzyScheduler(), charge_overhead=False)
    b = simulate_stream(traces.scale_workload_iter(400, TYPES, seed=7),
                        make_cluster(list(CLUSTER_SPEC)), FrenzyScheduler(),
                        charge_overhead=False)
    assert b.n_jobs == 400 and b.n_finished == len(a.finished)
    assert b.makespan == a.makespan
    assert b.sched_calls == a.sched_calls
    assert b.avg_jct == pytest.approx(a.avg_jct, rel=1e-12)
    assert b.avg_queue_time == pytest.approx(a.avg_queue_time, rel=1e-12,
                                             abs=1e-12)
    # the whole point: the engine never held the whole 400-job trace
    assert 0 < b.peak_live_jobs < 300
    assert b.sched_time_by_kind          # telemetry populated


def test_stream_engine_drops_finished_jobs():
    engine_holder = {}
    orig_run = lifecycle.LifecycleEngine.run

    def spy_run(self, *a, **k):
        engine_holder["engine"] = self
        return orig_run(self, *a, **k)

    lifecycle.LifecycleEngine.run = spy_run
    try:
        res = simulate_stream(
            traces.scale_workload_iter(200, TYPES, seed=3),
            make_cluster(list(CLUSTER_SPEC)), FrenzyScheduler(),
            charge_overhead=False)
    finally:
        lifecycle.LifecycleEngine.run = orig_run
    assert res.n_finished == 200
    assert len(engine_holder["engine"].jobs) == 0   # all dropped on finish


def test_stream_per_job_outcomes_match_list():
    captured = []
    nodes = make_cluster(list(CLUSTER_SPEC))
    jobs = traces.scale_workload(150, TYPES, seed=13)
    a = simulate(copy.deepcopy(jobs), nodes, FrenzyScheduler(),
                 charge_overhead=False)

    from repro.cluster.simulator import job_rate
    engine = lifecycle.LifecycleEngine(
        make_cluster(list(CLUSTER_SPEC)), FrenzyScheduler(),
        charge_overhead=False, retain_jobs=False,
        on_complete=lambda j: captured.append(_job_state(j)), reset=True)
    pool_nodes = engine.pool.nodes
    engine.rate_fn = lambda job, placements, d, t: \
        job_rate(job, placements, pool_nodes, d, t)
    engine.run(iter(traces.scale_workload_iter(150, TYPES, seed=13)))
    assert sorted(captured) == sorted(map(_job_state, a.jobs))


# ------------------------------------------------- shard-exactness property

_PLAN_ST = st.builds(
    lambda dt, n, mem: ResourcePlan(n_devices=n, min_mem=mem * 2 ** 30,
                                    d=n, t=1, device_type=dt,
                                    pred_bytes=float(mem * 2 ** 30),
                                    score=1.0, zero=0),
    st.sampled_from(TYPES), st.integers(1, 24), st.sampled_from([8, 11, 24]))

_NODE_ST = st.builds(
    lambda i, dt, mem, total, used: Node(
        node_id=f"n{i}", device_type=dt, mem=mem * 2 ** 30, total=total,
        idle=max(total - used, 0)),
    st.integers(0, 10 ** 6), st.sampled_from(TYPES), st.sampled_from([11, 24, 40]),
    st.integers(1, 8), st.integers(0, 8))


@settings(max_examples=200, deadline=None)
@given(st.lists(_PLAN_ST, min_size=1, max_size=6, unique_by=id),
       st.lists(_NODE_ST, min_size=1, max_size=12,
                unique_by=lambda n: n.node_id))
def test_ineligible_shard_never_hides_an_admissible_job(plans, nodes):
    """The shard skip bound is a *necessary* condition for admission: when
    ``eligible()`` says skip, ``select_plan`` must fail too — a skipped
    shard can never contain a job the list scan would have admitted."""
    pool = ClusterPool(nodes)
    shard = _AdmissionShard(0, id(plans), tuple(plans))
    if not shard.eligible(pool.idle_by_type):
        assert pool.select_plan(tuple(plans)) is None


def _rand_plan(rng):
    mem = rng.choice([8, 11, 24])
    return ResourcePlan(n_devices=rng.randint(1, 24),
                        min_mem=mem * 2 ** 30, d=1, t=1,
                        device_type=rng.choice(TYPES),
                        pred_bytes=float(mem * 2 ** 30), score=1.0, zero=0)


def _rand_nodes(rng):
    out = []
    for i in range(rng.randint(1, 12)):
        total = rng.randint(1, 8)
        out.append(Node(node_id=f"n{i}", device_type=rng.choice(TYPES),
                        mem=rng.choice([11, 24, 40]) * 2 ** 30, total=total,
                        idle=rng.randint(0, total)))
    return out


def test_ineligible_shard_never_hides_admissible_job_random():
    """Deterministic-random fallback of the hypothesis property above —
    always runs, hypothesis installed or not."""
    rng = random.Random(1234)
    for _ in range(500):
        plans = tuple(_rand_plan(rng)
                      for _ in range(rng.randint(1, 6)))
        pool = ClusterPool(_rand_nodes(rng))
        shard = _AdmissionShard(0, id(plans), plans)
        if not shard.eligible(pool.idle_by_type):
            assert pool.select_plan(plans) is None


@settings(max_examples=100, deadline=None)
@given(st.lists(_NODE_ST, min_size=1, max_size=12,
                unique_by=lambda n: n.node_id))
def test_idle_by_type_counters_track_scan(nodes):
    pool = ClusterPool(nodes)
    scan = {}
    for n in pool.nodes.values():
        scan[n.device_type] = scan.get(n.device_type, 0) + n.idle
    assert {k: v for k, v in pool.idle_by_type.items() if v} == \
           {k: v for k, v in scan.items() if v}


# ---------------------------------------------------------- queue containers

def _mk_queue_job(jid, arrival, plans, preemptions=0, remaining=100.0):
    j = lifecycle.Job(job_id=jid, arrival=arrival, cfg=None, global_batch=8,
                      seq_len=128, total_samples=100, plans=plans)
    j.preemptions = preemptions
    j.samples_done = float(j.total_samples) - remaining
    return j


def _mk_plans(dt="RTX2080Ti", n=2):
    return (ResourcePlan(n_devices=n, min_mem=8 * 2 ** 30, d=n, t=1,
                         device_type=dt, pred_bytes=1.0, score=1.0,
                         zero=0),)


def test_admission_queue_matches_sorted_reference():
    rng = random.Random(42)
    plan_lists = [_mk_plans("RTX2080Ti", 2), _mk_plans("A100-40G", 4),
                  _mk_plans("RTX6000", 1)]
    q = AdmissionQueue()
    ref = []
    next_id = 0
    for step in range(600):
        op = rng.random()
        if op < 0.55 or not ref:
            pre = rng.random() < 0.3
            j = _mk_queue_job(next_id, rng.uniform(0, 1000),
                              rng.choice(plan_lists),
                              preemptions=1 if pre else 0,
                              remaining=rng.uniform(1, 99))
            next_id += 1
            q.append(j)
            ref.append(j)
        elif op < 0.8:
            j = rng.choice(ref)
            ref.remove(j)
            assert q.discard(j)
            assert not q.discard(j)         # idempotent
        else:
            # pop the global head through its shard, like the sharded pass
            shard = min(q.shards(), key=lambda s: s.head()[0])
            j = q.pop_head(shard)
            assert j is min(ref, key=_fifo_key)
            ref.remove(j)
        assert len(q) == len(ref)
        assert [j.job_id for j in q.ordered()] == \
               [j.job_id for j in sorted(ref, key=_fifo_key)]
        assert q.min_need() == min((j.min_devices for j in ref),
                                   default=float("inf"))
    assert fifo_order(q) == sorted(ref, key=_fifo_key)


def test_debug_queue_crosscheck_runs():
    old = lifecycle.DEBUG_QUEUE
    lifecycle.DEBUG_QUEUE = True
    try:
        jobs = traces.scale_workload(80, TYPES, seed=31,
                                     mean_interarrival=0.2)
        res = simulate(jobs, make_cluster(list(CLUSTER_SPEC)),
                       FrenzyScheduler(), charge_overhead=False)
        assert res.unfinished == 0
    finally:
        lifecycle.DEBUG_QUEUE = old


def test_sorted_id_set():
    s = SortedIdSet()
    ref = set()
    rng = random.Random(7)
    for _ in range(500):
        x = rng.randrange(100)
        if rng.random() < 0.6:
            s.add(x)
            ref.add(x)
        else:
            s.discard(x)
            ref.discard(x)
        assert list(s) == sorted(ref)
        assert (x in s) == (x in ref)
        assert len(s) == len(ref) and bool(s) == bool(ref)


def test_sorted_id_dict():
    d = SortedIdDict()
    ref = {}
    rng = random.Random(8)
    for _ in range(500):
        k = rng.randrange(60)
        if rng.random() < 0.65:
            v = rng.randrange(1, 9)
            d[k] = v
            ref[k] = v
        else:
            assert d.pop(k, None) == ref.pop(k, None)
        assert list(d) == sorted(ref)
        assert len(d) == len(ref)
        if ref:
            assert d.min_value() == min(ref.values())


# -------------------------------------------------------- finetune traffic

def test_lora_state_bytes_tiny_and_migration_cheap():
    from repro.ckpt.checkpoint import (lora_state_bytes, migration_seconds,
                                       state_bytes)
    cfg = traces.GPT2_SIZES["gpt2-774m"]
    full = state_bytes(cfg)
    lora = lora_state_bytes(cfg, rank=16)
    assert 0 < lora < full / 50             # adapters are a rounding error
    assert state_bytes(cfg, lora_rank=16) == lora
    assert migration_seconds(cfg, lora_rank=16) < migration_seconds(cfg) / 50


def test_finetune_workload_shape():
    jobs = traces.finetune_workload(40, TYPES, seed=1, start_id=1000)
    assert len(jobs) == 40
    assert all(j.kind == "finetune" and j.lora_rank in (8, 16, 32)
               for j in jobs)
    assert [j.job_id for j in jobs] == list(range(1000, 1040))
    assert all(j.cfg.name in traces.FINETUNE_SIZES for j in jobs)


def test_mixed_workload_merges_by_arrival_and_completes():
    jobs = list(traces.mixed_scale_workload_iter(80, 40, TYPES, seed=2))
    assert len(jobs) == 120
    assert all(a.arrival <= b.arrival for a, b in zip(jobs, jobs[1:]))
    assert len({j.job_id for j in jobs}) == 120
    res = simulate(jobs, make_cluster(list(CLUSTER_SPEC)),
                   FrenzyScheduler(), charge_overhead=False)
    assert res.unfinished == 0
    done_kinds = {j.kind for j in res.finished}
    assert done_kinds == {"train", "finetune"}
