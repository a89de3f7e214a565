"""Memory feedback plane (PR 4): telemetry, corrector, adaptive margin,
OOM lifecycle event, and the no-repeat-OOM invariant."""
import copy

import pytest

from hypothesis import given, settings, strategies as st

from repro.cluster.schedulers import FrenzyScheduler
from repro.cluster.simulator import simulate
from repro.cluster.traces import (GPT2_SIZES, misprediction_oracle,
                                  scale_workload)
from repro.core import memtrace
from repro.core.has import Node
from repro.core.lifecycle import Job, LifecycleEngine
from repro.core.marp import (MEM_SAFETY, predict_plans, predict_plans_shared,
                             predict_serve_plans)
from repro.core.orchestrator import Orchestrator

GB = 1024 ** 3


@pytest.fixture(autouse=True)
def _clean_memtrace():
    """Each test starts from an empty, disabled plane and leaves the
    process with the committed corpus re-seeded (import-time state)."""
    memtrace.reset()
    yield
    memtrace.reset()
    memtrace.seed_from_experiments()


# ------------------------------------------------------------- corrector ---

@settings(max_examples=200, deadline=None)
@given(family=st.sampled_from(["dense", "ssm", "moe"]),
       zero=st.integers(min_value=0, max_value=3),
       device_type=st.sampled_from(["A100-40G", "v5e", "*"]),
       pred=st.floats(min_value=1e6, max_value=1e12,
                      allow_nan=False, allow_infinity=False),
       ratios=st.lists(st.floats(min_value=0.05, max_value=8.0,
                                 allow_nan=False, allow_infinity=False),
                       min_size=1, max_size=8))
def test_no_repeat_oom_invariant(family, zero, device_type, pred, ratios):
    """After ingesting an observed peak, the corrected prediction for that
    class is >= every observation — the exact placement that OOMed can
    never again be deemed feasible."""
    memtrace.reset()
    memtrace.enable()
    observations = [pred * r for r in ratios]
    for obs in observations:
        memtrace.record(family, zero, device_type, pred, obs, source="oom")
        corrected = memtrace.corrected_bytes(family, zero, device_type, pred)
        assert corrected >= obs
    corrected = memtrace.corrected_bytes(family, zero, device_type, pred)
    assert corrected >= max(observations)
    memtrace.reset()


def test_no_repeat_oom_invariant_fuzz():
    """Deterministic twin of the hypothesis property above (the container
    may lack hypothesis; the invariant must still be exercised on CI)."""
    import random
    rng = random.Random(11)
    memtrace.enable()
    for _ in range(500):
        family = rng.choice(["dense", "ssm", "moe"])
        zero = rng.randint(0, 3)
        dt = rng.choice(["A100-40G", "v5e", "*"])
        pred = rng.uniform(1e6, 1e12)
        obs = pred * rng.uniform(0.05, 8.0)
        memtrace.record(family, zero, dt, pred, obs, source="oom")
        assert memtrace.corrected_bytes(family, zero, dt, pred) >= obs
        assert memtrace.MARGIN_MIN <= memtrace.margin_for(family, zero, dt) \
            <= memtrace.MARGIN_MAX or \
            memtrace.margin_for(family, zero, dt) == memtrace.BASE_MARGIN


def test_correction_identity_when_disabled():
    memtrace.record("dense", 1, "A100-40G", 10.0 * GB, 20.0 * GB)
    pred = 10.0 * GB + 0.123
    assert memtrace.corrected_bytes("dense", 1, "A100-40G", pred) == pred
    assert memtrace.correction_for("dense", 1, "A100-40G", pred) == 1.0


def test_correction_wildcard_fallback():
    """Samples measured off-catalog (device "*") correct on-catalog
    lookups of the same class; exact-device samples take precedence."""
    memtrace.enable()
    memtrace.record("dense", 1, memtrace.ANY_DEVICE, 10.0 * GB, 15.0 * GB)
    assert memtrace.corrected_bytes("dense", 1, "v5p", 10.0 * GB) \
        == 15.0 * GB
    memtrace.record("dense", 1, "v5p", 10.0 * GB, 30.0 * GB)
    assert memtrace.corrected_bytes("dense", 1, "v5p", 10.0 * GB) \
        == 30.0 * GB
    # a different zero level is a different class
    assert memtrace.corrected_bytes("dense", 0, "v5p", 10.0 * GB) \
        == 10.0 * GB


# ---------------------------------------------------------------- margin ---

def test_margin_bounds_and_default():
    assert memtrace.margin_for("dense", 1, "A100-40G") == MEM_SAFETY
    memtrace.enable()
    # below MARGIN_MIN_SAMPLES observations: still the seed constant
    memtrace.record("dense", 1, "A100-40G", 10.0 * GB, 11.0 * GB)
    assert memtrace.margin_for("dense", 1, "A100-40G") == MEM_SAFETY
    # consistent residuals relax the margin; noisy ones tighten it — and
    # the result always stays inside [MARGIN_MIN, MARGIN_MAX]
    for obs in (11.0 * GB, 11.0 * GB, 11.0 * GB):
        memtrace.record("dense", 1, "A100-40G", 10.0 * GB, obs)
    tight = memtrace.margin_for("dense", 1, "A100-40G")
    assert tight == memtrace.MARGIN_MAX
    for obs in (5.0 * GB, 30.0 * GB, 2.0 * GB):
        memtrace.record("dense", 1, "A100-40G", 10.0 * GB, obs)
    noisy = memtrace.margin_for("dense", 1, "A100-40G")
    assert memtrace.MARGIN_MIN <= noisy < tight


# ----------------------------------------------------------- cache token ---

def test_cache_token_contract():
    """PR 1/PR 3 contract: constant while off (including after round
    trips); fresh after every enable *and* every record while on."""
    assert memtrace.cache_token() == ("off",)
    memtrace.enable()
    t1 = memtrace.cache_token()
    assert t1[0] == "on"
    memtrace.record("dense", 1, "v5e", 1.0 * GB, 2.0 * GB)
    t2 = memtrace.cache_token()
    assert t2 != t1
    memtrace.disable()
    assert memtrace.cache_token() == ("off",)
    memtrace.enable()
    assert memtrace.cache_token() not in (t1, t2)


def test_feedback_context_manager_restores_state():
    assert not memtrace.is_enabled()
    with memtrace.feedback():
        assert memtrace.is_enabled()
    assert not memtrace.is_enabled()


# ------------------------------------------------------- MARP integration ---

def test_predict_plans_exclude_oomed_class():
    """Recording an observed peak above a device's memory removes that
    (device, shape-bucket) class from the feasible sweep."""
    cfg = GPT2_SIZES["gpt2-7b"]
    base = predict_plans(cfg, 8, 1024, device_types=["A100-40G"])
    top = base[0]
    memtrace.enable()
    memtrace.record(cfg.family, top.zero, top.device_type, top.pred_bytes,
                    57.0 * GB, source="oom")           # > 40 GB device
    corrected = predict_plans(cfg, 8, 1024, device_types=["A100-40G"])
    assert all((p.d, p.t) != (top.d, top.t) for p in corrected)
    for p in corrected:
        adj = memtrace.corrected_bytes(cfg.family, p.zero, p.device_type,
                                       p.pred_bytes)
        assert adj < 40 * GB * memtrace.margin_for(cfg.family, p.zero,
                                                   p.device_type)


def test_predict_serve_plans_feedback_applies():
    cfg = GPT2_SIZES["gpt2-2.7b"]
    base = predict_serve_plans(cfg, 8, 4096, device_types=["v5e"])
    assert base and base[0].zero == 0     # serving state is zero=0
    memtrace.enable()
    top = base[0]
    memtrace.record(cfg.family, 0, "v5e", top.pred_bytes, 17.0 * GB,
                    source="oom")         # > 16 GB v5e
    corrected = predict_serve_plans(cfg, 8, 4096, device_types=["v5e"])
    assert all((p.d, p.t) != (top.d, top.t) for p in corrected)
    memtrace.disable()
    assert predict_serve_plans(cfg, 8, 4096, device_types=["v5e"]) == base


# -------------------------------------------------------- OOM lifecycle ---

def _mk_oracle(mult):
    def check(job, placements, pool):
        plan = job.plan
        if plan is None:
            return None
        true_peak = plan.pred_bytes * mult
        mem = min(pool.nodes[nid].mem for nid, _ in placements)
        return true_peak if true_peak > mem else None
    return check


def _mk_job(cfg, types, job_id=0, samples=5000):
    job = Job(job_id=job_id, arrival=0.0, cfg=cfg, global_batch=8,
              seq_len=1024, total_samples=samples)
    job.plans = predict_plans_shared(cfg, 8, 1024, device_types=types,
                                     max_devices=64)
    return job


def test_oom_crash_loop_without_feedback():
    """Static margin: the requeued job re-lands on the identical doomed
    plan and is abandoned after max_oom_retries."""
    cfg = GPT2_SIZES["gpt2-7b"]
    types = ("A100-40G",)
    job = _mk_job(cfg, types)
    res = simulate([job], [Node("n1", "A100-40G", 40 * GB, 16, 16)],
                   FrenzyScheduler(), charge_overhead=False,
                   oom_check_fn=_mk_oracle(1.6),
                   replan_fn=lambda j: _mk_job(cfg, types).plans,
                   max_oom_retries=3)
    assert job.state == "failed"
    assert res.ooms == 4 and res.oom_failures == 1
    assert res.unfinished == 1
    # every retry died on the same (device, bucket) class
    keys = {(d, memtrace.shape_bucket(p)) for _, _, d, p, _ in res.oom_log}
    assert len(keys) == 1


def test_oom_feedback_requeues_onto_headroom():
    """Feedback on: one OOM, the observation excludes the doomed class,
    and the job completes on the next satisfiable plan with headroom."""
    cfg = GPT2_SIZES["gpt2-7b"]
    types = ("A100-40G",)
    memtrace.enable()
    job = _mk_job(cfg, types)
    res = simulate([job], [Node("n1", "A100-40G", 40 * GB, 16, 16)],
                   FrenzyScheduler(), charge_overhead=False,
                   oom_check_fn=_mk_oracle(1.6),
                   replan_fn=lambda j: predict_plans_shared(
                       j.cfg, j.global_batch, j.seq_len,
                       device_types=types, max_devices=64),
                   max_oom_retries=3)
    assert job.state == "done" and job.ooms == 1
    assert res.ooms == 1 and res.oom_failures == 0
    assert job.preemptions == 1           # checkpoint-restart accounting
    # the feedback plane now knows the class
    logged = res.oom_log[0]
    assert memtrace.corrected_bytes(cfg.family, 1, "A100-40G",
                                    logged[3]) >= logged[4]


def test_oom_simulation_trace_repeat_free_with_feedback():
    """Trace-level: with feedback on, no job ever re-dies on a class it
    already died on (the benchmark's repeat metric is structurally 0)."""
    from benchmarks.oom_resilience import count_repeat_ooms
    from benchmarks.sched_scale import make_scaled_cluster
    nodes = make_scaled_cluster(50)
    types = sorted({n.device_type for n in nodes})
    jobs = scale_workload(300, types, seed=7, mean_interarrival=1.0,
                          mean_minutes=30.0)
    memtrace.enable()
    res = simulate(copy.deepcopy(jobs), nodes, FrenzyScheduler(),
                   charge_overhead=False,
                   oom_check_fn=misprediction_oracle(severity=0.6,
                                                     frac=0.3, seed=3),
                   replan_fn=lambda j: predict_plans_shared(
                       j.cfg, j.global_batch, j.seq_len,
                       device_types=tuple(types), max_devices=64))
    assert res.ooms > 0                   # the scenario actually bites
    assert count_repeat_ooms(res) == 0
    assert res.oom_failures == 0 and res.unfinished == 0


def test_live_orchestrator_oom_requeue():
    """Live path: Orchestrator.oom feeds the plane, requeues with accrued
    state, and re-admission uses the corrected ranking."""
    cfg = GPT2_SIZES["gpt2-7b"]
    memtrace.enable()
    orch = Orchestrator([Node("n1", "A100-40G", 40 * GB, 16, 16)])
    plans = predict_plans(cfg, 8, 1024, device_types=["A100-40G"])
    job = orch.submit(plans, cfg=cfg, global_batch=8, seq_len=1024)
    assert job.state == "running"
    first_plan = job.plan
    orch.oom(job.job_id, 57.0 * GB)
    assert job.ooms == 1
    # re-admitted immediately (capacity freed by its own death) under a
    # corrected plan that avoids the class that just died
    assert job.state == "running"
    assert (job.plan.d, job.plan.t) != (first_plan.d, first_plan.t)
    assert memtrace.corrected_bytes(cfg.family, first_plan.zero,
                                    first_plan.device_type,
                                    first_plan.pred_bytes) >= 57.0 * GB


# ------------------------------------------------------ seeding / source ---

def test_seed_from_experiments_ingests_committed_jsons():
    n = memtrace.seed_from_experiments()
    assert n >= 20                        # both committed ZeRO stages
    summary = memtrace.stats_summary()
    assert summary["by_source"].get("memcheck", 0) == n
    # the measured path is exercisable on CPU-only CI: enabling makes the
    # dense-family corrections live
    memtrace.enable()
    s = next(x for x in memtrace.samples() if x.ratio > 1.0)
    assert memtrace.corrected_bytes(s.family, s.zero, s.device_type,
                                    s.pred_bytes) >= s.observed_bytes


def test_device_type_for_real_device_kinds():
    """Decorated real-world kinds map onto their exact catalog class (an
    A100-80G sample must never cross-pollute A100-40G planning via the
    wildcard), off-catalog kinds fall back to '*'."""
    assert memtrace.device_type_for("NVIDIA A100-SXM4-40GB") == "A100-40G"
    assert memtrace.device_type_for("NVIDIA A100-SXM4-80GB") == "A100-80G"
    assert memtrace.device_type_for("NVIDIA GeForce RTX 2080 Ti") \
        == "RTX2080Ti"
    assert memtrace.device_type_for("TPU v5 lite") == "v5e"
    assert memtrace.device_type_for("TPU v5p") == "v5p"
    assert memtrace.device_type_for("cpu") == memtrace.ANY_DEVICE
    assert memtrace.device_type_for("") == memtrace.ANY_DEVICE


def test_elastic_migration_rescues_doomed_placement():
    """A running job whose placement is doomed (OOM pending, finish_time
    sentinel -1) must still be migratable: a surviving better-ranked plan
    always 'pays off' against an infinite predicted finish."""
    cfg = GPT2_SIZES["gpt2-7b"]
    types = ("A100-40G", "A100-80G")
    memtrace.enable()
    blocker = _mk_job(cfg, types, job_id=0, samples=200)
    victim = _mk_job(cfg, types, job_id=1, samples=50000)
    victim.arrival = 1.0
    # only 80G placements are doomed (80G plans predict low but true peak
    # exceeds the device); 40G plans survive
    def oracle(job, placements, pool):
        plan = job.plan
        if plan is None:
            return None
        mem = min(pool.nodes[nid].mem for nid, _ in placements)
        true_peak = plan.pred_bytes * (2.6 if plan.device_type == "A100-80G"
                                       else 1.0)
        return true_peak if true_peak > mem else None
    nodes = [Node("n1", "A100-40G", 40 * GB, 8, 8),
             Node("n2", "A100-80G", 80 * GB, 16, 16)]
    res = simulate([blocker, victim], nodes, FrenzyScheduler(),
                   charge_overhead=False, elastic=True,
                   oom_check_fn=oracle,
                   replan_fn=lambda j: predict_plans_shared(
                       j.cfg, j.global_batch, j.seq_len,
                       device_types=types, max_devices=64))
    # whether by migration (blocker frees 40G capacity before the OOM
    # detect window elapses) or by post-OOM replan, the victim must end
    # done, never abandoned
    assert victim.state == "done"
    assert res.oom_failures == 0


def test_save_load_round_trip(tmp_path):
    memtrace.record("dense", 1, "v5e", 1.0 * GB, 2.0 * GB, source="xla")
    memtrace.record("ssm", 0, "*", 3.0 * GB, 2.5 * GB, source="memcheck")
    path = str(tmp_path / "samples.json")
    memtrace.save(path)
    memtrace.reset()
    assert memtrace.load(path) == 2
    assert {s.source for s in memtrace.samples()} == {"xla", "memcheck"}
