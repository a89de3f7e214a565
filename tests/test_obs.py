"""Observability plane (PR 9): tracer rings, metrics registry, exports,
and the telemetry-is-free contract.

The load-bearing guarantee is bit-identity: enabling tracing/metrics must
change no placement, timestamp, or ordering of the engine — tested here by
fingerprinting full churn + OOM runs with obs off, on, and off again
(round trip).  Everything else checks the plane's own promises: bounded
memory with *reported* eviction, correct span synthesis from the flat
scalar rings, and a Chrome-trace export that parses back.
"""
import io
import json

import pytest

from repro import obs
from repro.cluster.schedulers import FrenzyScheduler
from repro.cluster.simulator import simulate, simulate_stream
from repro.cluster.traces import (churn_schedule, misprediction_oracle,
                                  scale_workload, scale_workload_iter)
from repro.core.orchestrator import make_cluster, PAPER_SIM_CLUSTER
from repro.obs.export import chrome_trace, metrics_dump
from repro.obs.metrics import Histogram, MetricsRegistry, TimeSeries
from repro.obs.trace import RingLog, Tracer, TRACER


@pytest.fixture(autouse=True)
def _obs_reset():
    """The tracer/registry are process singletons: leave them dark for
    whatever test runs next, whatever happens here."""
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


def _churn_oom_sim(n_jobs=80, seed=11):
    """Small deterministic churn + misprediction sim (regenerated per
    call — simulate mutates its jobs)."""
    nodes = make_cluster(PAPER_SIM_CLUSTER)
    types = sorted({n.device_type for n in nodes})
    jobs = scale_workload(n_jobs, types, seed=seed)
    horizon = max(j.arrival for j in jobs)
    churn = churn_schedule(nodes, horizon=horizon, churn_frac=0.3,
                           seed=seed)
    return simulate(jobs, nodes, FrenzyScheduler(), charge_overhead=False,
                    cluster_events=churn,
                    oom_check_fn=misprediction_oracle(severity=0.6,
                                                      frac=0.3, seed=seed))


def _fingerprint(r):
    """Every decision-visible output of a run."""
    return (r.makespan, r.ooms, r.preemptions, r.oom_failures,
            tuple(r.oom_log),
            tuple((j.job_id, j.state, j.start_time, j.finish_time,
                   tuple(j.placements)) for j in r.jobs))


# ------------------------------------------------------------- RingLog ---

def test_ringlog_bounds_and_reports_drops():
    log = RingLog(capacity=4)
    for i in range(10):
        log.append(i)
    assert len(log) == 4
    assert log.dropped == 6                 # eviction is counted, not silent
    assert list(log) == [6, 7, 8, 9]        # newest entries survive
    assert log[0] == 6 and log[-1] == 9
    assert log[1:3] == [7, 8]
    log.clear()
    assert len(log) == 0 and log.dropped == 0


def test_ringlog_list_equivalence():
    log = RingLog(capacity=8)
    for x in ("a", "b", "c"):
        log.append(x)
    assert log == ["a", "b", "c"]           # engine tests compare to lists
    assert log == ("a", "b", "c")
    assert bool(log)
    assert not bool(RingLog(capacity=2))


# -------------------------------------------------------------- Tracer ---

def test_tracer_job_timeline_spans():
    tr = Tracer(capacity=64)
    tr.enable()
    tr.admitted(7, arrival=1.0, start=3.0)  # implies queued [1, 3)
    tr.finished(7, 9.0)
    spans = tr.spans()
    assert ("span", 7, "queued", 1.0, 3.0) in spans
    assert ("span", 7, "running", 3.0, 9.0) in spans
    assert tr.open_segments == 0


def test_tracer_oom_fused_record():
    """One ``oom:``-prefixed mark is both the instant and the state
    transition (the engine's whole-OOM fused emit)."""
    tr = Tracer(capacity=64)
    tr.enable()
    tr.admitted(1, arrival=0.0, start=0.5)
    tr.job_state(1, "oom:backoff", 2.0)     # OOM kill -> backoff
    tr.admitted(1, arrival=0.0, start=4.0)  # requeue re-admitted
    tr.finished(1, 6.0)
    assert ("inst", "oom", 2.0, 1) in tr.instants()
    spans = tr.spans()
    assert ("span", 1, "running", 0.5, 2.0) in spans
    assert ("span", 1, "backoff", 2.0, 4.0) in spans
    assert ("span", 1, "running", 4.0, 6.0) in spans
    # terminal fused form: closes the timeline and flags the failure
    tr.admitted(2, arrival=0.0, start=0.0)
    tr.job_state(2, "oom:failed", 1.0)
    assert ("inst", "oom", 1.0, 2) in tr.instants()
    assert ("inst", "failed", 1.0, 2) in tr.instants()
    assert tr.open_segments == 0


def test_tracer_fused_fast_admit_sched_span():
    tr = Tracer(capacity=64)
    tr.enable()
    tr.admitted(3, arrival=0.0, start=1.5, pass_wall=0.002)
    assert ("sched", "arrive", 1.5, 0.002, 1) in tr.sched_spans()


def test_tracer_trim_bounds_memory_and_reports_drops():
    tr = Tracer(capacity=8)
    tr.enable()
    for i in range(100):
        tr.admitted(i, arrival=float(i), start=float(i))
    held = len(tr.adm) // 4
    assert held <= 2 * tr.capacity          # amortized trim threshold
    assert tr.dropped == 100 - held
    assert tr.n == 100                      # emitted = held + dropped
    # degradation under eviction: partial history, never an error
    assert tr.events


def test_tracer_new_run_freezes_previous_timelines():
    tr = Tracer(capacity=64)
    tr.enable()
    tr.admitted(0, arrival=0.0, start=1.0)
    tr.finished(0, 5.0)
    tr.new_run()                            # job ids restart at zero
    tr.admitted(0, arrival=100.0, start=101.0)
    tr.finished(0, 102.0)
    spans = [s for s in tr.spans() if s[2] == "running"]
    assert ("span", 0, "running", 1.0, 5.0) in spans
    assert ("span", 0, "running", 101.0, 102.0) in spans
    assert len(spans) == 2                  # runs did not chain


def test_tracer_open_segments():
    tr = Tracer(capacity=64)
    tr.enable()
    tr.admitted(1, arrival=0.0, start=0.0)
    tr.admitted(2, arrival=0.0, start=0.0)
    tr.finished(1, 3.0)
    assert tr.open_segments == 1            # job 2 still running
    tr.job_state(2, "failed", 4.0)
    assert tr.open_segments == 0


def test_tracer_cache_token_round_trip():
    tr = Tracer()
    assert tr.cache_token() == ("off",)
    tr.enable()
    t1 = tr.cache_token()
    tr.enable()
    t2 = tr.cache_token()
    assert t1[0] == t2[0] == "on" and t1 != t2  # re-enable bumps freshness
    tr.disable()
    assert tr.cache_token() == ("off",)


# ------------------------------------------------------------- metrics ---

def test_timeseries_bounded_memory():
    ts = TimeSeries(max_points=16)
    for i in range(100_000):
        ts.add(float(i), float(i % 7))
    assert len(ts) < 2 * 16                 # fixed budget, 100k samples in
    assert ts.n_samples == 100_000          # nothing lost from aggregates
    assert ts.mean() == pytest.approx(sum(i % 7 for i in range(7)) / 7,
                                      rel=1e-3)


def test_histogram_observe_many_matches_loop():
    h1, h2 = Histogram(), Histogram()
    vals = [0.0, 1e-7, 0.003, 0.5, 2.0, 1e4, -1.0]
    for v in vals:
        h1.observe(v)
    h2.observe_many(vals)
    assert h1.counts == h2.counts
    assert h1.total == h2.total == len(vals)
    assert h1.sum == pytest.approx(h2.sum)
    assert h1.percentile(0.5) == h2.percentile(0.5)


def test_metrics_registry_round_trip():
    m = MetricsRegistry()
    assert m.cache_token() == ("off",)
    m.enable(max_points=32, sample_stride=16)
    m.inc("jobs/admitted", 3)
    m.sample("cluster/util_pct", 1.0, 50.0)
    m.observe("queue/admission_wait_s", 0.25)
    m.observe_many("queue/admission_wait_s", [0.5, 1.0])
    snap = m.snapshot()
    assert snap["counters"]["jobs/admitted"] == 3
    assert snap["series"]["cluster/util_pct"]["n_samples"] == 1
    assert snap["histograms"]["queue/admission_wait_s"]["total"] == 3
    m.disable()                             # data survives for export
    assert m.snapshot()["counters"]["jobs/admitted"] == 3
    m.enable()                              # ... until the next enable
    assert m.snapshot()["counters"] == {}


# ----------------------------------------------- the bit-identity golden --

def test_obs_round_trip_is_decision_invisible():
    """Enabling the whole plane changes no placement, timestamp, or
    ordering — the ROADMAP's telemetry-is-free invariant, over the
    densest event mix (churn + OOM + backoff)."""
    base = _fingerprint(_churn_oom_sim())
    obs.enable()
    try:
        traced = _fingerprint(_churn_oom_sim())
    finally:
        obs.disable()
    after = _fingerprint(_churn_oom_sim())  # singleton left no residue
    assert traced == base
    assert after == base


# ------------------------------------------------------------- exports ---

@pytest.fixture(scope="module")
def obs_export():
    """One obs-on churn + OOM run, exported (module-scoped: the payloads
    are plain dicts, independent of the singletons the autouse fixture
    clears)."""
    obs.enable()
    try:
        r = _churn_oom_sim()
    finally:
        obs.disable()
    trace = chrome_trace()
    metrics = metrics_dump()
    obs.clear()
    return r, trace, metrics


def test_chrome_trace_parses_and_has_structure(obs_export):
    r, trace, metrics = obs_export
    payload = json.loads(json.dumps(trace))  # Perfetto wants plain JSON
    evs = payload["traceEvents"]
    assert any(e.get("ph") == "X" and e.get("cat") == "job" for e in evs)
    assert any(e.get("ph") == "X" and e.get("cat") == "sched" for e in evs)
    assert any(e.get("ph") == "C" and e.get("name") == "cluster.util_pct"
               for e in evs)
    assert payload["otherData"]["dropped_events"] == 0
    # churn can strand requeued/backoff jobs at run end; every open
    # segment must belong to an unfinished job
    assert 0 <= payload["otherData"]["open_segments"] <= r.unfinished
    if r.ooms:
        assert any(e.get("ph") == "i" and e.get("name") == "oom"
                   for e in evs)
    # every OOM the engine counted is an instant in the trace
    ooms = [e for e in evs if e.get("ph") == "i" and e.get("name") == "oom"]
    assert len(ooms) == r.ooms
    # scheduler passes in the trace match the engine's counter
    sched = [e for e in evs
             if e.get("ph") == "X" and e.get("cat") == "sched"]
    assert len(sched) == r.sched_calls


def test_report_round_trip(obs_export):
    from repro.obs.report import report
    _, trace, metrics = obs_export
    out = io.StringIO()
    report(trace, metrics, out=out)
    text = out.getvalue()
    assert "utilization" in text
    assert "scheduler wall time by kind" in text
    assert "queue depth" in text
    assert "queue/admission_wait_s" in text


def test_serve_sim_feeds_serve_metrics():
    """The serve plane feeds the registry: replica-count series and SLO
    attainment samples appear once autoscaling activity starts (and the
    serve run's decisions stay obs-invisible like everything else)."""
    from repro.cluster.traces import serve_workload
    nodes = make_cluster(PAPER_SIM_CLUSTER)
    types = sorted({n.device_type for n in nodes})
    jobs, events = serve_workload(3, types, seed=4)
    obs.enable(sample_stride=4)             # serve sims are event-sparse
    try:
        r = simulate(jobs, nodes, FrenzyScheduler(), charge_overhead=False,
                     rate_events=events)
        from repro.obs.metrics import METRICS
        assert r.scale_ups > 0              # the bursty trace must scale
        assert METRICS.series["serve/replicas"].n_samples > 0
        assert METRICS.counters["serve/slo_total_s"] > 0.0
        assert "serve/slo_attainment" in METRICS.series
    finally:
        obs.disable()


# ----------------------------------------------------- engine ring logs --

def test_engine_oom_log_ring_drops_reported(monkeypatch):
    """With a tiny log cap the engine keeps the newest entries and the
    eviction count surfaces on the result — never silent."""
    monkeypatch.setattr("repro.core.lifecycle.DEFAULT_LOG_CAPACITY", 4)
    r = _churn_oom_sim()
    assert r.ooms > 4                       # the fixture must overflow it
    assert len(r.oom_log) == 4
    assert r.oom_log_dropped == r.ooms - 4


# ----------------------------------------------- streamed bounded memory --

def test_streamed_sim_with_obs_stays_bounded():
    """The streamed path is exactly where unbounded telemetry would bite:
    with a small ring capacity the tracer holds at most 2x capacity
    records per ring while the run keeps going, drops are reported, and
    metrics stay within their fixed budgets."""
    nodes = make_cluster(PAPER_SIM_CLUSTER)
    types = sorted({n.device_type for n in nodes})
    obs.enable(trace_capacity=256, max_points=64, sample_stride=8)
    try:
        r = simulate_stream(scale_workload_iter(2_000, types, seed=5),
                            nodes, FrenzyScheduler(),
                            charge_overhead=False)
        assert r.n_finished > 0
        assert len(TRACER.adm) // 4 <= 2 * 256
        assert TRACER.dropped > 0           # it really did wrap
        assert TRACER.n >= 2_000            # ... while counting everything
        from repro.obs.metrics import METRICS
        for ts in METRICS.series.values():
            assert len(ts) < 2 * 64
    finally:
        obs.disable()
