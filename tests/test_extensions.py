"""Beyond-paper extensions: serving-mode MARP, ElasticFlow baseline,
hlo-analysis unit behaviour, and additional hypothesis properties."""
import copy

import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.registry import ARCHS
from repro.core import memory_model as mm
from repro.core.marp import predict_plans, predict_serve_plans
from repro.cluster.schedulers import ElasticFlowScheduler, FrenzyScheduler
from repro.cluster.simulator import simulate
from repro.cluster.traces import new_workload
from repro.core.orchestrator import make_cluster, PAPER_SIM_CLUSTER
from repro.launch import hlo_analysis


# ----------------------------------------------------------- serve MARP ---

def test_serve_plans_starcoder_ring_cache():
    """SWA arch: serve plans are insensitive to cache_len beyond window."""
    cfg = ARCHS["starcoder2-7b"]
    p1 = predict_serve_plans(cfg, 32, 32_768, device_types=["v5e"])
    p2 = predict_serve_plans(cfg, 32, 524_288, device_types=["v5e"])
    assert p1 and p2
    assert p1[0].n_devices == p2[0].n_devices


def test_serve_plans_big_model_needs_tensor_parallel():
    cfg = ARCHS["mixtral-8x22b"]          # 141B params, bf16 282 GB
    plans = predict_serve_plans(cfg, 16, 4096, device_types=["v5e"])
    assert plans
    assert all(p.t >= 32 for p in plans)  # 282 GB / 16 GB -> t >= ~18


def test_serve_plans_feasible_memory():
    for arch in ("llama3.2-3b", "mamba2-130m", "stablelm-12b"):
        for p in predict_serve_plans(ARCHS[arch], 8, 8192,
                                     device_types=["v5e", "v5p"]):
            assert p.pred_bytes < 95 * 2 ** 30


# ----------------------------------------------------------- elasticflow ---

def test_elasticflow_runs_and_is_worse_or_equal():
    nodes = make_cluster(PAPER_SIM_CLUSTER)
    types = sorted({n.device_type for n in nodes})
    jobs = new_workload(15, types, seed=9)
    rf = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                  FrenzyScheduler(), charge_overhead=False)
    re_ = simulate(copy.deepcopy(jobs), copy.deepcopy(nodes),
                   ElasticFlowScheduler(), charge_overhead=False)
    assert len(re_.jobs) == 15
    # heterogeneity-blind scaling should not beat memory/type-aware HAS
    assert rf.avg_jct <= re_.avg_jct * 1.05


# ------------------------------------------------------- hlo analysis ------

SYNTH_HLO = """
HloModule synth

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={}, to_apply=%body
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%i2, %ar)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> (s32[], f32[8,8]) {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %tup = (s32[], f32[8,8]) tuple(%z, %a)
  ROOT %w = (s32[], f32[8,8]) while(%tup), condition=%cond, body=%body
}
"""


def test_hlo_analysis_synthetic_loop():
    stats = hlo_analysis.analyze(SYNTH_HLO)
    # dot: 2*8*8*8 = 1024 flops, 7 loop trips (from the condition constant)
    assert stats.flops == 1024 * 7
    assert stats.collective_bytes["all-reduce"] == 8 * 8 * 4 * 7
    assert stats.collective_counts["all-reduce"] == 1


def test_hlo_shape_bytes_tuple():
    assert hlo_analysis._shape_bytes("(s32[], bf16[4,4])") == 4 + 32
    assert hlo_analysis._shape_bytes("f8e4m3fn[10]") == 10


# ---------------------------------------------------- memory properties ----

@settings(max_examples=40, deadline=None)
@given(t=st.sampled_from([1, 2, 4, 8, 16]),
       d=st.sampled_from([1, 2, 4, 8, 16]),
       arch=st.sampled_from(["llama3.2-3b", "mixtral-8x22b", "mamba2-130m",
                             "deepseek-v2-236b"]))
def test_static_bytes_monotone_in_sharding(t, d, arch):
    cfg = ARCHS[arch]
    base = mm.static_bytes(cfg, 1, 1, zero=3)
    sharded = mm.static_bytes(cfg, t, d, zero=3)
    assert sharded <= base + 1e-6
    # fully sharded zero-3 divides everything by d*t
    assert sharded == pytest.approx(base / (d * t), rel=1e-9)


@settings(max_examples=30, deadline=None)
@given(s=st.sampled_from([512, 2048, 8192]),
       mb=st.sampled_from([1, 2, 4]),
       t=st.sampled_from([1, 4, 16]))
def test_activation_bytes_monotone(s, mb, t):
    cfg = ARCHS["llama3.2-3b"]
    a = mm.activation_bytes(cfg, s, mb, t)
    assert a > 0
    assert mm.activation_bytes(cfg, 2 * s, mb, t) > a
    assert mm.activation_bytes(cfg, s, 2 * mb, t) > a


@settings(max_examples=30, deadline=None)
@given(batch=st.sampled_from([8, 32, 256]),
       seq=st.sampled_from([1024, 4096]),
       arch=st.sampled_from(["gpt2-350m", "llama3.2-3b", "stablelm-12b"]))
def test_marp_plans_sorted_and_unique_keys(batch, seq, arch):
    plans = predict_plans(ARCHS[arch], batch, seq,
                          device_types=["v5e", "v5p", "A100-80G"])
    scores = [p.score for p in plans]
    assert scores == sorted(scores, reverse=True)
    for p in plans:
        assert p.n_devices == p.d * p.t
        assert batch % p.d == 0
