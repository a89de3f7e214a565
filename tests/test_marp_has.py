"""MARP plan enumeration + HAS Algorithm 1, incl. hypothesis properties."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import TrainConfig
from repro.configs.registry import ARCHS
from repro.core import memory_model as mm
from repro.core.devices import DEVICE_TYPES
from repro.core.has import Node, place, schedule, select_plan
from repro.core.marp import ResourcePlan, predict_plans
from repro.core.orchestrator import (Orchestrator, make_cluster,
                                     PAPER_SIM_CLUSTER)
from repro.core.serverless import submit


# ------------------------------------------------------------------ MARP ---

def test_marp_plans_feasible():
    cfg = ARCHS["gpt2-350m"]
    plans = predict_plans(cfg, 32, 1024)
    assert plans
    for p in plans:
        cap = DEVICE_TYPES[p.device_type].mem
        assert p.pred_bytes < cap
        assert p.n_devices == p.d * p.t


def test_marp_bigger_model_needs_more():
    small = predict_plans(ARCHS["gpt2-350m"], 32, 1024,
                          device_types=["A100-40G"])
    big = predict_plans(ARCHS["gpt2-7b"], 32, 1024,
                        device_types=["A100-40G"])
    assert small and big
    assert min(p.n_devices for p in big) > min(p.n_devices for p in small)


def test_marp_infeasible_on_tiny_gpu():
    plans = predict_plans(ARCHS["jamba-1.5-large-398b"], 256, 4096,
                          device_types=["RTX2080Ti"], max_devices=64)
    assert plans == []


def test_marp_paper_mode_matches_formula():
    cfg = ARCHS["gpt2-350m"]
    plans = predict_plans(cfg, 32, 1024, mode="paper",
                          device_types=["A100-40G"])
    assert plans
    p = plans[0]
    assert abs(p.pred_bytes
               - mm.paper_peak_bytes(cfg, 32, 1024, p.d, p.t)) < 1


# ------------------------------------------------------------------- HAS ---

def _nodes(spec):
    return make_cluster(spec)


def test_has_prefers_exact_fit():
    # paper example: Job(2,32GB) should go to the 40GB node with fewer
    # idle GPUs, not the 80GB one
    GB = 1024 ** 3
    nodes = [Node("a", "A100-40G", 40 * GB, 3, 3),
             Node("b", "A100-80G", 80 * GB, 6, 6)]
    plan = ResourcePlan(n_devices=2, min_mem=32 * GB, d=2, t=1,
                        device_type="A100-40G", pred_bytes=30 * GB, score=1.0)
    alloc = place(plan, nodes)
    assert alloc.placements == (("a", 2),)


def test_has_single_node_over_fragmentation():
    # Job(4,35GB): one Node(4,40) beats four Node(1,40)
    GB = 1024 ** 3
    nodes = [Node(f"one{i}", "A100-40G", 40 * GB, 1, 1) for i in range(4)]
    nodes.append(Node("big", "A100-40G", 40 * GB, 4, 4))
    plan = ResourcePlan(n_devices=4, min_mem=35 * GB, d=4, t=1,
                        device_type="A100-40G", pred_bytes=34 * GB, score=1.0)
    alloc = place(plan, nodes)
    assert alloc.placements == (("big", 4),)


def test_has_greedy_spill():
    GB = 1024 ** 3
    nodes = [Node("a", "A100-40G", 40 * GB, 2, 2),
             Node("b", "A100-40G", 40 * GB, 3, 3)]
    plan = ResourcePlan(n_devices=5, min_mem=32 * GB, d=5, t=1,
                        device_type="A100-40G", pred_bytes=30 * GB, score=1.0)
    alloc = place(plan, nodes)
    assert alloc is not None
    assert sum(k for _, k in alloc.placements) == 5


def test_select_plan_falls_through():
    GB = 1024 ** 3
    nodes = [Node("a", "A100-40G", 40 * GB, 2, 2)]
    plans = [
        ResourcePlan(1, 60 * GB, 1, 1, "A100-80G", 55 * GB, score=2.0),
        ResourcePlan(2, 30 * GB, 2, 1, "A100-40G", 28 * GB, score=1.0),
    ]
    assert select_plan(plans, nodes) is plans[1]


@settings(max_examples=60, deadline=None)
@given(
    idles=st.lists(st.tuples(st.integers(1, 8), st.sampled_from([16, 24, 40, 80])),
                   min_size=1, max_size=8),
    req_n=st.integers(1, 16),
    req_mem=st.integers(8, 80),
)
def test_has_place_invariants(idles, req_n, req_mem):
    """Property: placements never exceed idle counts, only use sufficient
    nodes, and total exactly req_n when a placement is returned."""
    GB = 1024 ** 3
    nodes = [Node(f"n{i}", "X", mem * GB, k, k)
             for i, (k, mem) in enumerate(idles)]
    plan = ResourcePlan(req_n, req_mem * GB, req_n, 1, "X",
                        req_mem * GB * 0.9, score=1.0)
    avail = sum(n.idle for n in nodes if n.mem >= plan.min_mem)
    alloc = place(plan, nodes)
    if avail >= req_n:
        assert alloc is not None
        used = {}
        for nid, k in alloc.placements:
            used[nid] = used.get(nid, 0) + k
        by_id = {n.node_id: n for n in nodes}
        for nid, k in used.items():
            assert k <= by_id[nid].idle
            assert by_id[nid].mem >= plan.min_mem
        assert sum(used.values()) == req_n
    else:
        assert alloc is None


# ----------------------------------------------------------- orchestrator --

def test_orchestrator_lifecycle():
    orch = Orchestrator(make_cluster(PAPER_SIM_CLUSTER))
    total = orch.idle_devices()
    res = submit(orch, ARCHS["gpt2-350m"], TrainConfig(global_batch=16,
                                                       seq_len=512))
    assert res.started
    used = total - orch.idle_devices()
    assert used == res.job.allocation.plan.n_devices
    orch.release(res.job.job_id)
    assert orch.idle_devices() == total


def test_orchestrator_queues_when_full():
    GB = 1024 ** 3
    orch = Orchestrator([Node("a", "A100-40G", 40 * GB, 1, 1)])
    r1 = submit(orch, ARCHS["gpt2-350m"], TrainConfig(global_batch=8,
                                                      seq_len=512))
    assert r1.started
    r2 = submit(orch, ARCHS["gpt2-350m"], TrainConfig(global_batch=8,
                                                      seq_len=512))
    assert not r2.started
    orch.release(r1.job.job_id)           # frees + auto-starts queued job
    assert orch.jobs[r2.job.job_id].state == "running"
