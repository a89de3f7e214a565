"""The train step's names in its compiled program and in a profiler trace.

The device scopes (``obs.device``) reach every HLO op's ``op_name``, where
differentiation's own markers split them into phases: ``jvp(model)`` the
forward, ``transpose(jvp(model))`` the backward, ``rematted_computation``
inside it the recomputed forward.  The jitted step opens the host span
``repro/train_step`` on each call, with ``compiled`` from the process's
compile counter.  Each public op of ``kernels.dispatch`` runs under a
scope of its own name, its backward included.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import TrainConfig
from repro.configs.registry import smoke_config
from repro.kernels import dispatch
from repro.launch.mesh import make_plan_mesh
from repro.obs import device as obs_device
from repro.train import build_train_step, make_train_state, state_specs


def _tiny(**kw):
    cfg = smoke_config("gpt2-350m")
    return dataclasses.replace(cfg, **kw)


def _batch(cfg, b, s):
    tok = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                             cfg.vocab_size)
    return {"tokens": tok, "labels": tok}


def _parts(op_name):
    return op_name.split("/")


def _under(scope, op_name):
    """``op_name`` lies under ``scope``, bare or inside a transform's
    marker (``jvp(scope)``, ``transpose(jvp(scope))``)."""
    pat = re.compile(r"(^|\()" + re.escape(scope) + r"\)*$")
    return any(pat.search(p) for p in _parts(op_name))


def _op_names(compiled_text):
    """``op_name``s of the program's own ops (those under its ``jit``)."""
    return {n for n in re.findall(r'op_name="([^"]+)"', compiled_text)
            if n.startswith("jit(")}


def test_train_step_phases_in_compiled_hlo():
    """gpt2-350m's block at a small width, remat'd, 2 micro-steps: every
    op under ``model`` is exactly one of forward, recompute or backward;
    the update sits under ``optimizer`` (its Adam under the dispatch op's
    scope); attention's ops sit under ``attention`` in all three passes."""
    cfg = _tiny(num_layers=2)
    tc = TrainConfig(global_batch=4, seq_len=64, microbatch=2, zero=1,
                     remat="block")
    mesh = make_plan_mesh(1, 1)
    state = make_train_state(cfg, tc, jax.random.PRNGKey(0))
    step, n_micro = build_train_step(cfg, tc, mesh, 4, 64)
    assert n_micro == 2
    names = _op_names(
        jax.jit(step).lower(state, _batch(cfg, 4, 64)).compile().as_text())

    phases = {"forward": set(), "recompute": set(), "backward": set()}
    for n in (n for n in names if _under("model", n)):
        assert "jvp(model)" in n, n                    # differentiated
        transposed = any(p.startswith("transpose(") for p in _parts(n))
        remat = "rematted_computation" in _parts(n)
        hits = [k for k, hit in (("forward", not transposed),
                                 ("recompute", remat),
                                 ("backward", transposed and not remat))
                if hit]
        assert len(hits) == 1, n                 # a recompute is transposed
        phases[hits[0]].add(n)
        assert not _under("optimizer", n) and not _under("grad_accum", n)
    assert all(phases.values()), {k: len(v) for k, v in phases.items()}

    opt = {n for n in names if _under("optimizer", n)}
    assert any(_under("adam_update_leaf", n) for n in opt)
    assert not any(_under("model", n) for n in opt)
    assert any(_under("grad_accum", n) for n in names)
    for k, v in phases.items():
        assert any(_under("attention", n) for n in v), k


def _spans(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.start_ns, dict(e.stats)) for e in line.events
                    if e.name == "repro/train_step"]
    return [s for _, s in sorted(out, key=lambda x: x[0])]


def test_train_step_span_and_compile_counter(tmp_path):
    """Under a CPU profiler trace the jitted step opens
    ``repro/train_step`` on every call: ``compiled=1`` on the first, which
    compiles (or loads) the program, and 0 on the second, which reuses it;
    the counter moves only on the first."""
    cfg = _tiny(num_layers=1, d_model=64, d_ff=128, head_dim=16,
                num_heads=4, num_kv_heads=4, vocab_size=128)
    tc = TrainConfig(global_batch=2, seq_len=32, microbatch=2, zero=1)
    mesh = make_plan_mesh(1, 1)
    step, _ = build_train_step(cfg, tc, mesh, 2, 32, jit=True)
    assert isinstance(step, obs_device.TracedStep)
    state = make_train_state(cfg, tc, jax.random.PRNGKey(0))
    # in the layout the step hands back, as launch.train places it
    state = jax.device_put(state, jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs(cfg, tc, mesh, state),
        is_leaf=lambda x: isinstance(x, P)))
    batch = _batch(cfg, 2, 32)
    counts = [obs_device.COMPILES.count]
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            state, met = step(state, batch)
            counts.append(obs_device.COMPILES.count)
        jax.block_until_ready(met)
    finally:
        jax.profiler.stop_trace()
    assert counts[1] > counts[0] and counts[2] == counts[1]
    spans = _spans(str(tmp_path))
    assert [(s["step"], s["compiled"]) for s in spans] == [(0, 1), (1, 0)]
    # the wrapper keeps the jitted step's AOT path (launch.train uses it)
    assert step.lower(state, batch).compile() is not None


def test_compile_counter_counts_compiles_not_calls():
    obs_device.COMPILES.listen()
    x, y = jnp.arange(5.0), jnp.ones(5)
    f = jax.jit(lambda x: x * 3 - 1)
    before = obs_device.COMPILES.count
    f(x)
    mid = obs_device.COMPILES.count
    f(y)                                          # same shape: no compile
    assert mid > before and obs_device.COMPILES.count == mid


# ------------------------------------------------------ kernel dispatch --

def _with_vjp(op, args):
    """(fn, args): ``op``'s output and its VJP at a cotangent given as the
    first argument, so the program holds the op's backward as well and
    nothing outside the op."""
    ct = jax.tree.map(lambda s: jnp.ones(s.shape, s.dtype),
                      jax.eval_shape(op, *args))

    def fn(ct, *args):
        out, vjp = jax.vjp(op, *args)
        return out, vjp(ct)
    return fn, (ct,) + tuple(args)


def _dispatch_case(op):
    """(impl, fn, args) for one public dispatch op at a tiny size; the
    differentiable ops are taken with their VJP."""
    bf, f32 = jnp.bfloat16, jnp.float32
    r = jax.random.normal
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    if op.startswith("attention"):
        qkv = tuple(r(k[i], (1, 32, 2, 16), bf) for i in range(3))
        return (op.partition(":")[2],) + _with_vjp(dispatch.attention, qkv)
    if op == "ssd":
        args = (r(k[0], (1, 32, 2, 8)), r(k[1], (1, 32, 2)),
                jnp.zeros((2,), f32), r(k[2], (1, 32, 4)),
                r(k[3], (1, 32, 4)), jnp.ones((2,), f32),
                jnp.zeros((2,), f32))
        return ("ref",) + _with_vjp(
            functools.partial(dispatch.ssd, chunk=16), args)
    if op == "adam_update_leaf":
        g, m, v, p = (r(k[i], (64,), f32) for i in range(4))
        return "ref", functools.partial(
            dispatch.adam_update_leaf, lr=1e-3, beta1=0.9, beta2=0.95,
            eps=1e-8, wd=0.1, c1=0.1, c2=0.1), (g, m, jnp.abs(v), p)
    valid = jnp.ones((1, 16), bool)
    if op == "flash_decode":
        return "ref", dispatch.flash_decode, (
            r(k[0], (1, 1, 2, 8), bf), r(k[1], (1, 16, 2, 8), bf),
            r(k[2], (1, 16, 2, 8), bf), valid)
    return "ref", functools.partial(dispatch.mla_flash_decode, denom=4.0), (
        r(k[0], (1, 2, 8), bf), r(k[1], (1, 2, 4), bf),
        r(k[2], (1, 16, 8), bf), r(k[3], (1, 16, 4), bf), valid)


@pytest.mark.parametrize("op", ["attention:ref", "attention:pallas", "ssd",
                                "adam_update_leaf", "flash_decode",
                                "mla_flash_decode"])
def test_dispatch_ops_carry_their_scopes(op):
    """Every op a public dispatch op lowers to, its backward included,
    carries the op's name as a scope of its ``op_name``: the attention
    kernel's ``custom_vjp`` backward as much as the ref's own gradient."""
    impl, fn, args = _dispatch_case(op)
    scope = op.partition(":")[0]
    with dispatch.force(impl):
        text = jax.jit(fn).lower(*args).compile().as_text()
    names = _op_names(text)
    assert names
    assert all(_under(scope, n) for n in names), sorted(
        n for n in names if not _under(scope, n))
    if scope in ("attention", "ssd"):
        assert any("transpose(" in n for n in names)
