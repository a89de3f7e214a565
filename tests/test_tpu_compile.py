"""Compile the Pallas kernels for a described TPU v5e chip at real widths.

Interpret mode accepts block shapes that the chip's compiler refuses, so
each test lowers and compiles a kernel (or its gradient through the
dispatch layer's ``custom_vjp``, taken with ``value_and_grad`` as the
train step does: ``grad`` alone needs no forward output, and the kernel
would be dead code) for one chip of a described ``v5e:2x2``
topology and checks that the kernel is in the program
(``tpu_custom_call``) under its own name, which names it in a trace.  Nothing runs; no chip is needed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels.adam_update import adam_update_fused
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode_gqa, flash_decode_mla
from repro.kernels.ssd_scan import ssd_scan

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_backend(monkeypatch):
    """Make the dispatch layer and the kernels take their TPU branch while
    tracing here: dispatch resolves to Pallas, and kernels whose interpret
    flag follows the backend lower for the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dispatch.clear_caches()
    yield
    dispatch.clear_caches()


def _compile(kernels, fn, *shapes):
    """Compile ``fn`` and check that the Pallas call named ``kernels`` (or
    each of several) is in the program: the name is its HLO
    instruction's."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for kernel in (kernels,) if isinstance(kernels, str) else kernels:
        assert re.search(r"%" + kernel + r"(\.\d+)? = .*custom_call_target="
                         r'"tpu_custom_call"', text), kernel
    return compiled


def _sds(sharding, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (q shape, kv shape, window): gpt2-350m (MHA, d_head 64), llama3.2-3b
# (GQA 24/8), deepseek-v2's MLA (q/k carry the rope part: 128 + 64) and
# starcoder2-3b at 8192 tokens with its 4096 window, batch 1, on one chip
ATTN_WIDTHS = {
    "gpt2-350m": ((4, 1024, 16, 64), (4, 1024, 16, 64), 0),
    "llama3.2-3b": ((4, 1024, 24, 128), (4, 1024, 8, 128), 0),
    "deepseek-v2-mla": ((1, 1024, 128, 192), (1, 1024, 128, 192), 0),
    "starcoder2-3b": ((1, 8192, 24, 128), (1, 8192, 2, 128), 4096),
}
# the grad at every forward width, and at starcoder2-3b's on one chip of a
# model axis of 2 (12 of 24 heads, 1 of 2 KV heads), as its four-chip cell
# runs it
ATTN_GRAD_WIDTHS = {
    **ATTN_WIDTHS,
    "starcoder2-3b-tp2": ((1, 8192, 12, 128), (1, 8192, 1, 128), 4096),
}
BWD_KERNELS = ("flash_attention_bwd_dq", "flash_attention_bwd_dkv")


@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_flash_attention_forward(one_chip, arch):
    qs, ks, window = ATTN_WIDTHS[arch]
    q, kv = _sds(one_chip, qs), _sds(one_chip, ks)
    _compile("flash_attention_fwd",
             lambda q, k, v: flash_attention(q, k, v, window=window,
                                             interpret=False),
             q, kv, kv)


@pytest.mark.parametrize("arch", sorted(ATTN_GRAD_WIDTHS))
def test_flash_attention_grad(one_chip, tpu_backend, arch):
    qs, ks, window = ATTN_GRAD_WIDTHS[arch]
    q, kv = _sds(one_chip, qs), _sds(one_chip, ks)
    assert dispatch.resolve("attention")[0] == "pallas"

    def loss(q, k, v):
        return dispatch.attention(q, k, v, causal=True,
                                  window=window).astype(F32).sum()
    # the Pallas backward, not the chunked ref's VJP, at every width
    _compile(("flash_attention_fwd", *BWD_KERNELS),
             jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv)


def _selector():
    path = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                        "events", "flash_attention.json")
    with open(path) as f:
        return re.compile(json.load(f)["match"]["name"])


@pytest.mark.parametrize("arch", ["gpt2-350m", "starcoder2-3b", "starcoder2-3b-tp2"])
def test_flash_attention_selector_picks_forward_only(one_chip, tpu_backend,
                                                     arch):
    """In the compiled value and grad, the benchmark's attention selector
    (``chipbench/events/flash_attention.json``, read, not edited) picks
    the forward kernel's instructions and no backward call, and every
    backward call carries ``attention_bwd`` in its ``op_name``."""
    qs, ks, window = ATTN_GRAD_WIDTHS[arch]
    q, kv = _sds(one_chip, qs), _sds(one_chip, ks)

    def loss(q, k, v):
        return dispatch.attention(q, k, v, causal=True,
                                  window=window).astype(F32).sum()
    from jax._src.lib import xla_client
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    # instructions as a trace's events name them: operands with shapes
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    text = "\n".join(m.to_string(opts) for m in
                     compiled.runtime_executable().hlo_modules())
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    picked = [c for c in calls if _selector().search(c)]
    assert picked and all(c.startswith("%flash_attention_fwd")
                          for c in picked), picked
    assert len(picked) == sum(c.startswith("%flash_attention_fwd")
                              for c in calls)
    for kernel in BWD_KERNELS:
        bwd = [c for c in calls if c.startswith("%" + kernel)]
        assert bwd, kernel
        for c in bwd:
            name = re.search(r'op_name="([^"]*)"', c)
            assert name and "/attention_bwd/" in name.group(1), c


# llama3.2-3b decode: the serve smoke's cache (128 prompt + 16 generated,
# not a multiple of the 128-row block) and a 2k cache
@pytest.mark.parametrize("cache_len", [144, 2048])
def test_flash_decode_gqa(one_chip, cache_len):
    q = _sds(one_chip, (4, 1, 24, 128))
    kv = _sds(one_chip, (4, cache_len, 8, 128))
    valid = _sds(one_chip, (4, cache_len), jnp.bool_)
    _compile("flash_decode_gqa",
             lambda q, k, v, m: flash_decode_gqa(q, k, v, m,
                                                 interpret=False),
             q, kv, kv, valid)


# deepseek-v2: 128 heads, latent rank 512, rope dim 64, denom sqrt(128 + 64)
@pytest.mark.parametrize("cache_len", [144, 2048])
def test_flash_decode_mla(one_chip, cache_len):
    b, H, r, dr = 4, 128, 512, 64
    args = (_sds(one_chip, (b, H, r)), _sds(one_chip, (b, H, dr)),
            _sds(one_chip, (b, cache_len, r)),
            _sds(one_chip, (b, cache_len, dr)),
            _sds(one_chip, (b, cache_len), jnp.bool_))
    _compile("flash_decode_mla",
             lambda *a: flash_decode_mla(*a, denom=(128 + 64) ** 0.5,
                                         interpret=False), *args)


def _ssd_shapes(sharding):
    # mamba2-130m: 24 heads of 64, state 128, batch 4 x seq 1024
    b, s, h, p, n = 4, 1024, 24, 64, 128
    return (_sds(sharding, (b, s, h, p)), _sds(sharding, (b, s, h)),
            _sds(sharding, (h,), F32), _sds(sharding, (b, s, n)),
            _sds(sharding, (b, s, n)), _sds(sharding, (h,), F32),
            _sds(sharding, (h,), F32))


def test_ssd_scan_forward(one_chip):
    _compile("ssd_scan_fwd", lambda *a: ssd_scan(*a, interpret=False),
             *_ssd_shapes(one_chip))


def test_ssd_scan_grad(one_chip, tpu_backend):
    assert dispatch.resolve("ssd_scan")[0] == "pallas"

    def loss(*a):
        y, state = dispatch.ssd(*a)
        return y.astype(F32).sum() + state.sum()
    _compile("ssd_scan_fwd",
             jax.value_and_grad(loss, argnums=tuple(range(7))),
             *_ssd_shapes(one_chip))


def test_adam_update_fused(one_chip):
    flat = _sds(one_chip, (1 << 24,), F32)
    _compile("adam_update", lambda g, m, v, p: adam_update_fused(
        g, m, v, p, lr=1e-3, beta1=0.9, beta2=0.95, eps=1e-8, wd=0.1,
        c1=0.1, c2=0.1, interpret=False), flat, flat, flat, flat)


def test_train_step_2x2_mesh(topo, tpu_backend):
    """The whole gpt2-350m train step on a 2x2 (data x model) mesh: the
    SPMD partitioner cannot split a Pallas call, so each kernel must run
    per shard (``parallel.act.per_shard``)."""
    from jax.sharding import AxisType, Mesh

    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.configs.registry import get_arch
    from repro.launch.inputs import train_inputs
    from repro.train import build_train_step

    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = get_arch("gpt2-350m")
    tc = TrainConfig(global_batch=8, seq_len=1024, zero=1)
    (state, batch), (s_sh, b_sh) = train_inputs(
        cfg, ShapeConfig("train", 1024, 8, "train"), mesh, tc)
    step, _ = build_train_step(cfg, tc, mesh, 8, 1024)
    compiled = jax.jit(step, in_shardings=(s_sh, b_sh),
                       donate_argnums=(0,)).lower(state, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()


COLLECTIVE = re.compile(r"(?:\}|\)|\]) ((?:all-reduce|all-gather|reduce-scatter"
                        r"|collective-permute|all-to-all)(?:-start)?)\(")


def test_train_step_2x2_mesh_starcoder2_3b_15l(topo, tpu_backend):
    """The whole step of ``starcoder2-3b-15l.train.s8192.2x2`` (its
    configuration and traffic files) on a 2x2 (data x model) mesh: 15
    layers at StarCoder2-3B's widths, 8,192 tokens, one row a data replica
    a micro-step.  The attention backward runs per shard, the state and
    the step's temporaries fit a 16 GB chip, and the exchanges carry the
    step's names: the new parameters' all-gathers ``param_gather``.  The
    compiler reduces the gradient over the data replicas inside the
    backward, per layer, in all-reduces it leaves without a name, so
    ``grad_sync`` (the ZeRO-1 slice of the summed gradient) names no
    collective and is left in the lowered program only."""
    import json

    from jax.sharding import AxisType, Mesh

    from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
    from repro.launch.inputs import train_inputs
    from repro.train import build_train_step

    bench = os.path.join(os.path.dirname(__file__), "..", "chipbench")
    with open(os.path.join(bench, "configs", "starcoder2-3b-15l.json")) as f:
        cfg = ModelConfig(**json.load(f)["model"])
    with open(os.path.join(bench, "traffic", "train.s8192.2x2.json")) as f:
        t = json.load(f)
    B, S = t["global_batch"], t["seq_len"]
    mesh = Mesh(np.array(topo.devices[:4]).reshape(t["mesh"]),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    tc = TrainConfig(global_batch=B, seq_len=S, microbatch=t["microbatch"],
                     zero=t["zero"], remat=t["remat"])
    (state, batch), (s_sh, b_sh) = train_inputs(
        cfg, ShapeConfig("train", S, B, "train"), mesh, tc)
    step, n_micro = build_train_step(cfg, tc, mesh, B, S)
    assert n_micro == 2
    lowered = jax.jit(step, in_shardings=(s_sh, b_sh),
                      donate_argnums=(0,)).lower(state, batch)
    assert "grad_sync" in lowered.as_text(debug_info=True)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9, mem
    text = compiled.as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("/attention_bwd/" in n for n in names)
    assert re.search(r"%flash_attention_fwd(\.\d+)? = .*custom_call_target="
                     r'"tpu_custom_call"', text)
    exchanges = []
    for line in text.splitlines():
        m = COLLECTIVE.search(line.partition(" = ")[2])
        if m:
            n = re.search(r'op_name="([^"]*)"', line)
            exchanges.append((m.group(1), n.group(1) if n else ""))
    gathers = [n for op, n in exchanges if "/param_gather/" in n]
    assert gathers and all(op == "all-gather" for op, n in exchanges
                           if "/param_gather/" in n), exchanges
    assert not any("grad_sync" in n for _, n in exchanges), exchanges
    assert any(op == "all-reduce" and not n for op, n in exchanges)
