"""Dispatch-layer guards.

The kernel registry rewrite must be *behaviour-preserving* on CPU: the
seed call sites invoked the chunked-jnp paths directly, so the functions
below include seed-verbatim copies of those call sites and assert the
dispatched production paths produce **bit-identical** outputs.  The Pallas
side is exercised through dispatch in interpret mode against the jnp
oracle.  Resolution overhead is perf-smoked (cached resolve must amortize
to a dict hit).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.configs.registry import smoke_config
from repro.kernels import dispatch
from repro.kernels.flash_attention import attention_ref
from repro.models.attention import (chunked_attention, gqa_attend_train,
                                    gqa_project_qkv, init_gqa)
from repro.models.mamba2 import init_mamba2, mamba2_forward, ssd_chunked
from repro.parallel.act import constrain
from repro.train.optimizer import adam_update, init_opt_state


def _identical(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    assert np.array_equal(np.asarray(jax.device_get(a), np.float32),
                          np.asarray(jax.device_get(b), np.float32))


# ------------------------------------------------------------ resolution ---

def test_resolve_defaults_per_backend():
    assert dispatch.resolve("attention", backend="cpu")[0] == "ref"
    assert dispatch.resolve("attention", backend="gpu")[0] == "ref"
    assert dispatch.resolve("attention", backend="tpu")[0] == "pallas"
    for op in dispatch.ops():
        name, fn = dispatch.resolve(op)
        assert name == ("pallas" if jax.default_backend() == "tpu" else "ref")
        assert callable(fn)


def test_force_context_overrides():
    assert dispatch.resolve("ssd_scan", backend="cpu")[0] == "ref"
    with dispatch.force("pallas"):
        assert dispatch.resolve("ssd_scan", backend="cpu")[0] == "pallas"
        with dispatch.force("ref"):
            assert dispatch.resolve("ssd_scan", backend="tpu")[0] == "ref"
        assert dispatch.resolve("ssd_scan", backend="cpu")[0] == "pallas"
    assert dispatch.resolve("ssd_scan", backend="cpu")[0] == "ref"


def test_env_override(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    assert dispatch.resolve("attention", backend="cpu")[0] == "pallas"
    monkeypatch.setenv(dispatch.ENV_VAR, "ref")
    assert dispatch.resolve("attention", backend="tpu")[0] == "ref"
    monkeypatch.setenv(dispatch.ENV_VAR, "auto")
    assert dispatch.resolve("attention", backend="cpu")[0] == "ref"
    # force() beats the env var
    monkeypatch.setenv(dispatch.ENV_VAR, "ref")
    with dispatch.force("pallas"):
        assert dispatch.resolve("attention", backend="cpu")[0] == "pallas"


def test_resolve_overhead_amortizes_to_dict_hit():
    """Perf smoke: steady-state resolve is a dict lookup.  The bound is
    ~40x above a laptop's measured ~0.5us/call, like test_sched_perf."""
    dispatch.resolve("attention")                      # warm the cache
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        dispatch.resolve("attention")
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 20e-6, f"resolve not cached: {per_call*1e6:.1f}us/call"


def test_autotune_cache_keying():
    dispatch.clear_caches()

    def tune(dims, dtype, **kw):
        return dispatch.autotuned("op", dims, dtype,
                                  candidates=[{"block": 64}, {"block": 128}],
                                  default={"block": 64}, **kw)

    assert tune((64, 32), jnp.float32) == {"block": 64}   # CPU: the default
    tune((60, 32), jnp.float32)                    # same bucket: no new key
    info1 = dispatch.autotune_cache_info()
    assert len(info1) == 1
    (op, bucket, dtype, backend), params = next(iter(info1.items()))
    assert op == "op" and bucket == (64, 32) and dtype == "float32"
    assert backend == jax.default_backend()
    tune((32, 32), jnp.float32)                    # new bucket
    assert len(dispatch.autotune_cache_info()) == 2
    tune((64, 32), jnp.bfloat16)                   # new dtype key
    assert len(dispatch.autotune_cache_info()) == 3
    tune((64, 32), jnp.float32, exact=(7,))        # exact key component
    assert len(dispatch.autotune_cache_info()) == 4
    # attention's blocks come from the kernel's own tiling, not the cache
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, 2, 32), jnp.float32)
    with dispatch.force("pallas"):
        dispatch.attention(q, k, v)
    assert len(dispatch.autotune_cache_info()) == 4
    dispatch.clear_caches()


# ------------------------------------- CPU golden: bit-identical to seed ---

def _seed_gqa_attend_train(cfg, p, x, positions):
    """Verbatim pre-dispatch ``gqa_attend_train`` (direct chunked call)."""
    q, k, v = gqa_project_qkv(cfg, p, x, positions)
    o = chunked_attention(q, k, v, causal=True, window=cfg.sliding_window)
    o = constrain(o, "batch", "seq", "heads", None)
    out = constrain(jnp.einsum("bshk,hkd->bsd", o, p["wo"]),
                    "batch", "seq", None)
    return out, {"k": k, "v": v}


@pytest.mark.parametrize("arch", ["gpt2-350m", "starcoder2-3b"])
def test_gqa_layer_cpu_bit_identical_to_seed(arch):
    if jax.default_backend() != "cpu":
        pytest.skip("CPU golden")
    cfg = smoke_config(arch)
    key = jax.random.PRNGKey(0)
    p = init_gqa(cfg, key)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model),
                          jnp.bfloat16)
    pos = jnp.arange(64)
    want, kv_w = _seed_gqa_attend_train(cfg, p, x, pos)
    got, kv_g = gqa_attend_train(cfg, p, x, pos)
    _identical(got, want)
    _identical(kv_g["k"], kv_w["k"])


def _seed_ssd_call(xs, dt_raw, A_log, B, C, D, dt_bias):
    """Verbatim pre-dispatch ``mamba2_forward`` SSD section."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias)
    A = -jnp.exp(A_log)
    return ssd_chunked(xs, dt, A, B, C, D)


def test_ssd_op_cpu_bit_identical_to_seed():
    if jax.default_backend() != "cpu":
        pytest.skip("CPU golden")
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 5)
    b, s, h, p, n = 2, 256, 4, 32, 16
    xs = jax.random.normal(ks[0], (b, s, h, p), jnp.bfloat16)
    dt_raw = jax.random.normal(ks[1], (b, s, h), jnp.bfloat16)
    A_log = jax.random.normal(ks[2], (h,)) * 0.3
    B = jax.random.normal(ks[3], (b, s, n), jnp.bfloat16)
    C = jax.random.normal(ks[4], (b, s, n), jnp.bfloat16)
    D = jnp.ones((h,))
    dtb = jnp.full((h,), 0.1, jnp.float32)
    y_w, st_w = _seed_ssd_call(xs, dt_raw, A_log, B, C, D, dtb)
    y_g, st_g = dispatch.ssd(xs, dt_raw, A_log, B, C, D, dtb)
    _identical(y_g, y_w)
    _identical(st_g, st_w)


def test_mamba2_forward_cpu_bit_identical_to_seed():
    """Whole-layer check: the dispatched mamba2_forward output equals the
    seed composition (projection/conv unchanged + seed SSD call)."""
    if jax.default_backend() != "cpu":
        pytest.skip("CPU golden")
    cfg = smoke_config("mamba2-130m")
    p = init_mamba2(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, cfg.d_model),
                          jnp.bfloat16)
    out, cache = mamba2_forward(cfg, p, x)
    with dispatch.force("ref"):                         # explicit = implicit
        out2, cache2 = mamba2_forward(cfg, p, x)
    _identical(out, out2)
    _identical(cache["ssd"], cache2["ssd"])


def _seed_adam_update(tc, params, opt, grads, step):
    """Verbatim pre-dispatch ``train.optimizer.adam_update``."""
    from repro.train.optimizer import lr_at
    lr = lr_at(tc, step)
    t = step.astype(jnp.float32) + 1.0
    c1 = 1.0 - tc.beta1 ** t
    c2 = 1.0 - tc.beta2 ** t
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))

    def upd(g, m, v, mp):
        g = g.astype(jnp.float32)
        m = tc.beta1 * m + (1.0 - tc.beta1) * g
        v = tc.beta2 * v + (1.0 - tc.beta2) * jnp.square(g)
        mhat = m / c1
        vhat = v / c2
        wd = tc.weight_decay if mp.ndim >= 2 else 0.0
        new_mp = mp - lr * (mhat / (jnp.sqrt(vhat) + tc.eps) + wd * mp)
        return m, v, new_mp

    flat_g, treedef = jax.tree.flatten(grads)
    flat_m = treedef.flatten_up_to(opt["m"])
    flat_v = treedef.flatten_up_to(opt["v"])
    flat_p = treedef.flatten_up_to(opt["master"])
    new_m, new_v, new_master = [], [], []
    for g, m, v, mp in zip(flat_g, flat_m, flat_v, flat_p):
        m2, v2, p2 = upd(g, m, v, mp)
        new_m.append(m2)
        new_v.append(v2)
        new_master.append(p2)
    new_opt = {"master": treedef.unflatten(new_master),
               "m": treedef.unflatten(new_m),
               "v": treedef.unflatten(new_v)}
    new_params = jax.tree.map(lambda mp, p: mp.astype(p.dtype),
                              new_opt["master"], params)
    return new_params, new_opt, gnorm


def test_adam_update_cpu_bit_identical_to_seed():
    if jax.default_backend() != "cpu":
        pytest.skip("CPU golden")
    tc = TrainConfig()
    key = jax.random.PRNGKey(5)
    params = {"w": jax.random.normal(key, (16, 8), jnp.bfloat16),
              "b": jax.random.normal(key, (8,), jnp.float32)}
    opt = init_opt_state(params)
    grads = jax.tree.map(
        lambda p: jax.random.normal(key, p.shape, jnp.float32), params)
    for step in (0, 7):
        s = jnp.asarray(step, jnp.int32)
        p_w, o_w, g_w = _seed_adam_update(tc, params, opt, grads, s)
        p_g, o_g, g_g = adam_update(tc, params, opt, grads, s)
        _identical(g_g, g_w)
        for k in params:
            _identical(p_g[k], p_w[k])
            for part in ("master", "m", "v"):
                _identical(o_g[part][k], o_w[part][k])


# ----------------------------- Pallas (interpret) through dispatch vs ref ---

@pytest.mark.parametrize("b,sq,sk,H,K,D,causal,window", [
    (2, 128, 128, 4, 2, 64, True, 0),          # GQA causal
    (1, 128, 128, 8, 8, 32, True, 64),         # MHA + sliding window
    (1, 64, 192, 4, 1, 64, False, 0),          # MQA, cross-length
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dispatch_pallas_attention_matches_ref(b, sq, sk, H, K, D, causal,
                                               window, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, H, D), dtype)
    k = jax.random.normal(ks[1], (b, sk, K, D), dtype)
    v = jax.random.normal(ks[2], (b, sk, K, D), dtype)
    with dispatch.force("pallas"):
        out = dispatch.attention(q, k, v, causal=causal, window=window)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


# ---------------- custom_vjp: Pallas forward, Pallas backward kernels ---

# blocks come from the shape (flash_attention_bwd_tiling): 1,100 tokens
# take 128-row blocks, 1,536 and 2,560 take 512
@pytest.mark.parametrize("b,s,H,K,D,window", [
    (2, 128, 4, 2, 64, 0),                     # GQA causal
    (1, 128, 4, 4, 32, 48),                    # MHA + sliding window
    (1, 256, 8, 2, 64, 0),                     # GQA, two 128-row blocks
    (1, 2560, 12, 1, 32, 1200),                # GQA 12:1, window of 3 blocks
    (1, 1100, 4, 2, 32, 0),                    # padded tail block
    (1, 256, 4, 2, 128, 0),                    # D 128
    (1, 256, 4, 4, 192, 0),                    # D 192 (MLA's width)
    (1, 1536, 4, 2, 64, 300),                  # window inside one block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dispatch_pallas_attention_grad_matches_ref(b, s, H, K, D, window,
                                                    dtype):
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (b, s, H, D), dtype)
    k = jax.random.normal(ks[1], (b, s, K, D), dtype)
    v = jax.random.normal(ks[2], (b, s, K, D), dtype)
    w = jax.random.normal(ks[3], (b, s, H, D), jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=True, window=window).astype(jnp.float32)
            * w)
    with dispatch.force("pallas"):
        got = jax.value_and_grad(loss(dispatch.attention),
                                 argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loss(attention_ref), argnums=(0, 1, 2))(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = max(float(jnp.max(jnp.abs(b_.astype(jnp.float32)))), 1.0)
        np.testing.assert_allclose(np.asarray(a, np.float32) / scale,
                                   np.asarray(b_, np.float32) / scale,
                                   atol=tol, rtol=tol)


def test_dispatch_pallas_attention_grad_falls_back_without_tiling():
    """A head too wide for any backward block pair: the custom_vjp takes
    the chunked ref's VJP, and the gradients still match the ref's."""
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_bwd_tiling)
    b, s, H, D = 1, 16, 1, 16384
    assert flash_attention_bwd_tiling(s, s, D, jnp.float32) is None
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q, k, v = (jax.random.normal(key, (b, s, H, D), jnp.float32)
               for key in ks)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v, causal=True) ** 2)
    with dispatch.force("pallas"):
        got = jax.grad(loss(dispatch.attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(attention_ref), argnums=(0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        scale = max(float(jnp.max(jnp.abs(w))), 1.0)
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(w) / scale,
                                   atol=2e-5, rtol=2e-5)


def test_dispatch_pallas_ssd_grad_matches_ref():
    from repro.kernels.ssd_scan import ssd_ref
    ks = jax.random.split(jax.random.PRNGKey(12), 8)
    b, s, h, p, n = 1, 128, 2, 32, 16
    args = (jax.random.normal(ks[0], (b, s, h, p)),
            jax.random.normal(ks[1], (b, s, h)) * 0.5,
            jax.random.normal(ks[2], (h,)) * 0.3,
            jax.random.normal(ks[3], (b, s, n)),
            jax.random.normal(ks[4], (b, s, n)),
            jax.random.normal(ks[5], (h,)),
            jnp.full((h,), 0.1, jnp.float32))
    wy = jax.random.normal(ks[6], (b, s, h, p))
    ws = jax.random.normal(ks[7], (b, h, p, n))

    def loss(y, st):
        return jnp.sum(y * wy) + jnp.sum(st * ws)

    def pallas_loss(*a):
        return loss(*dispatch.ssd(*a, chunk=32))

    def ref_loss(x, dt_raw, A_log, B, C, D, dt_bias):
        dt = jax.nn.softplus(dt_raw + dt_bias)
        return loss(*ssd_ref(x, dt, -jnp.exp(A_log), B, C, D))
    with dispatch.force("pallas"):
        got = jax.value_and_grad(pallas_loss, argnums=tuple(range(7)))(*args)
    want = jax.value_and_grad(ref_loss, argnums=tuple(range(7)))(*args)
    for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = max(float(jnp.max(jnp.abs(b_))), 1.0)
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b_) / scale,
                                   atol=2e-3, rtol=2e-3)


def test_dispatch_pallas_ssd_and_adam_match_ref():
    key = jax.random.PRNGKey(6)
    ks = jax.random.split(key, 5)
    b, s, h, p, n = 1, 128, 2, 32, 16
    xs = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt_raw = jax.random.normal(ks[1], (b, s, h)) * 0.5
    A_log = jax.random.normal(ks[2], (h,)) * 0.3
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    D = jnp.ones((h,))
    dtb = jnp.full((h,), 0.1, jnp.float32)
    y_ref, st_ref = dispatch.ssd(xs, dt_raw, A_log, B, C, D, dtb)
    with dispatch.force("pallas"):
        y, st = dispatch.ssd(xs, dt_raw, A_log, B, C, D, dtb)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               atol=2e-3, rtol=2e-3)

    g = jax.random.normal(ks[0], (1000,))
    m = jnp.zeros((1000,))
    v = jnp.abs(jax.random.normal(ks[1], (1000,))) * 0.01
    mp = jax.random.normal(ks[2], (1000,))
    kw = dict(lr=1e-3, beta1=0.9, beta2=0.95, eps=1e-8, wd=0.1,
              c1=0.5, c2=0.2)
    ref = dispatch.adam_update_leaf(g, m, v, mp, **kw)
    with dispatch.force("pallas"):
        out = dispatch.adam_update_leaf(g, m, v, mp, **kw)
    for a, b_ in zip(out, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-6, rtol=1e-5)
