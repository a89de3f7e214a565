"""Model correctness beyond smoke: prefill/decode consistency, SSD vs naive
recurrence, MLA absorbed-decode vs train attention, MoE dispatch properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import ModelConfig
from repro.configs.registry import smoke_config
from repro.models import (init_params, forward, decode_step, init_cache,
                          cache_from_prefill, cross_entropy)
from repro.models.mamba2 import ssd_chunked
from repro.models.moe import moe_ffn, moe_capacity, init_moe
from repro.serve import prefill, greedy_decode


@pytest.mark.parametrize("arch", ["llama3.2-3b", "mamba2-130m",
                                  "deepseek-v2-236b", "starcoder2-7b",
                                  "jamba-1.5-large-398b"])
def test_prefill_then_decode_matches_full_forward(arch):
    """Teacher-forcing consistency: decode step at position s must produce
    the same logits as a full forward over s+1 tokens."""
    cfg = smoke_config(arch)
    if cfg.num_modal_tokens:
        pytest.skip("covered separately")
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    b, s = 2, 32
    toks = jax.random.randint(key, (b, s + 1), 0, cfg.vocab_size, jnp.int32)
    # full forward over s+1 tokens: logits at position s
    logits_full, _, _ = forward(cfg, params, {"tokens": toks})
    want = logits_full[:, -1, :].astype(jnp.float32)
    # prefill s tokens, then decode token s
    _, cache = prefill(cfg, params, {"tokens": toks[:, :s]}, cache_len=s + 1)
    got, _ = decode_step(cfg, params, toks[:, s:s + 1], cache, jnp.int32(s))
    got = got[:, 0, :].astype(jnp.float32)
    # bf16 + reassociated matmuls (MLA absorbed decode) + MoE capacity-drop
    # differences bound the achievable tolerance; exact-math archs are tight
    loose = cfg.num_experts > 0 or cfg.attention == "mla"
    atol = 0.8 if loose else 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=atol, rtol=0.1)
    if not loose:        # bf16 reassociation flips near-ties on MoE/MLA
        assert (jnp.argmax(got, -1) == jnp.argmax(want, -1)).mean() >= 0.5


def test_ssd_chunked_matches_naive():
    from repro.kernels.ssd_scan.ref import ssd_ref
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 6)
    b, s, h, p, n = 2, 256, 4, 32, 16
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    D = jax.random.normal(ks[5], (h,))
    y1, st1 = ssd_chunked(x, dt, A, B, C, D, chunk=64)
    y2, st2 = ssd_ref(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2),
                               atol=2e-3, rtol=2e-3)


def test_sliding_window_ring_cache_decode():
    """SWA arch: the ring KV cache (window slots) must reproduce full-cache
    logits once the window covers the live positions."""
    cfg = smoke_config("starcoder2-7b")            # smoke window = 16
    key = jax.random.PRNGKey(2)
    params = init_params(cfg, key)
    b, s = 1, 32                                    # s = 2x window
    toks = jax.random.randint(key, (b, s + 1), 0, cfg.vocab_size, jnp.int32)
    logits_full, _, _ = forward(cfg, params, {"tokens": toks})
    want = logits_full[:, -1, :].astype(jnp.float32)
    _, cache = prefill(cfg, params, {"tokens": toks[:, :s]}, cache_len=s + 1)
    # ring cache has only `window` slots: (nb, b, S, K, hd)
    assert cache["sub0"]["k"].shape[2] == cfg.sliding_window
    got_l, _ = decode_step(cfg, params, toks[:, s:s + 1], cache, jnp.int32(s))
    got = got_l[:, 0, :].astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=0.1, rtol=0.1)


def test_moe_matches_dense_mixture():
    """With enough capacity, the row-local dispatch must EXACTLY equal the
    dense top-k expert mixture (fp32)."""
    import dataclasses
    cfg = dataclasses.replace(smoke_config("mixtral-8x22b"),
                              num_experts=4, top_k=2)
    key = jax.random.PRNGKey(7)
    p = init_moe(cfg, key)
    b, s, d = 2, 16, cfg.d_model
    x = (jax.random.normal(key, (b, s, d)) * 0.5).astype(jnp.float32)
    out, _ = moe_ffn(cfg, p, x)
    logits = jnp.einsum("bsd,de->bse", x, p["router"])
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), 2)
    w = w / w.sum(-1, keepdims=True)
    ref = jnp.zeros((b, s, d))
    for e in range(4):
        h = jax.nn.silu(x @ p["w1"][e]) * (x @ p["w3"][e])
        y = h @ p["w2"][e]
        ref += y * (((idx == e) * w).sum(-1))[..., None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_moe_capacity_and_dispatch_weights():
    cfg = smoke_config("mixtral-8x22b")
    key = jax.random.PRNGKey(3)
    p = init_moe(cfg, key)
    x = jax.random.normal(key, (2, 16, cfg.d_model), jnp.bfloat16) * 0.1
    out, aux = moe_ffn(cfg, p, x)
    assert out.shape == x.shape
    assert float(aux) >= 1.0 - 1e-3      # Switch aux loss lower bound is 1
    assert not bool(jnp.isnan(out.astype(jnp.float32)).any())


@settings(max_examples=20, deadline=None)
@given(T=st.integers(1, 4096), E=st.integers(2, 64), k=st.integers(1, 6))
def test_moe_capacity_properties(T, E, k):
    k = min(k, E)
    C = moe_capacity(T, E, k)
    assert C >= 8 and C % 8 == 0
    assert C * E >= T * k                 # enough slots for all assignments


def test_cross_entropy_uniform():
    V = 64
    logits = jnp.zeros((4, 8, V))
    labels = jnp.zeros((4, 8), jnp.int32)
    np.testing.assert_allclose(float(cross_entropy(logits, labels)),
                               np.log(V), rtol=1e-5)


def test_vlm_modal_prefix_changes_logits():
    cfg = smoke_config("llava-next-34b")
    key = jax.random.PRNGKey(4)
    params = init_params(cfg, key)
    toks = jax.random.randint(key, (1, 16), 0, cfg.vocab_size, jnp.int32)
    m0 = jnp.zeros((1, cfg.num_modal_tokens, cfg.d_model), jnp.bfloat16)
    m1 = 0.05 * jnp.ones_like(m0)
    l0, _, _ = forward(cfg, params, {"tokens": toks, "modal_embeds": m0})
    l1, _, _ = forward(cfg, params, {"tokens": toks, "modal_embeds": m1})
    assert l0.shape[1] == 16 + cfg.num_modal_tokens
    assert not jnp.array_equal(l0[:, -1], l1[:, -1])


def test_greedy_decode_runs():
    cfg = smoke_config("musicgen-medium")
    key = jax.random.PRNGKey(5)
    params = init_params(cfg, key)
    prompt = jax.random.randint(key, (2, 8), 0, cfg.vocab_size, jnp.int32)
    toks = greedy_decode(cfg, params, prompt, 4, cache_len=16)
    assert toks.shape == (2, 4)
    assert int(toks.min()) >= 0 and int(toks.max()) < cfg.vocab_size
