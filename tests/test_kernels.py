"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.adam_update import adam_ref, adam_update_fused
from repro.kernels.flash_attention import (attention_ref, flash_attention,
                                           flash_attention_bwd)
from repro.kernels.flash_attention.flash_attention import (
    VMEM_BUDGET, block_live, bwd_vmem_bytes, feasible_bwd_tilings,
    feasible_tilings, flash_attention_bwd_tiling, flash_attention_tiling,
    kv_span, q_span, vmem_bytes)
from repro.kernels.ssd_scan import ssd_ref, ssd_scan


@pytest.mark.parametrize("b,sq,sk,H,K,D,causal,window", [
    (2, 128, 128, 4, 2, 64, True, 0),
    (1, 256, 256, 8, 8, 32, True, 64),        # MHA + sliding window
    (2, 64, 192, 4, 1, 64, False, 0),         # MQA, cross-length
    (1, 96, 96, 6, 3, 128, True, 0),          # non-pow2 seq (padding path)
    (1, 128, 128, 4, 4, 64, True, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, sq, sk, H, K, D, causal, window, dtype):
    _check_flash(b, sq, sk, H, K, D, causal, window, 64, 64, dtype)


def _check_flash(b, sq, sk, H, K, D, causal, window, bq, bk, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, H, D), dtype)
    k = jax.random.normal(ks[1], (b, sk, K, D), dtype)
    v = jax.random.normal(ks[2], (b, sk, K, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


# blocks of the shape-derived tiling (None: the tiling's own pick)
@pytest.mark.parametrize("b,sq,sk,H,K,D,causal,window,bq,bk", [
    (1, 512, 512, 2, 2, 64, True, 0, 128, 256),     # bq < bk
    (1, 512, 512, 2, 1, 64, True, 0, 256, 128),     # bq > bk, MQA
    (1, 1024, 1024, 2, 2, 64, True, 0, 256, 256),   # clamped dead steps
    (1, 1024, 1024, 2, 1, 64, True, 300, 256, 256),  # window skips blocks
    (1, 96, 96, 6, 3, 128, True, 0, None, None),    # one whole block
    (1, 1000, 1000, 2, 2, 64, True, 0, None, None),  # padded whole block
    (1, 1000, 1000, 2, 2, 64, True, 0, 256, 512),   # padded tail block
    (2, 64, 192, 4, 1, 64, False, 0, None, None),   # cross-length
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_tiled(b, sq, sk, H, K, D, causal, window, bq, bk,
                               dtype):
    _check_flash(b, sq, sk, H, K, D, causal, window, bq, bk, dtype)


def _live_steps(sq, sk, bq, bk, causal, window):
    """Grid steps a (batch, head) that compute: the block pairs the
    kernel's ``block_live`` keeps.  Each q block's live steps are the whole
    of its ``kv_span``, so clamping the K/V index map to the span leaves
    every live step's block in place."""
    nq, nk = -(-sq // bq), -(-sk // bk)
    n = 0
    for iq in range(nq):
        live = [ik for ik in range(nk)
                if block_live(iq * bq, ik * bk, bq, bk, causal, window)]
        first, last = kv_span(iq * bq, bq, bk, nk, causal, window)
        assert live == list(range(int(first), int(last) + 1)), (iq, live)
        n += len(live)
    return n


def _live_steps_by_kv(sq, sk, bq, bk, causal, window):
    """The same block pairs counted from the KV side, as the dK/dV kernel
    walks them: each KV block's live q blocks are the whole of its
    ``q_span``."""
    nq, nk = -(-sq // bq), -(-sk // bk)
    n = 0
    for ik in range(nk):
        live = [iq for iq in range(nq)
                if block_live(iq * bq, ik * bk, bq, bk, causal, window)]
        first, last = q_span(ik * bk, bq, bk, nq, causal, window)
        if live:
            assert live == list(range(int(first), int(last) + 1)), (ik, live)
        n += len(live)
    return n


def _band_blocks(sq, sk, bq, bk, causal, window):
    """Block pairs holding a (q, k) pair inside the band, counted over the
    whole (padded) score matrix."""
    sq_p, sk_p = -(-sq // bq) * bq, -(-sk // bk) * bk
    qp = np.arange(sq_p)[:, None]
    kp = np.arange(sk_p)[None, :]
    ok = np.broadcast_to(kp < sk, (sq_p, sk_p))
    if causal:
        ok = ok & (kp <= qp)
    if window:
        ok = ok & (kp > qp - window)
    return int(ok.reshape(sq_p // bq, bq, sk_p // bk, bk)
               .any(axis=(1, 3)).sum())


# (sq, sk, D, window) of the widths that call the kernel: gpt2-350m,
# llama3.2-3b, MLA (q/k carry the rope part), starcoder2 at 8192 with its
# window, and two serve prompt lengths
@pytest.mark.parametrize("sq,sk,D,window", [
    (1024, 1024, 64, 0), (1024, 1024, 128, 0), (1024, 1024, 192, 0),
    (8192, 8192, 128, 4096), (37, 37, 128, 0), (144, 144, 128, 0),
])
def test_flash_attention_tiling(sq, sk, D, window):
    bq, bk = flash_attention_tiling(sq, sk, D, jnp.bfloat16)
    for blk, s in ((bq, sq), (bk, sk)):
        # the (8, 128) rule, or the whole (padded) sequence
        assert blk % 8 == 0 and (blk % 128 == 0 or blk >= s), (blk, s)
    assert vmem_bytes(bq, bk, D, jnp.bfloat16) <= VMEM_BUDGET
    tilings = feasible_tilings(sq, sk, D, jnp.bfloat16)
    assert (bq, bk) in tilings
    for tq, tk in tilings:
        assert _live_steps(sq, sk, tq, tk, True, window) == \
            _band_blocks(sq, sk, tq, tk, True, window), (tq, tk)
    if (sq, D) == (1024, 64):                       # gpt2-350m's cell
        assert (bq, bk) == (1024, 1024)


@pytest.mark.parametrize("sq,sk,bq,bk", [
    (1024, 1024, 128, 128), (1024, 1024, 256, 128), (1024, 1024, 128, 512),
    (1000, 1000, 128, 256), (300, 700, 128, 128), (8192, 8192, 1024, 512),
])
@pytest.mark.parametrize("causal,window", [
    (True, 0), (True, 1), (True, 100), (True, 128), (True, 300),
    (True, 4096), (False, 0), (False, 200),
])
def test_q_span_matches_block_live(sq, sk, bq, bk, causal, window):
    """Every (q block, KV block) pair: the KV side's ``q_span`` keeps
    exactly the pairs ``block_live`` keeps, as ``kv_span`` does from the
    q side, and both count the band's blocks."""
    n = _live_steps_by_kv(sq, sk, bq, bk, causal, window)
    assert n == _live_steps(sq, sk, bq, bk, causal, window)
    assert n == _band_blocks(sq, sk, bq, bk, causal, window)


# (sq, sk, D, window) of the widths that train through the backward:
# gpt2-350m, llama3.2-3b, MLA, and 1k to 8k tokens with and without
# starcoder2's window
@pytest.mark.parametrize("sq,sk,D,window", [
    (1024, 1024, 64, 0), (1024, 1024, 128, 0), (1024, 1024, 192, 0),
    (2048, 2048, 128, 0), (4096, 4096, 64, 0), (4096, 4096, 192, 2048),
    (8192, 8192, 128, 4096), (8192, 8192, 192, 0), (1100, 1100, 64, 0),
])
def test_flash_attention_bwd_tiling(sq, sk, D, window):
    tiling = flash_attention_bwd_tiling(sq, sk, D, jnp.bfloat16)
    assert tiling is not None                  # no fallback to the ref
    bq, bk = tiling
    assert bq % 128 == 0 and bk % 128 == 0, tiling
    assert bwd_vmem_bytes(bq, bk, D, jnp.bfloat16) <= VMEM_BUDGET
    tilings = feasible_bwd_tilings(sq, sk, D, jnp.bfloat16)
    assert tiling in tilings
    assert max(a * b for a, b in tilings) == bq * bk
    for tq, tk in tilings:
        assert bwd_vmem_bytes(tq, tk, D, jnp.bfloat16) <= VMEM_BUDGET
        assert _live_steps_by_kv(sq, sk, tq, tk, True, window) == \
            _band_blocks(sq, sk, tq, tk, True, window), (tq, tk)
    if (sq, D) in ((1024, 64), (8192, 128)):    # the benchmark's cells
        assert tiling == (1024, 1024)


def test_flash_attention_bwd_tiling_none_when_nothing_fits():
    """A head too wide for any block pair gives no tiling: the dispatch
    layer then takes the chunked ref's VJP."""
    assert flash_attention_bwd_tiling(1024, 1024, 8192, jnp.bfloat16) is None


# blocks of the backward's own tiling (None: the tiling's own pick)
@pytest.mark.parametrize("b,sq,sk,H,K,D,causal,window,bq,bk", [
    (1, 512, 512, 2, 2, 64, True, 0, 128, 256),     # bq < bk
    (1, 512, 512, 2, 1, 64, True, 0, 256, 128),     # bq > bk, MQA
    (1, 1024, 1024, 4, 1, 64, True, 300, 256, 256),  # GQA, window skips
    (1, 300, 300, 2, 2, 32, True, 0, 128, 128),     # padded tail block
    (2, 64, 192, 4, 1, 64, False, 0, None, None),   # cross-length
    (1, 200, 200, 2, 1, 32, True, 0, None, None),   # one padded block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_bwd_tiled(b, sq, sk, H, K, D, causal, window, bq,
                                   bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (b, sq, H, D), dtype)
    k = jax.random.normal(ks[1], (b, sk, K, D), dtype)
    v = jax.random.normal(ks[2], (b, sk, K, D), dtype)
    do = jax.random.normal(ks[3], (b, sq, H, D), dtype)
    o = flash_attention(q, k, v, causal=causal, window=window,
                        interpret=True)
    got = flash_attention_bwd(q, k, v, o, do, causal=causal, window=window,
                              block_q=bq, block_k=bk, interpret=True)
    _, vjp = jax.vjp(lambda q, k, v: attention_ref(
        q, k, v, causal=causal, window=window), q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for a, w in zip(got, vjp(do)):
        assert a.dtype == w.dtype and a.shape == w.shape
        scale = max(float(jnp.max(jnp.abs(w.astype(jnp.float32)))), 1.0)
        np.testing.assert_allclose(np.asarray(a, np.float32) / scale,
                                   np.asarray(w, np.float32) / scale,
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 128, 3, 32, 16, 32),
    (1, 100, 2, 16, 8, 32),                   # padded tail chunk
    (2, 256, 4, 64, 128, 128),                # production-like dims
    (1, 64, 24, 64, 128, 64),                 # mamba2-130m head count
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(b, s, h, p, n, chunk, dtype):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt_raw = (jax.random.normal(ks[1], (b, s, h)) * 0.5).astype(dtype)
    A_log = jax.random.normal(ks[2], (h,), jnp.float32) * 0.3
    B = jax.random.normal(ks[3], (b, s, n), dtype)
    C = jax.random.normal(ks[4], (b, s, n), dtype)
    D = jax.random.normal(ks[5], (h,), jnp.float32)
    dtb = jnp.full((h,), 0.1, jnp.float32)
    y, st = ssd_scan(x, dt_raw, A_log, B, C, D, dtb, chunk=chunk,
                     interpret=True)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dtb)
    y_ref, st_ref = ssd_ref(x.astype(jnp.float32), dt, -jnp.exp(A_log),
                            B.astype(jnp.float32), C.astype(jnp.float32), D)
    tol = 2e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("shape,block", [
    ((1000,), 256), ((64, 130), 1024), ((37,), 128), ((4096,), 512),
])
def test_adam_fused_sweep(shape, block):
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 4)
    g = jax.random.normal(ks[0], shape, jnp.float32)
    m = jax.random.normal(ks[1], shape) * 0.1
    v = jnp.abs(jax.random.normal(ks[2], shape)) * 0.01
    mp = jax.random.normal(ks[3], shape)
    kw = dict(lr=1e-3, beta1=0.9, beta2=0.95, eps=1e-8, wd=0.1,
              c1=0.5, c2=0.2)
    out = adam_update_fused(g, m, v, mp, block=block, interpret=True, **kw)
    ref = adam_ref(g, m, v, mp, **kw)
    names = ["m", "v", "master", "param"]
    for a, b_, nm in zip(out, ref, names):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32),
                                   atol=1e-6, rtol=1e-5, err_msg=nm)
        assert a.shape == b_.shape


def test_chunked_attention_matches_ref():
    """The model's pure-jnp chunked attention (production CPU path) matches
    the same oracle the Pallas kernel is validated against."""
    from repro.models.attention import chunked_attention
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    b, s, H, K, D = 2, 256, 8, 4, 64
    q = jax.random.normal(ks[0], (b, s, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, K, D), jnp.float32)
    for window in (0, 96):
        out = chunked_attention(q, k, v, causal=True, window=window,
                                q_chunk=64, kv_chunk=64)
        ref = attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
