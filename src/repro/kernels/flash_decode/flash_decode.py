"""Pallas TPU split-KV flash decode: single-token attention over a long
KV cache, parallelised over cache-length blocks.

Decode attention has almost no work per (batch, head) pair — one query row
against S cached keys — so the train flash-attention structure (sequential
KV walk carrying VMEM state per q-block) leaves the chip idle on the axis
that actually has parallelism: the cache length.  Here the grid's KV-block
axis carries **no** cross-step state; every (batch, kv-head, cache-block)
program emits an independent partial

    acc  = sum_j exp(s_j - m) v_j        (unnormalised, block-local max m)
    m    = max_j s_j
    l    = sum_j exp(s_j - m)

and a tiny second pass (plain jnp, fused by XLA) merges the partials with
the running-max rescale ``exp(m_block - m_global)`` — the classic
two-pass online-softmax reduction.  Blocks may therefore run on any core
in any order, which is what keeps long-context decode from serialising.

Both cache layouts served by ``models/attention.py`` are covered:

* ``flash_decode_gqa`` — q (b,1,H,D) against k/v (b,S,K,D), H = K*G;
* ``flash_decode_mla`` — matrix-absorbed latent decode: q_lat/q_rope
  against the compressed c_kv / shared k_rope cache, output in latent
  space (the per-head K/V are never materialised).

Masking is data-dependent (ring-buffer validity per row), so the mask
arrives as an explicit (b, S) operand rather than an iota comparison.
Fully-masked blocks emit (acc=0, l=0, m=NEG_INF) and drop out of the
combine with zero weight.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _combine(acc, m, l, out_dtype):
    """Merge per-block partials over the block axis (axis 1)."""
    m_g = jnp.max(m, axis=1)
    alpha = jnp.exp(m - jnp.expand_dims(m_g, 1))
    l_g = jnp.sum(l * alpha, axis=1)
    out = jnp.sum(acc * alpha[..., None], axis=1)
    return (out / jnp.maximum(l_g, 1e-30)[..., None]).astype(out_dtype)


# -------------------------------------------------------------- GQA ------
# The cache keeps its (b, S, K, D) layout: a block holds every KV head of
# bs cache rows, (bs, K, D), whose last two dims are whole, and the kernel
# walks the heads.  A one-head block would put a size-1 block on the head
# axis in the second-to-last position, which the TPU compiler refuses, and
# transposing the cache to head-major would cost a full cache pass per
# token.  m and l carry a trailing unit dim for the same tiling rule.

def _gqa_kernel(q_ref, k_ref, v_ref, valid_ref, acc_ref, m_ref, l_ref, *,
                scale: float, n_kv: int):
    ok = valid_ref[0] > 0                           # (1, bs)
    for h in range(n_kv):
        q = q_ref[0, h]                             # (G, D)
        k = k_ref[0, :, h, :]                       # (bs, D)
        v = v_ref[0, :, h, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, bs)
        s = jnp.where(ok, s, NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)       # (G, 1)
        # a fully-masked block has m == NEG_INF and exp(s - m) == 1 garbage;
        # zeroing p keeps its (acc, l) partial inert in the combine
        p = jnp.where(ok, jnp.exp(s - m), 0.0)
        acc_ref[0, 0, h] = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # (G, D)
        m_ref[0, 0, h] = m
        l_ref[0, 0, h] = jnp.sum(p, axis=1, keepdims=True)


def flash_decode_gqa(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     valid: jax.Array, *,
                     softmax_scale: Optional[float] = None,
                     block_s: int = 256,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q: (b, 1, H, D); k_cache, v_cache: (b, S, K, D); valid: (b, S) bool.
    Returns (b, 1, H, D)."""
    b, _, H, D = q.shape
    _, S, K, _ = k_cache.shape
    G = H // K
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bs = min(block_s, _round_up(S, 128))
    Sp = _round_up(S, bs)
    vmask = valid.astype(jnp.int32)
    if Sp != S:
        pad = ((0, 0), (0, Sp - S))
        k_cache = jnp.pad(k_cache, pad + ((0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, pad + ((0, 0), (0, 0)))
        vmask = jnp.pad(vmask, pad)                  # padding is masked out
    ns = Sp // bs

    acc, m, l = pl.pallas_call(
        functools.partial(_gqa_kernel, scale=scale, n_kv=K),
        grid=(b, ns),
        in_specs=[
            pl.BlockSpec((1, K, G, D), lambda ib, js: (ib, 0, 0, 0)),
            pl.BlockSpec((1, bs, K, D), lambda ib, js: (ib, js, 0, 0)),
            pl.BlockSpec((1, bs, K, D), lambda ib, js: (ib, js, 0, 0)),
            pl.BlockSpec((1, 1, bs), lambda ib, js: (ib, 0, js)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, K, G, D), lambda ib, js: (ib, js, 0, 0, 0)),
            pl.BlockSpec((1, 1, K, G, 1), lambda ib, js: (ib, js, 0, 0, 0)),
            pl.BlockSpec((1, 1, K, G, 1), lambda ib, js: (ib, js, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, ns, K, G, D), jnp.float32),
            jax.ShapeDtypeStruct((b, ns, K, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, ns, K, G, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode_gqa",
    )(q.reshape(b, K, G, D), k_cache, v_cache, vmask[:, None, :])
    out = _combine(acc, m[..., 0], l[..., 0], v_cache.dtype)  # (b, K, G, D)
    return out.reshape(b, 1, H, D)


# -------------------------------------------------------------- MLA ------

def _mla_kernel(ql_ref, qr_ref, c_ref, kr_ref, valid_ref, acc_ref, m_ref,
                l_ref, *, denom: float):
    ql = ql_ref[0]                                   # (H, r)
    qr = qr_ref[0]                                   # (H, dr)
    c = c_ref[0]                                     # (bs, r)
    kr = kr_ref[0]                                   # (bs, dr)
    s = (jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)) / denom
    ok = valid_ref[0] > 0                            # (1, bs)
    s = jnp.where(ok, s, NEG_INF)                    # (H, bs)
    m = jnp.max(s, axis=1, keepdims=True)            # (H, 1)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    acc_ref[0, 0] = jax.lax.dot_general(
        p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (H, r)
    m_ref[0, 0] = m
    l_ref[0, 0] = jnp.sum(p, axis=1, keepdims=True)


def flash_decode_mla(q_lat: jax.Array, q_rope: jax.Array, c_kv: jax.Array,
                     k_rope: jax.Array, valid: jax.Array, *, denom: float,
                     block_s: int = 256,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q_lat: (b, H, r); q_rope: (b, H, dr); c_kv: (b, S, r);
    k_rope: (b, S, dr); valid: (b, S) bool.  Returns o_lat (b, H, r)."""
    b, H, r = q_lat.shape
    _, S, dr = k_rope.shape
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bs = min(block_s, _round_up(S, 128))
    Sp = _round_up(S, bs)
    vmask = valid.astype(jnp.int32)
    if Sp != S:
        pad = ((0, 0), (0, Sp - S))
        c_kv = jnp.pad(c_kv, pad + ((0, 0),))
        k_rope = jnp.pad(k_rope, pad + ((0, 0),))
        vmask = jnp.pad(vmask, pad)
    ns = Sp // bs

    acc, m, l = pl.pallas_call(
        functools.partial(_mla_kernel, denom=denom),
        grid=(b, ns),
        in_specs=[
            pl.BlockSpec((1, H, r), lambda ib, js: (ib, 0, 0)),
            pl.BlockSpec((1, H, dr), lambda ib, js: (ib, 0, 0)),
            pl.BlockSpec((1, bs, r), lambda ib, js: (ib, js, 0)),
            pl.BlockSpec((1, bs, dr), lambda ib, js: (ib, js, 0)),
            pl.BlockSpec((1, 1, bs), lambda ib, js: (ib, 0, js)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, H, r), lambda ib, js: (ib, js, 0, 0)),
            pl.BlockSpec((1, 1, H, 1), lambda ib, js: (ib, js, 0, 0)),
            pl.BlockSpec((1, 1, H, 1), lambda ib, js: (ib, js, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, ns, H, r), jnp.float32),
            jax.ShapeDtypeStruct((b, ns, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, ns, H, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode_mla",
    )(q_lat, q_rope, c_kv, k_rope, vmask[:, None, :])
    return _combine(acc, m[..., 0], l[..., 0], c_kv.dtype)   # (b, H, r)
