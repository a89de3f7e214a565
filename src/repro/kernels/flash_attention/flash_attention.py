"""Pallas TPU flash attention: causal + sliding-window, GQA-aware.

TPU-native structure (not a CUDA port): the grid's minor-most axis walks KV
blocks sequentially per (batch, q-head, q-block), carrying the online-softmax
state (m, l, acc) in VMEM scratch across grid steps — the canonical TPU
revisiting-output pattern.  Blocks fully outside the causal/window band are
skipped with ``pl.when`` so the MXU only sees useful work.  Block shapes are
128-aligned for the MXU.

The kernel runs head-major: the wrapper transposes (b, s, H, D) to
(b, H, s, D) so every block is a (seq-block, D) tile.  The TPU compiler
only accepts blocks whose last two dims are (8, 128)-divisible or whole,
and a one-head slice of the sequence-major layout puts a size-1 block on
the head axis in the second-to-last position.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            sk: int):
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2)
    q_pos0 = qi * bq
    k_pos0 = j * bk

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # is any (q, k) pair in this block pair inside the causal/window band?
    live = True
    if causal:
        live = jnp.logical_and(live, k_pos0 <= q_pos0 + bq - 1)
    if window:
        live = jnp.logical_and(live, k_pos0 + bk - 1 > q_pos0 - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]                            # (bq, D)
        k = k_ref[0, 0]                            # (bk, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        qp = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = kp < sk                               # padding mask
        if causal:
            ok = jnp.logical_and(ok, kp <= qp)
        if window:
            ok = jnp.logical_and(ok, kp > qp - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softmax_scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q: (b, sq, H, D); k, v: (b, sk, K, D); H = K*G.  Returns (b, sq, H, D)."""
    b, sq, H, D = q.shape
    _, sk, K, _ = k.shape
    G = H // K
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(sk, 8))
    # head-major, sequences padded to block multiples
    sq_p = -(-sq // bq) * bq
    sk_p = -(-sk // bk) * bk
    q = jnp.pad(q.swapaxes(1, 2), ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    k = jnp.pad(k.swapaxes(1, 2), ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    v = jnp.pad(v.swapaxes(1, 2), ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    grid = (b, H, sq_p // bq, sk_p // bk)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          bq=bq, bk=bk, sk=sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda ib, ih, iq, ik: (ib, ih // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda ib, ih, iq, ik: (ib, ih // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, H, sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out[:, :, :sq].swapaxes(1, 2)
