"""Pallas TPU flash attention: causal + sliding-window, GQA-aware.

TPU-native structure (not a CUDA port): the grid's minor-most axis walks KV
blocks sequentially per (batch, q-head, q-block), carrying the online-softmax
state (m, l, acc) in VMEM scratch across grid steps — the canonical TPU
revisiting-output pattern.  Blocks fully outside the causal/window band are
skipped with ``pl.when`` so the MXU only sees useful work, and their K/V
index map repeats the nearest live block so Pallas copies nothing new.

Every grid step has a fixed cost that a small tile's math does not cover,
so the blocks come from the problem's shape (``flash_attention_tiling``):
the largest that cover the lengths with little padding and fit a VMEM
budget.

The kernel runs head-major: the wrapper transposes (b, s, H, D) to
(b, H, s, D) so every block is a (seq-block, D) tile.  The TPU compiler
only accepts blocks whose last two dims are (8, 128)-divisible or whole,
and a one-head slice of the sequence-major layout puts a size-1 block on
the head axis in the second-to-last position.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: block lengths the tiling chooses from, besides a whole (short) sequence
BLOCKS = (128, 256, 512, 1024)
#: VMEM one call may fill, given to the compiler as the kernel's limit (a
#: v5e core has 128 MiB; the default limit of 16 MiB would refuse the
#: reckoned need of 1024 x 1024 blocks)
VMEM_BUDGET = 32 * 2**20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublanes(dtype) -> int:
    """Rows of the dtype's native (rows, 128) tile: 8 for 32-bit, 16 for
    16-bit."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _block_options(s: int, dtype) -> list[int]:
    """Block lengths for a sequence of ``s``: each of ``BLOCKS`` shorter
    than it that pads it by at most an eighth, and the whole sequence
    (rounded up to the dtype's tile) when it fits in one block."""
    whole = _round_up(s, _sublanes(dtype))
    opts = [b for b in BLOCKS if b < whole and _round_up(s, b) - s <= s // 8]
    if whole <= BLOCKS[-1]:
        opts.append(whole)
    return opts


def vmem_bytes(bq: int, bk: int, D: int, dtype) -> int:
    """VMEM one grid step holds: double-buffered q, k, v and o tiles, the
    f32 m, l and accumulator scratch, and the (bq, bk) temporaries (the
    f32 scores, their exp and the mask select, and ``p`` in the value
    dtype).  Rows of fewer than 128 lanes take 128."""
    lanes = _round_up(D, 128)
    item = jnp.dtype(dtype).itemsize
    tiles = 2 * (2 * bq + 2 * bk) * lanes * item
    scratch = (2 * 128 + lanes) * bq * 4
    temps = bq * bk * (3 * 4 + item)
    return tiles + scratch + temps


def block_live(q0, k0, bq: int, bk: int, causal: bool, window: int):
    """Whether the q block from row ``q0`` and the KV block from key ``k0``
    hold a (q, k) pair inside the causal/window band.  Works on ints and on
    the grid's traced positions."""
    live = True
    if causal:
        live = live & (k0 <= q0 + bq - 1)
    if window:
        live = live & (k0 + bk - 1 > q0 - window)
    return live


def kv_span(q0, bq: int, bk: int, nk: int, causal: bool, window: int):
    """First and last of the ``nk`` KV blocks that ``block_live`` can keep
    for the q block from row ``q0``.  Works on ints and on the grid's
    traced positions."""
    first = (jnp.minimum(jnp.maximum((q0 - window + 1) // bk, 0), nk - 1)
             if window else 0)
    last = jnp.minimum((q0 + bq - 1) // bk, nk - 1) if causal else nk - 1
    return first, last


def feasible_tilings(sq: int, sk: int, D: int, dtype) -> list[tuple]:
    """Every (bq, bk) from ``_block_options`` that fits ``VMEM_BUDGET``."""
    return [p for p in itertools.product(_block_options(sq, dtype),
                                         _block_options(sk, dtype))
            if vmem_bytes(*p, D, dtype) <= VMEM_BUDGET]


def flash_attention_tiling(sq: int, sk: int, D: int,
                           dtype) -> tuple[int, int]:
    """(block_q, block_k) for this problem: the feasible pair of largest
    area, the longer KV block on a tie.  On a v5e a grid step's fixed cost
    outweighs the masked work a large block does above the diagonal or
    outside a 4096 window: the largest tiles were the fastest at every
    width swept, D 64 to 192 and 1024 to 8192 tokens (PERF.md)."""
    return max(feasible_tilings(sq, sk, D, dtype),
               key=lambda p: (p[0] * p[1], p[1]))


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

    @pl.when(block_live(q_pos0, k_pos0, bq, bk, causal, window))
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
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """q: (b, sq, H, D); k, v: (b, sk, K, D); H = K*G.  Returns (b, sq, H, D).

    Blocks not given come from ``flash_attention_tiling``."""
    b, sq, H, D = q.shape
    _, sk, K, _ = k.shape
    G = H // K
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if block_q is None or block_k is None:
        tq, tk = flash_attention_tiling(sq, sk, D, q.dtype)
        block_q = block_q or tq
        block_k = block_k or tk
    bq = min(block_q, _round_up(sq, _sublanes(q.dtype)))
    bk = min(block_k, _round_up(sk, _sublanes(q.dtype)))
    # head-major, sequences padded to block multiples
    sq_p = _round_up(sq, bq)
    sk_p = _round_up(sk, bk)
    q = jnp.pad(q.swapaxes(1, 2), ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    k = jnp.pad(k.swapaxes(1, 2), ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    v = jnp.pad(v.swapaxes(1, 2), ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    nk = sk_p // bk
    grid = (b, H, sq_p // bq, nk)

    def kv_index(ib, ih, iq, ik):
        # a step off the band repeats the nearest live block's index, so
        # Pallas starts no new copy for it
        first, last = kv_span(iq * bq, bq, bk, nk, causal, window)
        return (ib, ih // G, jnp.minimum(jnp.maximum(ik, first), last), 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          bq=bq, bk=bk, sk=sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), kv_index),
            pl.BlockSpec((1, 1, bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, H, sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out[:, :, :sq].swapaxes(1, 2)
