"""Pallas TPU flash attention: causal + sliding-window, GQA-aware, forward
and backward.

TPU-native structure (not a CUDA port): the grid's minor-most axis walks KV
blocks sequentially per (batch, q-head, q-block), carrying the online-softmax
state (m, l, acc) in VMEM scratch across grid steps — the canonical TPU
revisiting-output pattern.  Blocks fully outside the causal/window band are
skipped with ``pl.when`` so the MXU only sees useful work, and their K/V
index map repeats the nearest live block so Pallas copies nothing new.

The backward (``flash_attention_bwd``) is two calls over the same band,
each rebuilding the softmax from the scores rather than reading it from the
forward, whose only output is ``o``:

* ``flash_attention_bwd_dq`` walks a q block's live KV blocks twice: the
  first sweep finds each row's log-sum-exp (``lse``), the second
  accumulates ``dq``.  It also writes ``lse`` and ``delta = rowsum(dO · O)``
  as rows of ``sq`` lanes for the second call.
* ``flash_attention_bwd_dkv`` holds one KV block and walks the G query
  heads that read it and their q blocks that reach it (``q_span``),
  accumulating ``dk`` and ``dv``.  It works on the transposed scores
  (keys on rows), so the row statistics broadcast along sublanes and every
  product is a plain or a right-transposed matmul.

Every grid step has a fixed cost that a small tile's math does not cover,
so the blocks come from the problem's shape (``flash_attention_tiling``,
``flash_attention_bwd_tiling``): the largest that cover the lengths with
little padding and fit a VMEM budget.

The kernels run head-major: the wrappers transpose (b, s, H, D) to
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


def _block_options(s: int, tile: int) -> list[int]:
    """Block lengths for a sequence of ``s``: each of ``BLOCKS`` shorter
    than it that pads it by at most an eighth, and the whole sequence
    (rounded up to ``tile``) when it fits in one block."""
    whole = _round_up(s, tile)
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


def q_span(k0, bq: int, bk: int, nq: int, causal: bool, window: int):
    """First and last of the ``nq`` q blocks that ``block_live`` can keep
    for the KV block from key ``k0``: ``kv_span`` seen from the keys."""
    first = jnp.minimum(k0 // bq, nq - 1) if causal else 0
    last = (jnp.minimum((k0 + bk + window - 2) // bq, nq - 1) if window
            else nq - 1)
    return first, last


def _band(q0, k0, shape, q_axis: int, causal: bool, window: int,
          sk: int | None):
    """Which entries of a score block lie in the causal/window band: rows
    of queries from ``q0`` on axis ``q_axis``, keys from ``k0`` on the
    other, keys at or past ``sk`` (padding) out unless ``sk`` is None."""
    qp = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kp = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    ok = True if sk is None else kp < sk
    if causal:
        ok = jnp.logical_and(ok, kp <= qp)
    if window:
        ok = jnp.logical_and(ok, kp > qp - window)
    return ok


def _head_major(x: jax.Array, s_p: int) -> jax.Array:
    """(b, s, H, D) to (b, H, s_p, D), the sequence zero-padded to s_p."""
    return jnp.pad(x.swapaxes(1, 2),
                   ((0, 0), (0, 0), (0, s_p - x.shape[1]), (0, 0)))


def feasible_tilings(sq: int, sk: int, D: int, dtype) -> list[tuple]:
    """Every (bq, bk) from ``_block_options`` that fits ``VMEM_BUDGET``."""
    return [p for p in itertools.product(_block_options(sq, _sublanes(dtype)),
                                         _block_options(sk, _sublanes(dtype)))
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
        s = jnp.where(_band(q_pos0, k_pos0, (bq, bk), 0, causal, window, sk),
                      s, NEG_INF)
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
    q = _head_major(q, sq_p)
    k = _head_major(k, sk_p)
    v = _head_major(v, sk_p)
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


# ------------------------------------------------------------- backward ---

def bwd_vmem_bytes(bq: int, bk: int, D: int, dtype) -> int:
    """VMEM the larger of the two backward calls holds in one grid step:
    double-buffered tiles (the dq call's q, o, dO, k, v and f32 dq; the
    dK/dV call's q, dO, k, v, dk and dv), the double-buffered ``lse`` and
    ``delta`` rows (8 sublanes each), the f32 scratch (the dq call's m, l,
    ``lse`` and ``delta`` columns; the dk and dv accumulators), and the
    (bq, bk) temporaries: scores, P, dP, dS and the mask select in f32, P
    and dS in the value dtype.  Rows of fewer than 128 lanes take 128."""
    lanes = _round_up(D, 128)
    item = jnp.dtype(dtype).itemsize
    dq = (2 * (3 * bq + 2 * bk) * lanes * item + 2 * bq * lanes * 4
          + 4 * bq * 128 * 4)
    dkv = 2 * (2 * bq + 4 * bk) * lanes * item + 2 * bk * lanes * 4
    rows = 2 * 2 * 8 * bq * 4
    temps = bq * bk * (5 * 4 + 2 * item)
    return max(dq, dkv) + rows + temps


def feasible_bwd_tilings(sq: int, sk: int, D: int, dtype) -> list[tuple]:
    """Every (bq, bk) from ``_block_options`` at whole 128-row tiles (a q
    block is also the lane width of the ``lse`` and ``delta`` rows) that
    fits ``VMEM_BUDGET`` by ``bwd_vmem_bytes``."""
    return [p for p in itertools.product(_block_options(sq, 128),
                                         _block_options(sk, 128))
            if bwd_vmem_bytes(*p, D, dtype) <= VMEM_BUDGET]


def flash_attention_bwd_tiling(sq: int, sk: int, D: int,
                               dtype) -> tuple[int, int] | None:
    """(block_q, block_k) for the backward: the forward's rule (the
    feasible pair of largest area, the longer KV block on a tie) over
    ``feasible_bwd_tilings``.  None where no pair fits: the caller then
    takes another backward."""
    tilings = feasible_bwd_tilings(sq, sk, D, dtype)
    if not tilings:
        return None
    return max(tilings, key=lambda p: (p[0] * p[1], p[1]))


def _masked(s, ok):
    return s if ok is True else jnp.where(ok, s, NEG_INF)


def _col_to_row(x):
    """(n, 1) to (1, n), through a transpose of 128 lanes."""
    return jnp.broadcast_to(x, (x.shape[0], 128)).T[:1]


def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, dq_ref, lse_ref,
               delta_ref, m_scr, l_scr, lse_scr, delta_scr, *, scale: float,
               causal: bool, window: int, bq: int, bk: int, sk: int | None):
    """Grid (b, H, q block, 2 x KV block).  Sweep 1 (j < nk) keeps the
    online max and sum of each row; its last step turns them into
    ``lse``, takes ``delta`` from o and dO and writes both as rows.  Sweep
    2 accumulates ``dq`` in the f32 output block."""
    j = pl.program_id(3)
    nk = pl.num_programs(3) // 2
    q_pos0 = pl.program_id(2) * bq
    k_pos0 = (j % nk) * bk
    live = block_live(q_pos0, k_pos0, bq, bk, causal, window)

    def scores():
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk)
        return _masked(s, _band(q_pos0, k_pos0, (bq, bk), 0, causal, window,
                                sk))

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(jnp.logical_and(j < nk, live))
    def _stats():
        s = scores()
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        l_scr[...] = l_scr[...] * jnp.exp(m_prev - m_new) + jnp.sum(
            jnp.exp(s - m_new), axis=1, keepdims=True)
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _rows():
        lse = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))
        delta = jnp.sum(o_ref[0, 0].astype(jnp.float32)
                        * do_ref[0, 0].astype(jnp.float32),
                        axis=1, keepdims=True)
        lse_scr[...] = lse
        delta_scr[...] = delta
        lse_ref[0, 0] = _col_to_row(lse)
        delta_ref[0, 0] = _col_to_row(delta)
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(jnp.logical_and(j >= nk, live))
    def _dq():
        k = k_ref[0, 0]
        do = do_ref[0, 0]
        p = jnp.exp(scores() - lse_scr[...])
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_scr[...])
        dq_ref[0, 0] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == 2 * nk - 1)
    def _finish():
        dq_ref[...] = dq_ref[...] * scale


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale: float, causal: bool,
                window: int, bq: int, bk: int, sk: int | None, nq: int):
    """Grid (b, K, KV block, G x q block).  Scores are taken transposed,
    (bk, bq), so ``lse`` and ``delta`` apply as rows and dV += P^T dO,
    dK += dS^T Q are plain matmuls."""
    t = pl.program_id(3)
    q_pos0 = (t % nq) * bq
    k_pos0 = pl.program_id(2) * bk

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(block_live(q_pos0, k_pos0, bq, bk, causal, window))
    def _compute():
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        s = jax.lax.dot_general(
            k_ref[0, 0], q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bk, bq)
        s = _masked(s, _band(q_pos0, k_pos0, (bk, bq), 1, causal, window, sk))
        p = jnp.exp(s - lse_ref[0, 0])
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            v_ref[0, 0], do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0])
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        o: jax.Array, do: jax.Array, *, causal: bool = True,
                        window: int = 0, softmax_scale: float | None = None,
                        block_q: int | None = None,
                        block_k: int | None = None,
                        interpret: bool | None = None):
    """Gradients (dq, dk, dv) of ``flash_attention(q, k, v)`` whose output
    was ``o``, against the output's cotangent ``do``; shapes and dtypes as
    q, k, v.  P and dS enter the MXU in the value dtype, with f32
    accumulation and f32 row statistics.

    Blocks not given come from ``flash_attention_bwd_tiling``."""
    b, sq, H, D = q.shape
    _, sk, K, _ = k.shape
    G = H // K
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if block_q is None or block_k is None:
        tq, tk = flash_attention_bwd_tiling(sq, sk, D, q.dtype)
        block_q = block_q or tq
        block_k = block_k or tk
    bq = min(block_q, _round_up(sq, 128))
    bk = min(block_k, _round_up(sk, 128))
    sq_p = _round_up(sq, bq)
    sk_p = _round_up(sk, bk)
    nq, nk = sq_p // bq, sk_p // bk
    q, o, do = (_head_major(x, sq_p) for x in (q, o, do))
    k, v = (_head_major(x, sk_p) for x in (k, v))
    static = dict(scale=scale, causal=causal, window=window, bq=bq, bk=bk,
                  sk=sk if sk_p != sk else None)
    params = pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET)

    def kv_index(in_sweep_1: bool):
        # k walks the span in both sweeps; v, read in sweep 2 only, holds
        # the span's first block through sweep 1
        def index(ib, ih, iq, j):
            first, last = kv_span(iq * bq, bq, bk, nk, causal, window)
            jj = j % nk if in_sweep_1 else j - nk
            return (ib, ih // G, jnp.minimum(jnp.maximum(jj, first), last), 0)
        return index

    q_block = pl.BlockSpec((1, 1, bq, D), lambda ib, ih, iq, j: (ib, ih, iq, 0))
    row_block = pl.BlockSpec((1, 1, 1, bq),
                             lambda ib, ih, iq, j: (ib, ih, 0, iq))
    rows = jax.ShapeDtypeStruct((b, H, 1, sq_p), jnp.float32)
    dq, lse, delta = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid=(b, H, nq, 2 * nk),
        in_specs=[q_block,
                  pl.BlockSpec((1, 1, bk, D), kv_index(True)),
                  pl.BlockSpec((1, 1, bk, D), kv_index(False)),
                  q_block, q_block],
        out_specs=[q_block, row_block, row_block],
        out_shape=[jax.ShapeDtypeStruct((b, H, sq_p, D), jnp.float32),
                   rows, rows],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32)] * 4,
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, o, do)

    def q_head_block(ib, ik, ikv, t):
        # the G query heads of KV head ik in turn, each over its q blocks;
        # a step off the band repeats the nearest live q block
        first, last = q_span(ikv * bk, bq, bk, nq, causal, window)
        return ik * G + t // nq, jnp.minimum(jnp.maximum(t % nq, first), last)

    def q_index(ib, *g):
        ih, iq = q_head_block(ib, *g)
        return (ib, ih, iq, 0)

    def row_index(ib, *g):
        ih, iq = q_head_block(ib, *g)
        return (ib, ih, 0, iq)

    kv_block = pl.BlockSpec((1, 1, bk, D),
                            lambda ib, ik, ikv, t: (ib, ik, ikv, 0))
    q_blocks = pl.BlockSpec((1, 1, bq, D), q_index)
    stat_blocks = pl.BlockSpec((1, 1, 1, bq), row_index)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, **static),
        grid=(b, K, nk, G * nq),
        in_specs=[q_blocks, kv_block, kv_block, q_blocks, stat_blocks,
                  stat_blocks],
        out_specs=[kv_block, kv_block],
        out_shape=[jax.ShapeDtypeStruct((b, K, sk_p, D), k.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32)] * 2,
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse, delta)

    return (dq[:, :, :sq].swapaxes(1, 2).astype(q.dtype),
            dk[:, :, :sk].swapaxes(1, 2), dv[:, :, :sk].swapaxes(1, 2))
