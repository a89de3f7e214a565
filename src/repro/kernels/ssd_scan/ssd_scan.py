"""Pallas TPU kernel for the Mamba2 SSD chunked scan [arXiv:2405.21060].

TPU adaptation of the SSD algorithm: the grid walks (batch, head, chunk) with
the chunk axis minor-most/sequential; the inter-chunk recurrent state (P x N)
lives in VMEM scratch and is carried across grid steps — this replaces the
GPU implementation's cross-block shared-memory/atomics state passing, which
has no TPU analogue (DESIGN.md §3).  Within a chunk the three SSD terms
(diagonal block, state output, state update) are dense matmuls on the MXU
with 128-aligned chunk length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the chunk's matmul operands are f32 (decay weights, the carried state),
# not bf16 values; at the MXU's default precision the kernel missed the
# sequential reference by more than the bf16 tolerance on a v5e
HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(x_ref, dtc_ref, dtr_ref, A_ref, B_ref, C_ref, D_ref,
            y_ref, st_ref, state_scr, *, L: int):
    ih = pl.program_id(1)
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)                # (L, P)
    dt_c = dtc_ref[0, 0]                               # (L, 1) post-softplus
    dt_r = dtr_ref[0, 0]                               # (1, L) same values
    B = B_ref[0].astype(jnp.float32)                   # (L, N)
    C = C_ref[0].astype(jnp.float32)                   # (L, N)
    A = A_ref[ih]                                      # scalar, negative
    Dv = D_ref[ih]

    # inclusive cumsum of dA along the chunk, as a column and as a row
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tril = row >= col
    cum_c = jnp.sum(jnp.where(tril, dt_r * A, 0.0), axis=1,
                    keepdims=True)                     # (L, 1)
    cum_r = jnp.sum(jnp.where(row <= col, dt_c * A, 0.0), axis=0,
                    keepdims=True)                     # (1, L)
    total = jnp.sum(dt_r * A, axis=1, keepdims=True)   # (1, 1) = cum[L-1]

    # 1) diagonal block: y[i] = sum_{j<=i} C_i.B_j exp(cum_i - cum_j) dt_j x_j
    decay = jnp.exp(jnp.where(tril, cum_c - cum_r, -1e30))       # (L, L)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 precision=HIGHEST,
                                 preferred_element_type=jnp.float32)
    w = scores * decay * dt_r                          # (L, L)
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            precision=HIGHEST,
                            preferred_element_type=jnp.float32)

    # 2) contribution of the carried state: y[i] += exp(cum_i) C_i . state
    state = state_scr[...]                             # (P, N)
    y_off = jax.lax.dot_general(C, state, (((1,), (1,)), ((), ())),
                                precision=HIGHEST,
                                preferred_element_type=jnp.float32)
    y = y + y_off * jnp.exp(cum_c)

    # 3) state update: state' = exp(cum_L) state + sum_j dt_j exp(cum_L-cum_j) x_j B_j^T
    wstate = dt_c * jnp.exp(total - cum_c)             # (L, 1)
    upd = jax.lax.dot_general(x * wstate, B, (((0,), (0,)), ((), ())),
                              precision=HIGHEST,
                              preferred_element_type=jnp.float32)  # (P, N)
    state_scr[...] = state * jnp.exp(total) + upd

    y_ref[0, 0] = (y + Dv * x).astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _emit_state():
        st_ref[0, 0] = state_scr[...]


def ssd_scan(x: jax.Array, dt: jax.Array, A_log: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, dt_bias: jax.Array, *,
             chunk: int = 128, interpret: bool | None = None):
    """x: (b, s, h, p); dt (pre-softplus): (b, s, h); A_log, D, dt_bias: (h,);
    B, C: (b, s, n).  Returns (y (b,s,h,p) in x.dtype, state (b,h,p,n) f32).

    The kernel runs head-major: x is transposed to (b, h, s, p) so a block
    is an (L, p) tile, and dt arrives post-softplus as an (L, 1) column and
    an (1, L) row, with padding rows zeroed.  The per-head scalars A and D
    live in SMEM.  The TPU compiler refuses a size-1 block on the head axis
    in the second-to-last position, which the (b, s, h, p) layout needs."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    L = min(chunk, s)
    s_p = -(-s // L) * L
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)     # (b, s, h)
    # zero dt on padding rows: they then neither decay nor feed the state
    dt = jnp.pad(dt, ((0, 0), (0, s_p - s), (0, 0))).transpose(0, 2, 1)
    xh = jnp.pad(x, ((0, 0), (0, s_p - s), (0, 0), (0, 0))).swapaxes(1, 2)
    B = jnp.pad(B, ((0, 0), (0, s_p - s), (0, 0)))
    C = jnp.pad(C, ((0, 0), (0, s_p - s), (0, 0)))
    A = -jnp.exp(A_log.astype(jnp.float32))
    grid = (b, h, s_p // L)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    y, st = pl.pallas_call(
        functools.partial(_kernel, L=L),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, L, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, L), lambda ib, ih, ic: (ib, ih, 0, ic)),
            smem,
            pl.BlockSpec((1, L, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, L, n), lambda ib, ih, ic: (ib, ic, 0)),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_p, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_scan_fwd",
    )(xh, dt[..., None], dt[:, :, None, :], A, B, C,
      D.astype(jnp.float32))
    return y[:, :, :s].swapaxes(1, 2), st
