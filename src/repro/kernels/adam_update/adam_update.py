"""Pallas TPU fused mixed-precision Adam.

One VMEM pass over the paper's 20-byte/param state (fp32 grad + m + v +
master, bf16 param out) instead of the ~10 separate HBM-bound elementwise
ops XLA would emit unfused — the update is purely memory-bound, so fusing
is worth ~5x on the optimizer phase.  1-D grid over 128-lane-aligned tiles;
scalar hyper-parameters arrive via scalar prefetch (SMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(scal_ref, g_ref, m_ref, v_ref, mp_ref,
            m_out, v_out, mp_out, p_out):
    lr = scal_ref[0]
    beta1 = scal_ref[1]
    beta2 = scal_ref[2]
    eps = scal_ref[3]
    wd = scal_ref[4]
    c1 = scal_ref[5]
    c2 = scal_ref[6]
    g = g_ref[...].astype(jnp.float32)
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * g * g
    mp = mp_ref[...]
    upd = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * mp
    mp2 = mp - lr * upd
    m_out[...] = m
    v_out[...] = v
    mp_out[...] = mp2
    p_out[...] = mp2.astype(p_out.dtype)


def adam_update_fused(g: jax.Array, m: jax.Array, v: jax.Array,
                      master: jax.Array, *, lr, beta1: float, beta2: float,
                      eps: float, wd: float, c1, c2,
                      block: int = 64 * 1024,
                      interpret: bool | None = None):
    """Flat fp32 arrays (any shape; flattened internally).  Returns
    (m', v', master', params_bf16) with the original shape."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    shape = g.shape
    n = g.size
    gf, mf, vf, pf = (a.reshape(-1) for a in (g, m, v, master))
    blk = min(block, max(n, 128))
    n_p = -(-n // blk) * blk
    if n_p != n:
        pad = (0, n_p - n)
        gf, mf, vf, pf = (jnp.pad(a, pad) for a in (gf, mf, vf, pf))
    scal = jnp.asarray([lr, beta1, beta2, eps, wd, c1, c2], jnp.float32)
    grid = (n_p // blk,)
    spec = pl.BlockSpec((blk,), lambda i, scal: (i,))
    m2, v2, mp2, p2 = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[spec] * 4,
            out_specs=[spec] * 4,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_p,), jnp.float32),
            jax.ShapeDtypeStruct((n_p,), jnp.float32),
            jax.ShapeDtypeStruct((n_p,), jnp.float32),
            jax.ShapeDtypeStruct((n_p,), jnp.bfloat16),
        ],
        interpret=interpret,
        name="adam_update",
    )(scal, gf, mf, vf, pf)
    return (m2[:n].reshape(shape), v2[:n].reshape(shape),
            mp2[:n].reshape(shape), p2[:n].reshape(shape))
