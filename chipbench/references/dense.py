"""The dense family's layer equations, for the plain reference.

- token embedding; per layer RMSNorm, multi-head attention with grouped
  KV heads, rotary positions (rotate-half) and a causal or sliding window
  mask, output projection, residual; RMSNorm, GELU (tanh) or SwiGLU MLP,
  residual; final RMSNorm; logits from the tied embedding (or
  ``lm_head``);
- loss: mean next-token cross-entropy over every position of every row.

Attention runs one block of query positions at a time, and the loss one
chunk of positions after another in a ``lax.scan``, each block and chunk
under its own ``jax.checkpoint``: the backward holds a block's scores or
a chunk's logits, never a whole row's.  Its leaves take ``weights.py``'s
default initialisers, so the family exports no ``INIT``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

#: the most query positions in one attention block and positions in one
#: loss chunk.  At 8,192 tokens, 24 heads and a 4,096 window a block's
#: float32 scores take 503 MB a row; a chunk's logits at a 49,152 vocab
#: take 201 MB a row.
BLOCK = 1024


def block(s: int) -> int:
    """Positions a block or chunk holds: the largest divisor of ``s`` up
    to ``BLOCK`` and up to half of ``s``, so that a causal block reads at
    most the keys up to its own."""
    return max(d for d in range(1, max(1, min(s // 2, BLOCK)) + 1)
               if s % d == 0)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (b, s, heads, d); rotate-half over positions 0..s-1."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(ein, first, lo, window, q, k, v):
    """Queries at ``first``.. against keys at ``lo``..: (b, q, H, D)."""
    sc = ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    qp = first + jnp.arange(q.shape[1])[:, None]
    kp = lo + jnp.arange(k.shape[1])[None, :]
    ok = kp <= qp
    if window:
        ok &= kp > qp - window
    sc = jnp.where(ok, sc, -jnp.inf)
    return ein("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)


def attention(m, ein, q, k, v):
    """Causal (optionally windowed) softmax attention, one block of query
    positions at a time, each under its own checkpoint and reading only
    the keys its queries can reach.  q: (b, s, H, D); k, v: (b, s, K, D)."""
    s = q.shape[1]
    G = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    window = m.get("sliding_window", 0)
    qc = block(s)
    outs = []
    for i in range(0, s, qc):
        lo = max(0, i - window + 1) if window else 0
        f = jax.checkpoint(partial(_attend, ein, i, lo, window))
        outs.append(f(q[:, i:i + qc], k[:, lo:i + qc], v[:, lo:i + qc]))
    return jnp.concatenate(outs, axis=1)


def dense_layer(m, ein, x, p):
    eps = m["norm_eps"]
    h = rms(x, p["norm1"], eps)
    a = p["mixer"]
    q = rope(ein("bsd,dhk->bshk", h, a["wq"]), m["rope_theta"])
    k = rope(ein("bsd,dhk->bshk", h, a["wk"]), m["rope_theta"])
    v = ein("bsd,dhk->bshk", h, a["wv"])
    x = x + ein("bshk,hkd->bsd", attention(m, ein, q, k, v), a["wo"])
    h = rms(x, p["norm2"], eps)
    f = p["ffn"]
    u = ein("bsd,df->bsf", h, f["w1"])
    if m["mlp_variant"] == "swiglu":
        u = jax.nn.silu(u) * ein("bsd,df->bsf", h, f["w3"])
    else:
        u = jax.nn.gelu(u, approximate=True)
    return x + ein("bsf,fd->bsd", u, f["w2"])


def loss(m, ein, params, tokens):
    """Mean next-token cross-entropy of one block of rows."""
    x = params["embed"][tokens]

    def body(x, p):
        return dense_layer(m, ein, x, p["sub0"]), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["blocks"])
    x = rms(x, params["final_norm"], m["norm_eps"])
    head = params.get("lm_head")

    def logits(xc):
        return (ein("bsd,dv->bsv", xc, head) if head is not None
                else ein("bsd,vd->bsv", xc, params["embed"]))

    b, s, d = x.shape
    L = block(s)
    # position j predicts token j + 1; the last position predicts nothing
    gold = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], 1)
    live = jnp.arange(s) < s - 1

    def chunk(total, c):
        xc, gc, lc = c
        lg = logits(xc)
        nll = (jax.nn.logsumexp(lg, -1)
               - jnp.take_along_axis(lg, gc[..., None], -1)[..., 0])
        return total + jnp.sum(jnp.where(lc, nll, 0.0)), None

    nc = s // L
    chunks = (x.reshape(b, nc, L, d).swapaxes(0, 1),
              gold.reshape(b, nc, L).swapaxes(0, 1),
              live.reshape(nc, 1, L))
    total, _ = jax.lax.scan(jax.checkpoint(chunk), jnp.zeros((), x.dtype),
                            chunks)
    return total / (b * (s - 1))
