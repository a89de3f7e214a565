"""Attention: RoPE, memory-efficient chunked attention (pure jnp, flash-style),
single-token decode attention, and the GQA / MLA layer implementations.

Full-sequence attention goes through ``repro.kernels.dispatch``: on TPU the
Pallas flash kernel runs (blocks from the shape); on CPU/GPU the chunked
implementation below runs, bit-identical to calling it directly.  The Pallas
kernel is numerically validated against ``repro.kernels.flash_attention.ref``
which in turn matches this module.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import dispatch
from repro.models.common import dense_init, rms_norm
from repro.parallel.act import constrain

NEG_INF = -1e30


# ---------------------------------------------------------------- RoPE ------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (seq,) or (..., seq)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., s, d/2)
    cos = jnp.cos(angles)[..., :, None, :]             # (..., s, 1, d/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------- chunked (flash-style) ------

def _pair_attend(q, k, v, mask, softmax_scale):
    """One (q-chunk, kv-chunk) pair.  q:(b,qc,K,G,D) k,v:(b,kc,K,D).
    Returns unnormalised acc (b,qc,K,G,D), row max m, row sum l (fp32)."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32)
    s = s * softmax_scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                            # (b,K,G,qc)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return acc.astype(jnp.float32), m, l


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0,
                      softmax_scale: Optional[float] = None,
                      q_chunk: int = 2048, kv_chunk: int = 2048,
                      impl: str = "auto") -> jax.Array:
    """Memory-efficient causal/sliding-window attention.

    q: (b, sq, H, D); k, v: (b, sk, K, D) with H = K * G (GQA).

    impl='unrolled': only the (q-chunk, kv-chunk) pairs inside the causal/
    window band are materialised (python-unrolled; the compiled HLO contains
    exactly the useful FLOPs).  Best for short sequences.

    impl='scan': doubly-rolled lax.scan (q chunks x kv band) with online-
    softmax carry — O(one pair) live memory regardless of sequence length,
    at the cost of masked compute above the diagonal for full-causal runs.
    Selected automatically for sq >= 8192.
    """
    if impl == "auto":
        impl = "scan" if q.shape[1] >= 8192 else "unrolled"
    if impl == "scan":
        return _chunked_attention_scan(q, k, v, causal=causal, window=window,
                                       softmax_scale=softmax_scale,
                                       q_chunk=q_chunk, kv_chunk=kv_chunk)
    b, sq, H, D = q.shape
    _, sk, K, _ = k.shape
    G = H // K
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    qc = min(q_chunk, sq)
    kc = min(kv_chunk, sk)
    nq, nk = -(-sq // qc), -(-sk // kc)
    assert sq % qc == 0 and sk % kc == 0, (sq, qc, sk, kc)

    qr = q.reshape(b, nq, qc, K, G, D)
    outs = []
    for i in range(nq):
        q_i = qr[:, i]
        q_pos0 = i * qc                                # first query position
        acc = jnp.zeros((b, qc, K, G, D), jnp.float32)
        m = jnp.full((b, K, G, qc), NEG_INF, jnp.float32)
        l = jnp.zeros((b, K, G, qc), jnp.float32)
        for j in range(nk):
            k_pos0 = j * kc
            if causal and k_pos0 > q_pos0 + qc - 1:
                continue                               # fully above the diagonal
            if window and (k_pos0 + kc - 1) < (q_pos0 - window + 1):
                continue                               # fully outside the window
            mask = None
            needs_causal = causal and (k_pos0 + kc - 1) > q_pos0
            needs_window = window and k_pos0 < (q_pos0 + qc - 1 - window + 1)
            if needs_causal or needs_window:
                qp = q_pos0 + jnp.arange(qc)
                kp = k_pos0 + jnp.arange(kc)
                ok = jnp.ones((qc, kc), bool)
                if causal:
                    ok &= kp[None, :] <= qp[:, None]
                if window:
                    ok &= kp[None, :] > qp[:, None] - window
                mask = ok[None, None, None]            # (1,1,1,qc,kc)
            a, m_j, l_j = _pair_attend(q_i, k[:, k_pos0:k_pos0 + kc],
                                       v[:, k_pos0:k_pos0 + kc], mask, scale)
            m_new = jnp.maximum(m, m_j)
            c1 = jnp.exp(m - m_new)
            c2 = jnp.exp(m_j - m_new)
            acc = acc * jnp.moveaxis(c1, -1, 1)[..., None] \
                + a * jnp.moveaxis(c2, -1, 1)[..., None]
            l = l * c1 + l_j * c2
            m = m_new
        out_i = acc / jnp.maximum(jnp.moveaxis(l, -1, 1)[..., None], 1e-30)
        outs.append(out_i.astype(q.dtype))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out.reshape(b, sq, H, D)


def _chunked_attention_scan(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            causal: bool, window: int,
                            softmax_scale: Optional[float],
                            q_chunk: int, kv_chunk: int) -> jax.Array:
    """Rolled flash-style attention: outer scan over q chunks, inner scan
    over the kv band, (acc, m, l) online-softmax carry."""
    b, sq, H, D = q.shape
    _, sk, K, _ = k.shape
    G = H // K
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    qc = min(q_chunk, sq)
    kc = min(kv_chunk, sk)
    sq_p = -(-sq // qc) * qc
    sk_p = -(-sk // kc) * kc
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    nq, nk = sq_p // qc, sk_p // kc
    # kv band per q chunk: everything for full causal; window span for SWA
    band = nk if not window else min(nk, -(-(window + qc) // kc) + 1)

    # keep the per-chunk qc dim sequence-sharded (not the scan axis): the
    # reshape of a seq-sharded q is ambiguous to GSPMD and mapping shards to
    # the scan axis serialises the loop across devices
    qr = jnp.moveaxis(q.reshape(b, nq, qc, K, G, D), 1, 0)  # (nq,b,qc,K,G,D)
    qr = constrain(qr, None, "batch", "seq", "heads", None)

    def q_body(_, inp):
        q_i, i = inp
        j0 = 0 if band == nk else jnp.maximum(i * qc // kc - (band - 1), 0)

        def kv_body(carry, jj):
            acc, m, l = carry
            j = j0 + jj
            start = jnp.clip(j * kc, 0, sk_p - kc)
            k_j = jax.lax.dynamic_slice_in_dim(k, start, kc, axis=1)
            v_j = jax.lax.dynamic_slice_in_dim(v, start, kc, axis=1)
            s = jnp.einsum("bqkgd,bskd->bkgqs", q_i, k_j
                           ).astype(jnp.float32) * scale
            qp = i * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
            kp = start + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
            ok = kp < sk
            if causal:
                ok = jnp.logical_and(ok, kp <= qp)
            if window:
                ok = jnp.logical_and(ok, kp > qp - window)
            s = jnp.where(ok[None, None, None], s, NEG_INF)
            m_j = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m, m_j)
            p = jnp.exp(s - m_new[..., None])
            c1 = jnp.exp(m - m_new)
            l = l * c1 + jnp.sum(p, axis=-1)
            a = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v_j.dtype), v_j
                           ).astype(jnp.float32)
            acc = acc * jnp.moveaxis(c1, -1, 1)[..., None] + a
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, qc, K, G, D), jnp.float32)
        m0 = jnp.full((b, K, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, K, G, qc), jnp.float32)
        # the backward recomputes each pair's scores from the carry: saved,
        # they would hold (nq x band) score blocks of (qc x kc) at once
        (acc, m, l), _ = jax.lax.scan(jax.checkpoint(kv_body),
                                      (acc0, m0, l0),
                                      jnp.arange(band))
        out_i = acc / jnp.maximum(jnp.moveaxis(l, -1, 1)[..., None], 1e-30)
        return None, out_i.astype(q_i.dtype)

    _, outs = jax.lax.scan(q_body, None,
                           (qr, jnp.arange(nq, dtype=jnp.int32)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq_p, H, D)
    return out[:, :sq]


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     valid: jax.Array, *, softmax_scale: Optional[float] = None
                     ) -> jax.Array:
    """Single-token attention over a (possibly ring) KV cache.

    q: (b, 1, H, D); k_cache, v_cache: (b, S, K, D); valid: (b, S) bool.
    """
    b, _, H, D = q.shape
    _, S, K, _ = k_cache.shape
    G = H // K
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    qr = q.reshape(b, K, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache).astype(jnp.float32) * scale
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, 1, H, D)


# ----------------------------------------------------------------- GQA ------

def init_gqa(cfg: ModelConfig, key) -> dict:
    """Weights keep a separate head axis — (d, H, hd) etc. — so the sharding
    layer can partition heads over the 'model' mesh axis directly."""
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H, hd)),
        "wk": dense_init(ks[1], (d, K, hd)),
        "wv": dense_init(ks[2], (d, K, hd)),
        "wo": dense_init(ks[3], (H, hd, d),
                         scale=1.0 / math.sqrt(2 * cfg.num_layers)),
    }


def gqa_project_qkv(cfg: ModelConfig, p: dict, x: jax.Array,
                    positions: jax.Array):
    q = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wq"]),
                  "batch", "seq", "heads", None)
    k = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wk"]),
                  "batch", None, "heads", None)
    v = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wv"]),
                  "batch", None, "heads", None)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attend_train(cfg: ModelConfig, p: dict, x: jax.Array,
                     positions: jax.Array) -> Tuple[jax.Array, dict]:
    """Full-sequence (train / prefill) attention.  Returns (out, kv) where kv
    holds the k/v tensors for cache construction during prefill."""
    q, k, v = gqa_project_qkv(cfg, p, x, positions)
    o = dispatch.attention(q, k, v, causal=True, window=cfg.sliding_window)
    o = constrain(o, "batch", "seq", "heads", None)
    out = constrain(jnp.einsum("bshk,hkd->bsd", o, p["wo"]),
                    "batch", "seq", None)
    return out, {"k": k, "v": v}


def gqa_attend_decode(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
                      pos: jax.Array) -> Tuple[jax.Array, dict]:
    """x: (b, 1, d); cache: {'k','v'} of (b, S, K, hd); pos: scalar int32 —
    the absolute position of the incoming token (ring buffer write at
    pos % S) — or an (b,) int32 vector of per-row positions (continuous
    batching: each cache slot advances independently)."""
    b, _, d = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    S = cache["k"].shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if getattr(pos, "ndim", 0):
        # per-row positions: one-hot ring write + per-row validity mask
        # (same arithmetic per row as the scalar path below)
        posv = pos.astype(jnp.int32)
        q = apply_rope(q, posv[:, None], cfg.rope_theta)
        k = apply_rope(k, posv[:, None], cfg.rope_theta)
        slot = (posv % S).astype(jnp.int32)           # (b,)
        hit = jnp.arange(S)[None, :] == slot[:, None]  # (b, S)
        k_cache = jnp.where(hit[:, :, None, None], k, cache["k"])
        v_cache = jnp.where(hit[:, :, None, None], v, cache["v"])
        idx = jnp.arange(S)
        age = (slot[:, None] - idx[None, :]) % S
        valid = age <= jnp.minimum(posv[:, None], S - 1)
        o = dispatch.flash_decode(q, k_cache, v_cache, valid)
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        return out, {"k": k_cache, "v": v_cache}
    q = apply_rope(q, pos[None].astype(jnp.int32), cfg.rope_theta)
    k = apply_rope(k, pos[None].astype(jnp.int32), cfg.rope_theta)
    slot = (pos % S).astype(jnp.int32)
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    # slot i holds absolute position: p_i = i + S*floor((pos - i)/S) — valid iff
    # p_i <= pos and p_i > pos - window (ring semantics).  After the buffer has
    # filled once every slot is valid (window == S).
    idx = jnp.arange(S)
    age = (slot - idx) % S                            # 0 = newest
    valid = age <= jnp.minimum(pos, S - 1)
    o = dispatch.flash_decode(q, k_cache, v_cache,
                              jnp.broadcast_to(valid, (b, S)))
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, {"k": k_cache, "v": v_cache}


# ----------------------------------------------------------------- MLA ------
# DeepSeek-V2 Multi-head Latent Attention [arXiv:2405.04434].  The KV cache
# stores only the compressed latent c_kv (kv_lora) and the shared RoPE key
# (qk_rope_head_dim); decode uses the matrix-absorption trick so the per-head
# K/V are never materialised for the cache.

def init_mla(cfg: ModelConfig, key) -> dict:
    d, H = cfg.d_model, cfg.num_heads
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": dense_init(ks[0], (d, r_q)),
        "q_ln": jnp.ones((r_q,), jnp.bfloat16),
        "wq_b": dense_init(ks[1], (r_q, H, dn + dr)),
        "wkv_a": dense_init(ks[2], (d, r_kv + dr)),
        "kv_ln": jnp.ones((r_kv,), jnp.bfloat16),
        "wk_b": dense_init(ks[3], (r_kv, H, dn)),
        "wv_b": dense_init(ks[4], (r_kv, H, dv)),
        "wo": dense_init(ks[5], (H, dv, d),
                         scale=1.0 / math.sqrt(2 * cfg.num_layers)),
    }


def _mla_q(cfg, p, x, positions):
    b, s, _ = x.shape
    H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = rms_norm(x @ p["wq_a"], p["q_ln"], cfg.norm_eps)
    q = constrain(jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"]),
                  "batch", None, "heads", None)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(cfg, p, x, positions):
    b, s, _ = x.shape
    r_kv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    kv = x @ p["wkv_a"]                                # (b,s,r_kv+dr)
    c_kv = rms_norm(kv[..., :r_kv], p["kv_ln"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., r_kv:][:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0]        # shared single head
    return c_kv, k_rope


def mla_attend_train(cfg: ModelConfig, p: dict, x: jax.Array,
                     positions: jax.Array) -> Tuple[jax.Array, dict]:
    b, s, _ = x.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    c_kv, k_rope = _mla_latent(cfg, p, x, positions)
    c_kv = constrain(c_kv, "batch", None, None)
    k_nope = constrain(jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"]),
                       "batch", None, "heads", None)
    v = constrain(jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"]),
                  "batch", None, "heads", None)
    # pack rope part into the head dim so chunked_attention sees one tensor
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope[:, :, None, :], (b, s, H, dr))],
                        axis=-1)
    scale = 1.0 / math.sqrt(dn + dr)
    # v head dim may differ from qk head dim — pad v then slice (keeps the
    # chunked kernel generic)
    pad = (dn + dr) - dv
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad))) if pad > 0 else v
    o = dispatch.attention(q, k, v_p, causal=True, softmax_scale=scale)
    o = constrain(o[..., :dv], "batch", None, "heads", None)
    out = constrain(jnp.einsum("bshk,hkd->bsd", o, p["wo"]),
                    "batch", None, None)
    return out, {"c_kv": c_kv, "k_rope": k_rope}


def mla_attend_decode(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
                      pos: jax.Array) -> Tuple[jax.Array, dict]:
    """Matrix-absorbed MLA decode: scores/value both computed in latent
    space.  ``pos`` is a scalar int32, or an (b,) vector of per-row
    positions (continuous batching)."""
    b, _, _ = x.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r_kv = cfg.kv_lora_rank
    S = cache["c_kv"].shape[1]
    if getattr(pos, "ndim", 0):
        posv = pos.astype(jnp.int32)
        q_nope, q_rope = _mla_q(cfg, p, x, posv[:, None])
        c_new, kr_new = _mla_latent(cfg, p, x, posv[:, None])
        slot = (posv % S).astype(jnp.int32)           # (b,)
        hit = jnp.arange(S)[None, :] == slot[:, None]  # (b, S)
        c_kv = jnp.where(hit[:, :, None], c_new, cache["c_kv"])
        k_rope = jnp.where(hit[:, :, None], kr_new, cache["k_rope"])
        idx = jnp.arange(S)
        age = (slot[:, None] - idx[None, :]) % S
        valid = age <= jnp.minimum(posv[:, None], S - 1)   # (b, S)
    else:
        q_nope, q_rope = _mla_q(cfg, p, x, pos[None].astype(jnp.int32))
        c_new, kr_new = _mla_latent(cfg, p, x, pos[None].astype(jnp.int32))
        slot = (pos % S).astype(jnp.int32)
        c_kv = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_new,
                                                   slot, axis=1)
        k_rope = jax.lax.dynamic_update_slice_in_dim(cache["k_rope"],
                                                     kr_new, slot, axis=1)
        idx = jnp.arange(S)
        age = (slot - idx) % S
        valid = jnp.broadcast_to(age <= jnp.minimum(pos, S - 1), (b, S))
    # absorb W^UK into q: q_lat (b,H,r_kv); the masked latent softmax /
    # PV runs through the dispatched split-KV decode op (ref on CPU/GPU
    # is this block's seed math verbatim)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], p["wk_b"])
    o_lat = dispatch.mla_flash_decode(q_lat, q_rope[:, 0], c_kv, k_rope,
                                      valid, denom=math.sqrt(dn + dr))
    o = jnp.einsum("bhr,rhd->bhd", o_lat, p["wv_b"])
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None, :]
    return out, {"c_kv": c_kv, "k_rope": k_rope}
