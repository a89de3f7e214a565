"""The reference's blocking changes nothing: attention one block of
queries after another and the loss one chunk of positions after another,
each under its own checkpoint, give the loss and the gradient of a
computation that builds every score and every logit at once.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_reference.py
"""
from __future__ import annotations

import json
import math
import os

import numpy as np
import pytest

import tiny
from tiny import jax, jnp

import harness
import reference
import spec
import weights

dense = spec.reference_family("dense")


def _one_block_loss(m, ein, params, tokens):
    """The dense family's loss with every score and every logit at once."""
    def attention(q, k, v):
        s, G = q.shape[1], q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
        sc = ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
        qp, kp = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        ok = kp <= qp
        if m["sliding_window"]:
            ok &= kp > qp - m["sliding_window"]
        sc = jnp.where(ok, sc, -jnp.inf)
        return ein("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)

    def layer(x, p):
        p, eps, theta = p["sub0"], m["norm_eps"], m["rope_theta"]
        a, f = p["mixer"], p["ffn"]
        h = dense.rms(x, p["norm1"], eps)
        q = dense.rope(ein("bsd,dhk->bshk", h, a["wq"]), theta)
        k = dense.rope(ein("bsd,dhk->bshk", h, a["wk"]), theta)
        v = ein("bsd,dhk->bshk", h, a["wv"])
        x = x + ein("bshk,hkd->bsd", attention(q, k, v), a["wo"])
        u = ein("bsd,df->bsf", dense.rms(x, p["norm2"], eps), f["w1"])
        return x + ein("bsf,fd->bsd", jax.nn.gelu(u, approximate=True),
                       f["w2"]), None

    x, _ = jax.lax.scan(layer, params["embed"][tokens], params["blocks"])
    x = dense.rms(x, params["final_norm"], m["norm_eps"])
    logits = ein("bsd,vd->bsv", x, params["embed"])[:, :-1]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


@pytest.mark.parametrize("window", [0, 48, 1500])
def test_blocks_and_chunks_change_nothing(window):
    with open(os.path.join(tiny.DATA, "tiny-dense.json")) as f:
        m = dict(json.load(f)["model"], sliding_window=window)
    seq = 3 * dense.BLOCK
    assert seq // dense.block(seq) == 3 and window < seq
    cfg = harness.ModelConfig(**m)
    key = weights.seed_key(3000000011)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          weights.make_params(cfg, key))
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (2, seq), 0,
                                m["vocab_size"])
    ein = reference.make_ein("f32")
    got, want = (jax.jit(jax.value_and_grad(
        lambda p, t: f(m, ein, p, t)))(params, tokens)
        for f in (dense.loss, _one_block_loss))
    assert got[0] == pytest.approx(float(want[0]), rel=1e-6)
    g, w = (reference.to_host(reference.layer_norms(x[1]))
            for x in (got, want))
    for name in w:
        np.testing.assert_allclose(g[name], w[name], rtol=1e-6, err_msg=name)
