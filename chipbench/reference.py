"""The plain reference of a training step, in float32 at the highest
matmul precision, and the readings ``correct`` compares.

It imports nothing of the program.  It follows the layer equations of the
model configuration as the benchmark's configuration file states them:

- dense: token embedding; per layer RMSNorm, multi-head attention with
  grouped KV heads, rotary positions (rotate-half) and a causal or sliding
  window mask, output projection, residual; RMSNorm, GELU (tanh) MLP,
  residual; final RMSNorm; logits from the tied embedding (or ``lm_head``).
- loss: mean next-token cross-entropy over every position of every row.
- AdamW with bias correction and decoupled weight decay, the learning
  rate warmed up linearly and then decayed on a cosine to a tenth.

Rows are taken in blocks so that the whole batch fits; the batch gradient
is the mean of the blocks' gradients, as the program's micro-steps are.

``precision="fp8"`` is the control: every matrix product's operands, and
the cotangents the backward feeds to them, rounded to float8 (e4m3) with
one scale per tensor, the step below bfloat16 that a later change might
take.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# ------------------------------------------------------------ precision --

def _round_fp8(x):
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / FP8_MAX)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(F32) * s


@jax.custom_vjp
def fp8(x):
    return _round_fp8(x)


fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))


def make_ein(precision: str):
    """``ein(spec, a, b)``: a float32 einsum at the highest precision, or,
    for the control, the same with operands rounded to fp8."""
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, fp8(a), fp8(b),
                                             precision=HIGHEST)
    raise ValueError(f"unknown reference precision {precision!r}")


# --------------------------------------------------------------- layers --

def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (b, s, heads, d); rotate-half over positions 0..s-1."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(m, ein, q, k, v):
    """Causal (optionally windowed) softmax attention, one block of query
    positions at a time.  q: (b, s, H, D); k, v: (b, s, K, D)."""
    b, s, H, D = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    window = m.get("sliding_window", 0)
    qc = min(s, 1024)
    outs = []
    for i in range(0, s, qc):
        sc = ein("bqhd,bkhd->bhqk", q[:, i:i + qc], k) / math.sqrt(D)
        qp = i + jnp.arange(qc)[:, None]
        kp = jnp.arange(s)[None, :]
        ok = kp <= qp
        if window:
            ok &= kp > qp - window
        sc = jnp.where(ok, sc, -jnp.inf)
        outs.append(ein("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v))
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


def loss_fn(m, ein, params, tokens):
    """Mean next-token cross-entropy of one block of rows."""
    if m["family"] != "dense":
        raise ValueError(f"no reference for model family {m['family']!r}")
    x = params["embed"][tokens]

    def body(x, p):
        return dense_layer(m, ein, x, p["sub0"]), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["blocks"])
    x = rms(x, params["final_norm"], m["norm_eps"])
    head = params.get("lm_head")
    logits = (ein("bsd,dv->bsv", x, head) if head is not None
              else ein("bsd,vd->bsv", x, params["embed"]))[:, :-1]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


# ------------------------------------------------------------- training --

def learning_rate(t: Dict, step):
    """Linear warm-up over ``warmup_steps``, then a cosine from the peak
    to a tenth of it at ``total_steps``."""
    warm = jnp.minimum((step + 1.0) / t["warmup_steps"], 1.0)
    prog = jnp.clip((step - t["warmup_steps"])
                    / (t["total_steps"] - t["warmup_steps"]), 0.0, 1.0)
    return t["learning_rate"] * warm * (0.1 + 0.45 * (1.0 + jnp.cos(
        jnp.pi * prog)))


def decays(name: str, shape: Sequence[int]) -> bool:
    """Decoupled weight decay applies to matrices: leaves whose shape, per
    layer, has two axes or more (never to norm scales)."""
    return len(shape) - (1 if name.startswith("blocks/") else 0) >= 2


def _names(tree) -> List[str]:
    return ["/".join(str(e.key) for e in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def layer_norms(tree) -> Dict[str, jax.Array]:
    """Norm of every layer of every leaf (leaves under ``blocks`` carry
    the layers on axis 0; the others count as one).  Traceable."""
    out = {}
    for name, x in zip(_names(tree), jax.tree.leaves(tree)):
        x = jnp.asarray(x, F32)
        if name.startswith("blocks/"):
            out[name] = jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1) ** 2, 1))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))[None]
    return out


def to_host(norms: Dict[str, jax.Array]) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in norms.items()}


def host_norms(tree) -> Dict[str, np.ndarray]:
    return to_host(jax.jit(layer_norms)(tree))


def make_step(m: Dict, t: Dict, precision: str, rows: int):
    """``step(params, m1, v1, tokens, step) -> (loss, grads, params, m, v)``
    jitted: the batch gradient in blocks of ``rows`` rows, then AdamW."""
    ein = make_ein(precision)

    def grads_of(params, tokens):
        blocks = tokens.reshape((-1, rows) + tokens.shape[1:])
        vg = jax.value_and_grad(partial(loss_fn, m, ein))

        def acc(carry, blk):
            l, g = vg(params, blk)
            return jax.tree.map(jnp.add, carry, (l, g)), None

        zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, params))
        (l, g), _ = jax.lax.scan(acc, zero, blocks)
        nb = blocks.shape[0]
        return l / nb, jax.tree.map(lambda x: x / nb, g)

    def step(params, mom, vel, tokens, i):
        loss, g = grads_of(params, tokens)
        lr = learning_rate(t, i)
        tt = i + 1.0
        c1, c2 = 1 - t["beta1"] ** tt, 1 - t["beta2"] ** tt
        flat_p, tdef = jax.tree.flatten(params)
        new_p, new_m, new_v = [], [], []
        for name, p, gg, mm, vv in zip(_names(params), flat_p,
                                       tdef.flatten_up_to(g),
                                       tdef.flatten_up_to(mom),
                                       tdef.flatten_up_to(vel)):
            mm = t["beta1"] * mm + (1 - t["beta1"]) * gg
            vv = t["beta2"] * vv + (1 - t["beta2"]) * gg * gg
            upd = (mm / c1) / (jnp.sqrt(vv / c2) + t["eps"])
            if decays(name, p.shape):
                upd = upd + t["weight_decay"] * p
            new_p.append(p - lr * upd)
            new_m.append(mm)
            new_v.append(vv)
        un = tdef.unflatten
        return loss, g, un(new_p), un(new_m), un(new_v)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def readings(model: Dict, traffic: Dict, params0: Any,
             batches: Sequence[np.ndarray], *, precision: str = "f32",
             fault: Optional[str] = None) -> Dict[str, Any]:
    """Run the reference through ``len(batches)`` steps from ``params0``.

    Returns the loss of each step, the norm of every layer of every leaf
    of the first step's gradient, and of the parameters' change over all
    the steps.  ``fault="half_batch"`` leaves out the second half of every
    batch (the mean taken over the rest), to read that fault."""
    rows = int(traffic["reference_rows"])
    start = jax.tree.map(lambda x: jnp.asarray(x, F32), params0)
    params = jax.tree.map(jnp.copy, start)
    mom = jax.tree.map(jnp.zeros_like, start)
    vel = jax.tree.map(jnp.zeros_like, start)
    with jax.default_matmul_precision("highest"):
        step = make_step(model, traffic, precision, rows)
        losses, grad_norms = [], None
        for i, toks in enumerate(batches):
            if fault == "half_batch":
                toks = toks[:toks.shape[0] // 2]
            loss, g, params, mom, vel = step(params, mom, vel,
                                             jnp.asarray(toks), float(i))
            losses.append(float(loss))
            if i == 0:
                grad_norms = host_norms(g)
            del g
        change = jax.tree.map(jnp.subtract, params, start)
        change_norms = host_norms(change)
    return {"loss": losses, "grad_norms": grad_norms,
            "change_norms": change_norms}
