"""The plain reference of a training step, in float32 at the highest
matmul precision, and the readings ``correct`` compares.

It imports nothing of the program.  The layer equations and the loss are
the model family's, in ``references/<family>.py`` (found by name, see
``spec.py``); what every family shares is here:

- AdamW with bias correction and decoupled weight decay, the learning
  rate warmed up linearly and then decayed on a cosine to a tenth;
- rows taken in blocks so that the whole batch fits; the batch gradient
  is the mean of the blocks' gradients, as the program's micro-steps are;
- the placement of the reference's state over the cell's chips
  (``placement``): each leaf split along one axis, each block of rows
  split over the chips, so that a model no single chip holds can be
  checked.  The step is plain ``jax.numpy``; XLA partitions it.

``precision="fp8"`` is the control: every matrix product's operands, and
the cotangents the backward feeds to them, rounded to float8 (e4m3) with
one scale per tensor, the step below bfloat16 that a later change might
take.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
#: the reference's one mesh axis: all of the cell's chips
CHIPS = "chips"


# ------------------------------------------------------------ precision --

def _round_fp8(x):
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / FP8_MAX)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(F32) * s


@jax.custom_vjp
def fp8(x):
    return _round_fp8(x)


fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))


def make_ein(precision: str, mesh: Optional[Mesh] = None):
    """``ein(spec, a, b)``: a float32 einsum at the highest precision, or,
    for the control, the same with operands rounded to fp8.  Over a
    ``mesh`` of more than one chip, a product whose output starts with the
    rows (``b``) keeps them split over the chips where their count
    divides: the weights come to the rows, not the rows to the weights."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown reference precision {precision!r}")
    n = mesh.size if mesh is not None else 1

    def ein(spec, a, b):
        if precision == "fp8":
            a, b = fp8(a), fp8(b)
        out = jnp.einsum(spec, a, b, precision=HIGHEST)
        if n > 1 and spec.split("->")[1].startswith("b") \
                and out.shape[0] % n == 0:
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, P(CHIPS)))
        return out
    return ein


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


# ------------------------------------------------------------ placement --

def placement(devices: Sequence, tree) -> Any:
    """A ``NamedSharding`` for every leaf of ``tree`` (arrays or shapes)
    over ``devices``: split along the leaf's largest axis that the chip
    count divides (the first of equals), never the layer axis of a leaf
    under ``blocks``; replicated where no axis qualifies."""
    mesh = Mesh(np.asarray(devices), (CHIPS,))
    n = len(devices)

    def place(name, x):
        lead = 1 if name.startswith("blocks/") else 0
        axes = [a for a in range(lead, len(x.shape)) if x.shape[a] % n == 0]
        if not axes:
            return NamedSharding(mesh, P())
        a = max(axes, key=lambda a: (x.shape[a], -a))
        return NamedSharding(mesh, P(*[CHIPS if i == a else None
                                       for i in range(len(x.shape))]))

    flat, tdef = jax.tree.flatten(tree)
    return tdef.unflatten([place(name, x)
                           for name, x in zip(_names(tree), flat)])


def to_f32(tree) -> Any:
    """``tree`` in float32, each leaf where it was.  The cast is a program
    of its own, from arrays that hold the values: fused into the program
    that makes them, the compiler may skip their rounding to the
    program's dtype (it did on a TPU v5e)."""
    return jax.jit(lambda p: jax.tree.map(lambda x: x.astype(F32), p),
                   out_shardings=jax.tree.map(lambda x: x.sharding, tree))(
                       tree)


# ----------------------------------------------------------------- step --

def make_step(m: Dict, t: Dict, precision: str, rows: int, loss: Callable,
              shard: Any):
    """``step(params, m1, v1, tokens, step) -> (loss, grad_norms, params,
    m, v)`` jitted, with ``shard`` (``placement``) the parameters', the
    moments' and the gradients' placement in and out: the batch gradient
    in blocks of ``rows`` rows (``tokens``: ``(blocks, rows, seq)``), the
    norms of its layers, then AdamW."""
    mesh = jax.tree.leaves(shard)[0].mesh
    ein = make_ein(precision, mesh)
    one = NamedSharding(mesh, P())
    # each block's rows split over the chips where their count divides
    split_rows = NamedSharding(mesh, P(None, CHIPS) if rows % mesh.size == 0
                               else P())

    def grads_of(params, blocks):
        vg = jax.value_and_grad(lambda p, b: loss(m, ein, p, b))

        def acc(carry, blk):
            l, g = vg(params, blk)
            l, g = jax.tree.map(jnp.add, carry, (l, g))
            return (l, jax.lax.with_sharding_constraint(g, shard)), None

        zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, params))
        (l, g), _ = jax.lax.scan(acc, zero, blocks)
        nb = blocks.shape[0]
        return l / nb, jax.tree.map(lambda x: x / nb, g)

    def step(params, mom, vel, tokens, i):
        loss_, g = grads_of(params, tokens)
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
        return loss_, layer_norms(g), un(new_p), un(new_m), un(new_v)

    return jax.jit(step, in_shardings=(shard, shard, shard, split_rows,
                                       one),
                   out_shardings=(one, one, shard, shard, shard),
                   donate_argnums=(0, 1, 2))


def readings(model: Dict, traffic: Dict, init: Callable[[Any], Any],
             key: Any, batches: Sequence[np.ndarray], *, family: Any,
             precision: str = "f32",
             fault: Optional[str] = None) -> Dict[str, Any]:
    """Run the reference through ``len(batches)`` steps from
    ``init(key)``, the starting weights in the program's dtypes, already
    in their ``placement``; ``family`` is the module of the model
    family's equations.

    Returns the loss of each step, the norm of every layer of every leaf
    of the first step's gradient, and of the parameters' change over all
    the steps: the starting weights are made again by ``init(key)`` for
    it, not held through the steps.  ``fault="half_batch"`` leaves out the
    second half of every batch (the mean taken over the rest), to read
    that fault."""
    rows = int(traffic["reference_rows"])
    params = to_f32(init(key))
    shard = jax.tree.map(lambda x: x.sharding, params)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=shard)
    mom, vel = zeros(params), zeros(params)
    with jax.default_matmul_precision("highest"):
        step = make_step(model, traffic, precision, rows, family.loss, shard)
        losses, grad_norms = [], None
        for i, toks in enumerate(batches):
            if fault == "half_batch":
                toks = toks[:toks.shape[0] // 2]
            toks = np.asarray(toks).reshape((-1, rows) + toks.shape[1:])
            loss, gn, params, mom, vel = step(params, mom, vel, toks,
                                              float(i))
            losses.append(float(loss))
            if i == 0:
                grad_norms = to_host(gn)
        del mom, vel
        change_norms = to_host(jax.jit(lambda p, s: layer_norms(
            jax.tree.map(lambda a, b: a - b.astype(F32), p, s)))(
                params, init(key)))
    return {"loss": losses, "grad_norms": grad_norms,
            "change_norms": change_norms}
