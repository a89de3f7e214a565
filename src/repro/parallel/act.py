"""Logical activation-sharding annotations (MaxText-style).

GSPMD propagates input/output shardings well through the forward pass, but
the remat'd backward of the (microbatch x block) double scan loses the batch
sharding on large intermediates (observed: per-device attention scores with
the full micro-batch — 194 GiB temp on llava-train).  Explicit
``with_sharding_constraint`` anchors inside the model fix propagation in
both directions.

Models call ``constrain(x, 'batch', None, 'heads', 'head_dim')`` with
logical dim names; the active context (set by the train/serve step builders)
resolves them to mesh axes for the current (cfg, mesh), dropping axes that
do not divide the dim (jit requires exact tiling).  With no context active
this is a no-op, so model code runs unchanged outside pjit.

Code traced for one shard inside ``per_shard``'s map sees no context
either: the mesh is manual there and takes no constraint on its global
axes.  The rule is read from the mesh JAX traces under, so it also holds
where the map's transpose traces a ``custom_vjp``'s backward, after the
forward's wrapper has returned.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CTX = threading.local()


@contextmanager
def activation_sharding(mesh: Mesh, cfg):
    from repro.parallel import sharding as sh
    tp = mesh.shape.get("model", 1)
    heads_ok = sh.attn_head_sharded(cfg, tp)
    resolved = {
        "batch": (tuple(sh.data_axes(mesh)) or None),
        "heads": "model" if heads_ok else None,
        # context parallelism: when head counts do not divide the model
        # axis, attention activations shard the sequence dim instead —
        # scores then need no 'model' all-reduce (weights stay hd-sharded)
        "seq": None if heads_ok else "model",
        "head_dim": None,
        "experts": "model" if sh.expert_sharded(cfg, tp) else None,
        "expert_ffn": None if sh.expert_sharded(cfg, tp) else "model",
        # MoE dispatch slots: shard capacity over the data axes so the
        # expert-ffn psum (ffn-sharded experts) moves 1/|data| of the bytes
        "capacity": (tuple(sh.data_axes(mesh)) or None),
        "ffn": "model",
        "inner": "model",
        "heads_inner": ("model" if cfg.ssm_state
                        and cfg.n_ssm_heads % tp == 0 else None),
        "vocab": "model" if cfg.vocab_size % tp == 0 else None,
        "model_dim": None,
        None: None,
    }
    prev = getattr(_CTX, "ctx", None)
    _CTX.ctx = (mesh, resolved)
    try:
        yield
    finally:
        _CTX.ctx = prev


def _active():
    """The active ``(mesh, resolved)`` pair, or None: none was set, or the
    code is being traced for one shard of a ``shard_map``."""
    ctx = getattr(_CTX, "ctx", None)
    if ctx is None or jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return ctx


def logical_spec(shape, *dims) -> P:
    """The PartitionSpec the active context gives an array of ``shape``
    with logical ``dims``; axes that do not divide their dim are dropped.
    Fully replicated when no context is active."""
    ctx = _active()
    if ctx is None:
        return P()
    mesh, resolved = ctx
    entries = []
    for dim_size, name in zip(shape, dims):
        ax = resolved.get(name)
        if ax is not None:
            n = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                n *= mesh.shape[a]
            if dim_size % n != 0:
                ax = None
        if isinstance(ax, tuple) and len(ax) == 1:
            ax = ax[0]
        entries.append(ax)
    return P(*entries)


def constrain(x: jax.Array, *dims) -> jax.Array:
    ctx = _active()
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx[0], logical_spec(x.shape, *dims)))


def per_shard(fn, in_specs, out_specs):
    """``fn`` run once per shard of the active mesh (``jax.shard_map``
    with PartitionSpec pytrees ``in_specs``/``out_specs``).

    For kernels the SPMD partitioner cannot split: a Pallas call is one
    opaque custom call.  ``fn`` must give the right answer on each shard
    alone.  With no context active, or a one-device mesh, ``fn`` itself
    is returned."""
    ctx = _active()
    if ctx is None or ctx[0].size == 1:
        return fn
    return jax.shard_map(fn, mesh=ctx[0], in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
