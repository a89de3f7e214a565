"""The distributed training step: microbatch gradient accumulation (remat'd
block scan inside), mixed-precision Adam with ZeRO-sharded state, explicit
sharding constraints so GSPMD reduce-scatters gradients instead of keeping
them replicated."""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.models import forward, cross_entropy, init_params
from repro.obs import device as obs_device
from repro.parallel import sharding as sh
from repro.parallel.act import activation_sharding
from repro.train.optimizer import adam_update, init_opt_state

AUX_WEIGHT = 0.01


def n_data_shards(mesh: Mesh) -> int:
    n = 1
    for a in sh.data_axes(mesh):
        n *= mesh.shape[a]
    return n


def resolve_microbatches(tc: TrainConfig, global_batch: int, mesh: Mesh) -> int:
    """Number of grad-accumulation steps."""
    nd = n_data_shards(mesh)
    per_shard = max(global_batch // max(nd, 1), 1)
    mb = tc.microbatch or 1
    mb = min(mb, per_shard)
    return max(per_shard // mb, 1)


def make_train_state(cfg: ModelConfig, tc: TrainConfig, key) -> Dict[str, Any]:
    params = init_params(cfg, key)
    return {"params": params, "opt": init_opt_state(params),
            "step": jnp.zeros((), jnp.int32)}


def state_specs(cfg: ModelConfig, tc: TrainConfig, mesh: Mesh,
                state_shape: Any) -> Any:
    """PartitionSpec pytree for the train state."""
    p_spec = sh.param_specs(cfg, state_shape["params"], mesh,
                            zero_data=tc.zero >= 3)
    o_spec = sh.param_specs(cfg, state_shape["params"], mesh,
                            zero_data=tc.zero >= 1)
    return {"params": p_spec,
            "opt": {"master": o_spec,
                    "m": jax.tree.map(lambda s: s, o_spec,
                                      is_leaf=lambda x: isinstance(x, P)),
                    "v": jax.tree.map(lambda s: s, o_spec,
                                      is_leaf=lambda x: isinstance(x, P))},
            "step": P()}


def build_train_step(cfg: ModelConfig, tc: TrainConfig, mesh: Mesh,
                     global_batch: int, seq_len: int, *, jit: bool = False):
    """Returns step(state, batch) -> (state, metrics).

    jit=True returns the step already jitted with the state buffers donated
    (argnums 0): params/opt/m/v are rewritten in place instead of
    double-buffered, halving the optimizer-state working set.  It is wrapped
    in ``obs.device.TracedStep``: each call opens the ``repro/train_step``
    host span.  Either way the step's ops carry the ``obs.device`` scopes
    (``model``, ``grad_accum``, ``optimizer``, and ``grad_sync`` and
    ``param_gather`` inside the last two) in their names.  jit=False
    (default) returns the traceable step for callers that lower it with
    explicit shardings (launch.dryrun) or wrap it themselves.
    """
    n_micro = resolve_microbatches(tc, global_batch, mesh)
    daxes = sh.data_axes(mesh)
    dax = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)

    def constrain(x, spec):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def micro_loss(params, micro):
        batch = {"tokens": micro["tokens"]}
        if "modal_embeds" in micro:
            batch["modal_embeds"] = micro["modal_embeds"]
        with jax.named_scope(obs_device.MODEL):
            logits, aux, _ = forward(cfg, params, batch,
                                     remat=tc.remat != "none")
            # labels cover the full (modal + text) sequence
            ce = cross_entropy(logits[:, :-1], micro["labels"][:, 1:])
            return ce + AUX_WEIGHT * aux, ce

    grad_fn = jax.value_and_grad(micro_loss, has_aux=True)

    def step(state, batch):
        with activation_sharding(mesh, cfg):
            return _step(state, batch)

    def _step(state, batch):
        params = state["params"]
        opt_spec = sh.param_specs(
            cfg, jax.tree.map(lambda x: x, params), mesh,
            zero_data=tc.zero >= 1)

        def reshape_micro(x):
            y = x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
            return constrain(y, P(None, dax, *([None] * (x.ndim - 1))))

        micros = jax.tree.map(reshape_micro, batch)

        def accum(carry, micro):
            g_acc, loss_acc = carry
            (loss, ce), g = grad_fn(params, micro)
            with jax.named_scope(obs_device.GRAD_ACCUM):
                g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                 g_acc, g)
                # the sum to the optimizer's ZeRO-1 shards; the compiler
                # reduces it over the data replicas in the backward already
                with jax.named_scope(obs_device.GRAD_SYNC):
                    g = jax.tree_util.tree_map(
                        lambda x, s: constrain(x, s), g, opt_spec)
            return (g, loss_acc + ce), None

        with jax.named_scope(obs_device.GRAD_ACCUM):
            g0 = jax.tree.map(
                lambda p, s: constrain(jnp.zeros(p.shape, jnp.float32), s),
                params, opt_spec)
        (g_sum, loss_sum), _ = jax.lax.scan(accum, (g0, 0.0), micros)
        with jax.named_scope(obs_device.OPTIMIZER):
            grads = jax.tree.map(lambda g: g / n_micro, g_sum)
            _, new_opt, gnorm = adam_update(
                tc, params, state["opt"], grads, state["step"], opt_spec)
            # hand the params back in the layout they came in: left free,
            # the compiler shards them like the ZeRO master copy they are
            # cast from, and a compiled step then refuses its own output
            # next step
            p_spec = sh.param_specs(cfg, params, mesh,
                                    zero_data=tc.zero >= 3)
            # the all-gather of the new parameters over the data replicas;
            # the compiler names it after the cast, so the cast is in here
            with jax.named_scope(obs_device.PARAM_GATHER):
                new_params = jax.tree.map(
                    lambda mp, p, s: constrain(mp.astype(p.dtype), s),
                    new_opt["master"], params, p_spec)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = {"loss": loss_sum / n_micro, "grad_norm": gnorm}
        return new_state, metrics

    if jit:
        step = obs_device.TracedStep(jax.jit(step, donate_argnums=(0,)))
    return step, n_micro
