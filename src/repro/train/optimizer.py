"""Mixed-precision Adam matching the paper's 20-byte/param accounting:
bf16 params (2) + bf16/fp32 grads (2-4 transient) + fp32 master (4) +
Adam m (4) + v (4).  ZeRO sharding of the fp32 state is applied by the
caller via PartitionSpecs (sharding.param_specs(zero_data=True)).

The per-leaf update goes through ``repro.kernels.dispatch``: the tree is
flattened and each leaf updated by the resolved ``adam_update`` op — the
Pallas fused kernel (one VMEM pass over the 20-byte state) on TPU, the
pure-jnp math (bit-identical to the pre-dispatch loop) on CPU/GPU."""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.kernels import dispatch


def lr_at(tc: TrainConfig, step: jax.Array) -> jax.Array:
    """Linear warmup then cosine decay to 10%."""
    warm = jnp.minimum((step + 1.0) / max(tc.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - tc.warmup_steps)
                    / max(tc.steps - tc.warmup_steps, 1), 0.0, 1.0)
    cos = 0.1 + 0.45 * (1.0 + jnp.cos(jnp.pi * prog))
    return tc.learning_rate * warm * cos


def init_opt_state(params: Any) -> Dict[str, Any]:
    # copy=True: fp32 leaves (A_log, D, dt_bias) must not alias the params
    # buffers, or donation in the jitted step sees the same buffer twice.
    master = jax.tree.map(lambda p: jnp.array(p, jnp.float32, copy=True),
                          params)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"master": master, "m": zeros,
            "v": jax.tree.map(jnp.copy, zeros)}


def adam_update(tc: TrainConfig, params: Any, opt: Dict[str, Any],
                grads: Any, step: jax.Array, specs: Any = None
                ) -> Tuple[Any, Dict[str, Any], jax.Array]:
    """One Adam step.  grads are fp32, already mean-reduced.  ``specs`` is
    the PartitionSpec tree of the grads and optimizer state on the active
    mesh, if any.  Returns (new bf16 params, new opt state, global grad
    norm)."""
    lr = lr_at(tc, step)
    t = step.astype(jnp.float32) + 1.0
    c1 = 1.0 - tc.beta1 ** t
    c2 = 1.0 - tc.beta2 ** t

    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))

    flat_g, treedef = jax.tree.flatten(grads)
    flat_m = treedef.flatten_up_to(opt["m"])
    flat_v = treedef.flatten_up_to(opt["v"])
    flat_p = treedef.flatten_up_to(opt["master"])
    flat_s = ([None] * len(flat_g) if specs is None
              else treedef.flatten_up_to(specs))
    new_m, new_v, new_master = [], [], []
    for g, m, v, mp, spec in zip(flat_g, flat_m, flat_v, flat_p, flat_s):
        # decoupled weight decay on matrices only (ndim >= 2)
        wd = tc.weight_decay if mp.ndim >= 2 else 0.0
        m2, v2, p2 = dispatch.adam_update_leaf(
            g, m, v, mp, lr=lr, beta1=tc.beta1, beta2=tc.beta2,
            eps=tc.eps, wd=wd, c1=c1, c2=c2, spec=spec)
        new_m.append(m2)
        new_v.append(v2)
        new_master.append(p2)
    new_opt = {"master": treedef.unflatten(new_master),
               "m": treedef.unflatten(new_m),
               "v": treedef.unflatten(new_v)}
    new_params = jax.tree.map(lambda mp, p: mp.astype(p.dtype),
                              new_opt["master"], params)
    return new_params, new_opt, gnorm
