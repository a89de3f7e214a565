"""Weights made by the benchmark from ``--seed``, not by the program.

The program's parameter tree gives the names, shapes and dtypes
(``jax.eval_shape`` of its initialiser: no values).  Each leaf is then
filled here by the usual rule for its role, from a key folded from the
seed and the leaf's path, so the plain reference can make the very same
weights without taking anything the program made:

- a leaf whose last name the model family's ``INIT`` lists
  (``references/<family>.py``): its rule, ``"ones"``, ``"zeros"`` or a
  function of the key, the shape and the dtype;
- embedding: truncated normal, std 0.02 (GPT-2);
- norm scales: ones;
- every other leaf is a matrix: truncated normal with std
  1/sqrt(fan-in), fan-in being the product of its dimensions but the last,
  and output projections (``wo``, ``w2``) scaled down by sqrt(2 x layers)
  as in GPT-2.

Leaves under ``blocks`` carry the layers on their leading axis.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

ONES = ("norm1", "norm2", "final_norm")
OUT_PROJ = ("wo", "w2")

#: a family's initialiser of a leaf, by the leaf's last name
Rule = Union[str, Callable[[jax.Array, Tuple[int, ...], Any], jax.Array]]


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of up to 64 bits."""
    seed = int(seed) % (1 << 64)
    return jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def path_name(path) -> str:
    return "/".join(str(getattr(e, "key", getattr(e, "idx", e)))
                    for e in path)


def _leaf(name: str, key, shape: Tuple[int, ...], dtype, stacked: bool,
          num_layers: int, init: Dict[str, Rule]):
    last = name.rsplit("/", 1)[-1]
    per_layer = shape[1:] if stacked else shape
    f32 = jnp.float32
    rule = init.get(last)
    if rule == "ones":
        return jnp.ones(shape, dtype)
    if rule == "zeros":
        return jnp.zeros(shape, dtype)
    if rule is not None:
        return rule(key, shape, dtype)
    if last in ONES:
        return jnp.ones(shape, dtype)
    if last == "embed":
        std = 0.02
    else:
        if len(per_layer) < 2:
            raise ValueError(f"no initialiser for vector leaf {name}"
                             f" {shape}")
        std = 1.0 / math.sqrt(math.prod(per_layer[:-1]))
        if last in OUT_PROJ:
            std /= math.sqrt(2 * num_layers)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, f32)
            * std).astype(dtype)


def make_params(cfg, key, init: Optional[Dict[str, Rule]] = None) -> Any:
    """The parameter tree of ``cfg`` filled from ``key`` (traceable), with
    the family's rules ``init`` before the defaults."""
    from repro.models import init_params
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))

    def fill(path, s):
        name = path_name(path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        return _leaf(name, k, tuple(s.shape), s.dtype,
                     name.startswith("blocks/"), cfg.num_layers, init or {})

    return jax.tree_util.tree_map_with_path(fill, shapes)
