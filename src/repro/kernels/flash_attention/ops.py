"""Jit'd public wrapper for the flash attention kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention.flash_attention import flash_attention


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention_op(q, k, v, *, causal: bool = True, window: int = 0,
                       block_q: int | None = None,
                       block_k: int | None = None,
                       interpret: bool | None = None):
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
