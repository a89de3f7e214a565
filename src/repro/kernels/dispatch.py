"""Backend-dispatched kernel registry — the data-plane fast path.

Every compute hot-spot (``attention``, ``flash_decode``, ``ssd_scan``,
``adam_update``) registers two implementations:

* ``pallas`` — the TPU kernel (``repro.kernels.*``); attention's blocks
  come from its shape (``flash_attention_tiling``), the other kernels'
  through a per-process autotune cache keyed on
  ``(op, shape-bucket, dtype, backend)``;
* ``ref`` — the chunked pure-jnp production path (``repro.models.*`` /
  the per-leaf optimizer math), **bit-identical** to the pre-dispatch
  call sites (tests/test_dispatch.py goldens).

Call sites resolve per backend: TPU -> ``pallas``, CPU/GPU -> ``ref``.
The choice can be forced either way with the ``REPRO_KERNELS`` env var
(``pallas`` | ``ref`` | ``auto``) or programmatically with the
``force()`` context manager (tests and benchmarks use the latter).

Each public op (``attention``, ``ssd``, ``adam_update_leaf``,
``flash_decode``, ``mla_flash_decode``) runs inside a ``jax.named_scope``
of its own name, so every device op it lowers to (a kernel's backward
included) carries that name in a profiler trace (``obs.device``); the
attention kernels' backward carries ``attention_bwd`` inside it.

Resolution is memoized — after the first call per ``(op, backend,
override)`` the lookup amortizes to a single dict hit, guarded by the
perf smoke in tests/test_dispatch.py.  Implementation modules are
imported lazily at first *call* (not at registry import), so importing
this module never drags in the model or kernel packages.
"""
from __future__ import annotations

import functools
import os
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.obs import device as obs_device
from repro.parallel.act import logical_spec, per_shard

ENV_VAR = "REPRO_KERNELS"

#: op -> {"pallas": fn, "ref": fn}; populated by ``register`` below.
_REGISTRY: Dict[str, Dict[str, Callable]] = {}

#: (op, backend, override) -> (impl_name, fn) — the amortized dict hit.
_RESOLVE_CACHE: Dict[Tuple, Tuple[str, Callable]] = {}

#: (op, shape_bucket, dtype, backend) -> tuning params dict.
_AUTOTUNE_CACHE: Dict[Tuple, Dict[str, Any]] = {}

_forced: Optional[str] = None            # force() context override


def register(op: str, *, pallas: Callable, ref: Callable) -> None:
    _REGISTRY[op] = {"pallas": pallas, "ref": ref}
    _RESOLVE_CACHE.clear()


def ops() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _env_override() -> Optional[str]:
    val = os.environ.get(ENV_VAR, "auto").lower()
    return val if val in ("pallas", "ref") else None


@contextmanager
def force(impl: Optional[str]):
    """Force every op to the given impl ('pallas' | 'ref' | None=auto).

    Resolution happens when the op is *traced*: an already-jitted function
    keeps whichever impl it was first traced with (jax caches traces on
    shapes/dtypes only).  To switch impls, enter the context before the
    first call, or build a fresh jitted function inside it.
    """
    global _forced
    assert impl in (None, "pallas", "ref"), impl
    prev, _forced = _forced, impl
    try:
        yield
    finally:
        _forced = prev


def resolve(op: str, backend: Optional[str] = None) -> Tuple[str, Callable]:
    """Pick the implementation for ``op`` on ``backend`` (default: the
    process backend).  Returns ``(impl_name, fn)``; cached per
    ``(op, backend, override)`` so steady-state cost is one dict hit."""
    key = (op, backend, _forced, os.environ.get(ENV_VAR))
    try:
        return _RESOLVE_CACHE[key]
    except KeyError:
        pass
    impls = _REGISTRY[op]
    name = _forced or _env_override() \
        or ("pallas" if (backend or jax.default_backend()) == "tpu" else "ref")
    out = (name, impls[name])
    _RESOLVE_CACHE[key] = out
    return out


# ------------------------------------------------------------ autotune ---

def _bucket(dims: Sequence[int]) -> Tuple[int, ...]:
    """Round each dim up to the next power of two — shapes sharing a bucket
    share tuning parameters."""
    return tuple(1 << max(int(d) - 1, 0).bit_length() if d > 1 else 1
                 for d in dims)


def _concrete(*values) -> bool:
    """True iff no value is a jax tracer — i.e. we are *not* inside a jit
    trace and candidate thunks would measure real execution, not tracing."""
    return not any(isinstance(v, jax.core.Tracer) for v in values)


def autotuned(op: str, dims: Sequence[int], dtype, *,
              candidates: Sequence[Dict[str, Any]],
              default: Dict[str, Any],
              make_thunk: Optional[Callable[[Dict[str, Any]], Callable]] = None,
              backend: Optional[str] = None,
              exact: Tuple = ()) -> Dict[str, Any]:
    """Tuning params for ``op`` on arrays with key dims ``dims``.

    Cached on ``(op, shape-bucket, dtype, backend)``; ``exact`` values are
    appended to the key *unbucketed* (caller-chosen parameters like the
    ssd chunk must separate entries precisely, not by power-of-two
    bucket).  On a real TPU each
    candidate is timed once (via ``make_thunk(params)() -> array`` with
    ``block_until_ready``) and the fastest wins.  Timing requires concrete
    arrays: callers pass ``make_thunk=None`` when tracing (inside jit), and
    the heuristic ``default`` is then returned **without caching** so a
    later eager call can still tune the bucket.  On CPU/GPU (interpret
    mode — timing is meaningless) the default is returned and cached.
    When every candidate fails, the last failure is raised.
    """
    be = backend or jax.default_backend()
    key = (op, _bucket(dims) + tuple(exact), jnp.dtype(dtype).name, be)
    try:
        return _AUTOTUNE_CACHE[key]
    except KeyError:
        pass
    best = dict(default)
    if be == "tpu":
        if make_thunk is None:
            return best               # tracing: usable but not tuned/cached
        best_t, last_err = float("inf"), None
        for params in candidates:
            try:
                thunk = make_thunk(params)
                thunk()                                   # compile + warm
                t0 = time.perf_counter()
                thunk()
                dt = time.perf_counter() - t0
            except Exception as e:                        # noqa: BLE001
                last_err = e                              # infeasible tile
                continue
            if dt < best_t:
                best_t, best = dt, dict(params)
        if best_t == float("inf"):
            raise RuntimeError(
                f"autotune: every {op} candidate failed") from last_err
    _AUTOTUNE_CACHE[key] = best
    return best


def autotune_cache_info() -> Dict[Tuple, Dict[str, Any]]:
    return dict(_AUTOTUNE_CACHE)


def clear_caches() -> None:
    _RESOLVE_CACHE.clear()
    _AUTOTUNE_CACHE.clear()


# ------------------------------------------------------------- the ops ---
# Implementations import their modules lazily so `import dispatch` stays
# dependency-free (models/attention.py itself imports this module).

def _attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                   softmax_scale: Optional[float] = None):
    from repro.models.attention import chunked_attention
    return chunked_attention(q, k, v, causal=causal, window=window,
                             softmax_scale=softmax_scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention_vjp(q, k, v, causal, window, softmax_scale):
    from repro.kernels.flash_attention import flash_attention
    # the kernel takes its blocks from the shape (flash_attention_tiling)
    return flash_attention(q, k, v, causal=causal, window=window,
                           softmax_scale=softmax_scale)


def _flash_attention_fwd(q, k, v, *static):
    o = _flash_attention_vjp(q, k, v, *static)
    return o, (q, k, v, o)


def _flash_attention_bwd(causal, window, softmax_scale, res, g):
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_bwd, flash_attention_bwd_tiling)
    q, k, v, o = res
    with jax.named_scope(obs_device.ATTENTION_BWD):
        if flash_attention_bwd_tiling(q.shape[1], k.shape[1], q.shape[3],
                                      q.dtype) is None:
            # no block pair fits the kernels' VMEM: the chunked ref's VJP
            _, vjp = jax.vjp(functools.partial(
                _attention_ref, causal=causal, window=window,
                softmax_scale=softmax_scale), q, k, v)
            return vjp(g)
        return flash_attention_bwd(q, k, v, o, g, causal=causal,
                                   window=window, softmax_scale=softmax_scale)


_flash_attention_vjp.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def _attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                      softmax_scale: Optional[float] = None):
    # per (batch, head) shard: attention never mixes either
    qs = logical_spec(q.shape, "batch", None, "heads", None)
    ks = logical_spec(k.shape, "batch", None, "heads", None)
    return per_shard(
        lambda q, k, v: _flash_attention_vjp(q, k, v, causal, window,
                                             softmax_scale),
        (qs, ks, ks), qs)(q, k, v)


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              softmax_scale: Optional[float] = None):
    """q: (b, sq, H, D); k, v: (b, sk, K, D), H = K*G.  Returns (b, sq, H, D).

    On the Pallas path a ``custom_vjp`` saves q, k, v and the output, and
    its backward is the Pallas flash backward (``flash_attention_bwd``: a
    dq call that also rebuilds each row's log-sum-exp, then a dK/dV call),
    tiled by ``flash_attention_bwd_tiling``; only a shape that no block
    pair fits falls back to the VJP of the chunked ref
    (``models.attention.chunked_attention``)."""
    with jax.named_scope(obs_device.ATTENTION):
        return resolve("attention")[1](q, k, v, causal=causal,
                                       window=window,
                                       softmax_scale=softmax_scale)


def _flash_decode_ref(kind, *args, **kw):
    from repro.kernels.flash_decode import ref
    fn = ref.gqa_decode_ref if kind == "gqa" else ref.mla_decode_ref
    return fn(*args, **kw)


def _flash_decode_pallas(kind, *args, **kw):
    from repro.kernels.flash_decode import (flash_decode_gqa,
                                            flash_decode_mla)
    if kind == "gqa":
        q, k_cache, v_cache, valid = args
        fn = flash_decode_gqa
        dims = (q.shape[0], k_cache.shape[1], q.shape[2], q.shape[3])
    else:
        q_lat, q_rope, c_kv, k_rope, valid = args
        fn = flash_decode_mla
        dims = (q_lat.shape[0], c_kv.shape[1], q_lat.shape[1],
                c_kv.shape[2])

    def thunk_for(params):
        def thunk():
            return fn(*args, **kw, **params).block_until_ready()
        return thunk

    # the cache length (dims[1]) is a first-class shape-bucket axis: the
    # best split width depends on how many KV blocks there are to split
    params = autotuned(
        "flash_decode", dims, args[0].dtype,
        candidates=[{"block_s": bs} for bs in (128, 256, 512, 1024)],
        default={"block_s": 256}, exact=(kind,),
        make_thunk=thunk_for if _concrete(*args) else None)
    return fn(*args, **kw, **params)


def flash_decode(q, k_cache, v_cache, valid, *,
                 softmax_scale: Optional[float] = None):
    """Single-token GQA attention over a (ring) KV cache.

    q: (b, 1, H, D); k_cache, v_cache: (b, S, K, D); valid: (b, S) bool.
    Returns (b, 1, H, D).  TPU: split-KV Pallas kernel (parallel over
    cache blocks, two-pass online-softmax reduction); CPU/GPU: ref
    bit-identical to the seed ``decode_attention``."""
    with jax.named_scope(obs_device.FLASH_DECODE):
        return resolve("flash_decode")[1]("gqa", q, k_cache, v_cache, valid,
                                          softmax_scale=softmax_scale)


def mla_flash_decode(q_lat, q_rope, c_kv, k_rope, valid, *, denom: float):
    """Matrix-absorbed MLA latent decode attention.

    q_lat: (b, H, r); q_rope: (b, H, dr); c_kv: (b, S, r); k_rope:
    (b, S, dr); valid: (b, S) bool; denom = sqrt(dn + dr).  Returns
    o_lat (b, H, r)."""
    with jax.named_scope(obs_device.MLA_FLASH_DECODE):
        return resolve("flash_decode")[1]("mla", q_lat, q_rope, c_kv,
                                          k_rope, valid, denom=denom)


def _ssd_ref(x, dt_raw, A_log, B, C, D, dt_bias, *, chunk: int = 128):
    from repro.models.mamba2 import ssd_chunked
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias)
    A = -jnp.exp(A_log)
    return ssd_chunked(x, dt, A, B, C, D, chunk=chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _ssd_scan_vjp(x, dt_raw, A_log, B, C, D, dt_bias, chunk, kernel_chunk):
    from repro.kernels.ssd_scan import ssd_scan
    return ssd_scan(x, dt_raw, A_log, B, C, D, dt_bias, chunk=kernel_chunk)


def _ssd_scan_fwd(*args):
    return _ssd_scan_vjp(*args), args[:7]


def _ssd_scan_bwd(chunk, kernel_chunk, res, g):
    _, vjp = jax.vjp(functools.partial(_ssd_ref, chunk=chunk), *res)
    return vjp(g)


_ssd_scan_vjp.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)


def _ssd_pallas(x, dt_raw, A_log, B, C, D, dt_bias, *, chunk: int = 128):
    from repro.kernels.ssd_scan import ssd_scan

    def thunk_for(params):
        def thunk():
            return ssd_scan(x, dt_raw, A_log, B, C, D, dt_bias,
                            **params)[0].block_until_ready()
        return thunk

    # the caller's chunk is an exact key component: the default is cached,
    # and two calls differing only in chunk= must not share one entry
    params = autotuned(
        "ssd_scan", (x.shape[1], x.shape[3], B.shape[-1]), x.dtype,
        candidates=[{"chunk": c} for c in (64, 128, 256)],
        default={"chunk": chunk}, exact=(chunk,),
        make_thunk=thunk_for if _concrete(x, dt_raw, B, C) else None)
    # per (batch, head) shard: the scan never mixes either
    b, _, h, p = x.shape
    hs = logical_spec((h,), "heads_inner")
    bn = logical_spec(B.shape, "batch", None, None)
    xs = logical_spec(x.shape, "batch", None, "heads_inner", None)
    return per_shard(
        lambda *a: _ssd_scan_vjp(*a, chunk, params["chunk"]),
        (xs, logical_spec(dt_raw.shape, "batch", None, "heads_inner"), hs,
         bn, bn, hs, hs),
        (xs, logical_spec((b, h, p, B.shape[-1]), "batch", "heads_inner",
                          None, None)))(x, dt_raw, A_log, B, C, D, dt_bias)


def ssd(x, dt_raw, A_log, B, C, D, dt_bias, *, chunk: int = 128):
    """x: (b,s,h,p); dt_raw pre-softplus (b,s,h); A_log/D/dt_bias (h,);
    B, C: (b,s,n).  Returns (y (b,s,h,p), final_state (b,h,p,n) fp32).

    The Pallas kernel is forward-only.  Its ``custom_vjp`` saves the seven
    inputs and takes the backward as the VJP of the chunked ref
    (``models.mamba2.ssd_chunked`` at the caller's ``chunk``), recomputed
    from them."""
    with jax.named_scope(obs_device.SSD):
        return resolve("ssd_scan")[1](x, dt_raw, A_log, B, C, D, dt_bias,
                                      chunk=chunk)


def _adam_ref(g, m, v, master, *, lr, beta1: float, beta2: float,
              eps: float, wd: float, c1, c2, spec=None):
    g = g.astype(jnp.float32)
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * jnp.square(g)
    mhat = m / c1
    vhat = v / c2
    new_mp = master - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * master)
    return m, v, new_mp


def _adam_pallas(g, m, v, master, *, lr, beta1: float, beta2: float,
                 eps: float, wd: float, c1, c2, spec=None):
    from repro.kernels.adam_update import adam_update_fused

    def thunk_for(params):
        def thunk():
            return adam_update_fused(
                g, m, v, master, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                wd=wd, c1=c1, c2=c2, **params)[2].block_until_ready()
        return thunk

    params = autotuned(
        "adam_update", (g.size,), jnp.float32,
        candidates=[{"block": b} for b in (32 * 1024, 64 * 1024, 128 * 1024)],
        default={"block": 64 * 1024},
        make_thunk=thunk_for if _concrete(g, m, v, master, lr, c1, c2)
        else None)

    def update(g, m, v, master, lr, c1, c2):
        return adam_update_fused(g, m, v, master, lr=lr, beta1=beta1,
                                 beta2=beta2, eps=eps, wd=wd, c1=c1, c2=c2,
                                 **params)[:3]
    # elementwise: any shard of the leaf updates on its own
    spec = P() if spec is None else spec
    return per_shard(update, (spec,) * 4 + (P(),) * 3, (spec,) * 3)(
        g, m, v, master, lr, c1, c2)


def adam_update_leaf(g, m, v, master, *, lr, beta1: float, beta2: float,
                     eps: float, wd: float, c1, c2, spec=None):
    """One fused Adam step on one (flattened) parameter leaf.  All fp32;
    lr/c1/c2 may be traced.  ``spec`` is the leaf's PartitionSpec on the
    active mesh; the Pallas kernel runs once per shard of it.  Returns
    (m', v', master')."""
    with jax.named_scope(obs_device.ADAM_UPDATE_LEAF):
        return resolve("adam_update")[1](g, m, v, master, lr=lr,
                                         beta1=beta1, beta2=beta2, eps=eps,
                                         wd=wd, c1=c1, c2=c2, spec=spec)


register("attention", pallas=_attention_pallas, ref=_attention_ref)
register("flash_decode", pallas=_flash_decode_pallas, ref=_flash_decode_ref)
register("ssd_scan", pallas=_ssd_pallas, ref=_ssd_ref)
register("adam_update", pallas=_adam_pallas, ref=_adam_ref)
