"""Names the train step leaves in a profiler trace, and the compile counter.

Three kinds, all read back from one ``jax.profiler`` trace:

* device scopes (``jax.named_scope``): compile-time metadata that every HLO
  op a scope's code lowers to carries in its ``op_name``; free at run time.
  Differentiation adds its own markers around them, so one scope splits
  into phases: ``jvp(model)`` is the forward, ``transpose(jvp(model))`` the
  backward, and ``rematted_computation`` inside it the recomputed forward.
  ``MODEL`` covers the forward and loss, ``GRAD_ACCUM`` the f32 gradient
  accumulation (backward work), ``OPTIMIZER`` the whole update; each public
  op of ``kernels.dispatch`` runs under a scope of its own name.  Nested
  scopes name the exchanges between chips and a kernel's backward:
  ``GRAD_SYNC`` (in ``GRAD_ACCUM``) the summed gradient taken to the
  optimizer's ZeRO-1 shards (XLA reduces it over the data replicas in the
  backward already, in all-reduces it leaves unnamed); ``PARAM_GATHER``
  (in ``OPTIMIZER``) the new parameters cast and taken back to their own
  sharding, the all-gather over the data replicas; ``ATTENTION_BWD`` (in
  ``ATTENTION``) the backward of the attention kernel's ``custom_vjp``;
* host spans (``span``): ``jax.profiler.TraceAnnotation`` under ``repro/``,
  with their keyword arguments as stats; next to free with no profiler
  running.  ``repro/train_step`` marks one dispatch of the step
  (``step=<n>``, ``compiled=<0|1>``), ``repro/data`` one batch's
  preparation;
* ``COMPILES``: XLA compiles and persistent-cache loads in this process,
  counted by ``jax.monitoring`` listeners, which feeds ``compiled``.
"""
from __future__ import annotations

import threading
from typing import Callable

import jax
from jax import monitoring

MODEL = "model"
OPTIMIZER = "optimizer"
GRAD_ACCUM = "grad_accum"
GRAD_SYNC = "grad_sync"
PARAM_GATHER = "param_gather"
# ``kernels.dispatch``: each public op runs under a scope of its own name
ATTENTION = "attention"
ATTENTION_BWD = "attention_bwd"
SSD = "ssd"
ADAM_UPDATE_LEAF = "adam_update_leaf"
FLASH_DECODE = "flash_decode"
MLA_FLASH_DECODE = "mla_flash_decode"

SPAN_PREFIX = "repro/"
TRAIN_STEP = "train_step"
DATA = "data"

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span ``repro/<name>`` on the profiler's clock."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **stats)


class CompileCounter:
    """Programs this process compiled or loaded from the persistent cache,
    from the moment ``listen`` was first called."""

    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()
        self._listening = False

    def listen(self) -> None:
        """Register the listeners; later calls do nothing."""
        with self._lock:
            if self._listening:
                return
            self._listening = True
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self._add()

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self._add()

    def _add(self) -> None:
        with self._lock:
            self.count += 1


#: the process-wide counter (compiles are process-wide)
COMPILES = CompileCounter()


class TracedStep:
    """A jitted step whose every call opens ``repro/train_step`` with the
    call's number and whether it compiled or loaded a program.  ``lower``
    and every other attribute are the jitted function's."""

    def __init__(self, fn: Callable):
        self._fn = fn
        self._calls = 0
        COMPILES.listen()

    def __call__(self, *args, **kw):
        n, self._calls = self._calls, self._calls + 1
        with span(TRAIN_STEP, step=n) as s:
            before = COMPILES.count
            out = self._fn(*args, **kw)
            s.set_metadata(compiled=int(COMPILES.count != before))
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)
