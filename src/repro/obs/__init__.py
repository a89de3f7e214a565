"""Observability plane (opt-in, decision-free).

``obs.enable()`` turns on the structured event tracer (``obs.trace``) and
the metrics registry (``obs.metrics``); the lifecycle engine and cluster
pool then feed them — spans, instants, counters, downsampled time
series — at bounded memory.  ``obs.export`` renders a Chrome-trace JSON
(Perfetto / ``chrome://tracing``) and a metrics dump;
``python -m repro.obs.report`` summarizes either a live registry or the
exported files.  The device path is named for the JAX profiler instead
(``obs.device``: scopes, host spans, compile counter).

Contract (ROADMAP "Observability plane"): telemetry is free — no decision
ever reads obs state, and every placement/timestamp is bit-identical with
obs on or off (golden-tested, including enable → run → disable round
trips).  When disabled, the entire plane costs one boolean check per
hook.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

from repro.obs.metrics import METRICS
from repro.obs.trace import TRACER


def enable(*, trace_capacity: Optional[int] = None,
           max_points: Optional[int] = None,
           sample_stride: Optional[int] = None) -> None:
    """Enable tracing + metrics (clears any previous run's data)."""
    TRACER.enable(capacity=trace_capacity)
    METRICS.enable(max_points=max_points, sample_stride=sample_stride)


def disable() -> None:
    """Stop collecting; collected data survives for export until the
    next ``enable()`` or ``clear()``."""
    TRACER.disable()
    METRICS.disable()


def clear() -> None:
    TRACER.clear()
    METRICS.clear()


def is_enabled() -> bool:
    return TRACER.enabled or METRICS.enabled


@contextmanager
def observed(**kwargs):
    """``with obs.observed(): simulate(...)`` — enable for the block,
    disable after (data kept for export)."""
    enable(**kwargs)
    try:
        yield
    finally:
        disable()
