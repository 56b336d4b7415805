"""Named host phases on the profiler's clock (DESIGN.md §11).

``phase(name, obs)`` is the one way the mining path marks a host phase.
It always opens ``jax.profiler.TraceAnnotation("repro." + name)``, so
the phase lands in the profiler's host plane on the same clock as the
device's operations (a no-op TraceMe, about a microsecond, when no
profiler runs).  When ``obs`` is an enabled hub it also observes the
phase's wall time, in milliseconds, into the hub's
``pipeline_stage_ms{stage=name}`` histogram (or ``metric`` with
``stage``, where a loop keeps its own series).

JAX is imported on first use, so ``repro.obs`` stays importable where
JAX is not (the router, load generators).
"""
from __future__ import annotations

import time
from typing import Optional

__all__ = ["phase"]


class phase:
    """Context manager for one host phase.  ``args`` become the span's
    arguments (``bytes=...``); ``ms`` holds the phase's wall time after
    exit when a hub timed it, else None."""

    __slots__ = ("_span", "_hist", "_t0", "ms")

    def __init__(self, name: str, obs=None, *,
                 metric: str = "pipeline_stage_ms",
                 stage: Optional[str] = None, **args):
        from jax.profiler import TraceAnnotation
        self._span = TraceAnnotation("repro." + name, **args)
        self._hist = (obs.metrics.histogram(metric, stage=stage or name)
                      if obs is not None and getattr(obs, "enabled", False)
                      else None)
        self.ms = None

    def __enter__(self) -> "phase":
        self._span.__enter__()
        if self._hist is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        if self._hist is not None:
            self.ms = (time.perf_counter() - self._t0) * 1e3
            self._hist.observe(self.ms)
        self._span.__exit__(*exc)
        return False
