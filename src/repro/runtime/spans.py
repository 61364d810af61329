"""Named host phases on the profiler's clock, with their times kept.

``with spans("decode.wait", live=n):`` opens a
``jax.profiler.TraceAnnotation`` named ``<prefix>.decode.wait`` whose
arguments become stats of the trace event, so a profiled run sees the
phase on the same clock as the device's ops; and it adds the phase's
``time.perf_counter`` duration to ``last_step`` (the current step's
phases, begun anew by :meth:`Spans.step`) and to ``totals``.  With no
trace active the annotation costs about a microsecond.

A phase whose name ends in ``.wait`` is, by convention, the host blocked
on the device; no other phase takes that suffix.
"""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, Iterator

from jax.profiler import TraceAnnotation

__all__ = ["Spans"]


class Spans:
    """Per-phase host times of a loop, each phase also a profiler span."""

    def __init__(self, prefix: str):
        self.prefix = prefix
        self.last_step: Dict[str, float] = {}
        self.totals: Dict[str, float] = defaultdict(float)

    @contextlib.contextmanager
    def __call__(self, name: str, **args: int) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(f"{self.prefix}.{name}", **args):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.last_step[name] = self.last_step.get(name, 0.0) + dt
            self.totals[name] += dt

    def step(self):
        """The span around one whole step; starts a new ``last_step``."""
        self.last_step = {}
        return self("step")
