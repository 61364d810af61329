"""repro.runtime — the staged-execution engine under train, sweep, serve.

A run is a sequence of compiled segments separated by static (qcfg)
transitions; every loop in the repo executes that model through this
package:

* :mod:`~repro.runtime.segments` — :class:`SegmentFn` (jit + explicit
  shardings + donation + per-static-key trace accounting),
  :func:`plan_segments` (phases + scheduled guard -> step spans),
  :class:`SegmentTracker` (live segment numbering), and
  :class:`MetricsWindow` (deferred host-sync windows).
* :mod:`~repro.runtime.journal` — :class:`Journal`, the single
  append-only event bus (typed records, JSONL sink, replay), plus the
  one checkpoint-meta serializer (:func:`checkpoint_meta` /
  :func:`parse_checkpoint_meta`).
* :mod:`~repro.runtime.memory` — :class:`MemoryLedger` device-memory
  accounting with a budget guard.
* :mod:`~repro.runtime.spans` — :class:`Spans`, named host phases that
  land on the profiler's clock and keep their times per step.
* :func:`snapshot_to_serve` — a mid-training model handed to the
  serving engine on-device, no checkpoint round-trip.
"""
from .bridge import snapshot_to_serve
from .journal import (RECORD_KINDS, Journal, JsonlSink, RestoredMeta,
                      checkpoint_meta, parse_checkpoint_meta, read_jsonl)
from .memory import MemoryBudgetError, MemoryLedger, tree_bytes
from .segments import (MetricsWindow, Segment, SegmentFn, SegmentTracker,
                       cache_stats, plan_segments, registry, total_traces)
from .spans import Spans

__all__ = [
    "Journal", "JsonlSink", "RECORD_KINDS", "read_jsonl", "RestoredMeta",
    "checkpoint_meta", "parse_checkpoint_meta",
    "SegmentFn", "Segment", "plan_segments", "SegmentTracker",
    "MetricsWindow", "registry", "cache_stats", "total_traces",
    "MemoryLedger", "MemoryBudgetError", "tree_bytes",
    "snapshot_to_serve", "Spans",
]
