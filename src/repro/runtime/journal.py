"""Unified append-only event/journal bus for train, sweep, and serve.

Every loop in this repo narrates itself the same way: an ordered list of
small JSON-able records (``{"event": kind, ...}``) that is (a) consumed
in-process by tests and reports, (b) optionally mirrored to a JSONL sink
for CI artifacts, and (c) partially persisted into checkpoint / run-DB
meta.  Before this module each loop hand-rolled that trio — the Trainer's
``events`` list, the guard controller's transition journal, the sweep
executor's run records, the serve engines' request stream.  Now they all
hold a :class:`Journal`.

:class:`Journal` subclasses ``list`` on purpose: every existing consumer
(`trainer.events[-1]`, ``[e for e in eng.events if ...]``, journal
equality in the guard replay tests) keeps working unchanged, while new
code gains :meth:`emit` (typed construction), :meth:`of_kind` (filtered
views), :meth:`replay` and JSONL round-tripping.  Records are validated on
append: a record must be a mapping with a string ``"event"`` kind.

Checkpoint / run-DB meta is serialized from one place too:
:func:`checkpoint_meta` builds the meta dict the Trainer persists
(qcfg + recovery count + guard controller state + runtime segment index)
and :func:`parse_checkpoint_meta` inverts it, so the save and restore
sides can never drift apart field-by-field.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Iterable, Iterator, NamedTuple, Optional

__all__ = ["RECORD_KINDS", "Journal", "JsonlSink", "read_jsonl",
           "checkpoint_meta", "parse_checkpoint_meta", "RestoredMeta"]

# The registry of record kinds emitted in-tree.  Documentation + a tripwire
# for typos: emitting an unknown kind is allowed (downstream tools must
# tolerate forward-compatible streams) but `Journal(strict=True)` raises.
RECORD_KINDS = frozenset({
    # training loop
    "run_start", "recovery", "recovery_exhausted", "straggler",
    "qcfg_restored", "guard_restored",
    # staged execution
    "segment", "snapshot_to_serve",
    # guard controller
    "guard_transition",
    # serving engines
    "submit", "prefill", "request_done", "preempt", "pack_weights",
    # sweep executor
    "sweep_pack", "sweep_run",
    # memory accounting
    "memory",
})


class JsonlSink:
    """Append-only JSONL writer: one ``json.dumps`` line per record, flushed
    and fsync'd so a crash loses at most the in-flight record (the RunDB
    durability contract, now shared by every journal sink)."""

    def __init__(self, path: str, fsync: bool = True):
        self.path = path
        self._fsync = fsync
        self._fh = None

    def write(self, obj: Any) -> None:
        if self._fh is None:
            d = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(d, exist_ok=True)
            self._fh = open(self.path, "a")
        self._fh.write(json.dumps(obj) + "\n")
        self._fh.flush()
        if self._fsync:
            os.fsync(self._fh.fileno())

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def read_jsonl(path: str) -> Iterator[dict]:
    """Yield one dict per non-blank line (the RunDB/journal read path)."""
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                yield json.loads(line)


class Journal(list):
    """Append-only typed event journal (a ``list`` of record dicts).

    ``sink``: optional JSONL path (or an open :class:`JsonlSink`) every
    appended record is mirrored to.  ``strict=True`` additionally rejects
    kinds missing from :data:`RECORD_KINDS`.
    """

    def __init__(self, records: Iterable[dict] = (), *,
                 sink: Any = None, strict: bool = False):
        super().__init__()
        self.strict = strict
        self._sink = (JsonlSink(sink) if isinstance(sink, str) else sink)
        for rec in records:
            self.append(rec)

    # ---- write -------------------------------------------------------------
    def _validate(self, rec) -> dict:
        if not isinstance(rec, dict):
            raise TypeError(
                f"journal records are dicts, got {type(rec).__name__}")
        kind = rec.get("event")
        if not isinstance(kind, str) or not kind:
            raise ValueError(
                f"journal record needs a string 'event' kind: {rec!r}")
        if self.strict and kind not in RECORD_KINDS:
            raise ValueError(f"unknown journal record kind {kind!r}; "
                             f"known: {sorted(RECORD_KINDS)}")
        return rec

    def append(self, rec: dict) -> None:
        super().append(self._validate(rec))
        if self._sink is not None:
            self._sink.write(rec)

    def extend(self, recs: Iterable[dict]) -> None:
        for rec in recs:
            self.append(rec)

    def emit(self, kind: str, **fields) -> dict:
        """Build, validate, append and return a record."""
        rec = {"event": kind, **fields}
        self.append(rec)
        return rec

    # ---- read --------------------------------------------------------------
    def of_kind(self, *kinds: str) -> list:
        return [r for r in self if r.get("event") in kinds]

    def last(self, kind: str) -> Optional[dict]:
        for r in reversed(self):
            if r.get("event") == kind:
                return r
        return None

    def replay(self, kind: Optional[str] = None) -> Iterator[dict]:
        """Iterate records in append order, optionally filtered by kind —
        the read side of journal-driven re-execution (guard schedule
        replay, segment reconstruction)."""
        for r in self:
            if kind is None or r.get("event") == kind:
                yield r

    # ---- JSONL round trip --------------------------------------------------
    def to_jsonl(self, path: str) -> str:
        with JsonlSink(path, fsync=False) as sink:
            for rec in self:
                sink.write(rec)
        return path

    @classmethod
    def from_jsonl(cls, path: str, **kw) -> "Journal":
        return cls(read_jsonl(path), **kw)

    def close(self) -> None:
        if self._sink is not None:
            self._sink.close()


# ---------------------------------------------------------------------------
# checkpoint / run-DB meta (one serializer for save and restore)
# ---------------------------------------------------------------------------
class RestoredMeta(NamedTuple):
    """Parsed checkpoint meta.  ``qcfg`` is a QuantConfig (or None when the
    checkpoint predates qcfg persistence); ``guard`` is the raw controller
    ``state_dict`` (or None)."""
    step: Optional[int]
    qcfg: Optional[Any]
    qcfg_describe: Optional[str]
    recoveries: Optional[int]
    guard: Optional[dict]
    segment_index: int


def checkpoint_meta(*, step: int, qcfg, recoveries: int = 0,
                    controller=None, segment_index: int = 0,
                    extra: Optional[Dict[str, Any]] = None) -> dict:
    """The Trainer's checkpoint meta, built in one place: active precision
    scheme (so a resume can never silently revert a mid-run intervention),
    recovery count, runtime segment index, and — when a guard controller
    is live — its full autopilot state."""
    meta = {"step": int(step),
            "qcfg": qcfg.describe(),
            "qcfg_dict": qcfg.to_dict(),
            "recoveries": int(recoveries),
            "segment_index": int(segment_index)}
    if controller is not None:
        meta["guard"] = controller.state_dict()
    if extra:
        meta.update(extra)
    return meta


def parse_checkpoint_meta(meta: Optional[dict]) -> RestoredMeta:
    """Invert :func:`checkpoint_meta` (tolerating older checkpoints that
    lack newer fields — ``None`` marks absent channels)."""
    meta = meta or {}
    qcfg = None
    if meta.get("qcfg_dict") is not None:
        from repro.core import QuantConfig
        qcfg = QuantConfig.from_dict(meta["qcfg_dict"])
    return RestoredMeta(
        step=None if meta.get("step") is None else int(meta["step"]),
        qcfg=qcfg,
        qcfg_describe=meta.get("qcfg"),
        recoveries=(None if meta.get("recoveries") is None
                    else int(meta["recoveries"])),
        guard=meta.get("guard"),
        segment_index=int(meta.get("segment_index", 0)))
