"""Compiled-segment scheduler: the staged-execution model under all loops.

The paper's central mitigation result (Fig. 7) is that an *in situ*
precision-scheme change averts an impending divergence — which makes
"a run is a sequence of compiled segments separated by static (qcfg)
transitions" the natural execution model.  Trainer recompiles on a guard
or watchdog intervention, the sweep executor splits its scan at phase
switches, the serve engines key their step functions on (cfg, qcfg):
these are all the same operation — end segment, swap statics,
recompile-or-hit-cache.  This module owns that operation:

* :class:`SegmentFn` wraps ``jax.jit`` with the repo-wide compilation
  discipline (static hashable config args, explicit in/out shardings,
  donated carries) **plus trace accounting**: every retrace is recorded
  under its static-arg key, so "a revisited qcfg must not retrace" is a
  testable invariant instead of folklore (jit's cache is keyed on the
  static args + shapes, so re-entering a previously compiled segment
  must be a cache hit — the CI smoke in benchmarks/runtime_unify.py
  asserts exactly this).

* :func:`plan_segments` compiles an intervention schedule (explicit
  phases + a *scheduled* guard policy) into ``[(start, end, qcfg)]``
  :class:`Segment` spans — the shared planner behind the sweep
  executor's phase splits and the Fig. 7 benchmarks.

* :class:`SegmentTracker` numbers the segments of a *live* run (Trainer):
  each qcfg transition — guard escalation, watchdog recovery, restore
  adoption — bumps the index and lands a ``segment`` record on the
  journal; the index rides checkpoint meta so a resumed run continues
  the same segment sequence.

* :class:`MetricsWindow` is the deferred host-sync window shared by the
  training loop: metrics stay on device, one ``block_until_ready`` per
  window, wall time amortized over the window's steps.
"""
from __future__ import annotations

import functools
import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

__all__ = ["SegmentFn", "Segment", "plan_segments", "SegmentTracker",
           "MetricsWindow", "registry", "cache_stats", "total_traces"]


# Every live SegmentFn registers here so benchmarks / smokes can audit the
# process-wide compilation behavior without threading handles around.
_REGISTRY: List["SegmentFn"] = []


class SegmentFn:
    """A jitted step function with per-static-key trace accounting.

    Semantics are exactly ``jax.jit(fn, static_argnums=..., donate_argnums=
    ..., in_shardings=..., out_shardings=...)``; additionally every trace
    (jit invoking the wrapped Python function) is counted under the tuple
    of its static argument values.  With ``static_argnums`` jit calls the
    Python function only when compiling for a new (statics, shapes) key,
    so ``traces_for(key)`` staying flat across repeated transitions is the
    proof that a revisited segment hit the compile cache.
    """

    def __init__(self, fn: Callable, *, static_argnums: Sequence[int] = (),
                 donate_argnums: Sequence[int] = (), in_shardings=None,
                 out_shardings=None, name: Optional[str] = None):
        import jax
        self.name = name or getattr(fn, "__name__", "segment")
        self.static_argnums = tuple(static_argnums)
        self.calls = 0
        self._trace_log: List[tuple] = []
        self._trace_counts: Dict[tuple, int] = {}
        statics = self.static_argnums

        @functools.wraps(fn)
        def traced(*args, **kwargs):
            key = tuple(args[i] for i in statics)
            self._trace_log.append(key)
            self._trace_counts[key] = self._trace_counts.get(key, 0) + 1
            return fn(*args, **kwargs)

        kw: Dict[str, Any] = {}
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
        if out_shardings is not None:
            kw["out_shardings"] = out_shardings
        self._jit = jax.jit(traced, static_argnums=self.static_argnums,
                            donate_argnums=tuple(donate_argnums), **kw)
        _REGISTRY.append(self)

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self._jit(*args, **kwargs)

    def lower(self, *args, **kwargs):
        """``jax.jit(...).lower``: the program a call with these arguments
        runs, for inspection (``.compile().as_text()``); runs nothing."""
        return self._jit.lower(*args, **kwargs)

    # ---- accounting --------------------------------------------------------
    @property
    def n_traces(self) -> int:
        return len(self._trace_log)

    @property
    def n_keys(self) -> int:
        return len(self._trace_counts)

    def traces_for(self, *static_args) -> int:
        """Trace count for one static-arg tuple (0 = never compiled)."""
        return self._trace_counts.get(tuple(static_args), 0)

    def stats(self) -> dict:
        return {"name": self.name, "calls": self.calls,
                "traces": self.n_traces, "keys": self.n_keys}


def registry() -> List[SegmentFn]:
    return list(_REGISTRY)


def cache_stats() -> List[dict]:
    """Per-SegmentFn compile/call accounting for the whole process."""
    return [f.stats() for f in _REGISTRY]


def total_traces() -> int:
    return sum(f.n_traces for f in _REGISTRY)


# ---------------------------------------------------------------------------
# segment planning (phases + scheduled guard -> [(start, end, qcfg)])
# ---------------------------------------------------------------------------
class Segment(NamedTuple):
    start: int
    end: int
    qcfg: Any


def plan_segments(steps: int, qcfg0, phases: Sequence[Tuple[int, str]] = (),
                  guard: Any = None) -> List[Segment]:
    """Compile an intervention schedule into contiguous step segments.

    ``phases``: ``((switch_step, intervention_name), ...)`` applied
    cumulatively (the paper's Fig. 7 protocol).  ``guard``: a policy
    name/spec/instance — a *scheduled* policy's entries merge into the
    same split (string entries apply cumulatively like phases, integer
    entries jump to an absolute ladder level of the base scheme); online
    policies contribute nothing here (their transitions are decided live,
    one segment at a time, by the caller's controller).  Switches are
    clipped to [0, steps]; coincident switches apply in (step, str(what))
    order so the plan is deterministic.
    """
    from repro.core import apply_intervention
    switches: List[Tuple[int, Any]] = [(int(s), iv) for s, iv in phases]
    ctl = None
    if guard:
        from repro.guard import PrecisionController, get_policy
        pol = get_policy(guard)
        if pol.is_scheduled:
            ctl = PrecisionController(qcfg0, pol)
            switches += [(int(s), w) for s, w in pol.schedule]
    segs: List[Segment] = []
    qcfg, prev = qcfg0, 0
    for step, what in sorted(switches, key=lambda x: (x[0], str(x[1]))):
        step = min(max(int(step), 0), int(steps))
        if step > prev:
            segs.append(Segment(prev, step, qcfg))
            prev = step
        if isinstance(what, str):
            qcfg = apply_intervention(qcfg, what)
        else:
            qcfg = ctl.qcfg_at_level(what)
    if prev < steps:
        segs.append(Segment(prev, int(steps), qcfg))
    return segs or [Segment(0, int(steps), qcfg0)]


# ---------------------------------------------------------------------------
# live segment tracking (Trainer)
# ---------------------------------------------------------------------------
class SegmentTracker:
    """Numbers the compiled segments of a live run.

    Each accepted qcfg transition bumps ``index`` and (when a journal is
    attached) lands a ``segment`` record carrying the boundary step, the
    reason (``guard`` / ``recovery`` / ``restore`` / ``manual``), and the
    before/after schemes.  ``index`` is persisted in checkpoint meta so a
    resume continues the original segment numbering.
    """

    def __init__(self, qcfg, journal=None, index: int = 0):
        self.qcfg = qcfg
        self.index = int(index)
        self.journal = journal

    def transition(self, step: int, qcfg, reason: str = "manual") -> bool:
        """Enter a new segment iff the scheme actually changed."""
        if qcfg == self.qcfg:
            return False
        old = self.qcfg
        self.index += 1
        self.qcfg = qcfg
        if self.journal is not None:
            self.journal.append({
                "event": "segment", "index": self.index, "step": int(step),
                "reason": reason, "from_qcfg": old.describe(),
                "to_qcfg": qcfg.describe()})
        return True

    def restore(self, index: int, qcfg) -> None:
        """Adopt a checkpointed (segment_index, qcfg) without journaling —
        a restore re-enters an existing segment, it does not start one."""
        self.index = int(index)
        self.qcfg = qcfg


# ---------------------------------------------------------------------------
# deferred host-sync metric window (Trainer)
# ---------------------------------------------------------------------------
class MetricsWindow:
    """Buffers on-device per-step metrics; one host sync per drain.

    Steps chain through their carries, so the *last* metric being ready
    means the whole window finished; wall time is amortized over the
    window's steps (exact step latency when the window is one step).
    ``reset_clock()`` excludes host-side work done after a drain (recovery
    handling, checkpoint writes) from the next window's timing.
    """

    def __init__(self, sync_key: str = "loss"):
        self._key = sync_key
        self._pending: List[tuple] = []
        self._t0 = time.monotonic()

    def push(self, step: int, metrics) -> None:
        self._pending.append((step, metrics))

    def __len__(self) -> int:
        return len(self._pending)

    def __bool__(self) -> bool:
        return bool(self._pending)

    def drain(self) -> List[tuple]:
        """Block on the window's last metric; return [(step, metrics,
        per_step_seconds)] and clear the buffer."""
        if not self._pending:
            return []
        import jax
        jax.block_until_ready(self._pending[-1][1][self._key])
        per = (time.monotonic() - self._t0) / len(self._pending)
        out = [(s, m, per) for s, m in self._pending]
        self._pending = []
        self._t0 = time.monotonic()
        return out

    def reset_clock(self) -> None:
        self._t0 = time.monotonic()
