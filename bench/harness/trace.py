"""From a profiler trace to intervals: what ran on each chip, and what the
host was doing, inside the benchmark's own ``bench.window`` span.

The benchmark records its own host spans (``jax.profiler.TraceAnnotation``)
around the calls it makes into the program.  On a TPU each device op event
is named by its HLO text (``%mx_matmul_pallas.115 = bf16[...]
custom-call(...)``); an op is labelled by the instruction name with the
instance number dropped, so a Pallas kernel reads as its function's name
(``mx_matmul_pallas``) and XLA's own ops as ``fusion``, ``copy``,
``all-gather-start`` and so on.  Control-flow ops (``while``,
``conditional``, ``call``) span the ops of their bodies, which the trace
also holds, so they count towards busy time but not as ops of their own.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
# A traced run measures at most this long: a trace holds every device op,
# and tracing slows the host.
TRACE_WINDOW_S = 10.0
HOST_SPANS = ("engine.step", "client.submit", "client.observe",
              "client.wait")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s|=|$)")
CONTAINERS = frozenset({"while", "conditional", "call"})


def label(name: str) -> str:
    """``%mx_matmul_pallas.115 = bf16[...] ...`` -> ``mx_matmul_pallas``."""
    m = _NAME.match(name)
    return m.group(1) if m else name.split(" ", 1)[0]


class Op:
    __slots__ = ("start", "end", "label", "module")

    def __init__(self, start, end, label, module=""):
        self.start, self.end, self.label = start, end, label
        self.module = module


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> List[Tuple[float, float]]:
    """Parts of the merged intervals ``a`` that the merged ``b`` leave
    uncovered."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class Trace:
    """Ops per chip and the benchmark's host spans, clipped to the window,
    in seconds on the trace's clock."""

    def __init__(self, ops: Dict[int, List[Op]],
                 spans: List[Tuple[float, float, str]],
                 window: Tuple[float, float]):
        self.ops = ops
        self.spans = spans
        self.window = window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def _mean(self, fn: Callable[[List[Op]], float]) -> float:
        return sum(fn(self.ops[c]) for c in self.chips) / max(len(self.ops),
                                                                1)

    def busy_s(self) -> float:
        """Seconds in which any op ran, averaged over the chips."""
        return self._mean(lambda ops: length(union(
            [(o.start, o.end) for o in ops])))

    def op_s(self, pred: Callable[[Op], bool]) -> float:
        """Summed duration of the ops ``pred`` selects, averaged over the
        chips."""
        return self._mean(lambda ops: sum(o.end - o.start for o in ops
                                          if pred(o)))

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` op labels that took the most device time (seconds,
        averaged over the chips)."""
        tot: Dict[str, float] = defaultdict(float)
        for c in self.chips:
            for o in self.ops[c]:
                if o.label not in CONTAINERS:
                    tot[o.label] += (o.end - o.start) / len(self.ops)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def host_activity(self, t: float) -> str:
        """The innermost benchmark span around time ``t``."""
        best: Optional[Tuple[float, str]] = None
        for s, e, name in self.spans:
            if s <= t <= e and (best is None or e - s < best[0]):
                best = (e - s, name)
        return best[1] if best else "host:outside-spans"

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest idle gaps of the first chip, each named by
        what the host was doing in its middle."""
        if not self.ops:
            return []
        busy = union([(o.start, o.end) for o in self.ops[self.chips[0]]])
        gaps = subtract([self.window], busy)
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_activity((s + e) / 2), e - s]
                for s, e in gaps[:n]]


def read(path: str) -> Trace:
    """Reduce the ``.xplane.pb`` under ``path`` to a :class:`Trace`."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    pd = ProfileData.from_file(files[0])
    spans: List[Tuple[float, float, str]] = []
    window: Optional[Tuple[float, float]] = None
    raw: Dict[int, List[Op]] = {}
    modules: Dict[int, List[Tuple[float, float, str]]] = {}
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m:
                if line.name == "XLA Ops":
                    labels: Dict[str, str] = {}
                    ops_ = []
                    for ev in line.events:
                        name = ev.name
                        if name not in labels:
                            labels[name] = label(name)
                        ops_.append(Op(ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                       labels[name]))
                    raw[int(m.group(1))] = ops_
                elif line.name == "XLA Modules":
                    modules[int(m.group(1))] = sorted(
                        (ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                        for ev in line.events)
            elif plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    elif ev.name in HOST_SPANS:
                        spans.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                      ev.name))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    w0, w1 = window
    ops: Dict[int, List[Op]] = {}
    for chip, evs in raw.items():
        mods = modules.get(chip, [])
        starts = [s for s, _, _ in mods]
        kept = []
        for o in evs:
            if o.end <= w0 or o.start >= w1:
                continue
            i = bisect.bisect_right(starts, o.start) - 1
            if i >= 0 and mods[i][1] >= o.start:
                o.module = mods[i][2]
            o.start, o.end = max(o.start, w0), min(o.end, w1)
            kept.append(o)
        ops[chip] = kept
    return Trace(ops, [s for s in spans if s[1] > w0 and s[0] < w1], window)


def window_s(seconds: float, trace: bool) -> float:
    """How long a run measures: ``--seconds``, capped when traced."""
    return min(seconds, TRACE_WINDOW_S) if trace else seconds


@contextlib.contextmanager
def traced(enabled: bool) -> Iterator[Dict[str, Optional[Trace]]]:
    """Profile the enclosed block when ``enabled``; afterwards
    ``out["trace"]`` holds the reduced trace.  The raw trace goes to a
    temporary directory under ``TMPDIR`` and is removed once read."""
    out: Dict[str, Optional[Trace]] = {"trace": None}
    if not enabled:
        yield out
        return
    import jax
    d = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(d)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        out["trace"] = read(d)
    finally:
        shutil.rmtree(d, ignore_errors=True)
