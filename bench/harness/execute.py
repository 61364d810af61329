"""One run of a cell after the chip check: drive it, reduce what it
measured to the cell's metrics, and judge ``correct``.

The driver is the file ``bench/drivers/<kind>.py`` that the mix's
``kind`` names, and every metric, end to end or per layer, is read by its
own ``bench/metrics/<name>.py``: a cell of a new kind, or a new metric,
comes as new files.
"""
from __future__ import annotations

import json
import sys
from typing import Any, Dict

from . import common, spec


class RunRecord:
    """What a metric reader sees of a run: ``out`` is the driver's
    record (``setup_s``, ``window_s``, ``tokens``, ``window``, ``counts``,
    ``trace`` in a traced run)."""

    def __init__(self, cell: spec.Cell, out: Dict[str, Any],
                 peak: Dict[str, float]):
        self.cell = cell
        self.model = cell.model
        self.traffic = cell.traffic
        self.peak = peak
        self.out = out
        self.trace = out.get("trace")
        self.window_s = out["window_s"]
        self.counts = out.get("counts", {})


def read_metrics(cell: spec.Cell, metrics, out: Dict[str, Any],
                 peak: Dict[str, float]) -> Dict[str, Dict]:
    """Each metric's reader over the run; a reader that finds nothing to
    read returns None, and the metric is left out."""
    rec = RunRecord(cell, out, peak)
    res = {}
    for m in metrics:
        v = spec.metric_reader(m["name"], cell.root)(rec)
        if v is not None:
            res[m["name"]] = {"value": v, "unit": m["unit"]}
    return res


def execute(cell: spec.Cell, seed: int, seconds: float, trace: bool,
            devices, peak: Dict[str, float], t_start: float
            ) -> Dict[str, Any]:
    """Drive the cell and return the pieces of its result line."""
    out = cell.driver().run(cell, seed, seconds, trace, devices, t_start)
    diag = dict(out.get("diagnostics", {}))
    for ev in diag.pop("window_events", []):
        print("window event " + json.dumps(ev), file=sys.stderr)
    if diag:
        print("diagnostics " + json.dumps(diag), file=sys.stderr)
    compared, unjudged = common.judge(out["readings"], cell.limits)
    if unjudged:
        print("read, not judged " + json.dumps(unjudged), file=sys.stderr)
    device = common.device_info(devices)
    device["memory_peak_bytes"] = out["memory_peak"]
    breakdown = None
    if trace:
        metrics = read_metrics(cell, cell.per_layer, out, peak)
        tr = out["trace"]
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}
    else:
        metrics = read_metrics(cell, cell.end_to_end, out, peak)
    return {"correct": (all(c["ok"] for c in compared.values())
                        and out["failed"] == 0),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device, "compared": compared,
            "breakdown": breakdown, "out": out}
