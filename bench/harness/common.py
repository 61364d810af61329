"""Pieces every driver shares: the chip check, the compile counter,
percentiles, the comparison with limits, and the result line."""
from __future__ import annotations

import json
import math
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def require_chips(n: int):
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise.  A
    run never falls back to the CPU."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from None
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devs)}")
    return devs[:n]


def device_info(devices) -> Dict[str, Any]:
    import jax
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": jax.device_count()}


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts XLA executables built (JAX's backend-compile event) or loaded
    from the persistent compilation cache (its cache-hit event), and the
    program's own retraces (``runtime.total_traces``)."""

    def __init__(self):
        import jax
        self.backend = 0

        def built(event: str, duration: float, **kw) -> None:
            if event == BACKEND_COMPILE:
                self.backend += 1

        def loaded(event: str, **kw) -> None:
            if event == CACHE_HIT:
                self.backend += 1

        jax.monitoring.register_event_duration_secs_listener(built)
        jax.monitoring.register_event_listener(loaded)

    def count(self) -> int:
        from repro.runtime import total_traces
        return self.backend + total_traces()


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile over every sample (linear interpolation), NaN
    where there is none."""
    import numpy as np
    if not len(values):
        return math.nan
    return float(np.percentile(np.asarray(values, float), q))


def judge(readings: Dict[str, float], limits: Dict[str, Any]
          ) -> Tuple[Dict[str, Dict[str, Any]], Dict[str, float]]:
    """(compared, unjudged): each number the limits file gives a limit,
    beside it, passing when it is finite and at most its limit (a limit
    whose number the run did not read fails); and the readings that have
    no limit, which are printed and decide nothing."""
    out = {}
    for k, lim in limits.items():
        if not isinstance(lim, (int, float)) or isinstance(lim, bool):
            continue
        v = readings.get(k)
        ok = v is not None and math.isfinite(v) and v <= lim
        out[k] = {"value": v, "limit": lim, "ok": bool(ok)}
    rest = {k: v for k, v in readings.items() if k not in out}
    return out, rest


def emit(correct: bool, attempted: int, failed: int,
         metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
         compared: Dict[str, Dict[str, Any]],
         breakdown: Optional[Dict[str, List]] = None) -> None:
    """Print the numbers compared on standard error, then the result line
    (the last line of standard output), with ``compared`` as its last key."""
    for k, c in compared.items():
        print(f"compared {k} {c['value']!r} limit {c['limit']!r}"
              f"{'' if c['ok'] else '  FAIL'}", file=sys.stderr)
    line: Dict[str, Any] = {"correct": bool(correct),
                            "attempted": int(attempted),
                            "failed": int(failed), "metrics": metrics,
                            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in compared.items()}
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
