"""Readings that set the limits of ``correct``: the program's sound runs and
its control, many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51

For each seed it drives the cell's timed path as a run does and prints
the numbers compared and the diagnostics behind them; then, on the first
``--control`` seeds, the control: the plain reference put in the
program's place in the next precision below the configuration's (the
``control`` of the cell's limits file), at the same served positions.
Both are judged by the cell's limits, as a run is, so each line says
whether the run, and whether its control, came out correct; a control
has to come out not correct.  The last line sums up: the largest and
smallest reading and the smallest control reading of each number.  Runs
on the chip only, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", type=int, default=3,
                    help="run the control on this many of the seeds")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import common, spec

    cell = spec.resolve(args.workload, ROOT)
    try:
        devices = common.require_chips(cell.chips)
    except common.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    seeds = [int(s) for s in args.seeds.split(",")]
    sound, control = readings(cell, seeds, args.seconds, devices,
                              args.control)
    summary = {k: {"max": _extreme(max, sound, k),
                   "min": _extreme(min, sound, k),
                   "control_min": _extreme(min, control, k)}
               for k in sound[0]}
    print(json.dumps({"workload": cell.name, "seeds": args.seeds,
                      "summary": summary}), flush=True)
    return 0


def _extreme(fn, rows, k):
    vals = [r[k] for r in rows if isinstance(r.get(k), (int, float))]
    return fn(vals) if vals else None


def correct(readings, limits) -> bool:
    """Whether ``readings`` pass the cell's limits, as a run judges them."""
    from bench.harness.common import judge
    compared, _ = judge(readings, limits)
    return all(c["ok"] for c in compared.values())


def readings(cell, seeds, seconds, devices, n_control):
    """(readings, control readings): one dict per seed, the control on the
    first ``n_control`` seeds."""
    drv = cell.driver()
    sound, ctrl = [], []
    for i, seed in enumerate(seeds):
        out = drv.run(cell, seed, seconds, False, devices,
                      time.perf_counter())
        low = {}
        if i < n_control:
            # the control stands in for the program: the run's own
            # numbers that it does not replace are the run's
            low = dict(out["readings"],
                       **drv.control(cell, seed, seconds, devices, out))
            ctrl.append(low)
        sound.append(dict(out["readings"], **out.get("diagnostics", {})))
        print(json.dumps({
            "seed": seed, "readings": sound[-1],
            "correct": correct(out["readings"], cell.limits)
            and out["failed"] == 0,
            "control": low,
            "control_correct": correct(low, cell.limits) if low else None,
            "setup_s": out["setup_s"], "memory_peak": out["memory_peak"],
            "attempted": out["attempted"]}), flush=True)
    return sound, ctrl


if __name__ == "__main__":
    sys.exit(main())
