"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell is looked up by name in ``BENCHMARK.json``.  One process: it
makes the weights and inputs from ``--seed`` on the device, turns JAX's
persistent compilation cache on (``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` says otherwise), warms the cell's own
shapes, measures for ``--seconds``, checks what the timed path produced
against the plain reference, and prints one JSON line last.  With
``--trace 1`` the window is profiled and the line carries the cell's
per-layer metrics and a breakdown instead of its end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"bench: no program under {SRC}", file=sys.stderr)
        return 2
    for p in (str(ROOT), str(SRC)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness import common, spec

    cell = spec.resolve(args.workload, ROOT)
    try:
        devices = common.require_chips(cell.chips)
    except common.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # cache every program, however small or quick to compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    peak = spec.peaks(devices[0].device_kind, ROOT)

    from bench.harness.execute import execute
    res = execute(cell, args.seed, args.seconds, bool(args.trace), devices,
                  peak, T_START)
    out = res["out"]
    print("setup " + json.dumps({"setup_s": out["setup_s"],
                                 **out["setup_parts"]}), flush=True)
    common.emit(res["correct"], res["attempted"], res["failed"],
                res["metrics"], res["device"], res["compared"],
                res["breakdown"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
