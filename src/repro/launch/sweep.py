"""Sweep launcher (CLI).

  PYTHONPATH=src python -m repro.launch.sweep --preset fig6 --budget quick \
      --db runs.jsonl [--mesh 4,1] [--mode auto|sequential] \
      [--stop-after N] [--fake-devices N]

Runs a declarative sweep (a named preset from repro.sweep.presets, or a
SweepSpec JSON file via --spec) through the vectorized executor, appending
every completed run to the JSONL run database.  Re-launching with the same
spec + db *skips* completed runs — kill it mid-grid and run it again.

``--mesh data,model[,pod]`` shards the vectorized lane axis over the
"data" axis (proxy packs) and runs LM specs FSDP-sharded through the
Trainer.  ``--fake-devices N`` forces N host CPU devices for trying a
sharded sweep on one machine (must act before jax initializes).
"""
from __future__ import annotations

import argparse
import os
import sys


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default=None,
                    help="named sweep from repro.sweep.presets")
    ap.add_argument("--spec", default=None,
                    help="path to a SweepSpec JSON file")
    ap.add_argument("--budget", default="quick", choices=["quick", "full"])
    ap.add_argument("--db", default=None,
                    help="JSONL run database (enables resume)")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "vectorized", "sequential"])
    ap.add_argument("--stop-after", type=int, default=None,
                    help="execute at most N runs this launch")
    ap.add_argument("--by", default="label",
                    help="aggregate report key (label/scheme/lr/seed)")
    ap.add_argument("--journal", default=None,
                    help="write a unified runtime journal (one sweep_run "
                         "record per executed run, guard journal inlined) "
                         "to this JSONL path at exit (CI artifact)")
    ap.add_argument("--mesh", default=None,
                    help="data,model[,pod] device mesh, e.g. 4,1")
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="force N host CPU devices (XLA_FLAGS; must run "
                         "before jax init)")
    args = ap.parse_args(argv)
    if bool(args.preset) == bool(args.spec):
        ap.error("exactly one of --preset / --spec is required")
    return args


def main(argv=None) -> int:
    args = _parse_args(argv)
    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}")

    import jax

    if args.fake_devices and jax.device_count() < args.fake_devices:
        raise RuntimeError(
            f"--fake-devices {args.fake_devices} had no effect "
            f"({jax.device_count()} devices): jax was already initialized")

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import mesh_from_flag
    from repro.sweep import (RunDB, SweepSpec, aggregate, format_table,
                             get_sweep_spec, run_sweep)

    enable_compile_cache()
    if args.preset:
        spec = get_sweep_spec(args.preset, args.budget)
    else:
        with open(args.spec) as f:
            spec = SweepSpec.from_json(f.read())
    specs = spec if isinstance(spec, list) else [spec]
    runs = [r for s in specs for r in s.expand()]
    mesh = mesh_from_flag(args.mesh)
    name = args.preset or specs[0].name
    print(f"[sweep] {name}: {len(runs)} runs"
          + (f", mesh {dict(mesh.shape)}" if mesh is not None else "")
          + (f", db {args.db}" if args.db else ""), flush=True)

    db = RunDB(args.db) if args.db else None
    rep = run_sweep(runs, db=db, mesh=mesh, mode=args.mode,
                    stop_after=args.stop_after, verbose=True)
    print(f"[sweep] executed {rep.n_executed}, skipped (already in db) "
          f"{rep.n_skipped}" + (", INTERRUPTED by --stop-after"
                                if rep.interrupted else ""))
    done = [rep.results[rid] for rid in rep.order if rid in rep.results]
    print(format_table(aggregate(done, by=args.by)))
    if args.journal:
        from repro.runtime import Journal
        journal = Journal()
        for res in done:
            journal.emit("sweep_run", run_id=res.run_id, label=res.label,
                         scheme=res.scheme, steps=res.steps,
                         divergent=res.divergent,
                         diverge_step=res.diverge_step,
                         guard_journal=list(res.guard_journal))
        journal.to_jsonl(args.journal)
    if db is not None:
        db.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
