"""Training driver (CLI).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --variant smoke \
      --precision mxfp8_e4m3 --steps 200 --batch 8 --seq 128 \
      --ckpt-dir /tmp/run1 [--resume] [--auto-intervention bf16_activations] \
      [--guard autopilot] [--mesh 4,2] [--grad-accum 2] [--pod-compress e4m3]

Runs the fault-tolerant Trainer (spike watchdog → rollback → precision
intervention) on the selected architecture with the deterministic
synthetic LM stream.  ``--mesh data,model[,pod]`` shards the run over the
local devices (params/optimizer FSDP+TP, batch over pod×data); a third
mesh dim adds the cross-pod gradient all-reduce, optionally MX-compressed
with ``--pod-compress``.  ``--fake-devices N`` forces N host CPU devices
(must be set before jax initializes — use it as the first smoke test of a
sharded config on one machine).
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-paper")
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--precision", default="bf16")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--peak-lr", type=float, default=2e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--auto-intervention", default="bf16_activations")
    ap.add_argument("--guard", default=None,
                    help="precision-autopilot policy: a repro.guard preset "
                         "(autopilot|aggressive|conservative) or a "
                         "declarative schedule sched:STEP=LEVEL|NAME,... "
                         "(first line of defense ahead of the recovery "
                         "watchdog)")
    ap.add_argument("--guard-probe-every", type=int, default=25,
                    help="guard ζ-bound/LN-clamp probe stride in steps "
                         "(0 disables the probes; cheap channels stay on)")
    ap.add_argument("--guard-journal", default=None,
                    help="write the guard transition journal to this JSONL "
                         "path at exit (CI artifact)")
    ap.add_argument("--journal", default=None,
                    help="write the unified runtime journal (run_start / "
                         "segment / guard / recovery records) to this "
                         "JSONL path at exit (CI artifact)")
    ap.add_argument("--log-jsonl", default=None)
    ap.add_argument("--log-every", type=int, default=50,
                    help="host-sync/log window (steps); metrics stay "
                         "on-device between windows")
    ap.add_argument("--mesh", default=None,
                    help="data,model[,pod] device mesh, e.g. 4,2 or 2,2,2")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="sequential microbatches per optimizer step")
    ap.add_argument("--pod-compress", default=None,
                    help="MX element format for the cross-pod gradient "
                         "all-reduce (e.g. e4m3); needs a 3-dim --mesh")
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="force N host CPU devices (XLA_FLAGS; must run "
                         "before jax init)")
    return ap.parse_args(argv)


def build_trainer(args):
    """The Trainer ``main`` runs, built from parsed ``args`` (see
    :func:`parse_args`); the compilation cache is on before it returns."""
    if args.fake_devices:
        # jax may already be *imported* (package __init__), but XLA_FLAGS
        # is only read when the backend initializes — which is lazy, so
        # setting it here still works as long as no device has been
        # touched yet (verified below).
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}")

    import jax

    if args.fake_devices and jax.device_count() < args.fake_devices:
        raise RuntimeError(
            f"--fake-devices {args.fake_devices} had no effect "
            f"({jax.device_count()} devices): the jax backend was already "
            "initialized before the trainer was built")

    from repro.configs import get_config
    from repro.core import preset
    from repro.data.synthetic import lm_input_arrays
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import mesh_from_flag
    from repro.models import lm_init, lm_loss
    from repro.optim import AdamWConfig
    from repro.train import Trainer, TrainerConfig

    enable_compile_cache()
    cfg = get_config(args.arch, args.variant)
    qcfg = preset(args.precision)
    mesh = mesh_from_flag(args.mesh)
    params = lm_init(jax.random.PRNGKey(args.seed), cfg)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] {cfg.name}: {n/1e6:.2f}M params, precision "
          f"{qcfg.describe()}"
          + (f", mesh {dict(mesh.shape)}" if mesh is not None else ""))

    tcfg = TrainerConfig(total_steps=args.steps, peak_lr=args.peak_lr,
                         ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                         auto_intervention=args.auto_intervention,
                         log_every=args.log_every,
                         grad_accum=args.grad_accum,
                         pod_compression=args.pod_compress,
                         guard=args.guard,
                         guard_probe_every=args.guard_probe_every)
    return Trainer(
        loss_fn=lambda p, b, q: lm_loss(p, b, cfg, q),
        params=params, qcfg=qcfg,
        batch_fn=lambda step: lm_input_arrays(step, cfg, args.batch,
                                              args.seq, args.seed),
        opt_cfg=AdamWConfig(), tcfg=tcfg, mesh=mesh)


def main(argv=None):
    args = parse_args(argv)
    trainer = build_trainer(args)
    if args.resume and trainer.restore():
        # restore() adopts the checkpoint's recorded qcfg/recovery count,
        # so a resume after a mid-run intervention keeps the intervention.
        print(f"[train] resumed at step {trainer.step}, precision "
              f"{trainer.qcfg.describe()}")

    hist = trainer.run(args.steps - trainer.step)
    for rec in hist[:: max(len(hist) // 20, 1)]:
        print(f"  step {rec['step']:>6} loss {rec['loss']:.4f} "
              f"gnorm {rec['grad_norm']:.3f} {rec['time_s']*1e3:.0f}ms")
    if trainer.events:
        print("[train] events:", json.dumps(trainer.events, indent=1))
    if trainer._controller is not None:
        print(f"[train] guard: level {trainer._controller.level}, "
              f"{len(trainer._controller.journal)} transition(s), final "
              f"precision {trainer.qcfg.describe()}")
        if args.guard_journal:
            # the controller journal is a runtime Journal: JSONL for free
            trainer._controller.journal.to_jsonl(args.guard_journal)
    if args.journal:
        trainer.events.to_jsonl(args.journal)
    if args.log_jsonl:
        with open(args.log_jsonl, "w") as f:
            for rec in hist:
                f.write(json.dumps(rec) + "\n")
    if hist:
        print(f"[train] final loss {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
