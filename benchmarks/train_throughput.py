"""Training throughput: single-device vs sharded Trainer step.

  PYTHONPATH=src python -m benchmarks.train_throughput [--smoke]
      [--budget quick|full] [--fake-devices N]

Rows (CSV ``name,us_per_call,derived``):

  train.step.<preset>.1dev        jitted Trainer step, single device
  train.step.<preset>.dXmY[pZ]    sharded step on a (data,model[,pod]) mesh
  train.step.<preset>.d1m1p..mx   pod mesh with MX-compressed grad exchange

``--smoke`` (CI) forces 8 fake host CPU devices (flag is applied *before*
jax initializes), runs one small cell per path — single-device, FSDP+TP
mesh, pod mesh with E4M3 gradient compression — and **fails** unless every
cell trains to finite losses and the sharded losses agree with the
single-device run (the distributed path must not change the optimization
problem).  This is the CI gate for the distributed trainer.
"""
from __future__ import annotations

import argparse
import os
import sys

ARCH = "olmo-paper"
PRESETS = ("bf16", "mxfp8_e4m3")


def _trainer(mesh, qname: str, steps: int, batch: int, seq: int, **tkw):
    import jax

    from repro.configs import get_config
    from repro.core import preset
    from repro.data.synthetic import lm_input_arrays
    from repro.models import lm_init, lm_loss
    from repro.train import Trainer, TrainerConfig

    cfg = get_config(ARCH, "smoke")
    params = lm_init(jax.random.PRNGKey(0), cfg)
    # log_every=1: sync every step so time_s is true per-step latency and
    # the jit compile stays isolated in step 0 (dropped by _cell below)
    tcfg = TrainerConfig(total_steps=steps, peak_lr=1e-3, log_every=1,
                         **tkw)
    return Trainer(
        loss_fn=lambda p, b, q: lm_loss(p, b, cfg, q),
        params=params, qcfg=preset(qname),
        batch_fn=lambda s: lm_input_arrays(s, cfg, batch, seq),
        tcfg=tcfg, mesh=mesh), cfg


def _cell(mesh, qname: str, tag: str, steps: int, batch: int, seq: int,
          **tkw):
    """Run one trainer cell; return (Row, losses)."""
    import numpy as np

    from .common import Row

    tr, _ = _trainer(mesh, qname, steps, batch, seq, **tkw)
    hist = tr.run(steps)
    losses = [h["loss"] for h in hist]
    # median steady-state step time (first step carries the compile)
    times = sorted(h["time_s"] for h in hist[1:]) or \
        [h["time_s"] for h in hist]
    us = float(np.median(times) * 1e6)
    toks = batch * seq / (us / 1e6)
    extra = ""
    if hist and "compression_error" in hist[-1]:
        extra = f" comp_err={hist[-1]['compression_error']:.3g}"
    return Row(f"train.step.{qname}.{tag}", us,
               f"B={batch} T={seq} {toks:.0f}tok/s{extra}"), losses


def run(budget: str = "quick"):
    """Benchmark entry (benchmarks.run registry).  Sharded rows appear
    only when the process already has >= 8 devices (e.g. under
    --fake-devices or on real hardware)."""
    import jax

    from repro.launch.mesh import make_mesh

    steps = 4 if budget == "quick" else 16
    batch, seq = 8, 32
    rows = []
    for qname in PRESETS:
        row, _ = _cell(None, qname, "1dev", steps, batch, seq)
        rows.append(row)
    if len(jax.devices()) >= 8:
        for qname in PRESETS:
            mesh = make_mesh((4, 2), ("data", "model"))
            row, _ = _cell(mesh, qname, "d4m2", steps, batch, seq)
            rows.append(row)
        pod = make_mesh((2, 2, 2), ("pod", "data", "model"))
        row, _ = _cell(pod, "mxfp8_e4m3", "d2m2p2", steps, batch, seq)
        rows.append(row)
        row, _ = _cell(pod, "mxfp8_e4m3", "d2m2p2.mx", steps, batch, seq,
                       pod_compression="e4m3")
        rows.append(row)
    return rows


def _smoke() -> int:
    """CI gate: every distributed path trains, and sharded == single-device
    up to cross-device reduction order."""
    import jax
    import numpy as np

    from repro.launch.mesh import make_mesh

    from .common import emit

    steps, batch, seq = 3, 8, 32
    rows = []
    ok = True

    def check(name, losses, ref=None, tol=5e-3):
        if not all(np.isfinite(l) for l in losses):
            print(f"# FAIL {name}: non-finite losses {losses}")
            return False
        if ref is not None:
            rel = max(abs(a - b) / max(abs(b), 1e-9)
                      for a, b in zip(losses, ref))
            if rel > tol:
                print(f"# FAIL {name}: diverges from 1dev by {rel:.2e}")
                return False
        return True

    refs = {}
    for qname in PRESETS:
        row, losses = _cell(None, qname, "1dev", steps, batch, seq)
        rows.append(row)
        refs[qname] = losses
        ok &= check(row.name, losses)
    for qname in PRESETS:
        mesh = make_mesh((4, 2), ("data", "model"))
        row, losses = _cell(mesh, qname, "d4m2", steps, batch, seq)
        rows.append(row)
        ok &= check(row.name, losses, refs[qname])
    pod = make_mesh((2, 2, 2), ("pod", "data", "model"))
    row, losses = _cell(pod, "mxfp8_e4m3", "d2m2p2.mx", steps, batch, seq,
                        pod_compression="e4m3", grad_accum=2)
    rows.append(row)
    # compression adds bounded quantization noise: finite + close, not equal
    ok &= check(row.name, losses, refs["mxfp8_e4m3"], tol=5e-2)
    emit(rows)
    print(f"# train_throughput smoke: {'OK' if ok else 'FAILED'} "
          f"({len(rows)} cells, {len(jax.devices())} devices)")
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", default="quick", choices=["quick", "full"])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args(argv)
    n = args.fake_devices or (8 if args.smoke else 0)
    if n:
        if "jax" in sys.modules:
            raise RuntimeError("--fake-devices/--smoke need to set "
                               "XLA_FLAGS before jax initializes; run this "
                               "module directly, not via benchmarks.run")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}")
    if args.smoke:
        return _smoke()
    from .common import emit
    print("name,us_per_call,derived")
    emit(run(args.budget))
    return 0


if __name__ == "__main__":
    sys.exit(main())
