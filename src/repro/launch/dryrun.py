import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           "--xla_disable_hlo_passes="
                           "while-loop-invariant-code-motion")
# The disabled pass hoists whole-stack bf16->f32 converts out of scan
# backward loops — an artifact of the CPU backend's bf16 float
# normalization (TPUs consume bf16 natively; the hoisted f32 copy of every
# stacked residual tripled activation memory and does not exist on TPU).
# Verified pre-optimization StableHLO has no such buffer; see EXPERIMENTS.md.
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST precede any jax import: jax locks the device
count at first init, and the production meshes need 512 placeholder host
devices.  (Smoke tests and benchmarks must NOT import this module — they
see the real single CPU device.)

Per cell this driver:
  1. builds ShapeDtypeStruct params/opt/inputs (no allocation),
  2. jits the canonical step (train_step / prefill_step / serve_step) with
     the production shardings (parallel/sharding.py),
  3. .lower().compile()  — sharding mismatches, unsupported collectives
     or compile-time OOMs are FAILURES,
  4. records memory_analysis(), cost_analysis(), and the trip-count-
     corrected HLO analysis (dot FLOPs / traffic / collective bytes) into
     experiments/dryrun/<cell>.json for §Dry-run / §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --precision mxfp8_e4m3 [--skip-existing]
"""
import argparse
import gzip
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, input_specs, list_archs, \
    supported
from repro.core import preset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_prefill_step, make_serve_step, \
    make_train_step
from repro.models import lm_init
from repro.optim import AdamWConfig, adamw_init
from repro.parallel import (batch_pspecs, cache_pspecs, param_pspecs,
                            shardings_like)
from repro.parallel.sharding import activation_sharding
from jax.sharding import NamedSharding, PartitionSpec as P


def _bf16_params(shapes_tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
        shapes_tree)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             precision: str = "mxfp8_e4m3", out_dir: str = None,
             skip_existing: bool = False, microbatch: int = 1,
             tag: str = "") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}__{precision}{tag}"
    out_path = os.path.join(out_dir, f"{cell_id}.json") if out_dir else None
    if skip_existing and out_path and os.path.exists(out_path):
        with open(out_path) as f:
            return json.load(f)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "precision": precision, "tag": tag, "microbatch": microbatch,
           "status": "unknown"}
    t0 = time.time()
    try:
        cfg = get_config(arch)
        ok, reason = supported(cfg, shape_name)
        if not ok:
            rec.update(status="skip", reason=reason)
            return _finish(rec, out_path, t0)
        shape = SHAPES[shape_name]
        qcfg = preset(precision)
        mesh = make_production_mesh(multi_pod=multi_pod)
        specs = input_specs(cfg, shape_name)
        pshapes = _bf16_params(
            jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), cfg)))
        psh = shardings_like(param_pspecs(pshapes, mesh), mesh)

        with mesh, activation_sharding(mesh):
            if shape.kind == "train":
                opt_cfg = AdamWConfig(master=True)
                oshapes = jax.eval_shape(
                    lambda p: adamw_init(p, opt_cfg), pshapes)
                osh = shardings_like(param_pspecs(oshapes, mesh), mesh)
                bsh = shardings_like(batch_pspecs(specs, mesh), mesh)
                step = make_train_step(cfg, qcfg, opt_cfg,
                                       microbatch=microbatch)
                fn = jax.jit(step, in_shardings=(psh, osh, bsh, None),
                             donate_argnums=(0, 1))
                lowered = fn.lower(pshapes, oshapes, specs,
                                   jax.ShapeDtypeStruct((), jnp.int32))
            elif shape.kind == "prefill":
                bsh = shardings_like(batch_pspecs(specs, mesh), mesh)
                step = make_prefill_step(cfg, qcfg)
                fn = jax.jit(step, in_shardings=(psh, bsh))
                lowered = fn.lower(pshapes, specs)
            else:  # decode
                csh = shardings_like(cache_pspecs(specs["cache"], mesh),
                                     mesh)
                tok_sh = shardings_like(
                    batch_pspecs(specs["tok"], mesh), mesh)
                step = make_serve_step(cfg, qcfg)
                args = [pshapes, specs["cache"], specs["tok"], specs["pos"]]
                in_sh = [psh, csh, tok_sh, None]
                if "enc_out" in specs:
                    args.append(specs["enc_out"])
                    in_sh.append(shardings_like(
                        batch_pspecs(specs["enc_out"], mesh), mesh))
                fn = jax.jit(step, in_shardings=tuple(in_sh),
                             donate_argnums=(1,))
                lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo_text = compiled.as_text()
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            with gzip.open(os.path.join(out_dir, f"{cell_id}.hlo.gz"),
                           "wt") as f:
                f.write(hlo_text)
        hlo = analyze_hlo(hlo_text)
        rec.update(
            status="ok", lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            mem={k: int(getattr(ma, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes")},
            bytes_per_device=int(ma.argument_size_in_bytes
                                 + ma.temp_size_in_bytes
                                 + ma.output_size_in_bytes
                                 - ma.alias_size_in_bytes),
            xla_cost={k: ca.get(k) for k in ("flops", "bytes accessed")},
            hlo=hlo,
            n_devices=int(len(mesh.devices.flat) if hasattr(mesh.devices,
                                                            "flat")
                          else mesh.devices.size),
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    return _finish(rec, out_path, t0)


def _finish(rec, out_path, t0):
    rec["wall_s"] = round(time.time() - t0, 1)
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    gb = rec.get("bytes_per_device", 0) / 2**30
    print(f"[dryrun] {rec['arch']:<24} {rec['shape']:<12} {rec['mesh']:<10} "
          f"{rec['status']:<5} {gb:6.2f} GiB/dev  wall={rec['wall_s']}s"
          + (f"  ({rec.get('reason', rec.get('error',''))[:80]})"
             if rec["status"] != "ok" else ""), flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--precision", default="mxfp8_e4m3")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    # Persistent compilation cache: §Perf iterations re-lower unchanged
    # cells for free.
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    archs = [a for a in list_archs() if a != "olmo-paper"] \
        if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, args.precision, args.out,
                               args.skip_existing, args.microbatch,
                               args.tag)
                n_fail += rec["status"] == "fail"
    print(f"[dryrun] done; {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
