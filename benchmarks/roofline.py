"""Roofline analysis from the dry-run compiled artifacts (§Roofline).

Per (arch × shape) cell, from the trip-count-corrected HLO analysis of the
single-pod program:

  compute term    = dot_FLOPs / peak_FLOPs
  memory term     = traffic_bytes / HBM_bw
  collective term = collective_bytes / link_bw

(all per-device — the HLO is the SPMD program), with the published peaks
of one TPU v5e chip, for which the dry-run compiles its meshes.  These are
estimates from HLO compiled on the CPU: nothing here reads a device or a
device trace, so the peaks never follow the chip a process runs on.  Also
derives
MODEL_FLOPS = 6·N_active·D (train) / 2·N_active·D (prefill/decode) and the
useful-compute ratio MODEL/HLO-dot (catches remat + masked-attention +
padding waste), plus roofline_frac = ideal-model-compute-time over the
dominant term — the score optimized by the §Perf hillclimb.

Each train/prefill cell also reports ``attn_reclaim``: the fraction of
attention-BMM FLOPs that causal/window tile-skipping reclaims (fully
masked KV tiles are skipped by both the flash Pallas kernels and the jnp
emulation scan, so those FLOPs never hit the MXU — the compute term of
attention-heavy cells shrinks by exactly this fraction).

CPU-backend caveat (documented in EXPERIMENTS.md): float-normalization
rewrites some bf16 elementwise ops to f32, biasing traffic_bytes UP — the
memory terms are conservative upper bounds.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro.configs import SHAPES, get_config
from .common import Row
from .kernel_microbench import attn_reclaimed_frac

# Published peaks of one TPU v5e chip (Google Cloud documentation, "TPU
# v5e"): 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI over 4 links.
PEAK_FLOPS = 197e12          # FLOP/s bf16 per chip
HBM_BW = 819e9               # B/s per chip
LINK_BW = 50e9               # B/s per link (ICI)

DRYRUN_DIR = os.environ.get("REPRO_DRYRUN_DIR", "experiments/dryrun")


def model_flops_per_device(arch: str, shape_name: str, n_dev: int) -> float:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.batch * shape.seq
        total = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.batch * shape.seq
        total = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n_active * shape.batch
    return total / n_dev


def attn_reclaim(arch: str, shape_name: str) -> Optional[float]:
    """Tile-skipping FLOPs saving for this cell's attention mask (None for
    decode shapes — one-token steps have no masked tiles to skip)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "decode":
        return None
    spec = cfg.attn_spec("attn")
    return attn_reclaimed_frac(spec, shape.seq, shape.seq)


def analyze_record(rec: dict) -> Optional[dict]:
    if rec.get("status") != "ok":
        return None
    hlo = rec["hlo"]
    n_dev = rec.get("n_devices", 256)
    t_comp = hlo["dot_flops"] / PEAK_FLOPS
    t_mem = hlo["traffic_bytes"] / HBM_BW
    t_coll = hlo["collective_bytes"] / LINK_BW
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops_per_device(rec["arch"], rec["shape"], n_dev)
    ideal = mf / PEAK_FLOPS
    dom = max(terms.values())
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "attn_reclaimed": attn_reclaim(rec["arch"], rec["shape"]),
        "precision": rec.get("precision", "?"),
        "t_compute_s": t_comp, "t_memory_s": t_mem,
        "t_collective_s": t_coll, "bottleneck": bottleneck,
        "model_flops_per_dev": mf,
        "useful_flops_ratio": mf / max(hlo["dot_flops"], 1e-30),
        "roofline_frac": ideal / max(dom, 1e-30),
        "bytes_per_device_gib": rec.get("bytes_per_device", 0) / 2 ** 30,
        "fits_16g": rec.get("bytes_per_device", 0) / 2 ** 30 <= 16.0,
    }


def load_all(dryrun_dir: str = DRYRUN_DIR, mesh: str = "pod16x16",
             precision: Optional[str] = None) -> List[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("mesh") != mesh:
            continue
        if precision and rec.get("precision") != precision:
            continue
        a = analyze_record(rec)
        if a:
            out.append(a)
    return out


def run(budget: str = "quick"):
    rows = []
    cells = load_all()
    if not cells:
        return [Row("roofline.missing", 0.0,
                    "no dry-run artifacts found; run "
                    "`python -m repro.launch.dryrun` first")]
    for c in cells:
        ar = c["attn_reclaimed"]
        rows.append(Row(
            f"roofline.{c['arch']}.{c['shape']}.{c['precision']}", 0.0,
            f"comp={c['t_compute_s']*1e3:.2f}ms "
            f"mem={c['t_memory_s']*1e3:.2f}ms "
            f"coll={c['t_collective_s']*1e3:.2f}ms "
            f"bottleneck={c['bottleneck']} "
            f"useful={c['useful_flops_ratio']:.2f} "
            f"roofline_frac={c['roofline_frac']:.3f} "
            f"mem_gib={c['bytes_per_device_gib']:.1f} "
            f"attn_reclaim={'n/a' if ar is None else format(ar, '.0%')}"))
    return rows
