"""A small benchmark beside the real one: tiny cells built only from added
files (a configuration, traffic mixes, limits, and for one cell a
generator and a metric of its own from ``bench/tests/extra/``), which the
CPU tests drive through the same harness the chip runs use."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
EXTRA = Path(__file__).resolve().parent / "extra"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {"name": "tiny", "n_layers": 2, "d_model": 64, "n_heads": 4,
              "n_kv_heads": 2, "d_head": 32, "d_ff": 128, "vocab": 256,
              "act": "gelu", "norm": "layernorm", "qk_norm": True,
              "qkv_bias": True, "out_bias": True, "rope_theta": 10000.0,
              "tie_embeddings": False, "loss_chunk": 64}
CPU_PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
SERVE_MIX = "chat-closed64"


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def _mix(name: str) -> dict:
    return json.loads((ROOT / f"bench/traffic/{name}.json").read_text())


def build_root(tmp: Path) -> Path:
    """A checkout-shaped directory: the real benchmark's files, plus tiny
    cells added as files and entries alone: ``tiny.serve`` (closed-loop
    chat) and ``tiny.sessions`` (open-loop Poisson arrivals of sessions
    that share prefixes, from the generator and the metric in
    ``bench/tests/extra/``)."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    _write(tmp / "bench/configs/tiny.json",
           {"name": "tiny", "source": "test", "reduced": [],
            "model": TINY_MODEL})
    serve = _mix(SERVE_MIX)
    serve.update(engine={"max_batch": 4, "max_len": 128, "page_size": 32,
                         "chunk_size": 32, "n_pages": 16},
                 arrivals={"kind": "closed", "clients": 4},
                 prompt_len={"dist": "lognormal", "median": 12,
                             "sigma": 0.6, "min": 4, "max": 70},
                 output_len={"dist": "lognormal", "median": 6, "sigma": 0.5,
                             "min": 2, "max": 16},
                 checked_requests=3)
    _write(tmp / "bench/traffic/tiny-serve.json", serve)
    sessions = dict(serve, generator="poisson_sessions",
                    arrivals={"kind": "poisson", "rate": 40.0},
                    sessions={"count": 2, "prefix_len": 40},
                    prompt_len={"dist": "uniform", "min": 2, "max": 30},
                    warm_s=0.2)
    sessions.pop("output_len")
    sessions["output_len"] = {"dist": "fixed", "value": 4}
    _write(tmp / "bench/traffic/tiny-sessions.json", sessions)
    shutil.copy(EXTRA / "poisson_sessions.py",
                tmp / "bench/generators/poisson_sessions.py")
    shutil.copy(EXTRA / "requests_done.serve.py",
                tmp / "bench/metrics/requests_done.serve.py")
    # Set, like the real cell's limit, between the sound readings and the
    # control's at this size on the CPU.
    for cell in ("tiny.serve", "tiny.sessions"):
        _write(tmp / f"bench/limits/{cell}.json", {
            "greedy_gap": 0.3, "window_compiles": 0, "control": "e2m1"})
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"] += [
        {"name": "tiny.serve", "config": "tiny", "traffic": "tiny-serve",
         "chips": 1, "why": "test"},
        {"name": "tiny.sessions", "config": "tiny",
         "traffic": "tiny-sessions", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"].startswith("serve") or m["name"].endswith(".serve"):
            m["workloads"] += ["tiny.serve", "tiny.sessions"]
    bench["per_layer"].append(
        {"name": "requests_done.serve", "unit": "requests", "better": "higher",
         "source": "host_clock", "layer": "serving engine",
         "moves": "serve_tokens_per_s", "workloads": ["tiny.sessions"]})
    _write(tmp / "BENCHMARK.json", bench)
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return build_root(tmp_path)
