"""The Moonlight cell's driver, reference and readers on the CPU: a tiny
cell of the same kind (``serve_moe``: latent attention, sigmoid-routed
experts held in part here, shared experts) added as files, as the real
cell is, and driven through the harness the chip runs use."""
import json
import time

import jax
import pytest

from conftest import CPU_PEAK, ROOT, _write, build_root
from test_correct import altered_token

from bench import control
from bench.harness import spec
from bench.harness.execute import execute

SEED = 2**32 + 23
CELL = "tiny-moe.serve"
REAL = "moonlight-16b-a3b-ep8.serve-chat-long"
TINY = {"name": "tiny-moe", "n_layers": 3, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 4, "d_head": 16, "d_ff": 128, "vocab": 256,
        "act": "swiglu", "norm": "rmsnorm", "mla": True, "q_lora": 0,
        "kv_lora": 32, "nope_dim": 16, "rope_dim": 16, "v_head": 16,
        "n_experts": 16, "experts_held": 4, "first_expert": 4, "top_k": 3,
        "n_shared": 2, "moe_dff": 32, "first_dense": 1, "router": "sigmoid",
        "routed_scale": 2.446, "rope_theta": 50000.0,
        "tie_embeddings": False}


@pytest.fixture
def moe_root(tmp_path):
    root = build_root(tmp_path)
    _write(root / "bench/configs/tiny-moe.json",
           {"name": "tiny-moe", "source": "test", "reduced": [],
            "model": TINY})
    mix = json.loads((ROOT / "bench/traffic/chat-long-closed64.json")
                     .read_text())
    mix.update(engine={"max_batch": 4, "max_len": 128, "page_size": 32,
                       "chunk_size": 32, "n_pages": 16},
               arrivals={"kind": "closed", "clients": 4},
               prompt_len={"dist": "lognormal", "median": 24, "sigma": 0.6,
                           "min": 8, "max": 70},
               output_len={"dist": "lognormal", "median": 6, "sigma": 0.5,
                           "min": 2, "max": 16},
               checked_requests=3)
    _write(root / "bench/traffic/tiny-moe.json", mix)
    # Set between the sound readings and the control's at this size.
    _write(root / f"bench/limits/{CELL}.json",
           {"greedy_gap": 0.3, "dropped_tokens": 0, "window_compiles": 0,
            "control": "e2m1"})
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "bench/configs/tiny-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-moe",
                               "traffic": "tiny-moe", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    _write(root / "BENCHMARK.json", bench)
    return root


def _run(root, trace=False):
    c = spec.resolve(CELL, root)
    return execute(c, SEED, 0.5, trace, jax.devices()[:1], CPU_PEAK,
                   time.perf_counter())


def test_the_cell_names_what_it_reads():
    c = spec.resolve(REAL, ROOT)
    assert c.kind == "serve_moe" and c.driver().run
    names = {m["name"] for m in c.per_layer}
    assert {"expert_gemm_roofline_pct.serve",
            "held_expert_tokens.serve"} <= names
    assert spec.lm_config(c.config).experts_held == 8


def test_sound_moe_serving_is_correct_and_counts_its_routing(moe_root):
    res = _run(moe_root, trace=True)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0
    assert res["compared"]["dropped_tokens"]["value"] == 0
    held = res["metrics"]["held_expert_tokens.serve"]["value"]
    # 4 rows a decode step at most, 3 of 16 experts each, 4 held here
    assert 0 < held <= 4 * 3
    # no TPU trace on the CPU: the kernel's reader finds nothing
    assert "expert_gemm_roofline_pct.serve" not in res["metrics"]


def test_altered_token_is_not_correct(moe_root):
    with altered_token():
        res = _run(moe_root)
    assert not res["correct"]


def test_moe_serving_control_is_not_correct(moe_root):
    c = spec.resolve(CELL, moe_root)
    res = _run(moe_root)
    low = dict(res["out"]["readings"], **c.driver().control(
        c, SEED, 0.5, jax.devices()[:1], res["out"]))
    assert control.correct(res["out"]["readings"], c.limits)
    assert not control.correct(low, c.limits), low
