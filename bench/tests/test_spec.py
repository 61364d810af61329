"""BENCHMARK.json holds to its contract, and every cell resolves to its
files by name: configuration, mix, driver, generator, metrics, limits."""
import json
import re

import pytest

from conftest import ROOT

from bench.harness import spec

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = spec.resolve(cell, ROOT)
    drv = c.driver()
    assert callable(drv.run) and callable(drv.control)
    assert callable(c.generator().make)
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.metric_reader(m["name"], ROOT))
    for m in c.per_layer:
        assert m["moves"] in names
    assert c.limits.get("window_compiles") == 0
    assert c.limits.get("control")


@pytest.mark.parametrize("cell", CELLS)
def test_config_builds_the_program_config(cell):
    c = spec.resolve(cell, ROOT)
    cfg = spec.lm_config(c.config)
    assert cfg.n_heads % cfg.n_kv_heads == 0
    assert cfg.d_model == c.model["d_model"]


def test_benchmark_keys_names_and_bounds():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for part, keys in ENTRY_KEYS.items():
        for e in BENCH[part]:
            assert keys <= set(e) <= keys | {"workloads"}, e
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for obj in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(obj["name"]), obj["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    for m in BENCH["per_layer"]:
        assert "\n" not in m["layer"]
        assert set(m["workloads"]) <= set(CELLS)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_cells_added_by_files_alone(tiny_root):
    """Cells that exist only as new files and entries, one of them with a
    generator and a metric of its own, resolve through the same lookup:
    nothing of the harness names them."""
    for name, kind, gen in (("tiny.serve", "serve", "chat"),
                            ("tiny.sessions", "serve", "poisson_sessions")):
        c = spec.resolve(name, tiny_root)
        assert c.kind == kind and c.model["name"] == "tiny"
        assert c.traffic["generator"] == gen
        assert callable(c.generator().make)
        assert c.per_layer and c.limits
    c = spec.resolve("tiny.sessions", tiny_root)
    assert "requests_done.serve" in [m["name"] for m in c.per_layer]
    assert callable(spec.metric_reader("requests_done.serve", tiny_root))


def test_unknown_cell_and_device():
    with pytest.raises(KeyError):
        spec.resolve("no-such.cell", ROOT)
    with pytest.raises(KeyError):
        spec.peaks("TPU v9000", ROOT)
    assert spec.peaks("TPU v5 lite", ROOT)["bf16_flops"] == 197e12


@pytest.mark.parametrize("name", ["../harness/spec", "no_such_kind", "a b"])
def test_unknown_or_malformed_names_load_nothing(name):
    with pytest.raises((ValueError, FileNotFoundError)):
        spec.load("drivers", name, ROOT)
