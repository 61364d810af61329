"""Cells by name: everything a run needs, found from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  Each piece lives in a
file of its own, found by name, so a later change adds a cell, a mix, a
driver, a generator or a metric by adding files and entries, never by
editing a file that is already there:

* ``configs[*].file`` (``bench/configs/<config>.json``): source, published
  values, cuts, deployment, memory reckoning, and the ``model`` that the
  program's ``LMConfig`` is built from and the reference reads;
* ``bench/traffic/<mix>.json``: the mix's parameters.  Its ``kind`` names
  the driver ``bench/drivers/<kind>.py`` and its ``generator`` the seeded
  generator ``bench/generators/<generator>.py`` that reads them;
* ``bench/limits/<cell>.json``: the limits ``correct`` is judged by;
* ``bench/metrics/<metric>.py``: one reader per metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file's contents
    traffic: Dict[str, Any]         # the mix file's contents
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path

    @property
    def model(self) -> Dict[str, Any]:
        return self.config["model"]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    def driver(self) -> ModuleType:
        return load("drivers", self.kind, self.root)

    def generator(self) -> ModuleType:
        return load("generators", self.traffic["generator"], self.root)


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _read_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell called ``name``, with its files read.  Raises KeyError for
    an unknown cell and FileNotFoundError for a missing file."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; know {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=_read_json(root / "bench" / "traffic"
                           / f"{_checked(w['traffic'])}.json"),
        limits=_read_json(root / "bench" / "limits"
                          / f"{_checked(name)}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def _checked(name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load(folder: str, name: str, root: Path = ROOT) -> ModuleType:
    """The module ``bench/<folder>/<name>.py`` of the checkout ``root``,
    loaded from its file (so a file added beside the others is found)."""
    path = root / "bench" / folder / f"{_checked(name)}.py"
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = "bench_{}_{}".format(folder, re.sub(r"\W", "_", name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT
                  ) -> Callable[[Any], Optional[float]]:
    """``read(run) -> float | None`` from ``bench/metrics/<name>.py``."""
    return load("metrics", name, root).read


# Keys of a configuration's ``model`` that the benchmark reads and the
# program's ``LMConfig`` does not have.
BENCH_MODEL_KEYS = ("out_bias",)


def lm_config(config: Dict[str, Any]):
    """The program's ``LMConfig`` of a configuration file's ``model``."""
    from repro.models import LMConfig
    return LMConfig(**{k: v for k, v in config["model"].items()
                       if k not in BENCH_MODEL_KEYS})


def peaks(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    """The chip's published peaks; an unknown device is an error."""
    table = _read_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; know {sorted(table)}")
    return table[device_kind]
