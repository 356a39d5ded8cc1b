"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, traffic mix, driver or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` — the deployment as it is run; its
  ``driver`` and ``reference`` keys name the two files below;
* ``bench/drivers/<driver>.py`` — builds the program and runs the window;
* ``bench/references/<reference>.py`` — the plain reference and its control;
* ``bench/traffic/<traffic>.json`` — parameters for ``bench/generate.py``;
* ``bench/metrics/<metric>.py`` — one per-layer reader each.

No cell name appears in code: adding a cell adds files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path) -> ModuleType:
    """Import a Python file by path (names may hold ``.`` and ``-``)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    name = "bench_file_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(BENCH)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    def driver(self) -> ModuleType:
        return load_module(BENCH / "drivers" / f"{self.config['driver']}.py")

    def reference(self) -> ModuleType:
        return load_module(
            BENCH / "references" / f"{self.config['reference']}.py")

    def reader(self, metric: str) -> ModuleType:
        return load_module(BENCH / "metrics" / f"{metric}.py")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def resolve(workload: str, spec: Dict = None) -> Cell:
    """The cell named ``workload``, with its configuration and traffic."""
    spec = spec or benchmark()
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[w["config"]]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_json(ROOT / entry["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
