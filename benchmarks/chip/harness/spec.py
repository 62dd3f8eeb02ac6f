"""Find a cell's configuration, traffic mix, limits and metrics by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, named in ``BENCHMARK.json``:

* ``configs/<config>.json``  -- the sizes as run (Hugging Face keys),
  whose ``reference`` key names the architecture (``harness.arch``);
* ``traffic/<traffic>.json`` -- the mix's parameters, read by the one
  harness of its ``kind`` (``train``);
* ``limits/<workload>.json`` -- the limits that decide ``correct``;
* ``metrics/<metric>.py``     -- a reader with ``read(run) -> float | None``.

A later cell or metric is new files plus new entries, never an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any

from harness import arch

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file, as run
    traffic_name: str
    traffic: dict           # the traffic file
    limits: dict            # name -> {"limit": x, ...}
    end_to_end: list        # BENCHMARK.json entries that this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str, e2e_names: set[str] | None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    arch.module(config)         # an unknown architecture stops here
    traffic = json.loads((BENCH_DIR / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits_file = BENCH_DIR / "limits" / f"{name}.json"
    limits = json.loads(limits_file.read_text()) if limits_file.exists() else {}
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, None)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, limits, e2e, per_layer)


def metric_reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_config(config: dict) -> Any:
    """The program's ``ModelConfig`` for a configuration given in Hugging
    Face keys, made by its architecture's module (``harness.arch``)."""
    return arch.module(config).model_config(config)
