"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); per-layer metrics are readers in
``metrics/<name>.py``; device peaks are in ``peaks.json``, keyed by
``device_kind``.  Adding a cell, a configuration, a mix or a metric adds
files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]                 # the checkout: BENCHMARK.json, src/


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict                       # configs/<config>.json
    traffic_name: str
    traffic: dict                      # traffic/<traffic>.json
    end_to_end: list                   # metric entries this cell reports
    per_layer: list


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path | None = None) -> Cell:
    bench = _load(bench_path or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], config=_load(ROOT / configs[w["config"]]["file"]),
        traffic_name=w["traffic"],
        traffic=_load(HERE / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def load_peaks(device_kind: str) -> dict | None:
    """The peaks of ``device_kind``, or None where the table lacks it."""
    return _load(HERE / "peaks.json")["devices"].get(device_kind)


def load_reader(metric_name: str):
    """The ``read(ctx)`` function of ``metrics/<metric_name>.py``."""
    path = HERE / "metrics" / f"{metric_name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"metric_{metric_name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
