"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); the configuration's ``model_type``, the key of
its published config, names its architecture module
(``archs/<model_type>.py``); per-layer metrics are readers in
``metrics/<name>.py``; device peaks are in ``peaks.json``, keyed by
``device_kind``.  Adding a cell, a configuration, an architecture, a mix or
a metric adds files and entries and edits none.

An architecture module holds everything that depends on the architecture,
under these names: ``program_config(model)``, the program's ``ModelConfig``;
``WEIGHT_KEYS`` and ``init(model, int8, key)``, the reference's seeded
weights; ``program_params(w)``, the program's parameter tree built from
them; ``REFERENCE_KEYS``, ``forward(model, mode, w, tokens)`` and
``logits(model, mode, w, x)``, the plain forward pass and LM head
(``reference.py``); ``projection_params``, ``decode_token_flops``,
``prefill_flops``, ``compressed_matmul_cost`` and ``page_attention_cost``
(``costs.py``); and ``TINY`` and ``TINY_LIMIT``, the CPU tests' size and
limit.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]                 # the checkout: BENCHMARK.json, src/
ARCHS = HERE / "archs"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict                       # configs/<config>.json
    arch: object                       # archs/<model_type>.py
    traffic_name: str
    traffic: dict                      # traffic/<traffic>.json
    end_to_end: list                   # metric entries this cell reports
    per_layer: list


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _exec(name: str, path: Path):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


@functools.cache
def load_arch(model_type: str):
    """The module ``archs/<model_type>.py``, loaded once."""
    path = ARCHS / f"{model_type}.py"
    if not path.is_file():
        raise SystemExit(f"no architecture module {path} for model_type "
                         f"{model_type!r}")
    return _exec(f"arch_{model_type}", path)


def arch_of(model: dict):
    """The architecture module of a configuration's dict."""
    return load_arch(model["model_type"])


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
    config_path = ROOT / configs[w["config"]]["file"]
    config = _load(config_path)
    if "model_type" not in config:
        raise SystemExit(f"{config_path} has no \"model_type\", which names "
                         f"its module {ARCHS}/<model_type>.py")
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        arch=load_arch(config["model_type"]),
        traffic_name=w["traffic"],
        traffic=_load(HERE / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def load_peaks(device_kind: str) -> dict | None:
    """The peaks of ``device_kind``, or None where the table lacks it."""
    return _load(HERE / "peaks.json")["devices"].get(device_kind)


def load_reader(metric_name: str):
    """The ``read(ctx)`` function of ``metrics/<metric_name>.py``."""
    return _exec(f"metric_{metric_name.replace('.', '_')}",
                 HERE / "metrics" / f"{metric_name}.py").read
