"""A whole run on the CPU at a tiny size, past the harness's look for a
chip: a sound run is correct, a run whose served tokens are altered where
the engine produces them is not, the control put in the program's place is
not, and the entry point refuses a machine without a TPU."""
import copy
import dataclasses
import json
import subprocess
import sys
import time

import pytest

import harness
import spec

TINY_MIX = dict(slots=4, max_len=96,
                prompt_tokens={"dist": "loguniform", "min": 20, "max": 64},
                output_tokens={"dist": "loguniform", "min": 8, "max": 32})
# The packed cell is out of BENCHMARK.json until its slow steps are
# understood; these entries add it back, as data alone.
PACKED = {
    "configs": {"name": "qwen3-1.7b-packed",
                "file": "benchmarks/serving/configs/qwen3-1.7b-packed.json"},
    "workloads": {"name": "qwen3-1.7b-packed.decode",
                  "config": "qwen3-1.7b-packed", "traffic": "decode",
                  "chips": 1},
}
BENCHMARK_CELLS = [w["name"] for w in json.loads(
    (spec.ROOT / "BENCHMARK.json").read_text())["workloads"]]
# every cell of BENCHMARK.json, and the packed one
CELLS = [PACKED["workloads"]["name"]] + [
    c for c in BENCHMARK_CELLS if c != PACKED["workloads"]["name"]]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """BENCHMARK.json with the packed cell added."""
    b = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for key, entry in PACKED.items():
        if entry["name"] not in {e["name"] for e in b[key]}:
            b[key].append(entry)
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(b))
    return path


def tiny_cell(name, bench):
    """The cell at its architecture's tiny size and limit."""
    cell = spec.load_cell(name, bench)
    cfg = copy.deepcopy(cell.config)
    cfg.update(cell.arch.TINY)
    cfg["limits"] = {"served_logit_gap": cell.arch.TINY_LIMIT}
    mix = dict(copy.deepcopy(cell.traffic), **TINY_MIX)
    return dataclasses.replace(cell, config=cfg, traffic=mix)


def run(cell, seed=5, control=None):
    import jax
    return harness.run(cell, seed, 2.0, False, time.perf_counter(),
                       spec.load_peaks("TPU v5 lite"), jax.devices()[0],
                       control=control)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, bench):
    out = run(tiny_cell(name, bench))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "token_gap_p95_ms",
                                   "peak_hbm_gib", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_altered_token_is_not_correct(name, bench, monkeypatch):
    """The fault a served cell can have: a token altered where the engine
    produces it (and fed on, as the engine would)."""
    from repro.serve import ServeEngine
    inner = ServeEngine.step

    def faulty(self):
        n = inner(self)
        for slot, req in enumerate(self.active):
            if req is not None and len(req.tokens) > 1:
                bad = (req.tokens[-1] + 1 + self.cfg.vocab_size // 2) \
                    % self.cfg.vocab_size
                req.tokens[-1] = bad
                self.last_tokens[slot, 0] = bad
                break
        return n

    monkeypatch.setattr(ServeEngine, "step", faulty)
    out = run(tiny_cell(name, bench))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, bench):
    cell = tiny_cell(name, bench)
    out = run(cell, seed=11, control=cell.config["control"])
    assert not out["correct"], out["checks"]


def test_no_tpu_no_result():
    proc = subprocess.run(
        [sys.executable, str(spec.HERE / "run.py"),
         "--workload", BENCHMARK_CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_unknown_device_kind_has_no_peaks():
    assert spec.load_peaks("TPU v5 lite") is not None
    assert spec.load_peaks("TPU v9 imaginary") is None
