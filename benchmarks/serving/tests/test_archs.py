"""Every architecture module in ``archs/`` keeps the contract the shared
harness calls (``spec.py``): it has the names, its tiny weights build the
program's own parameter layout, and its reference gives finite logits.  A
configuration without a ``model_type``, or with one that has no module,
ends the run with the path it looked for."""
import copy
import json

import numpy as np
import pytest

import harness
import reference
import spec
import weights

ARCHS = sorted(p.stem for p in spec.ARCHS.glob("*.py"))
NAMES = ("program_config", "WEIGHT_KEYS", "init", "program_params",
         "REFERENCE_KEYS", "forward", "logits", "projection_params",
         "decode_token_flops", "prefill_flops", "compressed_matmul_cost",
         "page_attention_cost", "TINY", "TINY_LIMIT")


def tiny_models(model_type):
    """Every configuration file of ``model_type``, at its tiny size."""
    arch = spec.load_arch(model_type)
    out = []
    for path in sorted((spec.HERE / "configs").glob("*.json")):
        cfg = json.loads(path.read_text())
        if cfg.get("model_type") == model_type:
            out.append(dict(copy.deepcopy(cfg), **arch.TINY))
    assert out, f"no configuration in configs/ has model_type {model_type!r}"
    return out


@pytest.mark.parametrize("model_type", ARCHS)
def test_module_has_the_names(model_type):
    arch = spec.load_arch(model_type)
    assert [n for n in NAMES if not hasattr(arch, n)] == []
    assert 0 < arch.TINY_LIMIT


@pytest.mark.parametrize("model_type", ARCHS)
def test_tiny_weights_have_the_program_layout(model_type):
    arch = spec.load_arch(model_type)
    for model in tiny_models(model_type):
        int8 = model["serving"]["weights"] == "apack-int8"
        harness.check_layout(arch.program_config(model), arch.program_params(
            weights.make(model, 3, int8=int8)))


@pytest.mark.parametrize("model_type", ARCHS)
def test_reference_gives_finite_logits(model_type):
    model = tiny_models(model_type)[0]
    w = weights.make(model, 3, int8=False)
    tokens = np.arange(40, dtype=np.int32) % model["vocab_size"]
    targets = tokens[:, None]
    for mode in reference.MODES:
        best, got, top = reference.read(model, w, tokens, targets, 40,
                                        mode=mode)
        assert np.isfinite(best).all() and np.isfinite(got).all()
        assert (got[:, 0] <= best).all()
        assert ((0 <= top) & (top < model["vocab_size"])).all()


def load_with(tmp_path, change):
    """``spec.load_cell`` of the first cell of BENCHMARK.json, its
    configuration file replaced by ``change(config)``; returns the call's
    SystemExit and the replaced file's path."""
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    cell = spec.load_cell(bench["workloads"][0]["name"])
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps(change(dict(cell.config))))
    for c in bench["configs"]:
        if c["name"] == cell.config_name:
            c["file"] = str(cfg_path)       # absolute: taken as it is
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    with pytest.raises(SystemExit) as e:
        spec.load_cell(cell.name, path)
    return str(e.value), str(cfg_path)


def test_missing_model_type_names_the_file(tmp_path):
    msg, cfg_path = load_with(
        tmp_path, lambda c: {k: v for k, v in c.items() if k != "model_type"})
    assert cfg_path in msg and str(spec.ARCHS / "<model_type>.py") in msg


def test_unknown_model_type_names_the_file(tmp_path):
    msg, _ = load_with(tmp_path, lambda c: dict(c, model_type="no_such_arch"))
    assert str(spec.ARCHS / "no_such_arch.py") in msg
