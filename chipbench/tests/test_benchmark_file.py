"""``BENCHMARK.json`` keeps the shape the harness and its checker rely on,
and every name in it finds its file."""
import json
import re

import pytest

import tiny
import bench
from repro.configs import ModelConfig

B = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_budget():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["chipbench"]
    assert 1 <= B["run_seconds"] <= 51
    cells = len(B["workloads"])
    full = 2 + 14 * 24
    assert full * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(1, cells // 2)


def test_entries_names_and_files():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for group, want in keys.items():
        names = [e["name"] for e in B[group]]
        assert len(names) == len(set(names))
        for e in B[group]:
            assert set(e) - {"workloads"} == want, (group, e)
            assert NAME.match(e["name"])
    for c in B["configs"]:
        assert (bench.ROOT / c["file"]).is_file()
        data = json.loads((bench.ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(data["reduced"])
        assert any(w["config"] == c["name"] for w in B["workloads"])
    for w in B["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = bench.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert (bench.HERE / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in B["end_to_end"]}
    assert "setup_s" in {m["name"] for m in B["end_to_end"]}


def test_every_cell_finds_its_files():
    for name in tiny.CELLS:
        cell = bench.load_cell(name)
        assert cell.end_to_end and cell.per_layer
        assert set(cell.limits) == {"loss_gap", "grad_norm_gap",
                                    "update_norm_gap"}


def test_every_model_type_resolves_to_its_interface():
    """Each configuration's ``model_type`` names a directory under
    ``models/`` whose modules offer what the harness calls."""
    for c in B["configs"]:
        config = json.loads((bench.ROOT / c["file"]).read_text())
        model = bench.model_of(config)
        assert model.model_type == config["model_type"]
        assert (bench.MODELS / model.model_type).is_dir()
        assert isinstance(model.model_config(config), ModelConfig)
        k = model.dims(config)
        hash(k)                         # a static argument of jit
        assert k.vocab == config["vocab_size"]
        assert set(model.toy) <= set(config)
        assert callable(model.init_params) and callable(model.sequence_loss)
        assert model.total_params(config) > 0
        assert model.flops_per_token(config, 2048) > 0
        for w in B["workloads"]:
            if w["config"] == c["name"]:
                cell = bench.load_cell(w["name"])
                for pattern, nbytes, flops in model.kernels(
                        config, cell.traffic).values():
                    re.compile(pattern)
                    assert nbytes > 0 and flops >= 0


def test_unknown_model_type_names_the_directory_searched(tmp_path):
    config = {"name": "x", "model_type": "no_such_model"}
    with pytest.raises(KeyError, match=str(bench.MODELS / "no_such_model")):
        bench.model_of(config)
    # a directory without the three modules is no model type
    (tmp_path / "half").mkdir()
    (tmp_path / "half" / "program.py").write_text("")
    with pytest.raises(KeyError, match="half"):
        bench.model_of(dict(config, model_type="half"), tmp_path)
    with pytest.raises(KeyError):
        bench.model_of(dict(config, model_type="../models/qwen3"))
