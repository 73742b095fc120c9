"""``BENCHMARK.json`` keeps the shape the harness and its checker rely on,
and every name in it finds its file."""
import json
import re

import tiny
import bench

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
        cell = tiny.full_cell(name)
        assert cell.end_to_end and cell.per_layer
        assert set(cell.limits) == {"loss_gap", "grad_norm_gap",
                                    "update_norm_gap"}
