"""A model type is three files under ``models/<model_type>/``: the counts
of the ones in the benchmark hold, and one that exists only as new files
runs through a whole cell."""
import shutil

import pytest

import tiny
import bench
import counts

# (total_params, flops_per_token at 2048, codec bytes a step and chip)
PINNED = {
    "qwen3-1.7b.coded-gather": (279_137_280, 1_642_659_840,
                                {"encode": 5_024_471_040,
                                 "decode": 3_349_647_360}),
    "qwen3-8b.worker": (541_479_424, 2_983_329_792,
                        {"encode": 4_331_835_392, "decode": 4_331_835_392}),
}


@pytest.mark.parametrize("workload", sorted(PINNED))
def test_counts_are_pinned(workload):
    cell = bench.load_cell(workload)
    params, flops, codec = PINNED[workload]
    m = cell.model
    assert m.total_params(cell.config) == params
    assert m.flops_per_token(cell.config, 2048) == flops
    assert cell.traffic["seq_len"] == 2048
    work = counts.kernel_work(params, cell.traffic["code"])
    assert {k: v[0] for k, v in work.items()} == codec
    assert m.kernels(cell.config, cell.traffic) == {}


@pytest.mark.parametrize("workload", tiny.CELLS)
def test_a_model_type_added_as_files_runs_a_cell(workload, tmp_path):
    """A copy of ``models/qwen3/`` under a new name, in a directory of its
    own, runs a whole cell on the CPU and reads as ``qwen3`` does."""
    shutil.copytree(bench.MODELS / "qwen3", tmp_path / "qwen3_copy",
                    ignore=shutil.ignore_patterns("__pycache__"))
    want = tiny.cell(workload)
    config = dict(want.config, model_type="qwen3_copy")
    model = bench.model_of(config, tmp_path)
    assert model is not want.model and model.model_type == "qwen3_copy"
    got = tiny.run(bench.Cell(want.name, want.chips, config, want.traffic,
                              want.limits, want.end_to_end, want.per_layer,
                              model))
    assert got["correct"], got["compared"]
    assert got["compared"] == tiny.run(want)["compared"]
