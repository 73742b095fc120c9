"""A cell small enough for the CPU: each model type's toy sizes (its
reference's ``TOY``), with the traffic files' shape of a step."""
import copy
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import bench  # noqa: E402

# every cell of BENCHMARK.json
CELLS = [w["name"] for w in bench.read_benchmark()["workloads"]]
# At these sizes a step averages over 128 or 512 tokens, not 8192 or 16384,
# so every gap reads several times what it does at a cell's size.  The tests
# hold limits set by the cells' rule from readings at these sizes (13 seeds
# of each cell, program against the fp8 control; PERF.md, section 6).
LIMITS = {"loss_gap": 6e-4, "grad_norm_gap": 4e-3, "update_norm_gap": 2e-2}


def cell(workload: str, seq_len: int = 64,
         sequences_per_subset: int = 2) -> bench.Cell:
    """The workload's cell with its configuration cut to its model type's
    toy sizes, its sequences shortened, and the limits for these sizes."""
    real = bench.load_cell(workload)
    config = copy.deepcopy(real.config)
    config.update(real.model.toy)
    traffic = dict(real.traffic, seq_len=seq_len,
                   sequences_per_subset=sequences_per_subset)
    return bench.Cell(workload, real.chips, config, traffic,
                      LIMITS, real.end_to_end, real.per_layer, real.model)


def run(c: bench.Cell, seed: int = 3, backend: str = "ref",
        trace: bool = False) -> dict:
    import time
    return bench.run(c, seed, 0.5, trace, t_start=time.perf_counter(),
                     require_chip=False, backend=backend)


if __name__ == "__main__":
    c = cell(sys.argv[1])
    print(json.dumps(run(c, trace=len(sys.argv) > 2)))
