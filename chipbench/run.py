"""Chip benchmark of coded data-parallel training: one run of one cell.

  python3 chipbench/run.py --workload qwen3-8b.worker --seed 7 \\
      --seconds 45 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
(``chipbench/configs/``), a traffic file (``chipbench/traffic/``) and has
a limits file (``chipbench/limits/<cell>.json``); per-layer metrics are
read by ``chipbench/metrics/<metric>.py``.  ``--trace 0`` prints the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.  The last
line of standard output is one JSON object; the numbers compared for
``correct`` close standard error and the JSON line.  A run exits nonzero,
and prints no result, where JAX finds no TPU or fewer chips than the cell
asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw and the extracted trace here")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "src"))
    import bench

    try:
        cell = bench.load_cell(args.workload)
        result = bench.run(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START, keep_trace=args.keep_trace)
    except bench.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
