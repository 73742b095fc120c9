"""Chip benchmark of coded data-parallel training: one run of one cell.

  python3 chipbench/run.py --workload qwen3-8b.worker --seed 7 \\
      --seconds 45 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
(``chipbench/configs/``), a traffic file (``chipbench/traffic/``) and has
a limits file (``chipbench/limits/<cell>.json``); the configuration's
``model_type`` names its model directory (``chipbench/models/``);
per-layer metrics are read by ``chipbench/metrics/<metric>.py``.
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics.  The last line of standard output is one JSON object;
the numbers compared for ``correct`` close standard error and the JSON
line.  A run exits nonzero, and prints no result, where JAX finds no TPU
or fewer chips than the cell asks for.

Adding a configuration edits no file that is there; it adds:

- ``configs/<name>.json``: the published config's keys, with the cuts
  under ``reduced``, ``precision`` and the ``model_type``;
- ``models/<model_type>/``, where the model type is new, three modules:
  ``program.py``, ``model_config(config)``: the program's ``ModelConfig``
  and the checks that the program runs what the file states (the one
  module here that imports ``repro``); ``reference.py``, the plain model
  (importing nothing of ``repro``): ``Dims.from_config(config)``, hashable
  sizes with ``vocab``, the rows the feed draws tokens from;
  ``init_params(seed, dims)``, the program's weights from the seed by its
  key layout; ``sequence_loss(params, tokens, labels, dims, quant=None)``,
  rounding with the shared ``reference.act`` and ``reference.mm``; and
  ``TOY``, toy sizes under the configuration's keys for the CPU tests;
  ``counts.py``: ``total_params(config)``, the coded parameters the codec
  bytes come from, ``flops_per_token(config, seq_len)``, and optionally
  ``kernels(config, traffic)`` -> ``{name: (op_pattern, bytes, flops)}``
  a step and chip for the model's own kernels (``ReadContext.roofline_of``);
- ``traffic/<mix>.json`` where the mix is new, and ``limits/<cell>.json``;
- ``metrics/<name>.py`` for any new per-layer metric: ``read(ctx)`` on a
  ``bench.ReadContext`` (time by kind, op and named scope; a declared
  kernel's roofline share), ``None`` where the trace holds nothing for it;
- the entries in ``BENCHMARK.json``.
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
