"""Readings that the limits of a cell's comparison are set from, on the chip
at the cell's own size.  The benchmark's own runs never run this.

  python3 chipbench/calibrate.py --workload <cell> \\
      --seeds 101,102,...,112 --control 101,102,103 --out readings.json

For each of ``--seeds``: the program's first steps exactly as a run's
set-up drives them, and the reference's, and the three numbers compared
(the lower readings).  For each of ``--control``: the control (the
reference with its bfloat16 activations in float8, ``reference.py``),
the half-batch fault (the reference over half of each batch) and the
altered answer (the reference with every loss, and so every gradient,
one part in a hundred high), each in the program's place, against the
reference (the upper readings).  ``--no-exchange`` seeds plant the
missing exchange between chips in the program itself (every worker
decodes its own encoding in the place of all n).  A state left unchanged
reads 1 on ``update_norm_gap`` and needs no run.
"""
import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import bench  # noqa: E402
import check  # noqa: E402
import program  # noqa: E402
import reference  # noqa: E402


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def program_side(cell, seed: int):
    wseed, dseed, sseed = bench.derive_seeds(seed)
    model = cell.model
    k = model.dims(cell.config)
    trainer = program.build_trainer(model.model_config(cell.config),
                                    cell.traffic, weight_seed=wseed,
                                    straggler_seed=sseed)
    feed = bench.Feed(k.vocab, cell.traffic, dseed)
    readings = bench.program_readings(trainer, feed, model, k, wseed,
                                      cell.traffic["optimizer"]["b1"])
    del trainer
    gc.collect()
    return readings


def reference_side(cell, seed: int, **kw):
    wseed, dseed, _ = bench.derive_seeds(seed)
    k = cell.model.dims(cell.config)
    feed = bench.Feed(k.vocab, cell.traffic, dseed)
    batches = [feed.next() for _ in range(bench.FIRST_STEPS)]
    return reference.train_readings(cell.model, wseed, k, batches,
                                    cell.traffic["optimizer"], **kw)


def altered_side(cell, seed: int):
    """The reference with its answer altered where it is produced: the
    cell's model with every sequence's loss one part in a hundred high."""
    real = cell.model.sequence_loss
    altered = dataclasses.replace(
        cell.model, sequence_loss=lambda *a, **kw: real(*a, **kw) * 1.01)
    try:
        return reference_side(dataclasses.replace(cell, model=altered), seed)
    finally:
        reference._programs.cache_clear()   # the altered programs


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--no-exchange", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    cell = bench.load_cell(args.workload)
    bench.chips_or_fail(cell, require_chip=True)
    bench.enable_cache()
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    out = {"workload": cell.name, "program": {}, "control": {},
           "half_batch": {}, "answer_altered": {}, "no_exchange": {}}
    refs = {}

    def ref(seed):
        if seed not in refs:
            refs[seed] = reference_side(cell, seed)
        return refs[seed]

    def record(kind, seed, got):
        gaps = check.gaps(got, ref(seed))
        out[kind][seed] = {"gaps": gaps, "losses": got.losses}
        print(f"{kind} seed {seed}: " + ", ".join(
            f"{n} {v:.4e}" for n, v in gaps.items()), flush=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))

    t0 = time.perf_counter()
    program_seeds, control_seeds = _seeds(args.seeds), _seeds(args.control)
    rows = cell.traffic["code"]["n"] * cell.traffic["sequences_per_subset"]
    # interleaved, so that a run cut short still holds both kinds of reading
    for seed in dict.fromkeys(program_seeds + control_seeds):
        if seed in program_seeds:
            record("program", seed, program_side(cell, seed))
        if seed in control_seeds:
            record("control", seed, reference_side(cell, seed, quant="fp8"))
            record("half_batch", seed,
                   reference_side(cell, seed, rows=slice(0, rows // 2)))
            record("answer_altered", seed, altered_side(cell, seed))
    if args.no_exchange:
        import jax.numpy as jnp
        from repro.coding import wire

        n = cell.traffic["code"]["n"]
        wire.all_gather_wire = lambda x, axes: jnp.broadcast_to(
            x[None], (n,) + x.shape)
        for seed in _seeds(args.no_exchange):
            record("no_exchange", seed, program_side(cell, seed))
    print(f"calibration: {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({k: {s: v["gaps"] for s, v in d.items()}
                      for k, d in out.items() if isinstance(d, dict)}))


if __name__ == "__main__":
    main()
