"""Benchmark entry point, driven by the `repro.bench` registry.

Every benchmark module registers a `BenchSpec` at import; this CLI selects
targets, runs them at full or `--quick` (CI-sized) problem sizes, prints the
human-readable lines and a gated-metric summary table, and (with
`--json-dir`) writes one schema-validated `BENCH_<target>.json` per target.
Exits nonzero if any bench raises or emits a schema-invalid result.

  PYTHONPATH=src python -m benchmarks.run                 # everything, full
  PYTHONPATH=src python -m benchmarks.run table1 fig3     # a subset
  PYTHONPATH=src python -m benchmarks.run --quick --json-dir bench-out

CI runs the `--quick --json-dir` form and gates the JSON against
`benchmarks/baseline.json` via `python -m repro.bench.gate` (EXPERIMENTS.md).
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

# the straggler e2e bench needs a multi-device host platform (64 slots for
# its large-n stable-family rows); the flag must be set before the first jax
# import (benchmark modules import jax at import)
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=64")

# modules that drive benches but register no spec of their own
_NON_BENCH_MODULES = {"run", "report", "check_regression"}


def _load_registry():
    """Import every benchmark module (registration happens at import).

    Discovery is by glob, not a hand-maintained list: a new bench_*.py that
    calls `repro.bench.register` is picked up automatically by the CLI, the
    smoke test, and CI.
    """
    import importlib
    import pathlib

    here = pathlib.Path(__file__).resolve().parent
    for path in sorted(here.glob("*.py")):
        name = path.stem
        if name.startswith("_") or name in _NON_BENCH_MODULES:
            continue
        importlib.import_module(f"benchmarks.{name}")
    from repro.bench import all_specs

    return {spec.name: spec for spec in all_specs()}


def _print_summary(all_results) -> None:
    rows = []
    for r in all_results:
        for metric, direction in sorted(r.gates.items()):
            rows.append((r.name, metric, r.metrics[metric], direction))
    if not rows:
        return
    print("\n# gated metrics (regression-checked in CI vs baseline.json)")
    print(f"{'result':<24} {'metric':<32} {'value':>12} dir")
    for name, metric, value, direction in rows:
        print(f"{name:<24} {metric:<32} {value:>12.4f} {direction}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="run registered benchmarks, optionally emitting JSON")
    ap.add_argument("targets", nargs="*",
                    help="bench names (default: all registered)")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized problems (small npts/iters/dims)")
    ap.add_argument("--json-dir", default=None,
                    help="write BENCH_<target>.json files into this directory")
    ap.add_argument("--list", action="store_true",
                    help="list registered benches and exit")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    registry = _load_registry()
    if args.list:
        for name, spec in sorted(registry.items()):
            print(f"{name:<12} {spec.description}")
        return 0
    unknown = [t for t in args.targets if t not in registry]
    if unknown:
        print(f"unknown target(s) {unknown}; registered: {sorted(registry)}",
              file=sys.stderr)
        return 2
    want = args.targets or sorted(registry)

    from repro.bench import write_results

    failures = 0
    collected = []
    for name in want:
        spec = registry[name]
        print(f"# --- {name}: {spec.description}", flush=True)
        t0 = time.time()
        try:
            results = spec.fn(args.quick)
            for r in results:
                r.validate()
                for line in r.extra.get("lines", []):
                    print(line, flush=True)
            collected.extend(results)
            if args.json_dir:
                path = write_results(results, name, args.json_dir)
                print(f"# wrote {path}", flush=True)
        except Exception as e:  # noqa: BLE001 — a failing bench must not
            failures += 1  # silently skip the rest; it fails the run instead
            traceback.print_exc()
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    _print_summary(collected)
    if failures:
        print(f"\n{failures} bench(es) FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
