import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

Lowers + compiles every requested (arch x input shape) on the production
mesh(es) with ShapeDtypeStruct stand-ins (no allocation), records
memory_analysis / cost_analysis / the collective-bytes breakdown, and writes
one JSON per combination under results/dryrun/.

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count on first init); do not set it globally — smoke tests and
benches must see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
      --shape train_4k --mesh single --schedule gather
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse      # noqa: E402
import json          # noqa: E402
import pathlib       # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import ARCHS                     # noqa: E402
from repro.launch import lowering                   # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.shapes import SHAPES              # noqa: E402

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def run_one(arch: str, shape: str, mesh_name: str, schedule: str,
            out_dir: pathlib.Path, code_spec: str | None = None,
            tag: str = "", opt: str = "", backend: str = "auto") -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    t0 = time.time()
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "schedule": schedule, "devices": int(mesh.size), "tag": tag,
           "opt": opt, "backend": backend}
    kw = {}
    opts = set((opt or "").split(",")) - {""}
    if "attn_remat" in opts:
        from repro.models import common as _cm
        _cm.REMAT_KV_STEP = True
    if "moe_einsum" in opts:
        from repro.models import moe as _moe
        _moe.DISPATCH = "einsum"
    if SHAPES[shape].kind == "train":
        kw["schedule"] = schedule
        kw["backend"] = backend
        if "bf16_wire" in opts:
            kw["encode_dtype"] = "bfloat16"
        if "partial" in opts:           # partial-recovery step (err bound)
            kw["partial"] = True
        if "hetero" in opts:
            # heterogeneous-load plan: a deterministic 2x geometric speed
            # skew across the data workers (speeds geomspace(1, 2, n)),
            # loads recorded in the result for the optimizer search.  Only
            # the s,m of --code apply: per-worker loads replace a uniform d
            from repro.launch.mesh import data_degree
            from repro.core import make_hetero_code
            import numpy as np
            n = data_degree(mesh)
            d, s, m = ((int(x) for x in code_spec.split(","))
                       if code_spec else (3, 1, 2))
            if code_spec:
                print(f"hetero: ignoring d={d} of --code (loads derive "
                      f"from the speed vector); using s={s}, m={m}",
                      flush=True)
            kw["code"] = make_hetero_code(
                np.geomspace(1.0, 2.0, n), s, m)
        elif "autotune" in opts:
            # the cluster-free measure->fit->plan loop (docs/autotune.md):
            # fit the Sec-VI model from a synthetic telemetry window drawn
            # at the demo calibration, rank the (d,s,m) x schedule space,
            # and lower the winning plan's codec; the ranked head is
            # recorded in the result JSON for the optimizer search.
            from repro.launch.mesh import data_degree
            from repro.core import make_code
            from repro.core.runtime_model import RuntimeParams
            from repro.tune import rank_plans, synthetic_fit
            n = data_degree(mesh)
            calib = RuntimeParams(n=n, lambda1=0.5, lambda2=0.2,
                                  t1=0.5, t2=16.0)
            fit = synthetic_fit(calib, steps=200, seed=7)
            ranked = rank_plans(fit, schedules=(schedule,), npts=10_000)
            top = ranked[0]
            print(f"autotune: fitted (t1={fit.params.t1:.3f}, "
                  f"l1={fit.params.lambda1:.3f}, t2={fit.params.t2:.3f}, "
                  f"l2={fit.params.lambda2:.3f}); lowering "
                  f"{top.describe()}", flush=True)
            kw["code"] = make_code(n, top.d, top.s, top.m)
            rec["autotune_plans"] = [p.describe() for p in ranked[:5]]
            rec["autotune_fit"] = {"t1": fit.params.t1,
                                   "lambda1": fit.params.lambda1,
                                   "t2": fit.params.t2,
                                   "lambda2": fit.params.lambda2}
        elif code_spec:
            d, s, m = (int(x) for x in code_spec.split(","))
            from repro.launch.mesh import data_degree
            from repro.core import make_code
            kw["code"] = make_code(data_degree(mesh), d, s, m)
    try:
        fn, args, meta = lowering.build_lowering(arch, shape, mesh, **kw)
    except lowering.SkipLowering as e:
        rec.update(status="skipped", reason=str(e))
        return rec
    with jax.sharding.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # old jax: one entry per device
        cost = cost[0] if cost else None
    if isinstance(mem, (list, tuple)):
        mem = mem[0] if mem else None
    from repro.launch import hlo_cost
    hlo = hlo_cost.analyze(compiled.as_text())
    rec.update(
        status="ok", meta=meta,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        memory=({k: int(getattr(mem, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")}
            if mem is not None else None),
        # raw XLA numbers (scan bodies counted once — see hlo_cost docstring)
        xla_flops_once=float(cost.get("flops", -1.0)) if cost else None,
        xla_bytes_once=float(cost.get("bytes accessed", -1.0)) if cost else None,
        # loop-aware numbers used by §Roofline
        flops=hlo["flops"],
        bytes_accessed=hlo["bytes_accessed"],
        collective_bytes=hlo["collective_bytes"],
        collective_counts=hlo["collective_counts"],
    )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCHS + ["all"],
                    help="architecture id (or 'all')")
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + ["all"])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--schedule", default="gather",
                    choices=["gather", "a2a", "psum"])
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "ref", "pallas", "interpret"],
                    help="codec compute backend for the train step")
    ap.add_argument("--code", default=None,
                    help="d,s,m triple for the gradient code (default 3,1,2)")
    ap.add_argument("--opt", default="",
                    help="comma list of levers: attn_remat, bf16_wire, "
                         "moe_einsum, hetero (skewed-speed HeteroCode), "
                         "partial (partial-recovery step with error "
                         "certificate), "
                         "autotune (fit the Sec-VI model from synthetic "
                         "telemetry and lower the planner's top (d,s,m))")
    ap.add_argument("--tag", default="", help="tag for the result filename")
    ap.add_argument("--all", action="store_true",
                    help="sweep all arch x shape combos")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = ARCHS if (args.all or args.arch in (None, "all")) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape in (None, "all")) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                name = f"{arch}__{shape}__{mesh_name}__{args.schedule}"
                if args.tag:
                    name += f"__{args.tag}"
                t0 = time.time()
                try:
                    rec = run_one(arch, shape, mesh_name, args.schedule,
                                  out_dir, args.code, args.tag, args.opt,
                                  args.backend)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "schedule": args.schedule, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    failures += 1
                rec["wall_s"] = round(time.time() - t0, 1)
                (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=1))
                print(f"{name}: {rec['status']} ({rec['wall_s']}s)", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
