"""Packed-codec benchmark: collective launches per step and wire padding.

The packed wire (`repro.coding.packing`) exists to collapse the per-step
collective count from O(#coded leaves) to <= 2 per bucket; this bench proves
and gates exactly that, plus the explicit padding the flat buffers add:

  - compiles the real coded train step for a
    multi-leaf LM on a (4 data x 1 model) host mesh and counts
    all-gather/all-to-all ops in the optimized HLO (`repro.launch.hlo_cost`
    — deterministic, hardware-independent, so it IS gated in CI);
  - reports the PackPlan's padded wire volume next to the schedule's
    `recv_elems_per_worker` prediction on the unpadded payload (the ratio
    is the whole padding overhead — gated close to 1);
  - (full mode) measures the packed step's wall-clock, ungated.

  PYTHONPATH=src python -m benchmarks.run coding_packed --quick
"""

from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench import (
    BenchResult,
    BenchSpec,
    TimerPolicy,
    capture_env,
    register,
    time_callable,
)
from repro import coding
from repro.coding import get_schedule
from repro.configs import get_config
from repro.core import make_code
from repro.data import CodedBatcher, make_synthetic_batch
from repro.launch import hlo_cost
from repro.launch.mesh import make_local_mesh
from repro.models import api as model_api
from repro.optim import get_optimizer
from repro.train.coded_step import make_coded_train_step

N_WORKERS = 4
CODE = make_code(N_WORKERS, 3, 1, 2)
ARCH = "qwen3-1.7b"


def _build(cfg, schedule: str):
    mesh = make_local_mesh(N_WORKERS, 1)
    opt = get_optimizer("sgd", 1e-2)
    spec = coding.SchemeSpec(schedule=schedule)
    arts = make_coded_train_step(cfg, CODE, mesh, opt, spec=spec)
    rng = np.random.default_rng(0)
    placed = jax.tree.map(jnp.asarray,
                          CodedBatcher(CODE).place(
                              make_synthetic_batch(rng, cfg, 8, 16)))
    return arts, opt, placed


def _collective_counts(arts, opt, placed, cfg) -> dict[str, int]:
    txt = arts.lowered(placed, cfg, opt).compile().as_text()
    return dict(hlo_cost.analyze(txt)["collective_counts"])


def _measured_step_s(arts, opt, placed, cfg, policy) -> float:
    fn = arts.compiled(placed, donate=True)
    params = model_api.init(jax.random.PRNGKey(0), cfg)
    state = {"p": params, "o": opt.init(params)}
    inp = arts.step_inputs([])

    def step():
        p2, o2, m = fn(state["p"], state["o"], placed,
                       inp["W"], inp["mask"], inp["rho"])
        state["p"], state["o"] = p2, o2
        return m

    return time_callable(step, policy=policy).mean_s


def bench_results(quick: bool = False) -> list[BenchResult]:
    cfg = get_config(ARCH).reduced()
    schedules = ("gather", "a2a")
    metrics: dict[str, float] = {}
    lines = []
    n_buckets = n_coded = 0
    within_bound = 1.0
    pack_plan = None

    for schedule in schedules:
        arts_p, opt, placed = _build(cfg, schedule)
        cp = _collective_counts(arts_p, opt, placed, cfg)
        pack_plan = arts_p.pack_plan
        n_buckets = len(pack_plan.buckets)
        n_coded = pack_plan.num_coded_leaves

        def launches(c):
            return c.get("all-gather", 0) + c.get("all-to-all", 0)

        bound = n_buckets if schedule == "gather" else 2 * n_buckets
        if launches(cp) > bound:
            within_bound = 0.0
        metrics[f"collectives_per_step_packed_{schedule}"] = float(launches(cp))

        sched = get_schedule(schedule)
        pred = sched.recv_elems_per_worker(
            pack_plan.unpadded_elems * pack_plan.m, N_WORKERS, pack_plan.m)
        padded = pack_plan.recv_elems_per_worker(sched)
        metrics[f"recv_padded_over_pred_{schedule}"] = round(padded / pred, 6)
        lines.append(
            f"coding_packed,schedule={schedule},buckets={n_buckets},"
            f"coded_leaves={n_coded},collectives_packed={launches(cp)},"
            f"recv_elems_padded={padded:.0f},recv_elems_pred={pred:.0f}")

        if not quick:
            policy = TimerPolicy(warmup=1, reps=8)
            t_p = _measured_step_s(arts_p, opt, placed, cfg, policy)
            metrics[f"measured_step_s_packed_{schedule}"] = round(t_p, 5)
            lines.append(
                f"coding_packed_timing,schedule={schedule},"
                f"packed_s={t_p:.5f}")

    metrics["packed_collectives_within_bound"] = within_bound
    metrics["padded_overhead"] = round(
        pack_plan.padded_elems / pack_plan.unpadded_elems, 6)
    lines.append(
        f"coding_packed_summary,padded_elems={pack_plan.padded_elems},"
        f"unpadded_elems={pack_plan.unpadded_elems},"
        f"overhead_ratio={metrics['padded_overhead']:.6f}")

    result = BenchResult(
        name="coding_packed",
        metrics=metrics,
        params={"arch": cfg.name, "n_workers": N_WORKERS,
                "code": {"n": CODE.n, "d": CODE.d, "s": CODE.s, "m": CODE.m},
                "n_buckets": n_buckets, "n_coded_leaves": n_coded,
                "quick": quick},
        env=capture_env(mesh=make_local_mesh(N_WORKERS, 1)),
        timing=None if quick else {"warmup": 1, "reps": 8,
                                   "policy": "donated steady-state step"},
        # deterministic structural metrics only: HLO collective counts and
        # the static padding ratio (wall-clock stays ungated, CI varies)
        gates={"collectives_per_step_packed_gather": "min",
               "collectives_per_step_packed_a2a": "min",
               "packed_collectives_within_bound": "max",
               "padded_overhead": "min"},
        extra={"lines": lines},
    )
    return [result]


register(BenchSpec(
    name="coding_packed",
    description="packed-wire collective counts + padding accounting",
    fn=bench_results,
    tags=("coding", "hlo"),
))


def run() -> list[str]:
    return bench_results(False)[0].extra["lines"]


if __name__ == "__main__":
    for line in run():
        print(line)
