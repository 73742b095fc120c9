"""Elastic membership vs fixed-n under worker churn: the robustness bench.

Replays a deterministic churn trace (worker 7 leaves, later rejoins)
against two trainers running the real jitted coded step on host meshes:

  fixed-n   the departed worker stays a permanent forced straggler at
            unchanged n (degradation rung 1 only): decode stays exact —
            the budget covers the hole — but every step pays the max of
            the alive workers (the drop budget is burnt on the hole)
  elastic   the full ladder: forced straggler -> zero-load re-plan ->
            resize to n_alive (prewarmed mesh, warm caches), then a
            scale-up resize back when the worker rejoins

Per step, total = modeled cluster wait (the order statistic a single
host cannot exhibit, drawn from the same shifted-exponential process as
``repro.bench.straggler`` with missing-heartbeat NaNs at down workers) +
measured wall of the jitted step.  The gated speedup uses the modeled
waits (scale-free and machine-independent); walls and recompile counts
are reported ungated.

Gated metrics:

  speedup_elastic_vs_fixed_n     modeled-wait total: the ladder beats
                                 paying the hole as a permanent straggler
  elastic_recovers_exact         after rejoin + scale-up the active code
                                 is bitwise-identical to a never-churned
                                 run's (C and decode weights)
  elastic_survives_past_s        a 2-departure burst past s=1 completes:
                                 partial-decode failover bridges the gap,
                                 the zero-load re-plan restores exact
  planner_resize_wins_long_horizon    membership-aware ranking: with the
                                 recompile charge amortized over a long
                                 remaining run, the resize candidate wins
  planner_degraded_wins_short_horizon ...and over a short horizon the
                                 stay-degraded candidate wins (the charge
                                 cannot be earned back)
"""

from __future__ import annotations

import dataclasses
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

from repro.bench import BenchResult, BenchSpec, capture_env, register
from repro.configs import get_config
from repro.core import make_code
from repro.core.runtime_model import RuntimeParams
from repro.data import make_synthetic_batch
from repro.elastic import ElasticPolicy, ElasticTrainer, MembershipTrace
from repro.launch.mesh import make_local_mesh
from repro.optim import get_optimizer
from repro.tune import (StepRecord, WorkerTimes, rank_plans, step_cost_book,
                        synthetic_fit)

N_WORKERS = 8
#: divisible by 8 and 7, so both cluster sizes split the batch evenly
GLOBAL_BATCH = 56
DESIGN = (3, 1, 2)            # (d, s, m): s + m = d, the paper's optimum
# spot-fleet-style constants: small shifts, heavy straggler tail
# (lambda2=0.05 -> mean comm excess 20s) — the regime where spending the
# drop budget on a genuine straggler (instead of burning it on the hole a
# departed worker leaves) matters most
PARAMS = RuntimeParams(n=N_WORKERS, lambda1=0.5, lambda2=0.05, t1=0.5, t2=4.0)


class ChurnAwareSampler:
    """Injector ``(step, code) -> WorkerTimes`` with membership churn.

    Draws the shifted-exponential process of
    :class:`repro.tune.ShiftedExpSampler`, with two twists:

    - compute is **batch-aware across cluster sizes**: worker ``i``'s
      share of the global batch is ``loads[i] / k``, so the per-subset
      draw is scaled by ``ref_k / k`` — a 7-worker cluster's subsets are
      8/7 the size of an 8-worker cluster's;
    - workers named down by the scripted outage (and still inside the
      active code's index space) report **NaN** — the missing-heartbeat
      convention :meth:`repro.tune.WorkerTimes.order_stat` maps to
      ``+inf``, so they can never be counted as responders.

    Passed bare to the trainer it is wrapped in
    :class:`repro.tune.TimedSource` (slowest ``code.s`` workers per draw
    are the stragglers).
    """

    def __init__(self, down_worker: int, leave_step: int, rejoin_step: int,
                 seed: int = 0, ref_k: int = N_WORKERS):
        """``down_worker`` is unreachable for ``leave_step <= t <
        rejoin_step`` while the cluster still has its original size."""
        self.down_worker = down_worker
        self.leave_step = leave_step
        self.rejoin_step = rejoin_step
        self.ref_k = ref_k
        self._rng = np.random.default_rng(seed)

    def __call__(self, step: int, code) -> WorkerTimes:
        """One step's per-worker durations under the active scheme.

        Common random numbers: ``ref_k`` variates are drawn per step and
        sliced to the active ``n``, so runs that resize and runs that do
        not face the *same* per-worker noise — the wait comparison is
        paired, isolating the scheme effect from sampling variance.
        """
        n = code.n
        loads = np.asarray(getattr(code, "loads", (code.d,) * n),
                           dtype=np.float64)
        k = int(getattr(code, "num_subsets", n))
        scale = loads * self.ref_k / k
        x1 = self._rng.exponential(1.0 / PARAMS.lambda1, self.ref_k)[:n]
        x2 = self._rng.exponential(1.0 / PARAMS.lambda2, self.ref_k)[:n]
        comp = scale * (PARAMS.t1 + x1)
        comm = (PARAMS.t2 + x2) / code.m
        if (self.leave_step <= step < self.rejoin_step
                and self.down_worker < n == N_WORKERS):
            comp[self.down_worker] = np.nan
            comm[self.down_worker] = np.nan
        return WorkerTimes(compute_s=comp, comm_s=comm)


def _run(cfg, policy, trace, injector, steps):
    """Drive an ElasticTrainer; return (trainer, waits, walls, losses)."""
    code = make_code(N_WORKERS, *DESIGN)
    tr = ElasticTrainer(cfg, code, make_local_mesh(N_WORKERS, 1),
                        get_optimizer("sgd", 1e-2),
                        straggler_source=injector, churn=trace,
                        elastic=policy, seed=0)
    rng = np.random.default_rng(5)
    waits, walls, losses = [], [], []
    for _ in range(steps):
        m = tr.step(make_synthetic_batch(rng, cfg, GLOBAL_BATCH, 0))
        waits.append(m["modeled_wait_s"])
        walls.append(m["step_time_s"])
        losses.append(m["loss"])
    return tr, np.asarray(waits), np.asarray(walls), np.asarray(losses)


def _planner_membership_check(npts: int) -> tuple[float, float]:
    """Deterministic membership-aware ranking check (no wall-clock).

    Builds a cost book whose compile observations make a retrace
    expensive (a 30 s trace against a 20 ms step), then ranks
    stay-degraded vs resize for a departed worker under a long and a
    short re-plan horizon.
    """
    fit = synthetic_fit(PARAMS, steps=200, seed=7)
    n = N_WORKERS
    recs = [StepRecord(step=i, d=DESIGN[0], s=DESIGN[1], m=DESIGN[2], k=n,
                       loads=(DESIGN[0],) * n, schedule="gather",
                       compute_s=np.full(n, 1.0), comm_s=np.full(n, 1.0),
                       measured_step_s=0.02, compile_s=30.0 if i == 0 else 0.0)
            for i in range(8)]
    book = step_cost_book(recs)
    common = dict(schedules=("gather",), cost_book=book, departed=(7,),
                  resize_options=(7,), mc_iters=300, npts=npts, seed=11)
    top_long = rank_plans(fit, replan_horizon=1000, **common)[0]
    top_short = rank_plans(fit, replan_horizon=1, **common)[0]
    return (float(top_long.resize_to == 7),
            float(top_short.resize_to is None))


def bench_results(quick: bool = False) -> list[BenchResult]:
    d_model = 256 if quick else 2048
    leave = 3 if quick else 6
    rejoin = 12 if quick else 26
    steps = 14 if quick else 30
    resize_after = 2 if quick else 3
    npts = 6_000 if quick else 20_000

    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=d_model)
    trace = [(leave, "leave", 7), (rejoin, "join", 7)]

    # --- scenario 1: single departure + rejoin, fixed-n vs full ladder
    fixed_policy = ElasticPolicy(partial_failover=True, replan_after=0,
                                 resize_after=0, scale_up=False)
    elastic_policy = ElasticPolicy(partial_failover=True, replan_after=1,
                                   resize_after=resize_after, scale_up=True,
                                   min_n=2, prewarm=(N_WORKERS - 1,))
    tr_f, w_f, t_f, _ = _run(
        cfg, fixed_policy, MembershipTrace(trace),
        ChurnAwareSampler(7, leave, rejoin, seed=3), steps)
    tr_e, w_e, t_e, _ = _run(
        cfg, elastic_policy, MembershipTrace(trace),
        ChurnAwareSampler(7, leave, rejoin, seed=3), steps)

    metrics: dict[str, float] = {}
    lines = []
    metrics["wait_total_s_fixed"] = round(float(w_f.sum()), 3)
    metrics["wait_total_s_elastic"] = round(float(w_e.sum()), 3)
    metrics["wall_total_s_fixed"] = round(float(t_f.sum()), 3)
    metrics["wall_total_s_elastic"] = round(float(t_e.sum()), 3)
    metrics["speedup_elastic_vs_fixed_n"] = round(
        float(w_f.sum() / w_e.sum()), 4)
    down = slice(leave, rejoin)   # the outage window, where the claim lives
    metrics["wait_down_s_fixed"] = round(float(w_f[down].sum()), 3)
    metrics["wait_down_s_elastic"] = round(float(w_e[down].sum()), 3)
    metrics["speedup_down_window"] = round(
        float(w_f[down].sum() / w_e[down].sum()), 4)
    for name, (tr, w, t) in (("fixed", (tr_f, w_f, t_f)),
                             ("elastic", (tr_e, w_e, t_e))):
        lines.append(
            f"elastic,run={name},steps={steps},wait_total_s={w.sum():.2f},"
            f"wall_total_s={t.sum():.2f},final_n={tr.code.n}")
    for e in tr_e.elastic_events:
        lines.append("elastic_event," + ",".join(
            f"{k}={v}" for k, v in e.items()))

    # recovery: after rejoin + scale-up the code must be bitwise-identical
    # to a never-churned run's deterministic construction
    home = make_code(N_WORKERS, *DESIGN)
    resp = list(range(1, N_WORKERS))
    recovered = (tr_e.code.n == N_WORKERS
                 and np.array_equal(np.asarray(tr_e.code.C),
                                    np.asarray(home.C))
                 and np.array_equal(tr_e.code.decode_weights(resp),
                                    home.decode_weights(resp)))
    metrics["elastic_recovers_exact"] = float(recovered)
    metrics["elastic_n_resizes"] = float(sum(
        1 for e in tr_e.elastic_events if e["action"] == "resize"))

    # --- scenario 2: a 2-departure burst past s=1 (partial failover ->
    # zero-load re-plan restores exact decode at unchanged n)
    burst_steps = 8 if quick else 12
    tr_b, _, _, losses_b = _run(
        cfg, ElasticPolicy(partial_failover=True, replan_after=1,
                           resize_after=0, scale_up=False),
        MembershipTrace([(3, "preempt", 6), (3, "preempt", 7)]),
        ChurnAwareSampler(99, 10**9, 10**9, seed=4), burst_steps)
    acted = {e["action"] for e in tr_b.elastic_events}
    loads_b = np.asarray(getattr(tr_b.code, "loads",
                                 (tr_b.code.d,) * tr_b.code.n))
    survives = (np.isfinite(losses_b).all()
                and "partial-failover" in acted
                and "replan-degraded" in acted
                and loads_b[6] == 0 and loads_b[7] == 0
                and tr_b.code.s >= 2)
    metrics["elastic_survives_past_s"] = float(survives)
    lines.append(
        f"elastic_burst,steps={burst_steps},actions={sorted(acted)},"
        f"final_loads={list(loads_b)},final_s={tr_b.code.s}")

    # --- membership-aware planner: resize vs stay-degraded flips on the
    # recompile-amortization horizon
    long_ok, short_ok = _planner_membership_check(npts)
    metrics["planner_resize_wins_long_horizon"] = long_ok
    metrics["planner_degraded_wins_short_horizon"] = short_ok
    lines.append(
        f"elastic_planner,long_horizon_resize={int(long_ok)},"
        f"short_horizon_degraded={int(short_ok)}")

    result = BenchResult(
        name="elastic",
        metrics=metrics,
        params={"n_workers": N_WORKERS, "design": list(DESIGN),
                "global_batch": GLOBAL_BATCH, "d_model": d_model,
                "leave_step": leave, "rejoin_step": rejoin, "steps": steps,
                "resize_after": resize_after, "quick": quick,
                "params": dataclasses.asdict(PARAMS)},
        env=capture_env(mesh=make_local_mesh(N_WORKERS, 1)),
        timing={"warmup": 0, "reps": steps,
                "policy": "per-step blocked wall + modeled wait"},
        gates={"speedup_elastic_vs_fixed_n": "max",
               "elastic_recovers_exact": "max",
               "elastic_survives_past_s": "max",
               "planner_resize_wins_long_horizon": "max",
               "planner_degraded_wins_short_horizon": "max"},
        extra={"lines": lines, "events": tr_e.elastic_events},
    )
    return [result]


register(BenchSpec(
    name="elastic",
    description="elastic membership ladder vs fixed-n under worker churn",
    fn=bench_results,
    tags=("e2e", "train", "elastic"),
))


def run() -> list[str]:
    return bench_results(False)[0].extra["lines"]


if __name__ == "__main__":
    for line in run():
        print(line)
