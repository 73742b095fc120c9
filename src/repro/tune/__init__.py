"""`repro.tune`: online straggler profiling + adaptive (d, s, m) auto-tuning.

The paper's headline result is that the optimal operating point
``(d, s, m)`` follows from a shifted-exponential straggler model — but real
clusters drift.  This package closes the measure -> fit -> re-plan loop at
runtime:

  telemetry — per-step, per-worker compute/communication durations and
              straggler events (`StepRecord` / `TelemetryLog`), plus the
              shifted-exponential injectors (`ShiftedExpSampler`,
              `DriftingSampler`) that stand in for worker heartbeats on
              single-host meshes
  estimator — closed-form MLE of the Section-VI constants
              ``(t1, lambda1, t2, lambda2)`` and a per-worker speed vector
              from observed timings (`fit_runtime_params`), cross-checked
              against the order-statistic math of
              ``repro.core.runtime_model`` (`crosscheck_waits`)
  planner   — ranked search of the feasible (d, s, m) x schedule
              x {uniform, hetero} space by predicted ``E[T_tot]``,
              calibrated with measured step times (`rank_plans`, `Plan`)
  policy    — the control loop (`AutotunePolicy`, `Autotuner`): re-plan
              every N steps, switch codecs only past a hysteresis margin
  stragglers— the `StragglerSource` protocol: one duck type for every way
              straggler sets enter a run (none / fixed / random / timed),
              shared by the Trainer and the serving engine's hedging loop
  arrivals  — the serving-side planner: Poisson arrival process, batching
              queue simulation, p50/p99 latency ranking of (d, s, m) x
              schedule plans and the `ServingAutotuner` re-plan loop

Entry points: ``Trainer(..., autotune=AutotunePolicy(...),
straggler_source=DriftingSampler(...))`` — the Trainer records telemetry,
re-plans on the policy's cadence, and swaps codecs through a compile cache
so returning to a previously used scheme does not retrace — and
``CodedServer(..., autotune=ServingPolicy(...))`` for the serving twin
ranking by modeled p99 under the arrival process.  See
``docs/autotune.md`` for the drift scenario walked end to end,
``docs/serving.md`` for the serving loop, and
``benchmarks/bench_autotune.py`` for the CI-gated adaptive-vs-static proof.
"""
from .arrivals import (PoissonArrivals, ServePlan, ServingAutotuner,
                       ServingPolicy, rank_serving_plans, simulate_queue)
from .estimator import (FitResult, crosscheck_waits, fit_runtime_params,
                        fit_shifted_exponential, synthetic_fit)
from .planner import (PIPELINE_EPS, Plan, StepCostBook, rank_plans,
                      score_plan, step_cost_book)
from .policy import AutotunePolicy, Autotuner
from .stragglers import (FixedStragglers, NoStragglers, RandomStragglers,
                         StragglerDraw, StragglerSource, TimedSource,
                         as_straggler_source)
from .telemetry import (DriftingSampler, ShiftedExpSampler, StepRecord,
                        TelemetryLog, WorkerTimes, record_from_times,
                        scheme_k, scheme_loads)

__all__ = [
    "AutotunePolicy",
    "Autotuner",
    "DriftingSampler",
    "FitResult",
    "FixedStragglers",
    "NoStragglers",
    "PIPELINE_EPS",
    "Plan",
    "PoissonArrivals",
    "RandomStragglers",
    "ServePlan",
    "ServingAutotuner",
    "ServingPolicy",
    "ShiftedExpSampler",
    "StepCostBook",
    "StepRecord",
    "StragglerDraw",
    "StragglerSource",
    "TelemetryLog",
    "TimedSource",
    "WorkerTimes",
    "as_straggler_source",
    "crosscheck_waits",
    "fit_runtime_params",
    "fit_shifted_exponential",
    "rank_plans",
    "rank_serving_plans",
    "record_from_times",
    "scheme_k",
    "scheme_loads",
    "score_plan",
    "simulate_queue",
    "step_cost_book",
    "synthetic_fit",
]
