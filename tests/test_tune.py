"""`repro.tune`: MLE recovery, planner paper-anchor, trainer autotune loop.

Layered like the rest of the suite:

  1. deterministic seeded checks always run (this container has no
     hypothesis);
  2. a hypothesis property test widens the MLE round-trip when hypothesis
     is installed (CI);
  3. a real-Trainer integration slice drives the measure -> fit -> re-plan
     -> codec-swap loop end to end on the 4-worker host mesh, including
     the compile-cache reuse and partial=True interop the ISSUE requires.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.runtime_model import (RuntimeParams, expected_total_runtime,
                                      optimal_triple)
import repro.coding as coding
from repro.tune import (AutotunePolicy, Autotuner, DriftingSampler,
                        FitResult, Plan, ShiftedExpSampler, StepRecord,
                        TelemetryLog, WorkerTimes, crosscheck_waits,
                        fit_runtime_params, fit_shifted_exponential,
                        rank_plans, record_from_times, step_cost_book,
                        synthetic_fit)

PAPER_N8 = RuntimeParams(n=8, lambda1=0.8, lambda2=0.1, t1=1.6, t2=6.0)


# ------------------------------------------------------------ MLE estimator
def test_shifted_exp_mle_deterministic_roundtrip():
    rng = np.random.default_rng(0)
    for t_true, lam_true in [(1.6, 0.8), (6.0, 0.1), (0.5, 2.0)]:
        x = t_true + rng.exponential(1.0 / lam_true, 4000)
        t_hat, lam_hat = fit_shifted_exponential(x)
        assert abs(t_hat - t_true) < 0.15 / lam_true + 1e-3
        assert abs(lam_hat - lam_true) / lam_true < 0.10


def test_shifted_exp_mle_rejects_tiny_samples():
    with pytest.raises(ValueError):
        fit_shifted_exponential([1.0])


def test_fit_runtime_params_recovers_ground_truth():
    """Full-pipeline round trip: sampler -> records -> fit, paper constants."""
    fit = synthetic_fit(PAPER_N8, steps=800, seed=7)
    p = fit.params
    assert abs(p.t1 - PAPER_N8.t1) / PAPER_N8.t1 < 0.10
    assert abs(p.lambda1 - PAPER_N8.lambda1) / PAPER_N8.lambda1 < 0.15
    assert abs(p.t2 - PAPER_N8.t2) / PAPER_N8.t2 < 0.10
    assert abs(p.lambda2 - PAPER_N8.lambda2) / PAPER_N8.lambda2 < 0.15
    # homogeneous ground truth -> estimated speeds hug 1
    assert fit.speed_spread < 1.15
    assert fit.n_steps == 800


def test_fit_normalises_across_mixed_schemes():
    """Records from different (d, m) pool into one consistent fit."""
    params = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0)
    sampler = ShiftedExpSampler(params, seed=11)
    records = []
    for t in range(600):
        d, s, m = [(4, 2, 2), (3, 1, 2), (1, 0, 1)][t % 3]
        wt = sampler.draw((d,) * 4, 4, m)
        records.append(record_from_times(
            t, _FakeCode(4, d, s, m), "gather", True, wt))
    fit = fit_runtime_params(records)
    assert abs(fit.params.t2 - params.t2) / params.t2 < 0.10
    assert abs(fit.params.t1 - params.t1) / params.t1 < 0.20


def test_fit_estimates_speed_vector():
    """A 2x skewed cluster shows up in the fitted speeds."""
    params = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=4.0, t2=4.0)
    speeds = np.array([0.5, 1.0, 1.0, 2.0])
    sampler = ShiftedExpSampler(params, speeds=speeds, seed=3)
    records = []
    for t in range(500):
        wt = sampler.draw((3,) * 4, 4, 2)
        records.append(record_from_times(
            t, _FakeCode(4, 3, 1, 2), "gather", True, wt))
    fit = fit_runtime_params(records)
    rel = speeds / speeds.mean()
    assert np.allclose(fit.speeds, rel, rtol=0.15)
    assert fit.speed_spread > 2.5   # true spread 4x, well past threshold


def test_crosscheck_agrees_for_exact_fit():
    """Observed mean waits match the fitted model's order-statistic E[T]."""
    params = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0)
    sampler = ShiftedExpSampler(params, seed=5)
    records = []
    for t in range(1500):
        wt = sampler.draw((4,) * 4, 4, 2)
        records.append(record_from_times(
            t, _FakeCode(4, 4, 2, 2), "gather", True, wt))
    fit = FitResult(params=params, speeds=np.ones(4), n_steps=0, n_samples=0)
    assert crosscheck_waits(fit, records, npts=30_000) < 0.05


# ---------------------------------------------------------------- planner
def test_planner_reproduces_paper_n8_optimum():
    """Fed the paper's exact constants, the ranked search returns the
    published optimal triple (4, 1, 3) — and agrees with optimal_triple
    across the whole frontier ordering."""
    exact = FitResult(params=PAPER_N8, speeds=np.ones(8), n_steps=0,
                      n_samples=0)
    ranked = rank_plans(exact, schedules=("gather",), npts=60_000)
    top = ranked[0]
    assert (top.d, top.s, top.m) == (4, 1, 3)
    (d, s, m), best_v = optimal_triple(PAPER_N8, npts=60_000)
    assert (top.d, top.s, top.m) == (d, s, m)
    assert top.predicted_wait_s == pytest.approx(best_v, rel=1e-3)
    # every uniform candidate's wait matches the runtime model directly
    for p in ranked[:5]:
        assert p.predicted_wait_s == pytest.approx(
            expected_total_runtime(PAPER_N8, p.d, p.s, p.m, npts=60_000),
            rel=1e-6)


def test_planner_min_s_floor_and_families():
    exact = FitResult(params=PAPER_N8, speeds=np.ones(8), n_steps=0,
                      n_samples=0)
    ranked = rank_plans(exact, schedules=("gather",), npts=8_000, min_s=1)
    assert all(p.s >= 1 for p in ranked)
    # homogeneous speeds: "hetero" stays locked behind the spread threshold
    ranked = rank_plans(exact, schedules=("gather",), npts=8_000,
                        families=("uniform", "hetero"))
    assert all(p.family == "uniform" for p in ranked)
    # ... but "hetero!" forces it
    ranked = rank_plans(exact, schedules=("gather",), npts=8_000,
                        families=("hetero!",), mc_iters=50)
    assert ranked and all(p.family == "hetero" for p in ranked)
    assert all(p.s >= 1 for p in ranked)


def test_planner_pipelined_candidates_use_overlapped_model():
    """pipelined_options=(False, True) doubles the uniform frontier: each
    pipelined candidate's wait is the overlapped model (per-worker cycle
    max(comp, comm) + PIPELINE_EPS), which dominates on comm-heavy
    constants — and the sync twin of every pipelined plan keeps the plain
    E[T_tot].  The default search space stays sync-only."""
    from repro.core.runtime_model import expected_total_runtime_overlapped
    from repro.tune import PIPELINE_EPS

    exact = FitResult(params=PAPER_N8, speeds=np.ones(8), n_steps=0,
                      n_samples=0)
    assert all(not p.pipelined
               for p in rank_plans(exact, schedules=("gather",), npts=8_000))
    ranked = rank_plans(exact, schedules=("gather",), npts=8_000,
                        pipelined_options=(False, True))
    assert {p.pipelined for p in ranked} == {False, True}
    top = ranked[0]
    assert top.pipelined   # overlap always wins on the modeled wait alone
    assert "pipelined" in top.describe()
    for p in ranked:
        want = (expected_total_runtime_overlapped(
                    PAPER_N8, p.d, p.s, p.m, npts=8_000, eps=PIPELINE_EPS)
                if p.pipelined
                else expected_total_runtime(PAPER_N8, p.d, p.s, p.m,
                                            npts=8_000))
        assert p.predicted_wait_s == pytest.approx(want, rel=1e-6)
    # scheme_key separates the twins (the trainer caches per signature)
    keys = {p.scheme_key for p in ranked}
    assert len(keys) == len(ranked)
    # hetero stays synchronous: pipelining is a uniform-family knob
    hranked = rank_plans(exact, schedules=("gather",), npts=8_000,
                         families=("hetero!",), mc_iters=30,
                         pipelined_options=(False, True))
    assert hranked and all(not p.pipelined for p in hranked)


def test_step_cost_book_keys_on_pipelined():
    """A pipelined steady-state measurement must not calibrate the sync
    twin (and vice versa): the book keys per (schedule, packed, pipelined)."""
    recs = [
        StepRecord(step=0, d=3, s=1, m=2, k=4, loads=(3,) * 4,
                   schedule="gather", packed=True, compute_s=np.zeros(4),
                   comm_s=np.zeros(4), measured_step_s=3.0),
        StepRecord(step=1, d=3, s=1, m=2, k=4, loads=(3,) * 4,
                   schedule="gather", packed=True, compute_s=np.zeros(4),
                   comm_s=np.zeros(4), measured_step_s=1.0, pipelined=True),
    ]
    book = step_cost_book(recs)
    assert book.cost(3, 4, (3,) * 4, "gather", True) == pytest.approx(3.0)
    assert book.cost(3, 4, (3,) * 4, "gather", True,
                     pipelined=True) == pytest.approx(1.0)


def test_planner_step_cost_calibration_breaks_ties():
    """Measured step costs reorder schedules with identical modeled waits."""
    exact = FitResult(params=PAPER_N8, speeds=np.ones(8), n_steps=0,
                      n_samples=0)
    recs = [
        StepRecord(step=0, d=3, s=1, m=2, k=8, loads=(3,) * 8,
                   schedule="gather", packed=True, compute_s=np.zeros(8),
                   comm_s=np.zeros(8), measured_step_s=5.0),
        StepRecord(step=1, d=3, s=1, m=2, k=8, loads=(3,) * 8,
                   schedule="a2a", packed=True, compute_s=np.zeros(8),
                   comm_s=np.zeros(8), measured_step_s=0.010),
    ]
    ranked = rank_plans(exact, schedules=("gather", "a2a"), npts=8_000,
                        cost_book=step_cost_book(recs))
    assert ranked[0].schedule == "a2a"
    assert 0 < ranked[0].predicted_step_s < 1.0


def test_step_cost_book_exact_and_load_scaled_fallback():
    recs = []
    for i, (sched, d, wall) in enumerate([("gather", 3, 1.0),
                                          ("gather", 3, 3.0),
                                          ("a2a", 2, 2.0),
                                          ("a2a", 2, 0.0)]):
        recs.append(StepRecord(
            step=i, d=d, s=1, m=1, k=4, loads=(d,) * 4, schedule=sched,
            packed=True, compute_s=np.zeros(4), comm_s=np.zeros(4),
            measured_step_s=wall))
    book = step_cost_book(recs)
    assert len(book) == 2   # zero-wall record contributes nothing new
    # exact scheme hit: the mean of its own measurements
    assert book.cost(3, 4, (3,) * 4, "gather", True) == pytest.approx(2.0)
    assert book.cost(2, 4, (2,) * 4, "a2a", True) == pytest.approx(2.0)
    # unseen d, known config: per-load mean (2.0/3) scaled by the new d —
    # a d=1 candidate is NOT charged the d=3 step's wall-clock
    assert book.cost(1, 4, (1,) * 4, "gather", True) == pytest.approx(2 / 3)
    # unseen config: global per-load mean ((1/3 + 3/3 + 2/2) / 3) * d
    assert book.cost(1, 4, (1,) * 4, "psum", True) == pytest.approx(
        (1 / 3 + 1.0 + 1.0) / 3)
    # empty book: free
    from repro.tune import StepCostBook
    assert StepCostBook().cost(4, 4, (4,) * 4, "gather", True) == 0.0


# ------------------------------------------------------- telemetry plumbing
class _FakeCode:
    """Minimal GradCode duck for telemetry/estimator unit tests."""

    def __init__(self, n, d, s, m, k=None, loads=None):
        self.n, self.d, self.s, self.m = n, d, s, m
        self.num_subsets = k if k is not None else n
        self.loads = tuple(loads) if loads is not None else (d,) * n


def test_worker_times_order_stat():
    wt = WorkerTimes(compute_s=np.array([1.0, 5.0, 2.0, 9.0]),
                     comm_s=np.array([0.5, 0.5, 0.5, 0.5]))
    slow, wait = wt.order_stat(1)
    assert slow == (3,)
    assert wait == pytest.approx(5.5)
    none, wait_all = wt.order_stat(0)
    assert none == () and wait_all == pytest.approx(9.5)


def test_telemetry_log_capacity_and_window():
    log = TelemetryLog(capacity=10)
    for t in range(25):
        log.append(StepRecord(
            step=t, d=3, s=1, m=2, k=4, loads=(3,) * 4, schedule="gather",
            packed=True, compute_s=np.zeros(4), comm_s=np.zeros(4)))
    assert len(log) == 10
    assert [r.step for r in log.window(3)] == [22, 23, 24]
    assert log.records[0].step == 15


def test_drifting_sampler_phases():
    pA = RuntimeParams(n=4, lambda1=1.0, lambda2=1.0, t1=1.0, t2=1.0)
    pB = RuntimeParams(n=4, lambda1=1.0, lambda2=1.0, t1=50.0, t2=1.0)
    drift = DriftingSampler([(0, pA), (10, pB)], seed=0)
    assert drift.params_at(0) is pA and drift.params_at(9) is pA
    assert drift.params_at(10) is pB
    code = _FakeCode(4, 2, 1, 1)
    early = drift(0, code)
    late = drift(12, code)
    assert early.compute_s.max() < 50.0 <= late.compute_s.min()
    with pytest.raises(ValueError):
        DriftingSampler([(10, pA), (0, pB)])


# ------------------------------------------------------------ control loop
def _mk_plan(d, s, m, schedule="gather"):
    return Plan(family="uniform", d=d, s=s, m=m, k=4, loads=(d,) * 4,
                schedule=schedule, packed=True, predicted_wait_s=0.0,
                predicted_step_s=0.0, predicted_total_s=0.0)


def test_autotuner_holds_then_switches_under_drift():
    pA = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0)
    pB = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=16.0, t2=0.5)
    policy = AutotunePolicy(interval=5, window=10, min_samples=5,
                            schedules=("gather",), npts=6_000)
    tuner = Autotuner(policy, current=_mk_plan(4, 2, 2))
    drift = DriftingSampler([(0, pA), (20, pB)], seed=9)
    code = _FakeCode(4, 4, 2, 2)
    switched_at = None
    for t in range(40):
        wt = drift(t, code)
        tuner.record(record_from_times(t, code, "gather", True, wt))
        new = tuner.maybe_replan(t)
        if new is not None:
            switched_at = t
            code = _FakeCode(4, new.d, new.s, new.m)
    # held the optimum through phase A, moved off it after the drift
    assert switched_at is not None and switched_at >= 20
    assert (code.d, code.s, code.m) != (4, 2, 2)
    assert any(e["switched"] for e in tuner.events)
    holds = [e for e in tuner.events if not e["switched"]]
    assert holds and all(e["current_predicted_s"] is not None
                         for e in holds)


def test_autotuner_rejects_implausible_fit():
    """A fit whose cross-check error exceeds the policy bound must not
    drive a switch (the documented refusal)."""
    params = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0)
    policy = AutotunePolicy(interval=4, window=8, min_samples=4,
                            schedules=("gather",), npts=4_000,
                            max_crosscheck_rel_err=0.0)   # reject everything
    tuner = Autotuner(policy, current=_mk_plan(4, 2, 2))
    sampler = ShiftedExpSampler(params, seed=1)
    code = _FakeCode(4, 4, 2, 2)
    for t in range(12):
        tuner.record(record_from_times(t, code, "gather", True,
                                       sampler(t, code)))
        assert tuner.maybe_replan(t) is None
    rejected = [e for e in tuner.events if e.get("rejected_fit")]
    assert rejected and all(not e["switched"] for e in tuner.events)
    # rejected events keep the full key set so consumers index uniformly
    assert all(e["best"] is None and e["current_predicted_s"] is None
               for e in rejected)
    assert tuner.current.scheme_key == _mk_plan(4, 2, 2).scheme_key


def test_autotuner_rescorees_current_outside_search_space():
    """An active plan absent from the ranking (schedule not searched) is
    re-scored for the hysteresis comparison — never auto-switched."""
    params = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0)
    policy = AutotunePolicy(interval=4, window=8, min_samples=4,
                            schedules=("gather",), npts=6_000)
    # active: the optimal triple but on a schedule the policy won't search;
    # the ranked gather twin has the same modeled wait, so hysteresis must
    # hold rather than flap onto it
    tuner = Autotuner(policy, current=_mk_plan(4, 2, 2, schedule="a2a"))
    sampler = ShiftedExpSampler(params, seed=2)
    code = _FakeCode(4, 4, 2, 2)
    for t in range(8):
        tuner.record(record_from_times(t, code, "gather", True,
                                       sampler(t, code)))
        assert tuner.maybe_replan(t) is None
    assert tuner.current.schedule == "a2a"   # held
    scored = [e for e in tuner.events if "current_predicted_s" in e]
    assert scored and all(e["current_predicted_s"] is not None
                          and e["current_predicted_s"] > 0 for e in scored)


def test_autotuner_not_due_before_min_samples():
    policy = AutotunePolicy(interval=2, window=8, min_samples=6)
    tuner = Autotuner(policy, current=_mk_plan(3, 1, 2))
    sampler = ShiftedExpSampler(
        RuntimeParams(n=4, lambda1=1.0, lambda2=1.0, t1=1.0, t2=1.0), seed=0)
    code = _FakeCode(4, 3, 1, 2)
    for t in range(5):
        tuner.record(record_from_times(t, code, "gather", True,
                                       sampler(t, code)))
        assert not tuner.due()
        assert tuner.maybe_replan(t) is None
    tuner.record(record_from_times(5, code, "gather", True,
                                   sampler(5, code)))
    assert tuner.due()


# ------------------------------------------------- hypothesis widening (CI)
try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(st.floats(0.2, 8.0), st.floats(0.1, 2.0),
           st.floats(0.2, 20.0), st.floats(0.05, 1.0),
           st.integers(0, 2**31 - 1))
    def test_mle_roundtrip_property(t1, lam1, t2, lam2, seed):
        """The acceptance-criterion property: the shifted-exponential MLE
        recovers (t1, l1, t2, l2) within tolerance on synthetic draws."""
        params = RuntimeParams(n=6, lambda1=lam1, lambda2=lam2, t1=t1, t2=t2)
        fit = synthetic_fit(params, steps=500, seed=seed, probe=(2, 1, 1))
        p = fit.params
        assert abs(p.t1 - t1) <= 0.25 / lam1 + 0.02 * t1
        assert abs(p.lambda1 - lam1) / lam1 < 0.25
        assert abs(p.t2 - t2) <= 0.25 / lam2 + 0.02 * t2
        assert abs(p.lambda2 - lam2) / lam2 < 0.25
except ImportError:  # hypothesis optional at runtime (declared in [test])
    pass


# ------------------------------------------------ trainer integration (e2e)
def test_trainer_autotune_swaps_codec_and_reuses_cache():
    """The tentpole loop on the real jitted step: telemetry -> fit ->
    re-plan -> codec swap, with compile-cache reuse on the way back."""
    from repro.configs import get_config
    from repro.core import make_code
    from repro.data import make_synthetic_batch
    from repro.launch.mesh import make_local_mesh
    from repro.optim import get_optimizer
    from repro.train import Trainer

    pA = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0)
    pB = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=16.0, t2=0.5)
    drift = DriftingSampler([(0, pA), (6, pB)], seed=3)
    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=64)
    policy = AutotunePolicy(interval=3, window=6, min_samples=3,
                            schedules=("gather",), npts=4_000)
    tr = Trainer(cfg, make_code(4, 4, 2, 2), make_local_mesh(4, 1),
                 optimizer=get_optimizer("sgd", 1e-2),
                 straggler_source=drift, autotune=policy)
    rng = np.random.default_rng(0)
    for i in range(16):
        m = tr.step(make_synthetic_batch(rng, cfg, 16, 0))
        assert "modeled_wait_s" in m and "step_time_s" in m
    assert any(e["switched"] for e in tr.autotune_events)
    assert (tr.code.d, tr.code.s, tr.code.m) != (4, 2, 2)
    assert len(tr.telemetry) == 16
    n_arts = len(tr._arts_cache)
    n_jit = len(tr._jitted)
    assert n_arts >= 2
    # force a swap back to the original scheme: both caches must be reused
    tr._apply_plan(_mk_plan(4, 2, 2))
    tr.step(make_synthetic_batch(rng, cfg, 16, 0))
    assert len(tr._arts_cache) == n_arts
    assert len(tr._jitted) == n_jit


def test_trainer_injector_conflicts_with_straggler_mode():
    from repro.configs import get_config
    from repro.core import make_code
    from repro.launch.mesh import make_local_mesh
    from repro.optim import get_optimizer
    from repro.train import Trainer

    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=64)
    sampler = ShiftedExpSampler(
        RuntimeParams(n=4, lambda1=1.0, lambda2=1.0, t1=1.0, t2=1.0))
    with pytest.raises(ValueError, match="injector"):
        Trainer(cfg, make_code(4, 3, 1, 2), make_local_mesh(4, 1),
                optimizer=get_optimizer("sgd", 1e-2),
                straggler_mode="random", injector=sampler)


def test_trainer_autotune_requires_injector():
    from repro.configs import get_config
    from repro.core import make_code
    from repro.launch.mesh import make_local_mesh
    from repro.optim import get_optimizer
    from repro.train import Trainer

    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=64)
    with pytest.raises(ValueError, match="injector"):
        Trainer(cfg, make_code(4, 3, 1, 2), make_local_mesh(4, 1),
                optimizer=get_optimizer("sgd", 1e-2),
                autotune=AutotunePolicy())


def test_trainer_autotune_partial_interop():
    """partial=True survives codec swaps: every cached artifact is built in
    partial mode and the step keeps emitting the error-bound metric."""
    from repro.configs import get_config
    from repro.core import make_code
    from repro.data import make_synthetic_batch
    from repro.launch.mesh import make_local_mesh
    from repro.optim import get_optimizer
    from repro.train import Trainer

    pA = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0)
    pB = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=16.0, t2=0.5)
    drift = DriftingSampler([(0, pA), (4, pB)], seed=6)
    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=64)
    policy = AutotunePolicy(interval=3, window=6, min_samples=3,
                            schedules=("gather",), npts=4_000)
    tr = Trainer(cfg, make_code(4, 4, 2, 2), make_local_mesh(4, 1),
                 optimizer=get_optimizer("sgd", 1e-2),
                 spec=coding.SchemeSpec(partial=True),
                 straggler_source=drift, autotune=policy)
    rng = np.random.default_rng(1)
    for i in range(10):
        m = tr.step(make_synthetic_batch(rng, cfg, 16, 0))
        assert "decode_err_bound" in m
        assert np.isfinite(m["decode_err_bound"])
    assert any(e["switched"] for e in tr.autotune_events)
    assert all(k[3] is True for k in tr._arts_cache)  # partial flag in key
