"""Elastic membership: churn events, the tracker state machine, the
degradation ladder, deterministic resize/recovery, and the membership-aware
planner extensions.

Compile-time note (1-core CI): the trainer tests run a tiny logistic config
(d_model=32) on a 4-device host mesh; resize tests bounce between n=4 and
n=3 whose artifacts are cached per size, so each size compiles once.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import make_code
from repro.core.hetero import plan_hetero
from repro.data import make_synthetic_batch
from repro.elastic import (ACTIVE, DEPARTED, SUSPECTED, ElasticPolicy,
                           ElasticTrainer, MembershipEvent, MembershipSource,
                           MembershipTracker, MembershipTrace, NoChurn,
                           PoissonChurn, as_churn_source)
from repro.launch.mesh import make_local_mesh
from repro.optim import get_optimizer
from repro.tune import FixedStragglers, StepRecord, rank_plans, score_plan
from repro.tune import step_cost_book, synthetic_fit
from repro.core.runtime_model import RuntimeParams

CFG = dataclasses.replace(get_config("logistic-paper"), d_model=32)
BATCH = 12          # divisible by 4 and 3: both cluster sizes split evenly


def _trainer(code=None, churn=None, policy=None, **kw):
    code = code or make_code(4, 3, 1, 2)
    return ElasticTrainer(CFG, code, make_local_mesh(code.n, 1),
                          get_optimizer("sgd", 1e-2), churn=churn,
                          elastic=policy or ElasticPolicy(), seed=0, **kw)


def _batch(rng):
    return make_synthetic_batch(rng, CFG, BATCH, 0)


# --------------------------------------------------------------- events
def test_membership_event_validation():
    MembershipEvent(step=0, kind="leave", worker=1)
    with pytest.raises(ValueError):
        MembershipEvent(step=0, kind="explode", worker=1)
    with pytest.raises(ValueError):
        MembershipEvent(step=0, kind="join", worker=-1)


def test_membership_trace_replays_in_step_order():
    tr = MembershipTrace([(5, "leave", 2), (1, "join", 0), (5, "join", 3)])
    assert [e.worker for e in tr.events(1)] == [0]
    assert sorted(e.worker for e in tr.events(5)) == [2, 3]
    assert tr.events(2) == ()


def test_poisson_churn_is_seed_deterministic():
    a = PoissonChurn(n=6, leave_rate=0.3, join_rate=0.3, seed=9)
    b = PoissonChurn(n=6, leave_rate=0.3, join_rate=0.3, seed=9)
    evs_a = [e for s in range(40) for e in a.events(s)]
    evs_b = [e for s in range(40) for e in b.events(s)]
    assert evs_a == evs_b
    assert all(e.kind in ("join", "leave", "preempt") for e in evs_a)


def test_as_churn_source_coercions():
    assert isinstance(as_churn_source(None), NoChurn)
    assert as_churn_source(None).events(0) == ()
    src = MembershipTrace([(0, "leave", 1)])
    assert as_churn_source(src) is src
    lst = as_churn_source([(2, "preempt", 0)])
    assert [e.kind for e in lst.events(2)] == ["preempt"]


# -------------------------------------------------------------- tracker
def test_tracker_explicit_leave_and_rejoin():
    t = MembershipTracker(4)
    t.apply(MembershipEvent(step=3, kind="leave", worker=2))
    assert t.departed == (2,)
    assert t.n_alive == 3
    assert t.state_of(2) == DEPARTED
    assert t.departed_for(2, step=7) == 4
    t.apply(MembershipEvent(step=8, kind="join", worker=2))
    assert t.departed == ()
    assert t.state_of(2) == ACTIVE
    assert t.departed_for(2, step=9) == 0


def test_tracker_pending_join_for_unknown_worker():
    t = MembershipTracker(4)
    t.apply(MembershipEvent(step=0, kind="join", worker=7))
    assert t.pending_joins == {7}
    assert t.departed == ()


def test_tracker_heartbeat_escalation():
    t = MembershipTracker(3, suspect_after=2, evict_after=1)
    t.observe([0], step=0)
    assert t.state_of(0) == ACTIVE          # one miss: still active
    t.observe([0], step=1)
    assert t.state_of(0) == SUSPECTED       # suspect_after=2 reached
    t.observe([0], step=2)
    assert t.state_of(0) == DEPARTED        # suspect_after + evict_after
    assert t.departed == (0,)


def test_tracker_backoff_lengthens_grace_after_eviction():
    t = MembershipTracker(2, suspect_after=1, evict_after=1, backoff=2.0)
    t.observe([1], 0)
    t.observe([1], 1)
    assert t.state_of(1) == DEPARTED        # misses 2 >= 1 + 1*2^0
    t.apply(MembershipEvent(step=2, kind="join", worker=1))
    t.observe([1], 3)
    t.observe([1], 4)
    assert t.state_of(1) == SUSPECTED       # threshold now 1 + 1*2^1 = 3
    t.observe([1], 5)
    assert t.state_of(1) == DEPARTED


def test_tracker_response_resets_escalation():
    t = MembershipTracker(2, suspect_after=2, evict_after=2)
    t.observe([0], 0)
    t.observe([0], 1)
    assert t.state_of(0) == SUSPECTED
    t.observe([], 2)                        # a heartbeat arrives
    assert t.state_of(0) == ACTIVE
    t.observe([0], 3)
    assert t.state_of(0) == ACTIVE          # counter restarted from zero


def test_tracker_resize_and_reactivate():
    t = MembershipTracker(4, suspect_after=1, evict_after=1)
    t.observe([3], 0)
    t.observe([3], 1)
    assert t.state_of(3) == DEPARTED
    evictions_before = t._workers[3].evictions
    assert evictions_before == 1
    t.resize(3, step=2)                     # shrink: index 3 drops out
    assert t.n == 3 and t.departed == ()
    t.resize(5, step=3)                     # grow: fresh active workers
    assert t.n == 5 and t.state_of(4) == ACTIVE
    t.apply(MembershipEvent(step=4, kind="leave", worker=0))
    t.reactivate_all(step=5)                # post-repack: everyone active
    assert t.departed == () and t.state_of(0) == ACTIVE


def test_membership_source_merges_departed_into_draws():
    t = MembershipTracker(4)
    t.apply(MembershipEvent(step=0, kind="leave", worker=3))
    src = MembershipSource(t, FixedStragglers([1]))
    code = make_code(4, 3, 1, 2)
    d = src.draw(0, code)
    assert d.stragglers == (1, 3)
    # the inner draw feeds escalation: worker 1 accrues misses
    assert t._workers[1].misses > 0


# --------------------------------------------------- ladder: rungs 1 & 2
def test_rung1_departed_is_forced_straggler():
    tr = _trainer(churn=[(1, "leave", 3)],
                  policy=ElasticPolicy(replan_after=0, resize_after=0))
    rng = np.random.default_rng(0)
    losses = [tr.step(_batch(rng))["loss"] for _ in range(3)]
    assert tr.tracker.departed == (3,)
    assert not tr._degraded                 # code untouched on rung 1
    assert np.isfinite(losses).all()


def test_rung2_replan_then_recover_home():
    tr = _trainer(churn=[(1, "leave", 3), (4, "join", 3)],
                  policy=ElasticPolicy(replan_after=1, resize_after=0))
    home_C = np.asarray(tr.code.C).copy()
    rng = np.random.default_rng(0)
    for _ in range(3):
        tr.step(_batch(rng))
    # after the departure outlives replan_after: zero-load exact re-plan
    assert tr._degraded
    assert tr.code.loads[3] == 0
    # the budget wants hole + original noise (2) but feasibility clamps
    # it: s + m replicas of every subset must fit on the 3 alive workers
    assert tr.code.s == 1
    for _ in range(3):
        tr.step(_batch(rng))
    # the rejoin heals every departure: back on the bitwise home scheme
    assert not tr._degraded
    np.testing.assert_array_equal(np.asarray(tr.code.C), home_C)
    actions = [e["action"] for e in tr.elastic_events]
    assert "replan-degraded" in actions and "recover-home" in actions


def test_partial_failover_past_budget():
    # two departures against s=1, and no n=4 re-plan can absorb them
    # (zero-loading 2 of 4 workers leaves no room for s+m replicas): the
    # trainer must keep taking certified approximate steps, not raise
    tr = _trainer(churn=[(1, "preempt", 2), (1, "preempt", 3)],
                  policy=ElasticPolicy(replan_after=1, resize_after=0))
    rng = np.random.default_rng(0)
    losses = [tr.step(_batch(rng))["loss"] for _ in range(4)]
    assert np.isfinite(losses).all()
    assert any(e["action"] == "partial-failover"
               for e in tr.elastic_events)


# ------------------------------------------------------- ladder: rung 3
def test_resize_preserves_params_bitwise():
    tr = _trainer()
    rng = np.random.default_rng(0)
    tr.step(_batch(rng))
    before = [np.asarray(x).copy() for x in jax.tree.leaves(tr.params)]
    tr.resize(3)
    assert tr.code.n == 3
    after = jax.tree.leaves(tr.params)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, np.asarray(b))
    # resizing back re-instantiates the bitwise-identical home design
    tr.resize(4)
    np.testing.assert_array_equal(np.asarray(tr.code.C),
                                  np.asarray(make_code(4, 3, 1, 2).C))


def test_rung3_resize_down_then_scale_up():
    tr = _trainer(churn=[(1, "leave", 3), (5, "join", 9)],
                  policy=ElasticPolicy(replan_after=0, resize_after=1,
                                       prewarm=(3,)))
    rng = np.random.default_rng(0)
    for _ in range(4):
        tr.step(_batch(rng))
    assert tr.code.n == 3                   # shrunk to n_alive
    assert tr.tracker.n == 3
    for _ in range(3):
        tr.step(_batch(rng))
    assert tr.code.n == 4                   # scale-up on the pending join
    np.testing.assert_array_equal(np.asarray(tr.code.C),
                                  np.asarray(make_code(4, 3, 1, 2).C))
    resizes = [e for e in tr.elastic_events if e["action"] == "resize"]
    assert [e["n"] for e in resizes] == [3, 4]
    assert resizes[0]["warm"]               # prewarm made the 3-mesh warm


def test_resize_infeasible_batch_split_is_skipped():
    # global batch 12 cannot split over n=5, so a 5th pending join must
    # not trigger a resize
    tr = _trainer(churn=[(1, "join", 9)],
                  policy=ElasticPolicy(scale_up=True))
    rng = np.random.default_rng(0)
    for _ in range(3):
        tr.step(_batch(rng))
    assert tr.code.n == 4
    assert tr.tracker.pending_joins == {9}


def test_resize_checkpoints_before_and_after(tmp_path):
    tr = _trainer(checkpoint_dir=str(tmp_path), checkpoint_every=0)
    rng = np.random.default_rng(0)
    tr.step(_batch(rng))
    tr.resize(3)
    assert tr._ckpt.steps()                 # forced snapshots landed


# ------------------------------------- membership-aware planner (no jit)
PARAMS = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=8.0)


def test_rank_plans_departed_offers_zero_load_candidate():
    fit = synthetic_fit(PARAMS, steps=120, seed=0)
    plans = rank_plans(fit, schedules=("gather",), npts=2000, mc_iters=120,
                       departed=(3,))
    hetero = [p for p in plans if p.family == "hetero"]
    assert hetero and all(p.loads[3] == 0 for p in hetero)
    assert all(np.isfinite(p.predicted_total_s) for p in plans)


def test_rank_plans_resize_candidates_are_marked():
    fit = synthetic_fit(PARAMS, steps=120, seed=0)
    plans = rank_plans(fit, schedules=("gather",), npts=2000, mc_iters=120,
                       departed=(3,), resize_options=(3,))
    resized = [p for p in plans if p.resize_to == 3]
    assert resized and all(len(p.loads) == 3 for p in resized)
    assert "resize->3" in resized[0].describe()


def test_rank_plans_classic_path_unchanged_by_elastic_args():
    fit = synthetic_fit(PARAMS, steps=120, seed=0)
    a = rank_plans(fit, schedules=("gather",), npts=2000)
    b = rank_plans(fit, schedules=("gather",), npts=2000, departed=(),
                   resize_options=(), replan_horizon=50)
    assert [(p.d, p.s, p.m, p.predicted_total_s) for p in a] == \
           [(p.d, p.s, p.m, p.predicted_total_s) for p in b]


def test_score_plan_uncoverable_budget_prices_inf():
    fit = synthetic_fit(PARAMS, steps=120, seed=0)
    plans = rank_plans(fit, schedules=("gather",), npts=2000)
    p10 = next(p for p in plans if (p.s, p.family) == (0, "uniform"))
    scored = score_plan(fit, p10, mc_iters=60, departed=(2,))
    assert not np.isfinite(scored.predicted_total_s)


def test_amortized_compile_charges_unmeasured_schemes_only():
    recs = [StepRecord(step=0, d=3, s=1, m=2, k=4, loads=(3,) * 4,
                       schedule="gather",
                       compute_s=np.ones(4), comm_s=np.ones(4),
                       measured_step_s=0.05, compile_s=6.0)]
    book = step_cost_book(recs)
    # the measured scheme is warm in the executable cache: no charge
    assert book.amortized_compile(3, 4, (3,) * 4, "gather") == 0.0
    # an unmeasured scheme pays the pooled compile wall over the horizon
    charge = book.amortized_compile(2, 4, (2,) * 4, "gather",
                                    horizon=60)
    assert charge == pytest.approx(6.0 / 60)


def test_plan_hetero_departed_infeasible_raises():
    with pytest.raises(ValueError):
        plan_hetero([1.0] * 4, s=1, m=2, departed=(2, 3))
