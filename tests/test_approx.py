"""Approximate gradient-coding family tests (FRC + expander).

Five layers:
  1. construction units — block/graph structure, one-hot sparse coefficients,
     validation errors, seeded-expander determinism (in-process and across a
     fresh interpreter);
  2. certificates — the FRC closed-form factor equals the true operator norm
     on every pattern, ``worst_err_bound`` dominates every sampled certified
     factor for both families, exactness exactly when every repetition group
     is alive;
  3. edge cases shared with the exact families — empty responder sets raise
     on the exact path, full responder sets short-circuit to ``err_factor``
     exactly 0.0 without touching the generic least-squares solver, and the
     ``sample_straggler_sets`` trial driver honours its contract;
  4. full-step integration — both families ride the real jitted
     ``make_coded_train_step(partial=True)`` on gather/a2a, the
     ``decode_err_bound`` metric matches the numpy-side certificate, and
     the two schedules agree tightly;
  5. planner/trainer seam — ``rank_plans(approx_options=, max_err=)`` admits
     a candidate iff its bound clears the ceiling, and the trainer
     materialises the ranked construction and flips to partial mode.
"""
import dataclasses
import functools
import itertools
import subprocess
import sys

import numpy as np
import pytest

from repro.core import make_code, make_expander, make_frc, make_hetero_code
from repro.core.approx import (APPROX_FAMILIES, ExpanderCode,
                               FractionalRepetitionCode, approx_candidates,
                               make_approx)
from repro.core.stability import sample_straggler_sets

N = 4
RNG = np.random.default_rng(11)


def _sigma_max(code, W):
    """True certificate: sigma_max(P @ W - 1_k (x) I_m)."""
    k, m = code.num_subsets, code.m
    target = np.tile(np.eye(m), (k, 1))
    return float(np.linalg.norm(code.P @ W - target, 2))


def _all_responder_sets(n):
    for r in range(n + 1):
        yield from itertools.combinations(range(n), r)


# ------------------------------------------------------------- construction
def test_frc_structure():
    code = make_frc(8, s=1, m=2)
    assert (code.d, code.num_subsets, code.n_blocks) == (4, 8, 2)
    assert code.replication == 2 and code.num_groups == 4
    assert code.loads == (4,) * 8 and code.comm_fraction == 0.5
    assert code.placement().shape == (8, 4) and code.slot_mask().all()
    # every (block, phase) cell has exactly s+1 clones with identical rows
    for g in range(code.num_groups):
        members = np.nonzero(code.groups == g)[0]
        assert len(members) == 2
        assert (code.P[:, members[0]] == code.P[:, members[1]]).all()
    assert "FractionalRepetitionCode" in code.describe()


def test_approx_coefficients_are_onehot_sparse():
    """The tentpole's encode claim: exactly one 1.0 per placement slot —
    no polynomial solve, no dense coefficient mass."""
    for code in (make_frc(8, 1, 2), make_expander(8, 2, 2)):
        C = code.C
        assert C.shape == (code.n, code.d, code.m)
        nz = (C != 0.0).sum(axis=2)
        assert (nz == 1).all()
        assert (C[C != 0.0] == 1.0).all()
        # column support of P matches the assignment exactly
        for i in range(code.n):
            held = np.nonzero(np.abs(code.P[:, i]).reshape(
                code.num_subsets, code.m).sum(axis=1))[0]
            assert sorted(held) == sorted(code.placement()[i])


def test_frc_validation_errors():
    with pytest.raises(ValueError, match="divisible"):
        make_frc(6, s=1, m=2)                    # 6 % (2*2) != 0
    with pytest.raises(ValueError):
        FractionalRepetitionCode(n=4, s=-1, m=1)
    with pytest.raises(ValueError, match=">= 0"):
        make_frc(4, 1, 1).worst_err_bound(-1)


def test_expander_structure_regular():
    code = make_expander(8, c=2, m=2)
    assert (code.d, code.num_subsets, code.phase_size) == (4, 8, 4)
    assert code.s == 0 and code.loads == (4,) * 8
    P = code.placement()
    # every worker holds d distinct subsets
    assert all(len(set(P[i])) == code.d for i in range(code.n))
    # every (subset, phase) cell has exactly c same-phase holders
    for u in range(code.m):
        phase_workers = [i for i in range(code.n) if i % code.m == u]
        counts = np.zeros(code.num_subsets, dtype=int)
        for i in phase_workers:
            counts[P[i]] += 1
        assert (counts == code.c).all()
    assert len(code.spectral_gaps) == code.m
    assert all(g >= 0 for g in code.spectral_gaps)
    assert "ExpanderCode" in code.describe()


def test_expander_validation_errors():
    with pytest.raises(ValueError, match="divisible"):
        make_expander(5, c=1, m=2)
    with pytest.raises(ValueError, match="exceeds phase size"):
        make_expander(4, c=3, m=2)
    with pytest.raises(ValueError, match=">= 0"):
        make_expander(4, 2, 1).worst_err_bound(-1)


def test_expander_deterministic_in_process():
    a = make_expander(8, c=2, m=2, seed=3)
    b = make_expander(8, c=2, m=2, seed=3)
    assert (a.placement() == b.placement()).all()
    assert (a.P == b.P).all()
    # a different seed is allowed to (and here does) pick another graph
    c = make_expander(8, c=2, m=2, seed=4)
    assert c.placement().shape == a.placement().shape


def test_expander_deterministic_across_processes():
    """The planner ranks a graph the trainer rebuilds in another process:
    the seeded construction must be byte-identical across interpreters."""
    prog = ("import numpy as np; from repro.core import make_expander; "
            "print(make_expander(8, c=2, m=2, seed=0).placement().tobytes()"
            ".hex())")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, check=True,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/tmp"}, cwd="/root/repo")
    here = make_expander(8, c=2, m=2, seed=0).placement().tobytes().hex()
    assert out.stdout.strip() == here


# ------------------------------------------------------------- certificates
@pytest.mark.parametrize("code", [
    make_frc(4, 1, 1), make_frc(4, 0, 2), make_frc(4, 3, 1),
    make_frc(6, 2, 1), make_frc(6, 0, 3),
], ids=lambda c: f"n{c.n}s{c.s}m{c.m}")
def test_frc_certificate_equals_operator_norm(code):
    """The closed-form FRC factor is the exact sigma_max of the selection
    decode's residual — checked on every responder set."""
    for resp in _all_responder_sets(code.n):
        W, factor = code.partial_decode_weights(resp)
        assert abs(factor - _sigma_max(code, W)) < 1e-9, resp


@pytest.mark.parametrize("code", [
    make_frc(8, 1, 2), make_frc(8, 3, 1),
    make_expander(8, 2, 2), make_expander(8, 2, 1), make_expander(6, 3, 1),
], ids=lambda c: type(c).__name__ + f"n{c.n}m{c.m}")
def test_worst_err_bound_dominates_certificates(code):
    """worst_err_bound(t) upper-bounds the certified factor of every
    sampled t-straggler pattern — and is monotone in t."""
    bounds = [code.worst_err_bound(t) for t in range(code.n)]
    assert bounds[0] == 0.0
    assert all(b2 >= b1 - 1e-12 for b1, b2 in zip(bounds, bounds[1:]))
    for t in range(1, code.n):
        for st in sample_straggler_sets(code.n, t, 12, seed=t):
            resp = np.setdiff1d(np.arange(code.n), st)
            _, factor = code.partial_decode_weights(resp)
            assert factor <= bounds[t] + 1e-9, (t, st, factor, bounds[t])


def test_frc_exact_iff_groups_alive():
    """Decode is bitwise-exact exactly when every repetition group has a
    live clone — including patterns far beyond s."""
    code = make_frc(8, s=1, m=1)
    G = RNG.integers(-8, 8, (code.num_subsets, 6)).astype(np.float64)
    F = code.encode(G)
    want = G.sum(0)
    for resp in [(0, 2, 4, 6), (1, 3, 5, 7), (0, 3, 4, 7)]:  # one per group
        got = code.decode(F, resp, partial=True)
        assert np.array_equal(got, want)                      # bitwise
        assert code.partial_decode_weights(resp)[1] == 0.0
    # kill group 0 entirely (workers 0 and 1): certified, not exact
    W, factor = code.partial_decode_weights((2, 3, 4, 5, 6, 7))
    assert factor == pytest.approx(np.sqrt(code.d))
    got = code.decode(F, (2, 3, 4, 5, 6, 7), partial=True)
    assert np.array_equal(got, G[code.d:].sum(0))  # the live blocks, exactly


def test_decode_weights_refuses_unrecoverable_patterns():
    code = make_frc(4, 1, 1)
    with pytest.raises(ValueError, match="no responder"):
        code.decode_weights((2, 3))              # group {0,1} fully dark
    exp = make_expander(4, 2, 1)
    with pytest.raises(ValueError, match="full response"):
        exp.decode_weights((0, 1, 2))            # expander: s = 0


# ---------------------------------------------------------------- edge cases
@pytest.mark.parametrize("code", [
    make_code(N, 3, 1, 2), make_hetero_code((0.5, 1.0, 1.0, 1.5), s=1, m=2),
    make_frc(N, 1, 1), make_expander(N, 2, 1),
], ids=["uniform", "hetero", "frc", "expander"])
def test_empty_responders_raise_on_exact_path(code):
    with pytest.raises(ValueError):
        code.decode_weights(())


@pytest.mark.parametrize("code", [
    make_code(N, 3, 1, 2), make_hetero_code((0.5, 1.0, 1.0, 1.5), s=1, m=2),
    make_frc(N, 1, 1), make_expander(N, 2, 1),
], ids=["uniform", "hetero", "frc", "expander"])
def test_full_responders_short_circuit_no_lstsq(code, monkeypatch):
    """responders == all workers must return err_factor exactly 0.0 and
    never enter the generic least-squares certificate solve."""
    def _boom(*a, **k):
        raise AssertionError("generic partial solve must not run")
    monkeypatch.setattr("repro.core.hetero.partial_decode_weights", _boom)
    monkeypatch.setattr("repro.core.approx._lstsq_decode_weights", _boom)
    W, factor = code.partial_decode_weights(range(code.n))
    assert factor == 0.0
    # bool-mask spelling of "everyone responded" takes the same path
    W2, factor2 = code.partial_decode_weights(np.ones(code.n, dtype=bool))
    assert factor2 == 0.0 and np.array_equal(W, W2)


def test_sample_straggler_sets_contract():
    sets = list(sample_straggler_sets(6, 2, 40, seed=1))
    assert all(len(s) == 2 and s == tuple(sorted(s)) for s in sets)
    assert len(set(sets)) == len(sets)               # deduped by default
    sets = list(sample_straggler_sets(6, 2, 40, seed=1, dedupe=False))
    assert len(sets) == 40
    # inclusive (lo, hi) size range, including the empty pattern
    sizes = {len(s) for s in
             sample_straggler_sets(6, (0, 3), 200, seed=2, dedupe=False)}
    assert sizes == {0, 1, 2, 3}
    with pytest.raises(ValueError, match="outside"):
        list(sample_straggler_sets(4, 5, 1))
    with pytest.raises(ValueError, match="n >= 1"):
        list(sample_straggler_sets(0, 0, 1))


def test_make_approx_and_candidates():
    with pytest.raises(ValueError, match="unknown approx family"):
        make_approx("polynomial", 8, 2, 1)
    with pytest.raises(ValueError, match="unknown approx family"):
        list(approx_candidates("nope", 8))
    for fam in APPROX_FAMILIES:
        for rep, m, code in approx_candidates(fam, 8):
            assert code.n == 8 and code.d == m * rep
            assert code.num_subsets == 8          # default d keeps k = n
            rebuilt = make_approx(fam, 8, code.d // code.m, code.m)
            assert (rebuilt.placement() == code.placement()).all()


# ------------------------------------------------------- step integration
@functools.lru_cache(maxsize=None)
def _linear_setup():
    import jax

    from repro.configs import get_config
    from repro.data import make_synthetic_batch
    from repro.launch.mesh import make_local_mesh
    from repro.models import api as model_api
    from repro.optim import get_optimizer

    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=64)
    mesh = make_local_mesh(N, 1)
    opt = get_optimizer("sgd", 1e-2)
    batch = make_synthetic_batch(np.random.default_rng(0), cfg, 16, 0)
    params = model_api.init(jax.random.PRNGKey(0), cfg)
    return cfg, mesh, opt, batch, params


def _run_step(code, schedule, stragglers, partial=False):
    import jax
    import jax.numpy as jnp

    import repro.coding as coding
    from repro.data import CodedBatcher
    from repro.train.coded_step import make_coded_train_step

    cfg, mesh, opt, batch, params = _linear_setup()
    arts = make_coded_train_step(
        cfg, code, mesh, opt,
        spec=coding.SchemeSpec(schedule=schedule, partial=partial))
    placed = jax.tree.map(jnp.asarray, CodedBatcher(code).place(batch))
    fn = arts.compiled(placed)
    inp = arts.step_inputs(stragglers)
    args = [inp["W"], inp["mask"], inp["rho"]]
    if partial:
        args.append(inp["err_factor"])
    p2, _, metrics = fn(params, opt.init(params), placed, *args)
    return jax.tree.map(np.asarray, p2), metrics, arts


def _max_diff(a, b):
    import jax
    return max(float(np.abs(x - y).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


APPROX_CODES = [make_frc(N, 1, 1), make_frc(N, 0, 2),
                make_expander(N, 2, 1), make_expander(N, 1, 2)]
_IDS = ["frc-r2-m1", "frc-r1-m2", "exp-c2-m1", "exp-c1-m2"]


@pytest.mark.parametrize("code", APPROX_CODES, ids=_IDS)
@pytest.mark.parametrize("schedule", ["gather", "a2a"])
def test_approx_step_full_response_matches_uncoded(code, schedule):
    """Full response through the real jitted partial step: zero reported
    bound and the same update as uncoded psum training."""
    ref, _, _ = _run_step(make_code(N, 1, 0, 1), "psum", ())
    got, metrics, arts = _run_step(code, schedule, (), partial=True)
    assert arts.partial
    assert float(metrics["decode_err_bound"][0]) < 1e-9
    assert _max_diff(got, ref) < 5e-5


@pytest.mark.parametrize("code", APPROX_CODES, ids=_IDS)
@pytest.mark.parametrize("schedule", ["gather", "a2a"])
def test_approx_step_completes_past_s(code, schedule):
    """Any straggler pattern past the structural budget still yields finite
    parameters plus a finite certified bound (expander: any straggler at
    all — its exact budget is zero), on both schedules."""
    stragglers = tuple(range(code.s + 1))
    got, metrics, _ = _run_step(code, schedule, stragglers, partial=True)
    import jax
    bound = float(metrics["decode_err_bound"][0])
    assert np.isfinite(bound)
    assert all(np.isfinite(x).all() for x in jax.tree.leaves(got))


@pytest.mark.parametrize("code", [APPROX_CODES[0], APPROX_CODES[2]],
                         ids=["frc", "expander"])
def test_decode_err_bound_metric_matches_numpy(code):
    """The in-step metric is err_factor * sqrt(sum_{covered j} ||g_j||^2):
    recompute both factors host-side from the same batch and params."""
    import jax
    import jax.numpy as jnp

    from repro.coding import make_step_inputs, uncovered_subsets
    from repro.models import api as model_api

    cfg, _, _, batch, params = _linear_setup()
    stragglers = (0, 1)
    _, metrics, _ = _run_step(code, "gather", stragglers, partial=True)
    got = float(metrics["decode_err_bound"][0])

    inp = make_step_inputs(code, stragglers, partial=True)
    loss = model_api.make_loss(cfg)
    k = code.num_subsets
    b = batch["x"].shape[0] // k
    subsets = {name: v.reshape(k, b, *v.shape[1:]) for name, v in
               batch.items()}
    live = np.setdiff1d(np.arange(code.n), stragglers)
    covered = set(int(j) for i in live for j in code.placement()[i])
    assert len(covered) == k - uncovered_subsets(code, stragglers)
    gss = 0.0
    for j in sorted(covered):
        g = jax.grad(loss)(params, {n: jnp.asarray(v[j])
                                    for n, v in subsets.items()})
        gss += sum(float(np.sum(np.square(x)))
                   for x in jax.tree.leaves(g))
    want = float(inp["err_factor"]) * np.sqrt(gss)
    assert got == pytest.approx(want, rel=1e-4, abs=1e-6)


@pytest.mark.parametrize("code", [APPROX_CODES[0], APPROX_CODES[3]],
                         ids=["frc", "expander"])
def test_packed_vs_per_leaf_parity(code):
    """The gather and a2a schedules decode the approx families to the same
    update and certificate (same straggler pattern)."""
    a, ma, _ = _run_step(code, "gather", (0,), partial=True)
    b, mb, _ = _run_step(code, "a2a", (0,), partial=True)
    assert _max_diff(a, b) < 1e-6
    assert float(ma["decode_err_bound"][0]) == pytest.approx(
        float(mb["decode_err_bound"][0]), rel=1e-5)


def test_partial_false_raises_past_structural_budget():
    """Without partial mode the approx families refuse over-budget patterns
    exactly like the exact families do."""
    from repro.coding import make_step_inputs
    with pytest.raises(ValueError, match="partial=True"):
        make_step_inputs(make_expander(N, 2, 1), (0,))     # s = 0
    with pytest.raises(ValueError, match="partial=True"):
        make_step_inputs(make_frc(N, 1, 1), (0, 1))        # s = 1


# ------------------------------------------------------ planner and trainer
def _fit(n=8):
    from repro.core.runtime_model import RuntimeParams
    from repro.tune.estimator import FitResult

    params = RuntimeParams(n=n, lambda1=2.0, lambda2=1.0, t1=0.01, t2=0.05)
    return FitResult(params=params, speeds=np.ones(n), n_steps=64,
                     n_samples=64)


def test_rank_plans_admits_approx_iff_bound_clears_ceiling():
    from repro.tune.planner import rank_plans, score_plan

    fit = _fit()
    assert all(p.family not in APPROX_FAMILIES for p in rank_plans(fit))
    # a negative ceiling excludes every approx candidate (bounds are >= 0)
    plans = rank_plans(fit, approx_options=APPROX_FAMILIES, max_err=-1.0)
    assert all(p.family not in APPROX_FAMILIES for p in plans)
    # a zero (or None) ceiling admits exactly the zero-bound operating points
    for ceiling in (0.0, None):
        plans = rank_plans(fit, approx_options=APPROX_FAMILIES,
                           max_err=ceiling)
        ap = [p for p in plans if p.family in APPROX_FAMILIES]
        assert ap and all(p.err_bound == 0.0 for p in ap)
    # a generous ceiling admits bounded plans — every one below it, the
    # drop budget maximal for its construction, the bound recomputable
    plans = rank_plans(fit, approx_options=APPROX_FAMILIES, max_err=1.5)
    ap = [p for p in plans if p.family in APPROX_FAMILIES]
    assert ap and any(p.err_bound > 0 for p in ap)
    for p in ap:
        assert p.err_bound <= 1.5 + 1e-12
        code = make_approx(p.family, 8, p.d // p.m, p.m)
        assert code.worst_err_bound(p.s) == pytest.approx(p.err_bound)
        if p.s + 1 <= code.n:          # the next drop budget must overshoot
            assert code.worst_err_bound(p.s + 1) > 1.5
        assert "err<=" in p.describe()
        assert np.isfinite(score_plan(fit, p).predicted_total_s)
    with pytest.raises(ValueError, match="unknown approx family"):
        rank_plans(fit, approx_options=("bogus",), max_err=1.0)


def test_rank_plans_approx_respects_departed_workers():
    from repro.tune.planner import rank_plans

    plans = rank_plans(_fit(), approx_options=("frc",), max_err=3.0,
                       departed=(3,), mc_iters=100)
    ap = [p for p in plans if p.family in APPROX_FAMILIES]
    assert ap and all(p.s >= 1 for p in ap)   # must absorb the departure


def test_trainer_applies_approx_plan_and_flips_partial():
    from repro.configs import get_config
    from repro.data import make_synthetic_batch
    from repro.launch.mesh import make_local_mesh
    from repro.optim import get_optimizer
    from repro.train import Trainer
    from repro.tune.planner import Plan

    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=64)
    tr = Trainer(cfg, make_code(N, 4, 2, 2), make_local_mesh(N, 1),
                 optimizer=get_optimizer("sgd", 1e-2))
    assert not tr.spec.partial
    plan = Plan(family="frc", d=2, s=3, m=1, k=N, loads=(2,) * N,
                schedule="gather", predicted_wait_s=0.0,
                predicted_step_s=0.0, predicted_total_s=0.0,
                err_bound=make_frc(N, 1, 1).worst_err_bound(3))
    tr._apply_plan(plan)
    assert isinstance(tr.code, FractionalRepetitionCode)
    assert tr.spec.partial and not tr.spec.pipelined
    assert tr._current_plan().family == "frc"
    # the swapped-in trainer takes a real step past the structural budget
    m = tr.step(make_synthetic_batch(np.random.default_rng(0), cfg, 16, 0))
    assert np.isfinite(float(np.asarray(m["loss"]).ravel()[0]))
    # expander plans materialise the seeded graph that was ranked
    plan2 = Plan(family="expander", d=2, s=1, m=1, k=N, loads=(2,) * N,
                 schedule="gather", predicted_wait_s=0.0,
                 predicted_step_s=0.0, predicted_total_s=0.0)
    tr._apply_plan(plan2)
    assert isinstance(tr.code, ExpanderCode) and tr.code.seed == 0
    assert tr._current_plan().family == "expander"
