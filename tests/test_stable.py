"""Stable construction family tests (chebyshev / rotation / block composite).

Six layers:
  1. construction units — orthonormal bases, V shapes, validation errors,
     seeded rotation determinism (in-process and across a fresh
     interpreter), block-composite structure (tiled C, block-diagonal P);
  2. certificates — the sigma_min(W_S) identity matches the brute-force
     sup of cond(V_F V_F^T) exactly at small n, the Gershgorin fallback is
     sound or honestly inf, the classic certificate is exact where
     enumerable and inf past its budget, and the decode-error bound
     dominates measured error for every certified construction;
  3. decode feasibility — P @ W hits the exact-reconstruction target
     (B_F . E = I_m) on every sampled responder set of each family;
  4. full-step integration — every family rides the real jitted
     ``make_coded_train_step`` on gather/a2a, a dropped worker still
     decodes to the uncoded psum step's update, and the pipelined
     fill+drain path reproduces the synchronous step bit for bit;
  5. planner/trainer seam — ``rank_plans(stable_options=, max_cond=)``
     admits a candidate iff its certificate clears the ceiling, the gate
     also covers the uniform family, and the trainer materialises the
     ranked construction;
  6. stability-module regressions — the eq. (7) gamma inversion no longer
     vacuously succeeds at x = n, the sampled conditioning path is seeded,
     and the Gaussian V is byte-identical across interpreters.
"""
import dataclasses
import functools
import itertools
import math
import subprocess
import sys

import numpy as np
import pytest

from repro.core import BlockCompositeCode, make_code, make_stable
from repro.core import polynomial
from repro.core.random_code import gaussian_V
from repro.core.stability import (f_n_n1, gamma_upper_bound,
                                  max_condition_number,
                                  sample_straggler_sets,
                                  worst_decode_relative_error)
from repro.core.stable import (STABLE_FAMILIES, block_certified_cond,
                               certified_cond, certified_cond_of,
                               certified_decode_err_bound,
                               certified_max_cond, chebyshev_V,
                               chebyshev_basis, classic_certified_cond,
                               dropped_rows, exhaustive_max_cond,
                               rotation_V, rotation_basis,
                               stable_candidates)

N = 4
SUBPROCESS_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                  "HOME": "/tmp"}


# ------------------------------------------------------------- construction
@pytest.mark.parametrize("n", [3, 8, 16])
def test_bases_are_orthonormal(n):
    for U in (chebyshev_basis(n), rotation_basis(n, seed=0)):
        assert U.shape == (n, n)
        assert np.allclose(U @ U.T, np.eye(n), atol=1e-12)


def test_v_shapes_and_validation():
    assert chebyshev_V(8, 2).shape == (6, 8)
    assert rotation_V(8, 3).shape == (5, 8)
    assert dropped_rows("chebyshev", 8, 2).shape == (2, 8)
    for bad in [(8, 8), (8, -1), (0, 0)]:
        with pytest.raises(ValueError, match="need n >= 1"):
            chebyshev_V(*bad)
    with pytest.raises(ValueError, match="no orthonormal-row basis"):
        dropped_rows("block", 8, 2)
    # V rows + dropped rows partition the orthogonal basis
    V, D = rotation_V(8, 3), dropped_rows("rotation", 8, 3)
    assert np.allclose(np.vstack([V, D]) @ np.vstack([V, D]).T, np.eye(8),
                       atol=1e-12)


def test_rotation_seeded_determinism_in_process():
    a = rotation_basis(12, seed=5)
    b = rotation_basis(12, seed=5)
    assert np.array_equal(a, b)
    c = rotation_basis(12, seed=6)
    assert not np.array_equal(a, c)          # another seed, another rotation
    assert np.allclose(c @ c.T, np.eye(12), atol=1e-12)


def test_rotation_deterministic_across_processes():
    """The planner ranks a rotation code the trainer rebuilds in another
    process: the seeded construction must be byte-identical across
    interpreters (encode coefficients included, not just the basis)."""
    prog = ("from repro.core import make_stable; "
            "c = make_stable('rotation', 8, 4, 2, 2); "
            "print(c.C.tobytes().hex())")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, check=True, env=SUBPROCESS_ENV,
                         cwd="/root/repo")
    here = make_stable("rotation", 8, 4, 2, 2).C.tobytes().hex()
    assert out.stdout.strip() == here


def test_make_stable_validation():
    with pytest.raises(ValueError, match="unknown stable family"):
        make_stable("vandermonde", 8, 3, 1, 2)
    for bad_n0 in (None, 1, 3):              # missing, too small, non-divisor
        with pytest.raises(ValueError, match="tile size"):
            make_stable("block", 8, 2, 1, 1, n0=bad_n0)


def test_block_composite_structure():
    code = make_stable("block", 8, 3, 1, 2, n0=4)
    assert isinstance(code, BlockCompositeCode)
    assert (code.n, code.n0, code.d, code.s, code.m) == (8, 4, 3, 1, 2)
    assert code.blocks == 2 and code.num_subsets == 8
    assert code.kind == "block-poly" and code.seed == 0
    assert code.loads == (3,) * 8 and code.comm_fraction == 0.5
    assert code.slot_mask().all()
    pl = code.placement()
    assert pl.shape == (8, 3)
    # tile t's workers only hold tile t's subset range
    assert (pl[:4] < 4).all() and (pl[4:] >= 4).all()
    assert np.array_equal(pl[4:], code.base.placement() + 4)
    # C repeats per tile; P is block diagonal with zero cross blocks
    assert np.array_equal(code.C[:4], code.C[4:])
    P = code.P
    k0m = code.base.num_subsets * code.m
    assert P.shape == (code.m * 8, 8)
    assert np.array_equal(P[:k0m, :4], code.base.P)
    assert not P[:k0m, 4:].any() and not P[k0m:, :4].any()
    # assignment rows match placement
    for i in range(code.n):
        assert sorted(np.nonzero(code.assignment[i])[0]) == sorted(pl[i])
    assert "BlockCompositeCode" in code.describe()


def test_block_composite_validation():
    base = make_code(4, 2, 1, 1)
    with pytest.raises(ValueError, match=">= 2 tiles"):
        BlockCompositeCode(base=base, blocks=1)


def test_block_decode_past_budget_when_no_tile_oversubscribed():
    """Like the repetition family: one straggler in *each* tile (2 > s=1
    global) still decodes exactly, while 2 in one tile raises."""
    code = make_stable("block", 8, 2, 1, 1, n0=4)
    rng = np.random.default_rng(3)
    G = rng.standard_normal((8, 6))
    F = code.encode(G)
    want = G.sum(0)
    got = code.decode(F, np.setdiff1d(np.arange(8), [1, 6]))
    assert np.allclose(got, want, atol=1e-10)
    with pytest.raises(ValueError):
        code.decode_weights(np.setdiff1d(np.arange(8), [0, 1]))
    # the partial path degrades instead, with a finite certificate
    W, factor = code.partial_decode_weights(np.setdiff1d(np.arange(8),
                                                         [0, 1]))
    assert np.isfinite(factor) and W.shape == (8, 1)


# ------------------------------------------------------------- certificates
@pytest.mark.parametrize("family", ["chebyshev", "rotation"])
@pytest.mark.parametrize("n,s", [(8, 2), (10, 3)])
def test_certificate_matches_bruteforce(family, n, s):
    """The sigma_min(W_S) certificate equals the brute-force sup of
    cond(V_F V_F^T) over every straggler set of size <= s."""
    cert = certified_max_cond(dropped_rows(family, n, s))
    V = (chebyshev_V(n, s) if family == "chebyshev" else rotation_V(n, s))
    brute = exhaustive_max_cond(V, s)
    assert cert == pytest.approx(brute, rel=1e-8)


def test_certified_max_cond_edges():
    assert certified_max_cond(dropped_rows("rotation", 8, 0)) == 1.0
    # Gershgorin fallback (budget forces it): sound (>= exact) or inf
    dropped = dropped_rows("rotation", 16, 2)
    exact = certified_max_cond(dropped)
    fb = certified_max_cond(dropped, budget=1)
    assert math.isinf(fb) or fb >= exact * (1 - 1e-12)


def test_certified_cond_dispatch():
    rot = make_stable("rotation", 8, 4, 2, 2)
    assert certified_cond_of(rot) == certified_cond("rotation", 8, 2)
    blk = make_stable("block", 8, 3, 1, 2, n0=4)
    assert certified_cond_of(blk) == block_certified_cond(4, 3, 1, 2)
    poly = make_code(8, 3, 1, 2)
    assert certified_cond_of(poly) == pytest.approx(
        exhaustive_max_cond(polynomial.vandermonde(8, 1), 1), rel=1e-9)
    assert math.isinf(certified_cond_of(object()))   # no V, no certificate
    with pytest.raises(ValueError, match="block_certified_cond"):
        certified_cond("block", 8, 2)


def test_classic_certificate_exact_small_inf_large():
    got = classic_certified_cond(8, 2, kind="poly")
    want = exhaustive_max_cond(polynomial.vandermonde(8, 2), 2)
    assert got == pytest.approx(want, rel=1e-9)
    # C(64, 3) = 41664 blows the classic 4096-set budget: honestly inf,
    # which is exactly where the gate pushes toward the stable families
    assert math.isinf(classic_certified_cond(64, 3))


@pytest.mark.parametrize("code", [
    make_stable("rotation", 16, 6, 4, 2),
    make_stable("chebyshev", 16, 4, 2, 2),
    make_stable("block", 16, 3, 1, 2, n0=8),
], ids=["rotation", "chebyshev", "block"])
def test_err_bound_dominates_measured(code):
    measured = worst_decode_relative_error(code, trials=24, seed=2)
    bound = certified_decode_err_bound(code)
    assert math.isfinite(bound)
    assert measured <= bound


def test_err_bound_vacuous_when_uncertified():
    code = make_stable("rotation", 8, 4, 2, 2)
    assert math.isinf(certified_decode_err_bound(code, float("inf")))


@pytest.mark.parametrize("family", list(STABLE_FAMILIES))
def test_stable_candidates_contract(family):
    cands = list(stable_candidates(family, 8))
    assert cands
    for d, s, m, n0, cond in cands:
        assert d == s + m and math.isfinite(cond) and cond >= 1.0
        if family == "block":
            assert n0 is not None and 8 % n0 == 0 and d <= n0
        else:
            assert n0 is None
        code = make_stable(family, 8, d, s, m, n0=n0)
        assert (code.n, code.d, code.s, code.m) == (8, d, s, m)
    with pytest.raises(ValueError, match="unknown stable family"):
        list(stable_candidates("nope", 8))


# ------------------------------------------------------- decode feasibility
STABLE_CODES = [make_stable("rotation", N, 3, 1, 2),
                make_stable("chebyshev", N, 3, 1, 2),
                make_stable("block", N, 2, 1, 1, n0=2)]
_IDS = ["rotation", "chebyshev", "block"]


def _sigma_max(code, W):
    """Residual of the exact-reconstruction condition B_F . E = I_m:
    sigma_max(P @ W - 1_k (x) I_m)."""
    target = np.tile(np.eye(code.m), (code.num_subsets, 1))
    return float(np.linalg.norm(code.P @ W - target, 2))


@pytest.mark.parametrize("code", [
    make_stable("rotation", 8, 5, 3, 2),
    make_stable("chebyshev", 8, 3, 1, 2),
    make_stable("block", 8, 3, 1, 2, n0=4),
], ids=_IDS)
def test_decode_feasibility_on_sampled_responder_sets(code):
    """decode_weights satisfies the exact-reconstruction condition on every
    sampled straggler pattern within budget — and the decoded sum matches
    the plain gradient sum."""
    rng = np.random.default_rng(7)
    G = rng.standard_normal((code.num_subsets, 8))
    F = code.encode(G)
    want = G.sum(0)
    for st in sample_straggler_sets(code.n, (0, code.s), 24, seed=13):
        resp = np.setdiff1d(np.arange(code.n), st)
        W = code.decode_weights(resp)
        assert (W[list(st)] == 0.0).all()
        assert _sigma_max(code, W) < 1e-7, st
        assert np.allclose(code.decode(F, resp), want, atol=1e-7)


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


def _run_step(code, schedule, stragglers):
    import jax
    import jax.numpy as jnp

    import repro.coding as coding
    from repro.data import CodedBatcher
    from repro.train.coded_step import make_coded_train_step

    cfg, mesh, opt, batch, params = _linear_setup()
    arts = make_coded_train_step(
        cfg, code, mesh, opt,
        spec=coding.SchemeSpec(schedule=schedule))
    placed = jax.tree.map(jnp.asarray, CodedBatcher(code).place(batch))
    fn = arts.compiled(placed)
    inp = arts.step_inputs(stragglers)
    p2, o2, metrics = fn(params, opt.init(params), placed,
                         inp["W"], inp["mask"], inp["rho"])
    return jax.tree.map(np.asarray, p2), jax.tree.map(np.asarray, o2), metrics


def _max_diff(a, b):
    import jax
    return max(float(np.abs(np.asarray(x, np.float64)
                            - np.asarray(y, np.float64)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("code", STABLE_CODES, ids=_IDS)
@pytest.mark.parametrize("schedule", ["gather", "a2a"])
def test_stable_step_full_response_matches_uncoded(code, schedule):
    ref, _, _ = _run_step(make_code(N, 1, 0, 1), "psum", ())
    got, _, _ = _run_step(code, schedule, ())
    assert _max_diff(got, ref) < 5e-5


@pytest.mark.parametrize("code", STABLE_CODES, ids=_IDS)
@pytest.mark.parametrize("schedule", ["gather", "a2a"])
def test_stable_packed_vs_per_leaf_bitwise(code, schedule):
    """With worker 2 dropped, the packed wire decodes every stable family to
    the uncoded psum step's update — both schedules, params, optimizer
    state and loss alike (``tests/test_coded_train.py``'s tolerances)."""
    a, oa, ma = _run_step(code, schedule, (2,))
    b, ob, mb = _run_step(make_code(N, 1, 0, 1), "psum", ())
    assert _max_diff(a, b) < 5e-4
    assert _max_diff(oa, ob) < 5e-4
    assert abs(float(np.asarray(ma["loss"]).ravel()[0])
               - float(np.asarray(mb["loss"]).ravel()[0])) < 1e-4


@pytest.mark.parametrize("code", STABLE_CODES, ids=_IDS)
def test_stable_pipelined_fill_drain_parity_bitwise(code):
    """fill + drain of the async pipelined step reproduces the synchronous
    coded step bit for bit for every stable family (chained over two
    straggler patterns)."""
    import jax
    import jax.numpy as jnp

    import repro.coding as coding
    from repro.data import CodedBatcher
    from repro.models import api as model_api
    from repro.train import PipelineDriver, pipelining_supported
    from repro.train.coded_step import make_coded_train_step

    cfg, mesh, opt, batch, _ = _linear_setup()
    if not pipelining_supported(mesh, "gather"):
        pytest.skip("pipelining unavailable on this stack")
    spec_s = coding.SchemeSpec(schedule="gather")
    spec_p = coding.SchemeSpec(schedule="gather", pipelined=True)
    arts_s = make_coded_train_step(cfg, code, mesh, opt, spec=spec_s)
    arts_p = make_coded_train_step(cfg, code, mesh, opt, spec=spec_p)
    placed = jax.tree.map(jnp.asarray, CodedBatcher(code).place(batch))
    params = model_api.init(jax.random.PRNGKey(42), cfg)
    ps = pp = params
    os_ = op = opt.init(params)
    fn = arts_s.compiled(placed)
    drv = PipelineDriver(arts_p, donate=False)
    for strag in ((2,), ()):
        inp = arts_s.step_inputs(strag)
        args = (inp["W"], inp["mask"], inp["rho"])
        ps, os_, ms = fn(ps, os_, placed, *args)
        pp, op, mp = drv.step(pp, op, placed, *args)
        assert mp is None
        pp, op, mp = drv.drain(pp, op)
        assert _max_diff(ps, pp) == 0.0
        assert _max_diff(os_, op) == 0.0
        assert _max_diff(ms, mp) == 0.0


# ------------------------------------------------------ planner and trainer
def _fit(n=8):
    from repro.core.runtime_model import RuntimeParams
    from repro.tune.estimator import FitResult

    params = RuntimeParams(n=n, lambda1=2.0, lambda2=1.0, t1=0.01, t2=0.05)
    return FitResult(params=params, speeds=np.ones(n), n_steps=64,
                     n_samples=64)


def test_rank_plans_admits_stable_iff_cond_clears_ceiling():
    from repro.tune.planner import rank_plans

    fit = _fit()
    assert all(p.family not in STABLE_FAMILIES for p in rank_plans(fit))
    # no ceiling: every certified candidate is ranked, with its certificate
    plans = rank_plans(fit, families=(), stable_options=("rotation",))
    allc = {(s + m, s, m): c for _, s, m, _, c in
            stable_candidates("rotation", 8)}
    assert {(p.d, p.s, p.m) for p in plans} == set(allc)
    for p in plans:
        assert p.cond_bound == pytest.approx(allc[(p.d, p.s, p.m)])
        assert "cond<=" in p.describe()
    # tight ceiling: admitted iff the certificate clears it — and the
    # rejection genuinely triggers (some candidate exceeds the ceiling)
    ceiling = 100.0
    gated = rank_plans(fit, families=(), stable_options=("rotation",),
                       max_cond=ceiling)
    admitted = {(p.d, p.s, p.m) for p in gated}
    expected = {k for k, c in allc.items() if c <= ceiling}
    assert admitted == expected and 0 < len(expected) < len(allc)
    # block plans carry their tile size through the scheme key
    blk = rank_plans(fit, families=(), stable_options=("block",))
    assert blk and all(p.n0 is not None and p.scheme_key[-1] == p.n0
                       for p in blk)
    with pytest.raises(ValueError, match="unknown stable family"):
        rank_plans(fit, stable_options=("bogus",))


def test_rank_plans_max_cond_gates_uniform_family():
    from repro.tune.planner import rank_plans

    fit = _fit()
    base = rank_plans(fit)
    assert all(p.cond_bound == 0.0 for p in base)     # gate off: no certs
    gated = rank_plans(fit, max_cond=1e6)
    uni = [p for p in gated if p.family == "uniform"]
    assert uni and all(0 < p.cond_bound <= 1e6 for p in uni)
    # the gate only filters — admitted uniform points are a subset
    assert {(p.d, p.s, p.m) for p in uni} <= \
        {(p.d, p.s, p.m) for p in base if p.family == "uniform"}


def test_trainer_applies_stable_plan():
    from repro.configs import get_config
    from repro.data import make_synthetic_batch
    from repro.launch.mesh import make_local_mesh
    from repro.optim import get_optimizer
    from repro.train import Trainer
    from repro.tune.planner import Plan

    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=64)
    tr = Trainer(cfg, make_code(N, 4, 2, 2), make_local_mesh(N, 1),
                 optimizer=get_optimizer("sgd", 1e-2))

    def mk(family, d, s, m, n0=None, cond=50.0):
        return Plan(family=family, d=d, s=s, m=m, k=N, loads=(d,) * N,
                    schedule="gather", predicted_wait_s=0.0,
                    predicted_step_s=0.0, predicted_total_s=0.0,
                    cond_bound=cond, n0=n0)

    tr._apply_plan(mk("rotation", 3, 1, 2))
    assert tr.code.kind == "rotation" and tr.code.seed == 0
    assert tr._current_plan().family == "rotation"
    m = tr.step(make_synthetic_batch(np.random.default_rng(0), cfg, 16, 0))
    assert np.isfinite(float(np.asarray(m["loss"]).ravel()[0]))
    tr._apply_plan(mk("block", 2, 1, 1, n0=2))
    assert isinstance(tr.code, BlockCompositeCode) and tr.code.n0 == 2
    assert tr._current_plan().n0 == 2
    m = tr.step(make_synthetic_batch(np.random.default_rng(1), cfg, 16, 0))
    assert np.isfinite(float(np.asarray(m["loss"]).ravel()[0]))


def test_admit_code_gate():
    from repro.coding import admit_code

    code = make_stable("rotation", 8, 4, 2, 2)
    assert admit_code(code) is code
    assert admit_code(code, n_data=8, max_cond=1e7) is code
    with pytest.raises(ValueError, match="n_data"):
        admit_code(code, n_data=4)
    # the classic construction at n=32 (certified cond ~6.5e11) fails a
    # ceiling the rotation construction (~1.5e8) clears
    classic = make_code(32, 4, 2, 2)
    with pytest.raises(ValueError, match="admission ceiling"):
        admit_code(classic, max_cond=1e9)
    assert admit_code(make_stable("rotation", 32, 4, 2, 2),
                      max_cond=1e9) is not None


# ----------------------------------------------- stability-module regressions
def test_gamma_upper_bound_endpoint_regression():
    """Eq. (7) inversion at hand-computed n=20, n1=11, kappa=1000: every
    x in [n1, n) has f(x) above the target, so the bound has *no* solution
    — the pre-fix scan returned x = n vacuously (entropy(1.0) = 0 makes
    f(n) = sqrt(n1/n) < target identically once kappa clears the
    threshold)."""
    n, n1, kappa = 20, 11, 1000.0
    target = (math.sqrt(kappa) - 1) / (math.sqrt(kappa) + 1)
    assert all(f_n_n1(n, n1, x) > target for x in range(n1, n))
    assert math.sqrt(n1 / n) < target          # the vacuous x = n "success"
    assert gamma_upper_bound(n, n1, kappa) is None
    # a genuine interior solution survives the fix: smallest x with
    # f(x) <= target at n=400 is 399
    got = gamma_upper_bound(400, 210, 1000.0)
    assert got == 399
    assert f_n_n1(400, 210, 399) <= target < f_n_n1(400, 210, 398)
    # hypothesis failures still return None
    assert gamma_upper_bound(20, 10, 1000.0) is None       # n1/n <= 1/2
    assert gamma_upper_bound(20, 11, 10.0) is None         # kappa <= thresh


def test_max_condition_number_sampled_path():
    """C(n, n3) above max_subsets takes the seeded sampling branch: the
    result is finite, >= 1, reproducible per seed, and bounded above by
    the exhaustive certificate over all <= s straggler sets."""
    V = gaussian_V(24, 4, seed=1)
    assert math.comb(24, 20) > 16
    a = max_condition_number(V, 20, max_subsets=16, seed=5)
    b = max_condition_number(V, 20, max_subsets=16, seed=5)
    assert a == b and math.isfinite(a) and a >= 1.0
    exhaustive = exhaustive_max_cond(V, 4, budget=60_000)
    assert a <= exhaustive * (1 + 1e-9)


def test_gaussian_v_deterministic_across_processes():
    """Theorem-2 codes are rebuilt from (n, s, seed) by the trainer: the
    Gaussian V must be byte-identical across interpreters."""
    prog = ("from repro.core.random_code import gaussian_V; "
            "print(gaussian_V(10, 3, seed=0).tobytes().hex())")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, check=True, env=SUBPROCESS_ENV,
                         cwd="/root/repo")
    assert out.stdout.strip() == gaussian_V(10, 3, seed=0).tobytes().hex()
