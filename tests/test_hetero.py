"""Heterogeneous-load & partial-recovery scheme family tests.

Four layers:
  1. construction units — load planning, balanced assignment, null-space
     coefficient support, exact decode for every straggler set <= s;
  2. partial recovery — least-squares decode past the budget, the error
     certificate upper-bounding the true L2 gap (deterministic sweep always;
     a hypothesis property test widens it when hypothesis is installed),
     and the exact path refusing over-budget patterns;
  3. full-step integration — the hetero coded step equals uncoded psum
     training on the linear workload for gather and a2a, and the partial
     step completes past s with a finite reported bound;
  4. the straggler-bench contract — the skewed-cluster plan search prefers
     the hetero plan over every uniform triple.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.coding as coding
from repro.coding import make_step_inputs, uncovered_subsets
from repro.configs import get_config
from repro.core import make_code, make_hetero_code
from repro.core.hetero import balanced_assignment, plan_loads
from repro.data import CodedBatcher, make_synthetic_batch
from repro.launch.mesh import make_local_mesh
from repro.models import api as model_api
from repro.optim import get_optimizer
from repro.train.coded_step import make_coded_train_step

N = 4
SPEEDS = (0.5, 1.0, 1.0, 1.5)
RNG = np.random.default_rng(7)


# ------------------------------------------------------------- construction
def test_plan_loads_proportional_and_capped():
    loads = plan_loads(SPEEDS, k=8, r=3)
    assert sum(loads) == 24 and max(loads) <= 8
    assert loads[0] < loads[1] <= loads[3]          # monotone in speed
    # saturating skew: the fast worker's proportional share exceeds k
    loads = plan_loads((0.1, 0.1, 0.1, 10.0), k=8, r=3)
    assert sum(loads) == 24 and max(loads) == 8


def test_balanced_assignment_properties():
    loads = plan_loads(SPEEDS, k=8, r=3)
    A = balanced_assignment(loads, k=8, r=3)
    assert (A.sum(axis=0) == 3).all()               # every subset r holders
    assert tuple(A.sum(axis=1)) == loads            # every worker its load
    with pytest.raises(ValueError):
        balanced_assignment((8, 8, 8, 1), k=8, r=3)  # sum != k*r


def test_hetero_coefficients_respect_placement():
    """C must be exactly zero at padded slots and the P matrix must vanish
    at (subset, worker) pairs outside the assignment."""
    code = make_hetero_code(SPEEDS, s=1, m=2)
    mask = code.slot_mask()
    assert (np.abs(code.C[~mask]) == 0).all()
    m, k = code.m, code.num_subsets
    for j in range(k):
        for i in range(code.n):
            if not code.assignment[i, j]:
                assert np.abs(code.P[j * m:(j + 1) * m, i]).max() < 1e-9


@pytest.mark.parametrize("kind", ["poly", "random"])
def test_hetero_exact_decode_any_straggler_set(kind):
    code = make_hetero_code(SPEEDS, s=1, m=2, kind=kind)
    G = RNG.standard_normal((code.num_subsets, 32))
    F = code.encode(G)
    true = G.sum(0)
    for st in [(), (0,), (1,), (2,), (3,)]:
        resp = [i for i in range(N) if i not in st]
        got = code.decode(F, resp)
        np.testing.assert_allclose(got, true, atol=1e-9)


def test_hetero_zero_load_worker_is_pure_straggler():
    code = make_hetero_code((1e-3, 1.0, 1.0, 1.0), s=1, m=1, kind="random")
    assert code.loads[0] == 0
    G = RNG.standard_normal((code.num_subsets, 16))
    F = code.encode(G)
    assert np.abs(F[0]).max() == 0            # transmits nothing useful
    np.testing.assert_allclose(code.decode(F, [1, 2, 3]), G.sum(0), atol=1e-9)


# -------------------------------------------------------- partial recovery
def _partial_gap_and_bound(code, G, responders):
    F = code.encode(G)
    W, factor = code.partial_decode_weights(responders)
    mask = np.isin(np.arange(code.n), responders).astype(float)
    ghat = np.einsum("nv,nu->vu", F * mask[:, None], W).reshape(-1)
    gap = float(np.linalg.norm(ghat - G.sum(0)))
    return gap, factor * float(np.linalg.norm(G))


@pytest.mark.parametrize("make", [
    lambda: make_code(N, 3, 1, 2),
    lambda: make_hetero_code(SPEEDS, s=1, m=2),
])
def test_certificate_bounds_true_gap_deterministic(make):
    code = make()
    G = RNG.standard_normal((code.num_subsets, 24))
    for resp in ([0], [3], [0, 1], [1, 3], [0, 1, 2], list(range(N))):
        gap, bound = _partial_gap_and_bound(code, G, resp)
        assert gap <= bound + 1e-8, (resp, gap, bound)
        if len(resp) >= N - code.s:
            assert bound < 1e-6          # reduces to the exact decode


def test_partial_inputs_contract():
    code = make_code(N, 4, 2, 2)
    with pytest.raises(ValueError):
        make_step_inputs(code, [0, 1, 2])            # s+1 without partial
    inp = make_step_inputs(code, [0, 1, 2], partial=True)
    assert inp["err_factor"] > 0 and np.isfinite(inp["err_factor"])
    assert inp["rho"].sum() > 0                       # still covers subsets
    # within-budget partial is exact: certificate collapses to ~0
    inp = make_step_inputs(code, [0, 1], partial=True)
    assert inp["err_factor"] < 1e-6
    assert uncovered_subsets(code, [0, 1, 2]) == 0    # d=4: all covered


def test_uncovered_subsets_counted():
    code = make_code(N, 1, 0, 1)                      # uncoded, no overlap
    assert uncovered_subsets(code, [2]) == 1


# ----------------------------------------------------- hypothesis widening
try:
    from hypothesis import given, settings, strategies as st

    @settings(deadline=None, max_examples=25)
    @given(st.data())
    def test_certificate_property_random_erasures(data):
        """Property (both families): for random codes, gradients and erasure
        patterns, the partial-recovery certificate upper-bounds the true L2
        gap of the least-squares decode."""
        hetero = data.draw(st.booleans(), label="hetero")
        s = data.draw(st.integers(0, 2), label="s")
        m = data.draw(st.integers(1, 2), label="m")
        if hetero:
            speeds = data.draw(
                st.lists(st.floats(0.2, 2.0), min_size=N, max_size=N),
                label="speeds")
            if s + m > N:
                return
            code = make_hetero_code(speeds, s=s, m=m,
                                    seed=data.draw(st.integers(0, 5)))
        else:
            d = s + m
            if d > N:
                return
            code = make_code(N, d, s, m)
        l = m * data.draw(st.integers(1, 6), label="groups")
        G = np.asarray(data.draw(st.lists(
            st.floats(-8, 8), min_size=code.num_subsets * l,
            max_size=code.num_subsets * l))).reshape(code.num_subsets, l)
        n_resp = data.draw(st.integers(1, N), label="n_resp")
        resp = sorted(data.draw(st.permutations(range(N)))[:n_resp])
        gap, bound = _partial_gap_and_bound(code, G, resp)
        assert gap <= bound * (1 + 1e-6) + 1e-6
except ImportError:  # hypothesis optional at runtime (declared in [test])
    pass


# ------------------------------------------------------- step integration
@functools.lru_cache(maxsize=None)
def _linear_setup(n_model: int):
    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=64)
    mesh = make_local_mesh(N, n_model)
    opt = get_optimizer("sgd", 1e-2)
    batch = make_synthetic_batch(np.random.default_rng(0), cfg, 16, 0)
    params = model_api.init(jax.random.PRNGKey(0), cfg)
    return cfg, mesh, opt, batch, params


def _run_step(code, schedule, stragglers, n_model=1, partial=False):
    cfg, mesh, opt, batch, params = _linear_setup(n_model)
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
    return max(float(np.abs(x - y).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_hetero_step_equals_uncoded():
    ref, _, _ = _run_step(make_code(N, 1, 0, 1), "psum", ())
    code = make_hetero_code(SPEEDS, s=1, m=2)
    arts = None
    for st_ in [(), (0,), (3,)]:
        got, _, arts = _run_step(code, "gather", st_)
        assert _max_diff(got, ref) < 5e-5, f"stragglers {st_}"
    assert arts.loads == code.loads
    got, _, _ = _run_step(code, "a2a", (1,))
    assert _max_diff(got, ref) < 5e-5


def test_partial_step_completes_past_s_and_reports_bound():
    code = make_code(N, 4, 2, 2)
    got, metrics, arts = _run_step(code, "gather", (0, 1, 3), partial=True)
    assert arts.partial
    bound = float(metrics["decode_err_bound"][0])
    assert np.isfinite(bound) and bound > 0
    assert all(np.isfinite(x).all() for x in jax.tree.leaves(got))
    # within budget the same executable reports a ~zero bound and matches
    # the exact-mode update
    got2, m2, _ = _run_step(code, "gather", (0, 1), partial=True)
    exact, _, _ = _run_step(code, "gather", (0, 1), partial=False)
    assert float(m2["decode_err_bound"][0]) < 1e-3
    assert _max_diff(got2, exact) < 1e-6


def test_partial_false_step_raises_past_s():
    code = make_code(N, 4, 2, 2)
    cfg, mesh, opt, batch, _ = _linear_setup(1)
    arts = make_coded_train_step(cfg, code, mesh, opt,
                                 spec=coding.SchemeSpec())
    with pytest.raises(ValueError):
        arts.step_inputs((0, 1, 3))


# --------------------------------------------------- bench contract (fast)
def test_skewed_plan_search_prefers_hetero():
    """The straggler bench's acceptance criterion, asserted deterministically
    at the model level: on the committed skewed speed vector the best hetero
    plan strictly beats the best uniform triple (same s >= 1 budget)."""
    from benchmarks.bench_straggler_e2e import HCALIB, _search_skewed_plans
    from repro.core.runtime_model import RuntimeParams

    params = RuntimeParams(n=N, **HCALIB)
    (tri_u, wait_u), (hplan, wait_h) = _search_skewed_plans(
        params, sim_iters=2000, seed=21)
    assert wait_h < wait_u, (tri_u, wait_u, hplan, wait_h)
    assert hplan.loads[0] < hplan.loads[-1]       # loads track the skew
    assert min(hplan.s, tri_u[1]) >= 1


def test_hetero_batcher_layout():
    code = make_hetero_code(SPEEDS, s=1, m=2)     # k=8, d_max variable
    batch = {"x": np.arange(16 * 3, dtype=np.float32).reshape(16, 3)}
    placed = CodedBatcher(code).place(batch)
    assert placed["x"].shape == (N, code.d, 2, 3)
    placement, mask = code.placement(), code.slot_mask()
    subsets = batch["x"].reshape(code.num_subsets, 2, 3)
    for i in range(N):
        for slot in range(code.d):
            np.testing.assert_array_equal(
                placed["x"][i, slot], subsets[placement[i, slot]])
            if not mask[i, slot]:                 # padding repeats a held one
                assert placement[i, slot] in placement[i][mask[i]]
