"""Async pipelined coded step tests.

The parity contract (`repro.train.pipeline`): *fill followed immediately by
drain* reproduces the synchronous coded step bit-for-bit on the same batch —
chained over several batches and straggler patterns, for both encoding
schedules and both codec backends, with the sync executable and the
pipelined triple compiled independently.  The steady state differs from
synchronous SGD only by the documented one-step gradient staleness: a
steady call decodes the *previous* batch's wire (producing exactly the sync
update for that batch) while encoding the current batch at the pre-update
params.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.coding as coding
from repro.configs import get_config
from repro.core import make_code
from repro.data import CodedBatcher, make_synthetic_batch
from repro.launch.mesh import make_local_mesh
from repro.models import api as model_api
from repro.optim import get_optimizer
from repro.train import PipelineDriver, Trainer, pipelining_supported
from repro.train.coded_step import make_coded_train_step

N = 4
CODE = make_code(N, 3, 1, 2)
STRAGGLER_SETS = ([2], [], [0])   # one pattern per chained batch


def _cfg():
    return dataclasses.replace(get_config("logistic-paper"), d_model=64)


def _batches(cfg, count=3, seed=0):
    rng = np.random.default_rng(seed)
    batcher = CodedBatcher(CODE)
    return [jax.tree.map(jnp.asarray,
                         batcher.place(make_synthetic_batch(rng, cfg, 16, 0)))
            for _ in range(count)]


def _tree_max_diff(a, b):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(fa) == len(fb)
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(fa, fb))


def _build(schedule, backend, opt, ms=1, **kw):
    cfg = _cfg()
    mesh = make_local_mesh(N, ms)
    spec = coding.SchemeSpec(schedule=schedule, backend=backend, **kw)
    return cfg, make_coded_train_step(cfg, CODE, mesh, opt, spec=spec)


# -------------------------------------------------------- fill/drain parity
@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("schedule", ["gather", "a2a"])
def test_fill_drain_parity_bitwise(schedule, backend):
    """fill + drain per batch == the synchronous step, bit for bit, chained
    over 3 batches x 3 straggler patterns."""
    opt = get_optimizer("sgd", 1e-2)
    cfg, arts_s = _build(schedule, backend, opt)
    _, arts_p = _build(schedule, backend, opt, pipelined=True)
    batches = _batches(cfg)
    params = model_api.init(jax.random.PRNGKey(42), cfg)
    ps = pp = params
    os_ = op = opt.init(params)
    fn = arts_s.compiled(batches[0])
    drv = PipelineDriver(arts_p, donate=False)
    for batch, strag in zip(batches, STRAGGLER_SETS):
        inp = arts_s.step_inputs(strag)
        args = (inp["W"], inp["mask"], inp["rho"])
        ps, os_, ms = fn(ps, os_, batch, *args)
        pp, op, mp = drv.step(pp, op, batch, *args)
        assert mp is None                       # the call only filled
        pp, op, mp = drv.drain(pp, op)
        assert _tree_max_diff(ps, pp) == 0.0
        assert _tree_max_diff(os_, op) == 0.0
        assert _tree_max_diff(ms, mp) == 0.0


def test_fill_drain_parity_nag_nonfused():
    """The paper's NAG optimizer goes through the generic (non-fused)
    decode + update path — same bitwise contract."""
    opt = get_optimizer("nag", 1e-3)
    cfg, arts_s = _build("gather", "ref", opt)
    _, arts_p = _build("gather", "ref", opt, pipelined=True)
    batches = _batches(cfg, seed=1)
    params = model_api.init(jax.random.PRNGKey(7), cfg)
    ps = pp = params
    os_ = op = opt.init(params)
    fn = arts_s.compiled(batches[0])
    drv = PipelineDriver(arts_p, donate=False)
    for batch, strag in zip(batches, STRAGGLER_SETS):
        inp = arts_s.step_inputs(strag)
        ps, os_, ms = fn(ps, os_, batch, inp["W"], inp["mask"], inp["rho"])
        pp, op, _ = drv.step(pp, op, batch, inp["W"], inp["mask"],
                             inp["rho"])
        pp, op, mp = drv.drain(pp, op)
        assert _tree_max_diff(ps, pp) == 0.0
        assert _tree_max_diff(os_, op) == 0.0
        assert _tree_max_diff(ms, mp) == 0.0


def test_fill_drain_parity_degraded_mesh():
    """(4, 2) mesh: the pipelined decode's collectives run inside a
    shard_map whose model axis stays GSPMD-auto — the parity contract must
    hold there too."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    opt = get_optimizer("sgd", 1e-2)
    cfg, arts_s = _build("gather", "ref", opt, ms=2)
    _, arts_p = _build("gather", "ref", opt, ms=2, pipelined=True)
    batches = _batches(cfg, count=2, seed=2)
    params = model_api.init(jax.random.PRNGKey(3), cfg)
    ps = pp = params
    os_ = op = opt.init(params)
    fn = arts_s.compiled(batches[0])
    drv = PipelineDriver(arts_p, donate=False)
    for batch, strag in zip(batches, STRAGGLER_SETS):
        inp = arts_s.step_inputs(strag)
        ps, os_, ms = fn(ps, os_, batch, inp["W"], inp["mask"], inp["rho"])
        pp, op, _ = drv.step(pp, op, batch, inp["W"], inp["mask"],
                             inp["rho"])
        pp, op, mp = drv.drain(pp, op)
        assert _tree_max_diff(ps, pp) == 0.0
        assert _tree_max_diff(os_, op) == 0.0
        assert _tree_max_diff(ms, mp) == 0.0


# ------------------------------------------------------ steady-state semantics
def test_steady_applies_previous_batch_gradient():
    """fill(b0) then steady(b1, W0) retires exactly the synchronous update
    of b0: the steady call's decode half IS the sync step for the in-flight
    batch, its encode half belongs to the next one."""
    opt = get_optimizer("sgd", 1e-2)
    cfg, arts_s = _build("gather", "ref", opt)
    _, arts_p = _build("gather", "ref", opt, pipelined=True)
    b0, b1 = _batches(cfg, count=2, seed=3)
    params = model_api.init(jax.random.PRNGKey(5), cfg)
    opt0 = opt.init(params)
    inp0 = arts_s.step_inputs([1])
    inp1 = arts_s.step_inputs([])
    cp = arts_p.compiled_pipeline(b0, donate=False)
    wire = cp.fill(params, b0, inp0["mask"], inp0["rho"])
    out = cp.steady(params, opt0, b1, inp0["W"], inp1["mask"], inp1["rho"],
                    *wire)
    fn = arts_s.compiled(b0)
    ps, os_, ms = fn(params, opt0, b0, inp0["W"], inp0["mask"], inp0["rho"])
    assert _tree_max_diff(ps, out[0]) == 0.0
    assert _tree_max_diff(os_, out[1]) == 0.0
    assert _tree_max_diff(ms, out[2]) == 0.0


# --------------------------------------------------------------- validation
def test_pipelined_builder_validation():
    cfg = _cfg()
    mesh = make_local_mesh(N, 1)
    sgd = get_optimizer("sgd", 1e-2)
    with pytest.raises(ValueError, match="encoding"):
        make_coded_train_step(cfg, CODE, mesh, sgd,
                              spec=coding.SchemeSpec(schedule="psum",
                                                     pipelined=True))
    with pytest.raises(ValueError, match="partial"):
        make_coded_train_step(cfg, CODE, mesh, sgd,
                              spec=coding.SchemeSpec(partial=True,
                                                     pipelined=True))


def test_pipelining_supported_predicate():
    mesh = make_local_mesh(N, 1)
    assert not pipelining_supported(mesh, "psum")   # nothing to overlap
    assert pipelining_supported(mesh, "gather")
    assert pipelining_supported(mesh, "a2a")


# ------------------------------------------------------------- trainer loop
def test_trainer_pipelined_staleness_bound():
    """Trainer(pipelined=True) on the paper's logistic workload: the fill
    step reports NaN metrics (no update retired yet), every later metric
    describes the previous batch, and after draining the trajectory lags
    the synchronous run by exactly the documented one step of gradient
    staleness — its final loss is bounded by the sync loss one step back."""
    cfg = _cfg()
    steps = 6
    rng = np.random.default_rng(11)
    fixed = make_synthetic_batch(rng, cfg, 16, 0)

    def run(pipelined):
        tr = Trainer(cfg, CODE, make_local_mesh(N, 1),
                     get_optimizer("sgd", 0.1),
                     spec=coding.SchemeSpec(pipelined=pipelined), seed=0)
        losses = [tr.step(fixed)["loss"] for _ in range(steps)]
        if pipelined:
            assert tr._driver is not None and tr._driver.in_flight
            tr.params, tr.opt_state, m = tr._driver.drain(
                tr.params, tr.opt_state)
            losses.append(float(m["loss"][0]))
        return losses

    sync = run(False)
    pipe = run(True)
    assert np.isnan(pipe[0])                 # fill call retired no update
    assert not any(np.isnan(v) for v in pipe[1:])
    # steady metric t describes batch t-1 -> the sync trajectory, shifted
    np.testing.assert_allclose(pipe[1], sync[0], rtol=1e-6)
    # one-step staleness bound on the drained end state (slack for the
    # stale-gradient update path): no worse than sync one step earlier
    assert pipe[-1] <= sync[-2] * 1.5
    assert pipe[-1] < pipe[1] * 1e-2         # and it genuinely trained


def test_trainer_swap_drains_in_flight_pipeline():
    """_apply_plan on a mid-flight pipelined trainer drains (applies the
    pending gradient) before swapping codecs."""
    from repro.tune import Plan

    cfg = _cfg()
    rng = np.random.default_rng(13)
    fixed = make_synthetic_batch(rng, cfg, 16, 0)
    tr = Trainer(cfg, CODE, make_local_mesh(N, 1),
                 get_optimizer("sgd", 0.1),
                 spec=coding.SchemeSpec(pipelined=True), seed=0)
    for _ in range(3):
        tr.step(fixed)
    assert tr._driver is not None and tr._driver.in_flight
    params_before = jax.tree.map(np.asarray, tr.params)
    plan = Plan(family="uniform", d=3, s=1, m=2, k=N, loads=(3,) * N,
                schedule="gather", predicted_wait_s=0.0,
                predicted_step_s=0.0, predicted_total_s=0.0,
                pipelined=False)
    tr._apply_plan(plan)
    assert tr._driver is None and not tr.spec.pipelined
    # the pending (3rd) gradient was applied by the drain, not dropped
    assert _tree_max_diff(params_before, tr.params) > 0.0
    after = [tr.step(fixed)["loss"] for _ in range(2)]
    assert all(np.isfinite(after))


# ------------------------------------------------- executables & memoization
def test_compiled_memoized_per_signature():
    """StepArtifacts.compiled is memoized per (batch signature, donate):
    the bench's donated steady-state step and any later caller get the
    same compilation, not HLO twins."""
    opt = get_optimizer("sgd", 1e-2)
    cfg, arts = _build("gather", "ref", opt)
    (batch,) = _batches(cfg, count=1)
    fn_d = arts.compiled(batch, donate=True)
    assert arts.compiled(batch, donate=True) is fn_d
    assert arts.compiled(batch, donate=False) is not fn_d   # separate key


def test_compiled_pipeline_memoized():
    opt = get_optimizer("sgd", 1e-2)
    cfg, arts = _build("gather", "ref", opt, pipelined=True)
    (batch,) = _batches(cfg, count=1)
    cp = arts.compiled_pipeline(batch, donate=True)
    assert arts.compiled_pipeline(batch, donate=True) is cp
    assert arts.compiled_pipeline(batch, donate=False) is not cp
    # sync artifacts refuse: the builder did not produce pipeline fns
    _, arts_sync = _build("gather", "ref", opt)
    with pytest.raises(ValueError, match="pipelined=True"):
        arts_sync.compiled_pipeline(batch)


# ----------------------------------------------------- overlap_fraction math
def test_overlap_fraction_endpoints():
    from repro.bench.straggler import overlap_fraction
    assert overlap_fraction(4.0, 6.0, 10.0) == 0.0     # fully sequential
    assert overlap_fraction(4.0, 6.0, 6.0) == 1.0      # perfectly hidden
    assert overlap_fraction(4.0, 6.0, 8.0) == pytest.approx(0.5)
    assert overlap_fraction(0.0, 6.0, 6.0) == 0.0      # nothing to hide
    assert overlap_fraction(4.0, 6.0, 12.0) == 0.0     # clipped below
    assert overlap_fraction(4.0, 6.0, 5.0) == 1.0      # clipped above


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # optional at runtime
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(st.floats(0.0, 1e3), st.floats(0.0, 1e3), st.floats(0.0, 3e3))
    def test_property_overlap_fraction_in_unit_interval(comp, comm, pipe):
        from repro.bench.straggler import overlap_fraction
        v = overlap_fraction(comp, comm, pipe)
        assert 0.0 <= v <= 1.0
