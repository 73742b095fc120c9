"""Coded inference serving: decode exactness, hedging bit-parity, partial
SLO certificates, the request-queue engine, the arrival-process planner and
the serving auto-tuner loop.

The central contracts under test:

  1. blockwise decode exactness — the forward decode equals the direct
     (uncoded) batched forward for every schedule, any <=s straggler set;
  2. the hedge — with the straggler pattern's W, the decoded bits are
     IDENTICAL whether the straggler replicas' payloads are real, zeroed
     or garbage, for every C(n, s) straggler subset: waiting for the
     fastest n-s replicas returns the same bits as waiting for all n;
  3. partial recovery — past-s serves carry a monotone error certificate
     and exact failed-request marking.
"""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.coding as coding
from repro.configs import get_config
from repro.core import make_code
from repro.core.runtime_model import RuntimeParams
from repro.data import CodedBatcher
from repro.launch.mesh import make_local_mesh
from repro.models import api as model_api
from repro.serving import (CodedServer, RequestBatcher, Request, ServeSLO,
                           failed_request_rows, make_coded_forward)
from repro.tune import (PoissonArrivals, ServingAutotuner, ServingPolicy,
                        ShiftedExpSampler, rank_serving_plans, simulate_queue,
                        synthetic_fit)

CODE = make_code(4, 3, 1, 2)


def _linear_cfg():
    return dataclasses.replace(get_config("logistic-paper"), d_model=64)


def _rand_params(cfg, seed=7):
    """Non-trivial linear weights (init is all-zero: outputs would be
    vacuously exact)."""
    beta = np.random.default_rng(seed).standard_normal(cfg.d_model)
    return {"beta": jnp.asarray(beta, jnp.float32)}


def _setup(code=CODE, b=2, spec=None, model=1):
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, model)
    params = _rand_params(cfg)
    arts = make_coded_forward(cfg, code, mesh, spec=spec, batch_per_subset=b)
    B = code.num_subsets * b
    rng = np.random.default_rng(0)
    batch = {"x": rng.standard_normal((B, cfg.d_model)).astype(np.float32)}
    placed = jax.tree.map(jnp.asarray, CodedBatcher(code).place(batch))
    direct = np.asarray(model_api.make_forward(cfg)(
        params, {"x": jnp.asarray(batch["x"])}))
    return cfg, mesh, params, arts, batch, placed, direct


# ----------------------------------------------------- decode exactness
@pytest.mark.parametrize("schedule", ["gather", "a2a", "psum"])
@pytest.mark.parametrize("stragglers", [(), (2,), (0,)])
def test_forward_decode_matches_direct(schedule, stragglers):
    """Coded serve == direct uncoded forward, per schedule, per pattern."""
    spec = coding.SchemeSpec(schedule=schedule)
    _, _, params, arts, _, placed, direct = _setup(spec=spec)
    inp = arts.step_inputs(stragglers)
    fn = arts.compiled(placed)
    out = np.asarray(fn(params, placed, inp["W"], inp["mask"], inp["rho"]))
    np.testing.assert_allclose(out, direct, rtol=1e-5, atol=1e-5)


def test_forward_decode_lm_family():
    """The LM path (prefill last-token logits) decodes exactly too, on a
    (4 data x 2 model) mesh."""
    cfg = get_config("qwen3-1.7b").reduced()
    mesh = make_local_mesh(4, 2)
    with jax.sharding.set_mesh(mesh):
        params = model_api.init(jax.random.PRNGKey(0), cfg)
    code = make_code(4, 2, 1, 1)
    b, seq = 1, 16
    arts = make_coded_forward(cfg, code, mesh, batch_per_subset=b,
                              seq_len=seq)
    B = code.num_subsets * b
    toks = np.random.default_rng(3).integers(0, cfg.vocab, (B, seq),
                                             dtype=np.int32)
    placed = jax.tree.map(jnp.asarray, CodedBatcher(code).place(
        {"tokens": toks}))
    inp = arts.step_inputs([3])
    out = np.asarray(arts.compiled(placed)(
        params, placed, inp["W"], inp["mask"], inp["rho"]))
    with jax.sharding.set_mesh(mesh):
        direct = np.asarray(model_api.make_forward(cfg)(
            params, {"tokens": jnp.asarray(toks)}))
    assert out.shape == direct.shape == (B, cfg.vocab)
    np.testing.assert_allclose(out, direct, rtol=2e-2, atol=2e-2)


# ------------------------------------------------------------- the hedge
def test_hedged_decode_bitwise_independent_of_straggler_payloads():
    """For EVERY straggler subset of size s: the decode under that
    pattern's W is bit-identical whether the straggler's payload is real,
    zero, or garbage — so decoding from the fastest n-s replicas equals
    waiting for all n, bit for bit (the acceptance criterion)."""
    _, _, params, arts, _, placed, _ = _setup()
    fn = arts.compiled(placed)
    n, s = CODE.n, CODE.s
    for stragglers in itertools.combinations(range(n), s):
        inp = arts.step_inputs(stragglers)
        full = np.asarray(fn(params, placed, inp["W"], inp["mask"],
                             inp["rho"]))
        # corrupt the straggler replicas' entire batch shard (finite
        # garbage — the wire mask zeroes it exactly) and also zero it
        # (nothing transmitted): neither may change a single output bit
        for junk in (999.0, 0.0):
            bad = placed
            for i in stragglers:
                bad = jax.tree.map(lambda x: x.at[i].set(junk), bad)
            hedged = np.asarray(fn(params, bad, inp["W"], inp["mask"],
                                   inp["rho"]))
            np.testing.assert_array_equal(
                hedged, full, err_msg=f"stragglers={stragglers}: straggler "
                f"payload leaked into the decoded output")


def test_hedged_decode_still_exact_per_pattern():
    """Each hedged pattern's decode also matches the direct forward (the
    reconstruction is exact, not merely payload-independent)."""
    _, _, params, arts, _, placed, direct = _setup()
    fn = arts.compiled(placed)
    for stragglers in itertools.combinations(range(CODE.n), CODE.s):
        inp = arts.step_inputs(stragglers)
        out = np.asarray(fn(params, placed, inp["W"], inp["mask"],
                            inp["rho"]))
        np.testing.assert_allclose(out, direct, rtol=1e-5, atol=1e-5,
                                   err_msg=f"stragglers={stragglers}")


# ------------------------------------------------------- partial recovery
def test_partial_err_bound_monotone_on_nested_straggler_sets():
    """The certified decode-error bound is monotone along a nested chain
    of straggler sets (more failures can only certify worse)."""
    code = make_code(4, 2, 1, 1)
    spec = coding.SchemeSpec(partial=True)
    _, _, params, arts, _, placed, _ = _setup(code=code, spec=spec)
    fn = arts.compiled(placed)
    bounds = []
    for stragglers in [(), (0,), (0, 1), (0, 1, 2)]:
        inp = arts.step_inputs(stragglers)
        _, bound = fn(params, placed, inp["W"], inp["mask"], inp["rho"],
                      inp["err_factor"])
        bounds.append(float(bound))
    # within the design s the lstsq is exact: the certificate collapses to
    # numerical noise
    assert bounds[0] < 1e-6 and bounds[1] < 1e-6
    for lo, hi in zip(bounds, bounds[1:]):
        assert hi >= lo - 1e-6, f"bound not monotone: {bounds}"
    assert bounds[-1] > 1e-3


def test_failed_request_rows_marks_uncovered_subsets():
    """Subsets whose every holder straggled map to exactly their request
    rows; covered subsets never appear."""
    code = make_code(4, 2, 1, 1)    # worker i holds subsets {i, i+1 mod 4}
    b = 3
    assert failed_request_rows(code, [], b) == []
    assert failed_request_rows(code, [2], b) == []
    # dropping workers 0 and 1 uncovers subset 1 (holders {0, 1})
    assert failed_request_rows(code, [0, 1], b) == [3, 4, 5]


def test_partial_serve_respects_slo():
    """CodedServer surfaces the certificate + SLO verdict per batch."""
    code = make_code(4, 2, 1, 1)
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    params = _rand_params(cfg)
    srv = CodedServer(cfg, code, mesh, params,
                      spec=coding.SchemeSpec(partial=True),
                      batch_per_subset=2, slo=ServeSLO(max_decode_err=1e-6))
    B = code.num_subsets * 2
    batch = {"x": np.random.default_rng(0).standard_normal(
        (B, cfg.d_model)).astype(np.float32)}
    ok = srv.serve_batch(batch, stragglers=[3])
    assert ok.within_slo and ok.err_bound < 1e-6 and ok.failed_rows == ()
    degraded = srv.serve_batch(batch, stragglers=[0, 1])
    assert degraded.failed_rows == (2, 3)
    assert not degraded.within_slo     # the tight SLO rejects the bound
    assert degraded.err_bound > 0.0


# ----------------------------------------------------- engine + batcher
def test_request_batcher_pads_and_preserves_order():
    rb = RequestBatcher(4)
    for i in range(6):
        rb.add(Request(i, {"x": np.full((3,), float(i), np.float32)}))
    reqs, batch, valid = rb.next_batch()
    assert [r.req_id for r in reqs] == [0, 1, 2, 3] and valid == 4
    np.testing.assert_array_equal(batch["x"][:, 0], [0, 1, 2, 3])
    reqs, batch, valid = rb.next_batch()
    assert [r.req_id for r in reqs] == [4, 5] and valid == 2
    np.testing.assert_array_equal(batch["x"][:, 0], [4, 5, 0, 0])
    with pytest.raises(ValueError, match="no queued"):
        rb.next_batch()


def test_coded_server_end_to_end_queue():
    """submit -> step serves decoded per-request outputs under injected
    stragglers, row-aligned with the drained requests."""
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    params = _rand_params(cfg)
    params_np = jax.tree.map(np.asarray, params)
    sampler = ShiftedExpSampler(
        RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0),
        seed=0)
    srv = CodedServer(cfg, CODE, mesh, params, batch_per_subset=2,
                      straggler_source=sampler)
    assert srv.step() is None          # empty queue
    rng = np.random.default_rng(1)
    xs = [rng.standard_normal(cfg.d_model).astype(np.float32)
          for _ in range(5)]
    for x in xs:
        srv.submit({"x": x})
    res = srv.step()
    assert [r.req_id for r in res.requests] == [1, 2, 3, 4, 5]
    assert res.outputs.shape == (5,)
    assert len(res.stragglers) == CODE.s and res.failed_rows == ()
    beta = params_np["beta"].astype(np.float32)
    want = np.stack([x @ beta for x in xs])
    np.testing.assert_allclose(res.outputs, want, rtol=1e-4, atol=1e-4)
    assert len(srv.batcher) == 0 and srv.step() is None


def test_coded_server_shares_spec_with_train_step():
    """Acceptance criterion: ONE SchemeSpec instance constructs both the
    coded train step and the CodedServer, and both bind the same
    schedule/backend/wire levers."""
    from repro.optim import get_optimizer
    from repro.train.coded_step import make_coded_train_step
    spec = coding.SchemeSpec(schedule="a2a", backend="ref",
                             encode_dtype="float32")
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    params = _rand_params(cfg)
    train_arts = make_coded_train_step(cfg, CODE, mesh,
                                       get_optimizer("sgd", 1e-2), spec=spec)
    srv = CodedServer(cfg, CODE, mesh, params, spec=spec)
    serve_codec = srv.artifacts.codec
    assert train_arts.spec is spec and srv.spec is spec
    assert type(serve_codec.schedule) is type(train_arts.codec.schedule)
    assert serve_codec.backend.name == train_arts.codec.backend.name
    assert serve_codec.wire_dtype == train_arts.codec.wire_dtype


def test_coded_server_rejects_train_only_levers():
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    params = _rand_params(cfg)
    srv = CodedServer(cfg, CODE, mesh, params,
                      spec=coding.SchemeSpec(pipelined=True, packed=True))
    with pytest.raises(ValueError, match="pipelined"):
        srv.artifacts  # noqa: B018 — building the forward is the test
    with pytest.raises(ValueError, match="timed straggler_source"):
        CodedServer(cfg, CODE, mesh, params,
                    autotune=ServingPolicy(
                        arrivals=PoissonArrivals(rate_rps=1.0)))


# ------------------------------------------------ arrival-process planner
def test_simulate_queue_latency_grows_with_load():
    arr_lo = PoissonArrivals(rate_rps=0.5)
    arr_hi = PoissonArrivals(rate_rps=20.0)
    pool = [1.0] * 64
    lo = simulate_queue(pool, arr_lo, batch_requests=4, seed=0)
    hi = simulate_queue(pool, arr_hi, batch_requests=4, seed=0)
    assert lo["utilization"] == pytest.approx(0.5 / 4)
    assert hi["utilization"] == pytest.approx(20.0 / 4)
    assert hi["p99_s"] > lo["p99_s"]
    assert lo["p50_s"] >= 1.0        # sojourn includes the service itself


def test_rank_serving_plans_covers_replication_frontier():
    """The plan space includes full replication (d=n, s=n-1, m=1) — the
    bench's replicated baseline is a point INSIDE the ranking — and a
    comm-heavy cluster prefers a communication-reducing coded plan."""
    params = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0)
    fit = synthetic_fit(params, steps=64, seed=0)
    plans = rank_serving_plans(fit, arrivals=PoissonArrivals(rate_rps=0.05),
                               batch_requests=8, wait_draws=200,
                               n_requests=800)
    keys = {(p.d, p.s, p.m) for p in plans}
    assert (4, 3, 1) in keys           # full replication is in the space
    best = plans[0]
    assert best.m > 1, f"comm-heavy cluster should reduce comm: {best}"
    repl = next(p for p in plans if (p.d, p.s, p.m) == (4, 3, 1))
    assert best.p99_s < repl.p99_s


def test_serving_autotuner_adopts_better_plan():
    """The serve-side loop fits telemetry and adopts a p99-better plan
    once due; a second window without drift holds (hysteresis)."""
    from repro.tune import record_from_times
    params = RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0)
    sampler = ShiftedExpSampler(params, seed=3)
    policy = ServingPolicy(arrivals=PoissonArrivals(rate_rps=0.05),
                           interval=8, min_samples=8, wait_draws=100,
                           n_requests=500)
    tuner = ServingAutotuner(policy, batch_requests=8)
    code = make_code(4, 1, 0, 1)       # start uncoded-ish: d=1
    for t in range(8):
        times = sampler(t, code)
        tuner.record(record_from_times(t, code, "gather", True, times,
                                       measured_step_s=0.01))
    assert tuner.due()
    plan = tuner.maybe_replan(8)
    assert plan is not None and plan.m > 1
    assert tuner.current is plan
    for t in range(8, 16):
        times = sampler(t, code)
        tuner.record(record_from_times(t, code, "gather", True, times,
                                       measured_step_s=0.01))
    again = tuner.maybe_replan(16)
    assert again is None               # no drift -> hysteresis holds
    assert tuner.events and tuner.events[0]["switched"]


def test_coded_server_autotune_replans_and_caches_artifacts():
    """A comm-heavy timed source drives the server from d=1 to a coded
    plan; the artifact cache grows (old scheme stays compiled)."""
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    params = _rand_params(cfg)
    sampler = ShiftedExpSampler(
        RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0),
        seed=0)
    policy = ServingPolicy(arrivals=PoissonArrivals(rate_rps=0.05),
                           interval=6, min_samples=6, wait_draws=100,
                           n_requests=400)
    srv = CodedServer(cfg, make_code(4, 1, 0, 1), mesh, params,
                      batch_per_subset=2, straggler_source=sampler,
                      autotune=policy)
    B = srv.batch_requests
    batch = {"x": np.random.default_rng(0).standard_normal(
        (B, cfg.d_model)).astype(np.float32)}
    for _ in range(7):
        srv.serve_batch(batch)
    assert srv.code.m > 1, "server never adopted a comm-reducing plan"
    assert srv.batch_requests == B     # k = n pinned: B never changes
    assert len(srv._arts) == 2         # old + new scheme both cached
    res = srv.serve_batch(batch)       # serves fine under the new scheme
    assert res.outputs.shape == (B,)
