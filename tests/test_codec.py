"""Codec subsystem tests: backend parity (ref vs the Pallas kernels,
interpreted on the CPU), schedule equivalence (gather / a2a / psum) across
wire dtypes and backends on a multi-device CPU mesh, and the regression
test that the kernel backend really executes the Pallas kernels inside the
train step (the old ``use_kernels`` flag imported them and silently never
called them)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.coding as coding
from repro.coding import backends as coding_backends
from repro.configs import get_config
from repro.core import make_code
from repro.data import CodedBatcher, make_synthetic_batch
from repro.launch.mesh import make_local_mesh
from repro.models import api as model_api
from repro.optim import get_optimizer
from repro.train.coded_step import make_coded_train_step

RNG = np.random.default_rng(11)
CODE = make_code(4, 3, 1, 2)


def _linear_cfg():
    import dataclasses
    return dataclasses.replace(get_config("logistic-paper"), d_model=64)


@functools.lru_cache(maxsize=None)
def _step_outputs(schedule: str, backend: str, wire: str):
    """One coded step on the paper's linear workload, (4 data x 1 model)."""
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    opt = get_optimizer("sgd", 1e-2)
    arts = make_coded_train_step(
        cfg, CODE, mesh, opt,
        spec=coding.SchemeSpec(schedule=schedule, backend=backend,
                               encode_dtype=wire))
    rng = np.random.default_rng(5)
    batch = make_synthetic_batch(rng, cfg, 16, 0)
    placed = jax.tree.map(jnp.asarray, CodedBatcher(CODE).place(batch))
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          placed)
    stepfn, _, _ = arts.step(shapes)
    params = model_api.init(jax.random.PRNGKey(7), cfg)
    inp = coding.make_step_inputs(CODE, [2])
    p2, _, metrics = jax.jit(stepfn)(
        params, opt.init(params), placed, jnp.asarray(inp["W"]),
        jnp.asarray(inp["mask"]), jnp.asarray(inp["rho"]))
    return p2, metrics


def _max_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                           - y.astype(jnp.float32)))), a, b)))


@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("wire", ["float32", "bfloat16"])
@pytest.mark.parametrize("schedule", ["gather", "a2a"])
def test_schedule_equivalence(schedule, backend, wire):
    """gather == a2a == psum decoded update, for both backends and both wire
    dtypes, with a straggler, on a multi-device CPU mesh."""
    ref, _ = _step_outputs("psum", "ref", "float32")
    got, _ = _step_outputs(schedule, backend, wire)
    tol = 5e-5 if wire == "float32" else 5e-3
    diff = _max_diff(got, ref)
    assert diff < tol, f"{schedule}/{backend}/{wire}: diverges by {diff}"


def test_backends_bitwise_equal_across_schedules():
    """ref and pallas backends produce identical decoded updates (both
    accumulate in f32), per schedule."""
    for schedule in ("gather", "a2a"):
        a, _ = _step_outputs(schedule, "ref", "float32")
        b, _ = _step_outputs(schedule, "interpret", "float32")
        assert _max_diff(a, b) < 1e-6, f"{schedule}: ref vs pallas diverge"


# ------------------------------------------------- pallas really executes
def test_pallas_backend_executes_kernels(monkeypatch):
    """The kernel backend must invoke the Pallas kernel entry points when the
    step is traced — the regression the dead use_kernels flag shipped with."""
    calls = {"encode": 0, "decode": 0}
    real_enc = coding_backends._encode_mod.coded_encode
    real_acc = coding_backends._encode_mod.coded_encode_acc
    real_dec = coding_backends._decode_mod.coded_decode

    def spy_enc(G, C, **kw):
        calls["encode"] += 1
        return real_enc(G, C, **kw)

    def spy_acc(acc, G, C, **kw):        # the packed step's encode-into-slot
        calls["encode"] += 1
        return real_acc(acc, G, C, **kw)

    def spy_dec(F, W, **kw):
        calls["decode"] += 1
        return real_dec(F, W, **kw)

    monkeypatch.setattr(coding_backends._encode_mod, "coded_encode", spy_enc)
    monkeypatch.setattr(coding_backends._encode_mod, "coded_encode_acc",
                        spy_acc)
    monkeypatch.setattr(coding_backends._decode_mod, "coded_decode", spy_dec)

    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    opt = get_optimizer("sgd", 1e-2)
    arts = make_coded_train_step(cfg, CODE, mesh, opt,
                                 spec=coding.SchemeSpec(backend="interpret"))
    assert arts.codec.backend.name == "pallas"
    rng = np.random.default_rng(5)
    placed = jax.tree.map(jnp.asarray, CodedBatcher(CODE).place(
        make_synthetic_batch(rng, cfg, 16, 0)))
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          placed)
    stepfn, _, _ = arts.step(shapes)
    params = model_api.init(jax.random.PRNGKey(7), cfg)
    inp = coding.make_step_inputs(CODE, [])
    p2, _, _ = jax.jit(stepfn)(
        params, opt.init(params), placed, jnp.asarray(inp["W"]),
        jnp.asarray(inp["mask"]), jnp.asarray(inp["rho"]))
    jax.block_until_ready(p2)
    assert calls["encode"] > 0, "pallas encode kernel never invoked"
    assert calls["decode"] > 0, "pallas decode kernel never invoked"

    # the ref backend must NOT touch the kernels
    calls["encode"] = calls["decode"] = 0
    _step_outputs.cache_clear()
    a, _ = _step_outputs("gather", "ref", "float32")
    jax.block_until_ready(a)
    assert calls["encode"] == 0 and calls["decode"] == 0


def test_use_kernels_flag_is_gone():
    """The pre-PR-1 boolean was retired in favour of SchemeSpec.backend:
    passing it must fail loudly (TypeError), not silently no-op."""
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    opt = get_optimizer("sgd", 1e-2)
    with pytest.raises(TypeError, match="use_kernels"):
        make_coded_train_step(cfg, CODE, mesh, opt, use_kernels=True)
    # the replacement spelling selects the same backends
    arts = make_coded_train_step(
        cfg, CODE, mesh, opt, spec=coding.SchemeSpec(backend="interpret"))
    assert arts.codec.backend.name == "pallas"
    arts = make_coded_train_step(
        cfg, CODE, mesh, opt, spec=coding.SchemeSpec(backend="ref"))
    assert arts.codec.backend.name == "ref"


# ---------------------------------------------------------- unit-level parity
@pytest.mark.parametrize("shape,gdim", [((64,), 0), ((6, 8, 5), 0),
                                        ((16, 3), 0)])
@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_encode_leaf_backend_parity(shape, gdim, backend):
    g = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    plan = coding.plan_leaf(shape, None, 2)
    assert plan.coded and plan.group_dim == gdim
    coef = jnp.asarray(RNG.standard_normal(2), jnp.float32)
    got = coding.encode_leaf(g, coef, plan, coding.resolve_backend(backend))
    # oracle: moveaxis + tensordot over the m leading blocks of the group dim
    x = jnp.moveaxis(g, plan.group_dim, 0)
    x = x.reshape(2, x.shape[0] // 2, *x.shape[1:])
    want = jnp.tensordot(coef, x, axes=[[0], [0]])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("wire", [jnp.float32, jnp.bfloat16])
def test_decode_stack_backend_parity(wire):
    F = jnp.asarray(RNG.standard_normal((4, 16, 5)), wire)
    W = jnp.asarray(RNG.standard_normal((4, 2)), jnp.float32)
    a = coding.RefBackend().decode(F, W, out_dtype=jnp.float32)
    b = coding.resolve_backend("interpret").decode(F, W, out_dtype=jnp.float32)
    assert a.dtype == b.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------- registry
def test_unknown_backend_and_schedule_rejected():
    with pytest.raises(ValueError):
        coding.resolve_backend("tpu-go-brr")
    with pytest.raises(ValueError):
        coding.get_schedule("ring")
    with pytest.raises(ValueError):
        coding.make_codec(CODE, schedule="nope")


def test_pallas_backend_needs_a_tpu():
    """``pallas`` means the compiled kernels: without a TPU it raises
    instead of falling back to interpret mode or to the einsum reference;
    ``interpret`` is the explicit spelling of the interpreted kernels and
    ``auto`` picks the reference off-TPU."""
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match="needs a TPU"):
        coding.resolve_backend("pallas")
    bk = coding.resolve_backend("interpret")
    assert bk.name == "pallas" and bk.interpret
    assert coding.resolve_backend("auto").name == "ref"


def test_coded_allreduce_shim_removed():
    """The core.coded_allreduce deprecation shim (PR 1-6) is gone: the old
    module neither imports nor resolves as an attribute of repro.core."""
    import importlib
    import sys

    import repro.core as core

    sys.modules.pop("repro.core.coded_allreduce", None)
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("repro.core.coded_allreduce")
    with pytest.raises(AttributeError):
        core.coded_allreduce  # noqa: B018 — attribute access is the test
    assert "coded_allreduce" not in core.__all__
