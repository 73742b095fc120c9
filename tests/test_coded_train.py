"""Integration tests of the coded train step on a (4 data x 2 model) mesh of
host devices: the coded aggregation (gather and a2a schedules) must produce
the same parameter update as the uncoded psum baseline, for any tolerable
straggler pattern, on representative architectures.

Compile-time note (1-core CI): the jitted step is cached per (arch,
schedule); straggler patterns are INPUTS (W/mask/rho), so invariance sweeps
reuse one executable.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import make_code
import repro.coding as coding
from repro.coding import make_step_inputs
from repro.tune import RandomStragglers
from repro.data import CodedBatcher, make_synthetic_batch
from repro.launch.mesh import make_local_mesh
from repro.models import api as model_api
from repro.optim import get_optimizer
from repro.train import Trainer
from repro.train.coded_step import make_coded_train_step

N, D_, S_, M_ = 4, 3, 1, 2
CODE = make_code(N, D_, S_, M_)

# model-axis size of the LM integration meshes: the model's scan-over-layers
# runs GSPMD-auto over 'model' inside the data-manual shard_map
MS = 2


@functools.lru_cache(maxsize=None)
def _compiled(arch: str, schedule: str):
    cfg = get_config(arch).reduced()
    mesh = make_local_mesh(4, MS)
    opt = get_optimizer("sgd", 1e-2)
    arts = make_coded_train_step(cfg, CODE, mesh, opt,
                                 spec=coding.SchemeSpec(schedule=schedule))
    rng = np.random.default_rng(0)
    batch = make_synthetic_batch(rng, cfg, 8, 16)
    placed = CodedBatcher(CODE).place(batch)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), placed)
    smapped, _, _ = arts.step(shapes)
    params = model_api.init(jax.random.PRNGKey(42), cfg)
    ost = opt.init(params)
    fn = jax.jit(smapped)
    return fn, params, ost, jax.tree.map(jnp.asarray, placed), arts


def _run(arch, schedule, stragglers):
    fn, params, ost, placed, arts = _compiled(arch, schedule)
    inp = make_step_inputs(CODE, stragglers)
    p2, o2, metrics = fn(params, ost, placed, jnp.asarray(inp["W"]),
                         jnp.asarray(inp["mask"]), jnp.asarray(inp["rho"]))
    return p2, metrics, arts


def _tree_max_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                           - y.astype(jnp.float32)))), a, b)))


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "olmoe-1b-7b", "xlstm-350m",
                                  "zamba2-1.2b", "deepseek-v2-lite"])
def test_coded_equals_uncoded(arch):
    ref, mref, _ = _run(arch, "psum", [])
    got, mgot, arts = _run(arch, "gather", [2])
    assert arts.coded_fraction > 0.9, f"{arch}: coded fraction too low"
    diff = _tree_max_diff(got, ref)
    assert diff < 5e-4, f"{arch}/gather: params diverge by {diff}"
    assert abs(float(mgot["loss"][0]) - float(mref["loss"][0])) < 1e-4


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "whisper-tiny",
                                  "internvl2-26b"])
def test_a2a_schedule_equals_uncoded(arch):
    ref, _, _ = _run(arch, "psum", [])
    got, _, _ = _run(arch, "a2a", [1])
    diff = _tree_max_diff(got, ref)
    assert diff < 5e-4, f"{arch}/a2a: params diverge by {diff}"


def test_straggler_invariance():
    """The decoded update must be identical for every straggler set of
    size <= s (paper Definition 1) — one executable, patterns as inputs."""
    base, _, _ = _run("qwen3-1.7b", "gather", [])
    for st in ([0], [1], [2], [3]):
        got, _, _ = _run("qwen3-1.7b", "gather", st)
        assert _tree_max_diff(got, base) < 5e-4, f"straggler {st} changed update"


def test_bf16_wire_close_to_f32():
    """bf16 encodings (the §Perf wire lever) stay within bf16 tolerance of
    the exact f32 coded update."""
    cfg = get_config("qwen3-1.7b").reduced()
    mesh = make_local_mesh(4, MS)
    opt = get_optimizer("sgd", 1e-2)
    rng = np.random.default_rng(0)
    batch = make_synthetic_batch(rng, cfg, 8, 16)
    placed = jax.tree.map(jnp.asarray, CodedBatcher(CODE).place(batch))
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), placed)
    params = model_api.init(jax.random.PRNGKey(42), cfg)
    inp = make_step_inputs(CODE, [2])
    outs = {}
    for ed in ("float32", "bfloat16"):
        arts = make_coded_train_step(cfg, CODE, mesh, opt,
                                     spec=coding.SchemeSpec(encode_dtype=ed))
        smapped, _, _ = arts.step(shapes)
        p2, _, _ = jax.jit(smapped)(params, opt.init(params), placed,
                                    jnp.asarray(inp["W"]),
                                    jnp.asarray(inp["mask"]),
                                    jnp.asarray(inp["rho"]))
        outs[ed] = p2
    diff = _tree_max_diff(outs["float32"], outs["bfloat16"])
    assert diff < 5e-3, f"bf16 wire diverges by {diff}"
    assert diff > 0.0  # it did actually quantize something


def test_too_many_stragglers_rejected():
    with pytest.raises(ValueError):
        make_step_inputs(CODE, [0, 1])  # s = 1


def test_trainer_loss_decreases():
    cfg = get_config("qwen3-1.7b").reduced()
    tr = Trainer(cfg, CODE, make_local_mesh(4, MS),
                 get_optimizer("adamw", 3e-3),
                 straggler_source=RandomStragglers(seed=1), seed=0)
    rng = np.random.default_rng(0)
    fixed = make_synthetic_batch(rng, cfg, 8, 16)   # overfit one batch
    losses = [tr.step(fixed)["loss"] for _ in range(10)]
    assert losses[-1] < losses[0] - 0.15, losses


def test_trainer_linear_paper_workload():
    import dataclasses
    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=64)
    tr = Trainer(cfg, CODE, make_local_mesh(4, 2),
                 get_optimizer("nag", 1e-3),
                 straggler_source=RandomStragglers(seed=2), seed=1)
    rng = np.random.default_rng(1)
    fixed = make_synthetic_batch(rng, cfg, 16, 0)
    losses = [tr.step(fixed)["loss"] for _ in range(12)]
    assert losses[-1] < losses[0], losses


def test_multiaxis_data_mesh():
    """Coding index flattens ('pod','data') — 2 pods x 2 groups, n=4 must
    reproduce the single-data-axis result for the same code + stragglers."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    cfg = get_config("qwen3-1.7b").reduced()
    opt = get_optimizer("sgd", 1e-2)
    arts = make_coded_train_step(cfg, CODE, mesh, opt,
                                 spec=coding.SchemeSpec())
    rng = np.random.default_rng(0)
    batch = make_synthetic_batch(rng, cfg, 8, 16)
    placed = CodedBatcher(CODE).place(batch)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), placed)
    smapped, _, _ = arts.step(shapes)
    inp = make_step_inputs(CODE, [1])
    params = model_api.init(jax.random.PRNGKey(42), cfg)
    p2, _, _ = jax.jit(smapped)(
        params, opt.init(params), jax.tree.map(jnp.asarray, placed),
        jnp.asarray(inp["W"]), jnp.asarray(inp["mask"]), jnp.asarray(inp["rho"]))
    ref, _, _ = _run(cfg.name.replace("-reduced", ""), "gather", [1])
    assert _tree_max_diff(p2, ref) < 5e-4
