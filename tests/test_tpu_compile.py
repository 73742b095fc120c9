"""Chip compiles at real widths, with no chip attached.

The TPU compiler is installed here and compiles for a described v5e
(``jax.experimental.topologies``).  It refuses what interpret mode lets
through — an unaligned block, a scalar store to VMEM, a block over the
16 MiB of scoped VMEM, a contraction Mosaic cannot express, a program that
does not fit the chip — so these tests guard the Pallas kernels and the
coded step the way a chip run would, at no chip time:

- every kernel at the shapes of the packed coded step of qwen3-1.7b at its
  published widths (d_model 2048, d_ff 6144), cut to 4 layers and 18992
  vocabulary rows, under code (4, 3, 1, 2): each coded leaf's encode, the
  accumulating encode into every slot of every ``(rows, lanes)`` wire
  bucket, and the decode of each whole bucket and of the widest 3-D leaf;
- the whole one-chip step of ``chip_smoke.py`` (code (1, 1, 0, 1), AdamW,
  4 x 2048 tokens) on the compiled kernels, which must fit the chip and
  hold no relayout of the coded gradient between backward and AdamW;
- a model's forward and backward at the coded cell's attention shape
  (2 x 2048 tokens, 16 query and 8 KV heads of 128, bf16) on the fused
  splash attention kernels, with no (S, S) scores left in the program.

A compile that passes is not a chip run: nothing here executes.  The
topology is described inside a module fixture (never at import, so every
xdist worker collects the same tests), and the persistent compilation cache
is off around the compiles (an entry written for a described chip cannot be
read back without one).
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import coding
from repro.coding.backends import PallasBackend
from repro.coding.layout import leaf_to_groups, to_plane_view
from repro.configs import get_config
from repro.core import make_code
from repro.data import CodedBatcher, make_synthetic_batch
from repro.kernels import coded_decode, coded_encode, coded_encode_acc
from repro.models import api as model_api, common as cm
from repro.optim import get_optimizer
from repro.train import sharding
from repro.train.coded_step import make_coded_train_step

CFG = get_config("qwen3-1.7b").cut(4, 8)          # 18992 vocabulary rows
CODE = make_code(4, 3, 1, 2)
HBM_BYTES = 16 * 10**9                            # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to guard
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding_):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                sharding=sharding_)


def _holds_kernel(hlo: str, name: str) -> bool:
    """Whether compiled HLO holds a Mosaic custom call from a
    ``pallas_call`` named ``name`` (the last part of its op_name path)."""
    return re.search(r'custom_call_target="tpu_custom_call".*op_name="[^"]*/'
                     + re.escape(name) + r'/pallas_call"', hlo) is not None


def _holds_named_call(hlo: str, name: str) -> bool:
    """Whether compiled HLO holds a Mosaic custom call whose instruction is
    named for the kernel (``%name.<n> = ... custom-call``)."""
    return re.search(r"%" + re.escape(name) + r"(\.\d+)? = [^\n]*"
                     r'custom_call_target="tpu_custom_call"', hlo) is not None


def _compile_kernel(name, fn, *args):
    """Compile ``fn`` for the described chip; the result must hold the
    Mosaic kernel ``name``, and its scratch HBM stays within twice its
    operands and results (the old (V, m) decode layout asked 64 times its
    result)."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert _holds_kernel(compiled.as_text(), name), name
    ma = compiled.memory_analysis()
    io = ma.argument_size_in_bytes + ma.output_size_in_bytes
    assert ma.temp_size_in_bytes <= 2 * io, (ma.temp_size_in_bytes, io)
    return compiled


def _plans(codec, device):
    """The step's shapes and leaf plans for ``codec`` on ``device``."""
    pshapes = jax.eval_shape(
        lambda: model_api.init(jax.random.PRNGKey(0), CFG))
    plans = codec.plan(pshapes, sharding.param_specs(pshapes, 1),
                       model_size=1, device=device)
    return pshapes, plans


def _coded_leaves(device):
    """(name, canonical (d, m, A[, B]) encode shape, LeafPlan) of every
    distinct coded leaf, each on its own plane."""
    codec = coding.make_codec(CODE, schedule="a2a", backend="ref")
    pshapes, plans = _plans(codec, device)
    seen, out = set(), []
    for (path, leaf), plan in zip(
            jax.tree_util.tree_leaves_with_path(pshapes),
            jax.tree.leaves(plans,
                            is_leaf=lambda x: isinstance(x, coding.LeafPlan))):
        if not plan.coded:
            continue
        G = jax.eval_shape(lambda g, p=plan: to_plane_view(
            leaf_to_groups(g, p, CODE.m), 1)[None], leaf)
        if G.shape not in seen:
            seen.add(G.shape)
            out.append((jax.tree_util.keystr(path), G.shape, plan))
    return out


def _buckets(device, codec_schedule="gather"):
    """The packed step's wire buckets (model axis of 1), widest first."""
    codec = coding.make_codec(CODE, schedule=codec_schedule, backend="ref")
    pshapes, plans = _plans(codec, device)
    pp = codec.pack_plan(pshapes, plans)
    return sorted(pp.buckets, key=lambda b: -b.size)


def test_encode_every_coded_leaf(one_chip, no_persistent_cache):
    """coded_encode (2-D and 3-D operands) at every coded leaf, each on
    its own plane, including a narrow tail on the flat lift."""
    leaves = _coded_leaves(next(iter(one_chip.device_set)))
    assert any(len(G) == 3 for _, G, _ in leaves)
    assert any(len(G) == 4 for _, G, _ in leaves)
    C = _sds((1, CODE.m), jnp.float32, one_chip)
    for name, G, _ in leaves:
        _compile_kernel("coded_encode", lambda g, c: coded_encode(g, c),
                        _sds(G, jnp.float32, one_chip), C)


def test_encode_acc_into_wire_slot(one_chip, no_persistent_cache):
    """coded_encode_acc folds every slot's (1, m, rows, lanes) plane into
    its rows of the whole (rows, lanes) bucket, in place (no scratch the
    size of the bucket), for every bucket of the coded cell's step."""
    buckets = _buckets(next(iter(one_chip.device_set)))
    assert len(buckets) >= 2 and all(b.rows % 16 == 0
                                     for b in buckets)
    C = _sds((1, CODE.m), jnp.float32, one_chip)
    for b in buckets:
        for s in {s.rows: s for s in b.slots}.values():
            compiled = _compile_kernel(
                "coded_encode_acc",
                lambda a, g, c, r=s.row: coded_encode_acc(a, g, c, row=r),
                _sds(b.shape, jnp.float32, one_chip),
                _sds((1, CODE.m, s.rows, b.lanes), jnp.float32, one_chip), C)
            assert compiled.memory_analysis().temp_size_in_bytes \
                < b.size * 4 // 2, (b.key, s.rows)


@pytest.mark.parametrize("schedule", ["gather", "a2a"])
def test_decode_full_wire_bucket(one_chip, no_persistent_cache, schedule):
    """coded_decode on every bucket's (n, rows, lanes) stack the gather
    schedule contracts, and on the (n, rows/n, lanes) slice a2a
    contracts — together the step's whole coded gradient, with no pad."""
    buckets = _buckets(next(iter(one_chip.device_set)), schedule)
    assert sum(b.size for b in buckets) > 10**8
    for b in buckets:
        rows = b.rows if schedule == "gather" else b.rows // CODE.n
        compiled = _compile_kernel(
            "coded_decode",
            lambda f, w: coded_decode(f, w, out_dtype=jnp.float32),
            _sds((CODE.n, rows, b.lanes), jnp.float32, one_chip),
            _sds((CODE.n, CODE.m), jnp.float32, one_chip))
        # an (m, rows, lanes) result: m is never the lane axis
        assert compiled.memory_analysis().output_size_in_bytes \
            < 1.01 * CODE.m * rows * max(b.lanes, 128) * 4


def test_decode_widest_3d_leaf(one_chip, no_persistent_cache):
    """coded_decode on the widest 3-D leaf's own (n, A, B) stack."""
    _, G, _ = max((x for x in _coded_leaves(next(iter(one_chip.device_set)))
                   if len(x[1]) == 4), key=lambda x: np.prod(x[1]))
    _compile_kernel("coded_decode", lambda f, w: coded_decode(f, w),
                    _sds((CODE.n,) + G[2:], jnp.float32, one_chip),
                    _sds((CODE.n, CODE.m), jnp.float32, one_chip))


@pytest.fixture(scope="module")
def one_chip_step(topo, no_persistent_cache):
    """The chip smoke's step — code (1, 1, 0, 1), AdamW, 4 x 2048 tokens,
    compiled kernels — compiled for one described chip, with its build."""
    code = make_code(1, 1, 0, 1)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    opt = get_optimizer("adamw", 3e-4)
    arts = make_coded_train_step(
        CFG, code, mesh, opt, spec=coding.SchemeSpec(backend=PallasBackend()))
    batch = CodedBatcher(code).place(make_synthetic_batch(
        np.random.default_rng(0), CFG, 4, 2048))
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          batch)
    fn, _, _ = arts.step(shapes)
    rep = NamedSharding(mesh, P())
    pshapes = jax.eval_shape(
        lambda: model_api.init(jax.random.PRNGKey(0), CFG))
    args = [jax.tree.map(lambda x: _sds(x.shape, x.dtype, rep), t)
            for t in (pshapes, jax.eval_shape(opt.init, pshapes), shapes)]
    args += [_sds(s, jnp.float32, rep)
             for s in ((code.n, code.m), (code.n,), (code.n, code.d))]
    with jax.sharding.set_mesh(mesh):
        compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(*args).compile()
    return arts, pshapes, compiled


def test_one_chip_coded_step_fits(one_chip_step):
    """The chip smoke's step compiles for one chip, holds the kernels —
    the encode folding into the wire buckets — and its arguments plus
    scratch fit the chip's HBM."""
    _, _, compiled = one_chip_step
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 2
    assert _holds_kernel(hlo, "coded_encode_acc")
    assert _holds_kernel(hlo, "coded_decode")
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES


def _layout_passes(hlo: str, min_elems: int) -> list[str]:
    """Every transpose, pad, copy or (relayout) reshape in compiled HLO —
    fused or not — whose f32 result holds at least ``min_elems`` elements,
    outside the model's forward and backward (``coded.grad``)."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = f32\[([\d,]*)\]\S* "
                     r"(transpose|pad|copy|reshape)\(", line)
        if not m or "coded.grad" in line:
            continue
        if math.prod(int(x) for x in m.group(2).split(",")) >= min_elems:
            out.append(f"{m.group(3)} %{m.group(1)} f32[{m.group(2)}]")
    return out


def test_coded_gradient_keeps_its_layout(one_chip_step):
    """From the end of backward to AdamW the coded gradient is only read
    and written by the encode kernel, the exchange and the decode kernel:
    the compiled step holds no transpose, pad, copy or relayout reshape of
    an f32 buffer as large as the smallest coded MLP leaf, and every coded
    element rides the copy-free path."""
    arts, pshapes, compiled = one_chip_step
    smallest_mlp = min(math.prod(x.shape) for x in
                       jax.tree.leaves(pshapes["layers"]["mlp"]))
    assert _layout_passes(compiled.as_text(), smallest_mlp) == []
    assert arts.copy_free_share >= 0.99


def test_model_attention_on_splash_kernels(one_chip, no_persistent_cache,
                                           monkeypatch):
    """With the platform check answering "tpu", one layer's forward and
    backward at the coded cell's attention shape compiles for one chip with
    the splash forward (run twice: forward and remat recompute), dq and dkv
    kernels, and no (B, Hkv, g, S, S) scores or mask anywhere."""
    monkeypatch.setattr(cm, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("qwen3-1.7b").cut(1, 8),
                              compute_dtype="bfloat16")
    B, S = 2, 2048
    assert cm.attention_path(cfg, S, "causal") == "fused"
    pshapes = jax.eval_shape(lambda: model_api.init(jax.random.PRNGKey(0),
                                                    cfg))
    params = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), pshapes)
    tokens = _sds((B, S), jnp.int32, one_chip)
    loss = model_api.make_loss(cfg)

    def grad(p, t):
        return jax.value_and_grad(loss)(p, {"tokens": t, "labels": t})

    hlo = jax.jit(grad).lower(params, tokens).compile().as_text()
    for name in ("splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals",
                 "splash_mqa_dkv_no_residuals"):
        assert _holds_named_call(hlo, name), name
    assert not re.search(rf"\[{B},{cfg.n_kv_heads},[\d,]*{S},{S}", hlo)


# ------------------------------------------------------- DeepSeek-V2-Lite
# the chip's share of the benchmark's cell: 1 dense + 4 expert layers,
# 12800 vocabulary rows, 8 of 64 experts held, bf16 activations
DSV2 = dataclasses.replace(get_config("deepseek-v2-lite").cut(5, 8, 8),
                           compute_dtype="bfloat16")


def test_latent_attention_on_splash_kernels(one_chip, no_persistent_cache,
                                            monkeypatch):
    """With the platform check answering "tpu", one latent-attention
    layer's forward and backward at the cell's shape (4096 positions, 16
    heads, q/k 192 and v 128 wide) compiles with the splash forward, dq and
    dkv kernels, and no (S, S) scores anywhere."""
    from repro.models import mla
    monkeypatch.setattr(cm, "_on_tpu", lambda: True)
    B, S = 1, 4096
    p = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                     jax.eval_shape(lambda: mla.init(jax.random.PRNGKey(0),
                                                     DSV2, jnp.float32)))
    x = _sds((B, S, DSV2.d_model), jnp.bfloat16, one_chip)

    def grad(p_, x_):
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        return jax.grad(lambda a, b: jnp.sum(mla.self_attention(
            a, DSV2, b, pos).astype(jnp.float32)), argnums=(0, 1))(p_, x_)

    hlo = jax.jit(grad).lower(p, x).compile().as_text()
    for name in ("splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals",
                 "splash_mqa_dkv_no_residuals"):
        assert _holds_named_call(hlo, name), name
    assert not re.search(rf"\[[\d,]*{S},{S}\]", hlo)


def test_held_experts_compile_at_published_widths(one_chip,
                                                  no_persistent_cache,
                                                  monkeypatch):
    """One expert layer's forward and backward at the cell's 16384 tokens
    (d_model 2048, 8 held experts of width 1408, shared width 2816), its
    grouped matmuls in megablox's gmm and tgmm kernels, within the scoped
    VMEM."""
    from repro.models import moe
    monkeypatch.setattr(cm, "_on_tpu", lambda: True)
    pshapes = jax.eval_shape(lambda: model_api.init(jax.random.PRNGKey(0),
                                                    DSV2))
    lp = jax.tree.map(lambda x: _sds(x.shape[1:], x.dtype, one_chip),
                      {k: pshapes["layers"][k]
                       for k in ("router", "moe", "shared")})
    x = _sds((4, 4096, DSV2.d_model), jnp.bfloat16, one_chip)

    def grad(lp_, x_):
        def f(a, b):
            y, aux, _ = moe.held_experts_ffn(a, DSV2, b)
            return jnp.sum(y.astype(jnp.float32)) + aux
        return jax.grad(f, argnums=(0, 1))(lp_, x_)

    hlo = jax.jit(grad).lower(lp, x).compile().as_text()
    assert _holds_named_call(hlo, "gmm")
    assert re.search(r"%[\w.]*tgmm[\w.]* = [^\n]*tpu_custom_call", hlo)


def test_deepseek_cell_step_fits(topo, no_persistent_cache, monkeypatch):
    """The deepseek-v2-lite.worker step (code (1, 1, 0, 1), AdamW, 4 x 4096
    tokens, compiled codec kernels, splash attention, grouped matmuls)
    compiles for one chip and its arguments plus scratch fit the chip."""
    monkeypatch.setattr(cm, "_on_tpu", lambda: True)
    code = make_code(1, 1, 0, 1)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    opt = get_optimizer("adamw", 3e-4)
    arts = make_coded_train_step(
        DSV2, code, mesh, opt, spec=coding.SchemeSpec(backend=PallasBackend()))
    toks = np.zeros((4, 4096), np.int32)
    batch = CodedBatcher(code).place({"tokens": toks, "labels": toks})
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          batch)
    fn, _, _ = arts.step(shapes)
    rep = NamedSharding(mesh, P())
    pshapes = jax.eval_shape(
        lambda: model_api.init(jax.random.PRNGKey(0), DSV2))
    args = [jax.tree.map(lambda x: _sds(x.shape, x.dtype, rep), t)
            for t in (pshapes, jax.eval_shape(opt.init, pshapes), shapes)]
    args += [_sds(s, jnp.float32, rep)
             for s in ((code.n, code.m), (code.n,), (code.n, code.d))]
    with jax.sharding.set_mesh(mesh):
        compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(*args).compile()
    hlo = compiled.as_text()
    assert _holds_kernel(hlo, "coded_encode_acc")
    assert _holds_named_call(hlo, "splash_mqa_fwd_residuals")
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES
