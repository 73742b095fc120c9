"""Packed single-collective codec tests.

Three layers:
  1. static ``PackPlan`` unit tests — slot alignment, a2a divisibility,
     bucketing by wire dtype x effective model sharding, padding accounting
     against the schedules' ``recv_elems_per_worker`` model;
  2. codec-level parity — pack -> collective -> fused decode -> unpack is
     *bit-identical* to the per-leaf decode path on a multi-device mesh,
     for both schedules, both wire dtypes, ref and interpret backends, with
     mixed coded/psum-fallback leaves (the deterministic sweep runs always;
     a hypothesis property test widens it when hypothesis is installed);
  3. full-step parity — ``make_coded_train_step(packed=True)`` (the default)
     equals ``packed=False`` bitwise on the paper's linear workload, on
     (4, 1) and (4, 2) meshes.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.coding as coding
from repro.coding.packing import (WIRE_ALIGN, enc_shape, make_pack_plan,
                                  pack_bucket, unpack_bucket)
from repro.configs import get_config
from repro.core import make_code
from repro.data import CodedBatcher, make_synthetic_batch
from repro.launch.mesh import make_local_mesh
from repro.models import api as model_api
from repro.optim import get_optimizer
from repro.train.coded_step import make_coded_train_step

RNG = np.random.default_rng(3)
N, M = 4, 2
CODE = make_code(N, 3, 1, M)


# ---------------------------------------------------------------- pack plan
def test_enc_shape_moves_group_dim_first():
    plan = coding.LeafPlan(coded=True, group_dim=1)
    assert enc_shape((3, 8, 5), plan, m=2) == (4, 3, 5)
    plan0 = coding.LeafPlan(coded=True, group_dim=0)
    assert enc_shape((64,), plan0, m=2) == (32,)


def test_pack_plan_alignment_and_divisibility():
    tree = {"a": jax.ShapeDtypeStruct((64,), jnp.float32),
            "b": jax.ShapeDtypeStruct((6, 8, 5), jnp.float32),
            "c": jax.ShapeDtypeStruct((7,), jnp.float32)}   # 7 % m != 0: psum
    plans = coding.plan_tree(tree, None, M)
    pp = make_pack_plan(tree, plans, m=M, n=N)
    assert len(pp.buckets) == 1
    b = pp.buckets[0]
    assert len(b.slots) == 2                      # "c" falls back to psum
    for s in b.slots:
        assert s.offset % WIRE_ALIGN == 0
        assert s.size == int(np.prod(s.enc_shape))
    # bucket length: 128-aligned AND divisible by n (a2a chunking)
    assert b.size % WIRE_ALIGN == 0 and b.size % N == 0
    assert b.size >= b.unpadded == sum(s.size for s in b.slots)
    assert pp.padded_elems == b.size and pp.unpadded_elems == b.unpadded
    # slots must not overlap
    spans = sorted((s.offset, s.offset + s.size) for s in b.slots)
    for (_, end), (start, _) in zip(spans, spans[1:]):
        assert start >= end


def test_pack_plan_buckets_by_model_sharding():
    tree = {"w1": jax.ShapeDtypeStruct((8, 16), jnp.float32),
            "w2": jax.ShapeDtypeStruct((8, 16), jnp.float32),
            "w3": jax.ShapeDtypeStruct((8, 4, 16), jnp.float32)}
    # w1/w2 encode to (V, 16-model) — pattern (1,); w3's largest replicated
    # dim is dim 0, so its encoding is (V, 4, 16-model) — pattern (2,)
    specs = {"w1": P(None, "model"), "w2": P(None, "model"),
             "w3": P(None, None, "model")}
    plans = coding.plan_tree(tree, specs, M)
    # model axis of size 1 carries no data: everything packs into one bucket
    pp1 = make_pack_plan(tree, plans, m=M, n=N, specs=specs, model_size=1)
    assert len(pp1.buckets) == 1
    # a real (>1) model axis splits by sharded-dim pattern of the encoding
    pp2 = make_pack_plan(tree, plans, m=M, n=N, specs=specs, model_size=2)
    assert len(pp2.buckets) == 2
    by_len = sorted(len(b.slots) for b in pp2.buckets)
    assert by_len == [1, 2]                      # {w1, w2} vs {w3}
    for b in pp2.buckets:
        assert b.key[0] == "float32"             # wire dtype in the key


def test_worker_chunk_slots_memoized():
    """WireBucket.worker_chunk_slots is lru_cached (the frozen dataclass is
    hashable): repeat calls during step retraces and tuning-loop scoring
    serve the same tuple object instead of re-running the O(n * slots)
    scan."""
    tree = {"a": jax.ShapeDtypeStruct((64,), jnp.float32),
            "b": jax.ShapeDtypeStruct((6, 8, 5), jnp.float32)}
    plans = coding.plan_tree(tree, None, M)
    (bucket,) = make_pack_plan(tree, plans, m=M, n=N).buckets
    from repro.coding.packing import WireBucket
    WireBucket.worker_chunk_slots.cache_clear()
    first = bucket.worker_chunk_slots(N)
    before = WireBucket.worker_chunk_slots.cache_info()
    assert bucket.worker_chunk_slots(N) is first    # identity, not equality
    after = WireBucket.worker_chunk_slots.cache_info()
    assert after.hits == before.hits + 1
    assert after.misses == before.misses
    # a different n is a different cache entry, still correct accounting
    assert bucket.worker_chunk_slots(2) is not first
    covered = sorted((li, lo, hi) for w in first for (li, lo, hi) in w)
    assert covered  # the union tiles the slots (full check in decode tests)


def test_pack_plan_recv_elems_accounts_padding():
    tree = {"a": jax.ShapeDtypeStruct((64,), jnp.float32)}
    plans = coding.plan_tree(tree, None, M)
    pp = make_pack_plan(tree, plans, m=M, n=N)
    for name in ("gather", "a2a"):
        sched = coding.get_schedule(name)
        got = pp.recv_elems_per_worker(sched)
        want = sched.recv_elems_per_worker(pp.padded_elems * M, N, M)
        assert got == want
        # padded cost >= the unpadded per-leaf prediction
        assert got >= sched.recv_elems_per_worker(64, N, M)


def test_pack_unpack_roundtrip_is_identity():
    """unpack(decode=identity) inverts pack exactly, slot by slot."""
    tree = {"a": jnp.asarray(RNG.standard_normal((64,)), jnp.float32),
            "b": jnp.asarray(RNG.standard_normal((6, 8, 5)), jnp.float32)}
    plans = coding.plan_tree(tree, None, M)
    flat, td = jax.tree.flatten(tree)
    flat_plans = td.flatten_up_to(plans)
    enc = [coding.encode_leaf(x, jnp.ones((M,), jnp.float32), pl)
           for x, pl in zip(flat, flat_plans)]
    pp = make_pack_plan(tree, plans, m=M, n=N)
    buf = pack_bucket(enc, pp.buckets[0], jnp.float32)
    assert buf.shape == (pp.buckets[0].size,)
    # fake a decode that replicates the buffer into m identical rows
    dec = jnp.stack([buf, buf])
    out = unpack_bucket(dec, pp.buckets[0])
    for s, e, x in zip(pp.buckets[0].slots, enc, flat):
        got = out[s.leaf_index]
        assert got.shape == x.shape
        # each group's m copies came from the same encoding element
        np.testing.assert_array_equal(
            np.asarray(jax.lax.slice_in_dim(buf, s.offset, s.offset + s.size)),
            np.asarray(e).reshape(-1))


# ----------------------------------------------- codec-level bitwise parity
def _data_mesh():
    if len(jax.devices()) < N:
        pytest.skip(f"needs {N} devices")
    return jax.make_mesh((N,), ("data",))


def _parity_case(shapes, schedule, wire, backend, seed=0):
    """Per-leaf vs packed decode of the same stacked encodings: bit-equal."""
    codec = coding.make_codec(CODE, schedule=schedule, backend=backend,
                              wire_dtype=wire)
    sched = codec.schedule
    tree = {f"p{i}": jax.ShapeDtypeStruct(s, jnp.float32)
            for i, s in enumerate(shapes)}
    plans = coding.plan_tree(tree, None, M, sched.n_split(N))
    flat_shapes, td = jax.tree.flatten(tree)
    flat_plans = td.flatten_up_to(plans)
    pp = codec.pack_plan(tree, plans)

    rng = np.random.default_rng(seed)
    wdt = jnp.dtype(wire)
    # stacked per-worker payloads: coded leaves in the wire dtype (already
    # masked), psum-fallback leaves in f32
    stacked = [jnp.asarray(rng.standard_normal(
                   (N,) + (enc_shape(tuple(x.shape), pl, M) if pl.coded
                           else tuple(x.shape))),
                   wdt if pl.coded else jnp.float32)
               for x, pl in zip(flat_shapes, flat_plans)]
    W = jnp.asarray(rng.standard_normal((N, M)), jnp.float32)
    mesh = _data_mesh()

    def per_leaf(Wf, *fs):
        out = []
        for f, pl in zip(fs, flat_plans):
            if pl.coded:
                out.append(sched.decode_leaf(f[0], Wf, pl, ("data",), N,
                                             codec.backend))
            else:
                out.append(jax.lax.psum(f[0], ("data",)))
        return tuple(out)

    def packed(Wf, *fs):
        flat = [f[0] for f in fs]
        bufs = codec.pack(flat, pp)
        decs = [codec.decode_packed(b, Wf, ("data",)) for b in bufs]
        out = list(flat)
        for i, g in codec.unpack(decs, pp).items():
            out[i] = g
        # same shared fallback the train step uses (packing.psum_fallback)
        for i, g in coding.psum_fallback(flat, flat_plans, ("data",)).items():
            out[i] = g
        return tuple(out)

    specs = (P(),) + tuple(P("data") for _ in stacked)
    kw = dict(mesh=mesh, in_specs=specs, out_specs=tuple(P() for _ in stacked),
              axis_names={"data"}, check_vma=False)
    a = jax.jit(jax.shard_map(per_leaf, **kw))(W, *stacked)
    b = jax.jit(jax.shard_map(packed, **kw))(W, *stacked)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


MIXED_SHAPES = [(64,), (6, 8, 5), (7,), (16, 3)]   # (7,) -> psum fallback


@pytest.mark.parametrize("schedule", ["gather", "a2a"])
@pytest.mark.parametrize("wire", ["float32", "bfloat16"])
def test_packed_decode_bitwise_equals_per_leaf_ref(schedule, wire):
    _parity_case(MIXED_SHAPES, schedule, wire, "ref")


@pytest.mark.parametrize("schedule", ["gather", "a2a"])
def test_packed_decode_bitwise_equals_per_leaf_interpret(schedule):
    _parity_case(MIXED_SHAPES, schedule, "float32", "interpret")


# ------------------------------------------------------- full-step parity
@functools.lru_cache(maxsize=None)
def _step_params(schedule: str, wire: str, packed: bool, ms: int = 1):
    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=64)
    mesh = make_local_mesh(N, ms)
    opt = get_optimizer("sgd", 1e-2)
    arts = make_coded_train_step(
        cfg, CODE, mesh, opt,
        spec=coding.SchemeSpec(schedule=schedule, encode_dtype=wire,
                               packed=packed))
    rng = np.random.default_rng(5)
    placed = jax.tree.map(jnp.asarray, CodedBatcher(CODE).place(
        make_synthetic_batch(rng, cfg, 16, 0)))
    fn = arts.compiled(placed)
    params = model_api.init(jax.random.PRNGKey(7), cfg)
    inp = arts.step_inputs([2])
    p2, _, _ = fn(params, opt.init(params), placed,
                  inp["W"], inp["mask"], inp["rho"])
    return p2, arts


def _max_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                           - y.astype(jnp.float32)))), a, b)))


@pytest.mark.parametrize("schedule", ["gather", "a2a"])
@pytest.mark.parametrize("wire", ["float32", "bfloat16"])
def test_packed_step_bitwise_equals_per_leaf(schedule, wire):
    a, arts = _step_params(schedule, wire, True)
    b, _ = _step_params(schedule, wire, False)
    assert _max_diff(a, b) == 0.0
    assert arts.pack_plan is not None and arts.pack_plan.num_coded_leaves == 1


@pytest.mark.parametrize("schedule", ["gather", "a2a"])
def test_packed_step_degraded_path_bitwise(schedule):
    """(4, 2) mesh: the packed decode's collectives run with a GSPMD-auto
    model axis beside the manual data axis — still equal to per-leaf."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    a, _ = _step_params(schedule, "float32", True, ms=2)
    b, _ = _step_params(schedule, "float32", False, ms=2)
    assert _max_diff(a, b) == 0.0


def test_packed_is_default_and_escape_hatch_exposed():
    _, arts = _step_params("gather", "float32", True)
    assert arts.pack_plan is not None
    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=64)
    arts2 = make_coded_train_step(cfg, CODE, make_local_mesh(N, 1),
                                  get_optimizer("sgd", 1e-2), packed=False)
    assert arts2.pack_plan is None


# ------------------------------------------------- hypothesis property test
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # optional at runtime
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @st.composite
    def leaf_shape_sets(draw):
        """1-4 leaves; dims chosen so some leaves are coded (divisible by
        m * n for a2a) and some fall back to psum (odd dims)."""
        k = draw(st.integers(1, 4))
        shapes = []
        for _ in range(k):
            rank = draw(st.integers(1, 3))
            coded = draw(st.booleans())
            if coded:
                lead = M * N * draw(st.integers(1, 4))
                rest = [draw(st.sampled_from([1, 2, 3, 5])) for _ in range(rank - 1)]
                shapes.append(tuple([lead] + rest))
            else:
                shapes.append(tuple(draw(st.sampled_from([3, 7, 11]))
                                    for _ in range(rank)))
        return shapes

    @settings(max_examples=12, deadline=None)
    @given(leaf_shape_sets(),
           st.sampled_from(["gather", "a2a"]),
           st.sampled_from(["float32", "bfloat16"]),
           st.sampled_from(["ref", "interpret"]),
           st.integers(0, 2**31 - 1))
    def test_property_packed_equals_per_leaf(shapes, schedule, wire, backend,
                                             seed):
        if len(jax.devices()) < N:
            pytest.skip(f"needs {N} devices")
        _parity_case(shapes, schedule, wire, backend, seed=seed)
