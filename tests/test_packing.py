"""Packed single-collective codec tests.

Three layers:
  1. static ``PackPlan`` unit tests — slot alignment, a2a divisibility,
     bucketing by wire dtype x effective model sharding, padding accounting
     against the schedules' ``recv_elems_per_worker`` model;
  2. codec-level parity — pack -> collective -> fused decode -> unpack
     equals a plain float64 einsum of eq. 19-21 over the gathered
     encodings on a multi-device mesh, for both schedules, both wire
     dtypes, ref and interpret backends, with mixed coded/psum-fallback
     leaves (the deterministic sweep runs always; a hypothesis property
     test widens it when hypothesis is installed);
  3. full-step parity — the coded step's update equals the uncoded psum
     step's on the paper's linear workload, on (4, 1) and (4, 2) meshes.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.coding as coding
from repro.coding.layout import from_slot_plane
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
@pytest.mark.parametrize("shape,spec,model_size,n_split,gdim", [
    # the leading dim is taken whenever it is usable, not the largest
    ((4, 2048, 6144), None, None, 1, 0),
    ((6, 8, 5), None, None, 1, 0),
    # a size-1 model axis shards nothing: its entries count as replicated
    ((4, 6144, 2048), (None, "model", None), 1, 1, 0),
    ((18992, 2048), ("model", None), 1, 1, 0),
    # ... but a real (or unknown) model axis keeps the dim whole-or-nothing
    ((18992, 2048), ("model", None), 2, 1, 1),
    ((18992, 2048), ("model", None), None, 1, 1),
    # the old rule otherwise: a2a at 4 layers, n = 4, m = 2 needs 8 | dim
    ((4, 2048, 6144), (None, None, "model"), 1, N, 2),
    ((4, 16, 128, 2048), (None, "model", None, None), 1, N, 3),
])
def test_plan_takes_the_leading_dim(shape, spec, model_size, n_split, gdim):
    plan = coding.plan_leaf(shape, spec, M, n_split, model_size)
    assert plan.coded and plan.group_dim == gdim
    assert plan.leads == (gdim == 0)


def test_plan_follows_the_device_order():
    """A leaf the device lays out with its dims swapped (an f32
    (2048, 18992) lives as (18992, 2048)) groups on its device-leading
    dim, and its encoding takes the dims in device order."""
    plan = coding.plan_leaf((2048, 18992), None, M, order=(1, 0))
    assert plan.group_dim == 1 and plan.leads and plan.order == (1, 0)
    assert enc_shape((2048, 18992), plan, M) == (18992 // M, 2048)
    # the row-major order is the default and is not stored
    assert coding.plan_leaf((4, 64), None, M, order=(0, 1)).order == ()
    assert coding.plan.device_order((3, 5, 7)) == (0, 1, 2)
    g = jnp.asarray(RNG.standard_normal((8, 6)), jnp.float32)
    p = coding.plan_leaf((8, 6), None, M, order=(1, 0))
    x = coding.leaf_to_groups(g, p, M)
    assert x.shape == (M, 3, 8)
    np.testing.assert_array_equal(np.asarray(coding.groups_to_leaf(x, p)),
                                  np.asarray(g))


@pytest.mark.parametrize("shape", [(4, 64, 256), (4, 32, 16, 128),
                                   (2, 24, 8, 384)])
def test_leaf_groups_plane_roundtrip_is_a_reshape(shape):
    """leaf -> groups -> slot plane -> leaf is the identity for stacked
    [L, a, b] and [L, D, H, hd] leaves grouped on the layer dim, and its
    jaxpr holds no transpose: every view is a reshape."""
    from repro.coding.layout import from_slot_plane, slot_plane, to_slot_plane
    plan = coding.plan_leaf(shape, None, M)
    assert plan.group_dim == 0
    es = enc_shape(shape, plan, M)
    rows, lanes, free = slot_plane(es)
    assert free and lanes == shape[-1]

    def roundtrip(g):
        x = to_slot_plane(coding.leaf_to_groups(g, plan, M), es, 1)
        assert x.shape == (M, rows, lanes)
        return coding.groups_to_leaf(from_slot_plane(x, es, 1), plan)

    g = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    np.testing.assert_array_equal(np.asarray(roundtrip(g)), np.asarray(g))
    prims = {e.primitive.name for e in jax.make_jaxpr(roundtrip)(g).eqns}
    assert prims <= {"reshape"}, prims


def test_pack_plan_buckets_by_lane_width_and_counts_free_share():
    """Each lane width is its own bucket of whole-row slots; narrow or
    unaligned planes take the flat 128-lane lift and are not copy-free."""
    tree = {"mlp": jax.ShapeDtypeStruct((4, 64, 256), jnp.float32),
            "attn": jax.ShapeDtypeStruct((4, 16, 8, 128), jnp.float32),
            "gain": jax.ShapeDtypeStruct((4, 256), jnp.float32)}
    plans = coding.plan_tree(tree, None, M)
    pp = make_pack_plan(tree, plans, m=M, n=N)
    assert sorted(b.lanes for b in pp.buckets) == [128, 256]
    for b in pp.buckets:
        assert b.rows % 16 == 0 and b.rows % N == 0
        for s in b.slots:
            assert s.row % 16 == 0 and s.row + s.rows <= b.rows
    free = {s.leaf_index: s.free for b in pp.buckets for s in b.slots}
    # tree order: attn, gain, mlp — the (2, 256) gain group is no 8-row plane
    assert free == {0: True, 1: False, 2: True}
    assert pp.free_share == (4 * 16 * 8 * 128 + 4 * 64 * 256) / M / (
        pp.unpadded_elems)


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
    # w1/w2 encode to (V, 16-model) — pattern (1,); w3 groups on its
    # leading dim, so its encoding is (V, 4, 16-model) — pattern (2,); all
    # three take the flat 128-lane lift (a 16-wide tail)
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
    """Encodings folded into zero buckets (``Codec.encode_into``) read back
    from their slots, pack into the same buffer, and unpack(decode=identity)
    returns each slot's encoding in every one of the leaf's m groups."""
    tree = {"a": jnp.asarray(RNG.standard_normal((64,)), jnp.float32),
            "b": jnp.asarray(RNG.standard_normal((6, 8, 5)), jnp.float32)}
    plans = coding.plan_tree(tree, None, M)
    flat = jax.tree.leaves(tree)
    codec = coding.make_codec(CODE, backend="ref")
    pp = make_pack_plan(tree, plans, m=M, n=N)
    bucket = pp.buckets[0]
    ones = jnp.ones((M,), jnp.float32)
    buf = codec.bucket_zeros(pp)[0]
    for s in bucket.slots:
        buf = codec.encode_into(buf, flat[s.leaf_index], ones, s)
    assert buf.shape == bucket.shape == (bucket.rows, bucket.lanes)
    enc = {s.leaf_index: from_slot_plane(buf[s.row:s.row + s.rows],
                                         s.enc_shape, 0)
           for s in bucket.slots}
    # with unit coefficients an encoding is the sum of the leaf's m groups
    for s in bucket.slots:
        np.testing.assert_allclose(
            np.asarray(enc[s.leaf_index]),
            np.asarray(coding.leaf_to_groups(flat[s.leaf_index], s.plan,
                                             M).sum(0)),
            rtol=1e-6, atol=1e-6)
    # packing the read-back encodings rebuilds the buffer, padding included
    packed = pack_bucket([enc.get(i) for i in range(len(flat))], bucket,
                         jnp.float32)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(buf))
    # fake a decode that replicates the buffer into m identical rows
    out = unpack_bucket(jnp.stack([buf] * M), bucket)
    flat_buf = buf.reshape(-1)
    for s in bucket.slots:
        x = flat[s.leaf_index]
        got = out[s.leaf_index]
        assert got.shape == x.shape
        # each group's m copies came from the same encoding element
        for grp in np.asarray(coding.leaf_to_groups(got, s.plan, M)):
            np.testing.assert_array_equal(grp, np.asarray(enc[s.leaf_index]))
        np.testing.assert_array_equal(
            np.asarray(jax.lax.slice_in_dim(flat_buf, s.offset,
                                            s.offset + s.size)),
            np.asarray(enc[s.leaf_index]).reshape(-1))


# ------------------------------------------------------- codec-level parity
def _data_mesh():
    if len(jax.devices()) < N:
        pytest.skip(f"needs {N} devices")
    return jax.make_mesh((N,), ("data",))


def _einsum_decode(stacked, W, flat_plans, wdt, schedule):
    """The plain reference: eq. 19-21 as a float64 einsum of every worker's
    encoding with the decode weights, back in the leaf's layout; the
    psum-fallback leaves summed over workers.  On the a2a schedule the
    decoded slices travel at the wire dtype, so the reference is rounded
    to it there too."""
    Wd = np.asarray(W, np.float64)
    out = []
    for f, pl in zip(stacked, flat_plans):
        x = np.asarray(f.astype(jnp.float32), np.float64)
        if not pl.coded:
            out.append(x.sum(0))
            continue
        dec = jnp.asarray(np.einsum("nm,n...->m...", Wd, x), jnp.float32)
        if schedule == "a2a":
            dec = dec.astype(wdt).astype(jnp.float32)
        out.append(np.asarray(coding.groups_to_leaf(dec, pl), np.float64))
    return out


def _parity_case(shapes, schedule, wire, backend, seed=0):
    """The packed decode of stacked encodings against the plain einsum."""
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
    got = jax.jit(jax.shard_map(packed, **kw))(W, *stacked)
    want = _einsum_decode(stacked, W, flat_plans, wdt, schedule)
    # one wire-dtype rounding of the decoded slices on a2a's second hop
    rtol = 2.0 ** -7 if schedule == "a2a" and wdt != jnp.float32 else 1e-5
    for x, y in zip(got, want):
        assert x.dtype == jnp.float32 and x.shape == y.shape
        np.testing.assert_allclose(np.asarray(x, np.float64), y, rtol=rtol,
                                   atol=1e-5)


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
def _step_params(schedule: str, wire: str, ms: int = 1):
    """One step on the paper's linear workload with worker 2 dropped (the
    psum schedule, the uncoded reference, drops nobody)."""
    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=64)
    mesh = make_local_mesh(N, ms)
    opt = get_optimizer("sgd", 1e-2)
    arts = make_coded_train_step(
        cfg, CODE, mesh, opt,
        spec=coding.SchemeSpec(schedule=schedule, encode_dtype=wire))
    rng = np.random.default_rng(5)
    placed = jax.tree.map(jnp.asarray, CodedBatcher(CODE).place(
        make_synthetic_batch(rng, cfg, 16, 0)))
    fn = arts.compiled(placed)
    params = model_api.init(jax.random.PRNGKey(7), cfg)
    inp = arts.step_inputs([] if schedule == "psum" else [2])
    p2, _, m = fn(params, opt.init(params), placed,
                  inp["W"], inp["mask"], inp["rho"])
    return p2, float(m["loss"][0]), arts


def _max_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                           - y.astype(jnp.float32)))), a, b)))


# the uncoded-reference tolerances of tests/test_coded_train.py: the f32
# wire's update within 5e-4 of psum's (test_coded_equals_uncoded), a bf16
# wire's within 5e-3 (test_bf16_wire_close_to_f32)
PARAM_TOL = {"float32": 5e-4, "bfloat16": 5e-3}


@pytest.mark.parametrize("schedule", ["gather", "a2a"])
@pytest.mark.parametrize("wire", ["float32", "bfloat16"])
def test_packed_step_bitwise_equals_per_leaf(schedule, wire):
    """The packed coded step, worker 2 dropped, trains as the uncoded
    psum step does."""
    a, loss_a, arts = _step_params(schedule, wire)
    b, loss_b, _ = _step_params("psum", "float32")
    assert _max_diff(a, b) < PARAM_TOL[wire]
    assert abs(loss_a - loss_b) < 1e-4
    assert arts.pack_plan is not None and arts.pack_plan.num_coded_leaves == 1


@pytest.mark.parametrize("schedule", ["gather", "a2a"])
def test_packed_step_degraded_path_bitwise(schedule):
    """(4, 2) mesh: the packed decode's collectives run with a GSPMD-auto
    model axis beside the manual data axis — still the psum step's update."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    a, loss_a, _ = _step_params(schedule, "float32", ms=2)
    b, loss_b, _ = _step_params("psum", "float32", ms=2)
    assert _max_diff(a, b) < PARAM_TOL["float32"]
    assert abs(loss_a - loss_b) < 1e-4


def test_packed_is_default_and_escape_hatch_exposed():
    """Every encoding schedule builds a ``PackPlan``; psum builds none."""
    for schedule in ("gather", "a2a"):
        assert _step_params(schedule, "float32")[2].pack_plan is not None
    arts = _step_params("psum", "float32")[2]
    assert arts.pack_plan is None and arts.copy_free_share == 0.0


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
        """Random leaf sets: the packed decode matches the plain einsum."""
        if len(jax.devices()) < N:
            pytest.skip(f"needs {N} devices")
        _parity_case(shapes, schedule, wire, backend, seed=seed)
