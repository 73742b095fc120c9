"""Static packing of coded-leaf encodings into bucketed flat wire buffers.

The per-leaf decode path issues one ``all_gather``/``all_to_all`` (plus one
skinny contraction) *per coded parameter leaf*; for zoo configs with
dozens-to-hundreds of leaves the per-collective latency term alpha dominates
exactly the way the paper's shifted-exponential T_comm model (Sec. VI)
predicts.  This module computes, once at step-build time, a ``PackPlan``
that lays every coded leaf's flattened ``(V, *rest)`` encoding into one (or
a few) flat wire buffers, so each train step issues O(1) collectives per
*bucket* instead of per leaf and runs one large, aligned decode contraction
over the packed buffer.

Bucketing: leaves are grouped by (wire dtype, effective model-sharding
pattern of the encoding).  Axes of size 1 carry no data movement, so their
spec entries are dropped from the pattern ("effective"): on a 1-sized model
axis everything lands in a single replicated bucket.  Leaves whose encodings
really are model-sharded (>1 axis) form separate buckets per pattern — the
flat layout costs them a GSPMD reshard over the model axis, a trade made
visible (and separable) by the bucket key rather than hidden per leaf.

Layout invariants (see DESIGN.md §7 for the wire-format diagram):
  - slot offsets are ``align`` (default 128) element-aligned, so the fused
    decode kernel always sees lane-aligned tiles;
  - each bucket's padded length is divisible by lcm(align, n), so the a2a
    schedule can split it into n equal chunks without per-leaf divisibility
    constraints;
  - padding elements are zeros on the wire and are never read back — the
    unpack phase uses static slices from the slot table.

All padding is explicit: ``PackPlan.padded_elems`` vs ``unpadded_elems`` is
the exact wire overhead, reported by the ``coding_packed`` bench next to the
schedule's ``recv_elems_per_worker`` prediction.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import wire
from .layout import groups_to_leaf, leaf_to_groups
from .plan import LeafPlan

PyTree = Any

# element alignment of slot offsets and bucket lengths: one VPU lane row
WIRE_ALIGN = 128


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def enc_shape(shape: Sequence[int], plan: LeafPlan, m: int) -> tuple[int, ...]:
    """The ``(V, *rest)`` encoding shape of a coded leaf (the shape
    ``encode_leaf`` produces: grouping dim moved first and split by m)."""
    assert plan.coded
    k = plan.group_dim
    moved = (shape[k],) + tuple(shape[:k]) + tuple(shape[k + 1:])
    return (moved[0] // m,) + moved[1:]


def _mentions_model(entry, model_axis: str) -> bool:
    if entry is None:
        return False
    if isinstance(entry, tuple):
        return model_axis in entry
    return entry == model_axis


def sharding_pattern(spec, plan: LeafPlan, rank: int, model_size: int,
                     model_axis: str = "model") -> tuple[int, ...]:
    """Indices of the *encoding* dims (``(V, *rest)`` order) that are
    effectively model-sharded.  () when the model axis is trivial (size 1)
    or the spec is unknown — such encodings pack into the replicated bucket."""
    if spec is None or model_size <= 1:
        return ()
    entries = list(spec) + [None] * (rank - len(list(spec)))
    k = plan.group_dim
    moved = [entries[k]] + entries[:k] + entries[k + 1:]
    # moved[0] is the grouping dim — the planner only groups model-replicated
    # dims, so its entry never names the model axis
    return tuple(i for i, e in enumerate(moved)
                 if _mentions_model(e, model_axis))


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one coded leaf's flattened encoding lives in its bucket."""
    leaf_index: int            # position in the flattened (tree-order) leaves
    offset: int                # start element in the bucket's flat buffer
    size: int                  # unpadded elements = prod(enc_shape)
    enc_shape: tuple[int, ...]  # (V, *rest)
    plan: LeafPlan


@dataclasses.dataclass(frozen=True)
class WireBucket:
    """One flat wire buffer: a slot table plus its padded length."""
    key: tuple                 # (wire dtype name, model-sharding pattern)
    slots: tuple[LeafSlot, ...]
    size: int                  # padded length: align-multiple and n-divisible
    unpadded: int              # sum of slot sizes

    @property
    def padding(self) -> int:
        """Zero elements added for alignment and the n-divisible tail."""
        return self.size - self.unpadded

    @functools.lru_cache(maxsize=None)
    def worker_chunk_slots(self, n: int) -> tuple[tuple, ...]:
        """Ragged per-worker view of the a2a chunking of this bucket.

        The a2a schedule splits the ``size``-element buffer into ``n`` equal
        chunks and worker ``p`` decodes chunk ``p`` — but the *slot*
        boundaries do not align with the chunk boundaries, so each worker
        covers a ragged set of (possibly partial) leaf segments.  Returns,
        per worker, a tuple of ``(leaf_index, elem_lo, elem_hi)`` triples in
        that leaf's flattened-encoding coordinates.  The union over workers
        tiles every slot exactly once (asserted in tests) — the accounting
        used to attribute per-worker decode work under heterogeneous loads.

        Memoized (the dataclass is frozen and hashable): the O(n * slots)
        scan runs at Python trace time inside every step (re)trace and the
        tuning loop asks for the same (bucket, n) pair constantly.
        """
        assert self.size % n == 0, f"bucket size {self.size} not n={n}-divisible"
        chunk = self.size // n
        out = []
        for p in range(n):
            lo_p, hi_p = p * chunk, (p + 1) * chunk
            segs = []
            for s in self.slots:
                lo = max(s.offset, lo_p)
                hi = min(s.offset + s.size, hi_p)
                if lo < hi:
                    segs.append((s.leaf_index, lo - s.offset, hi - s.offset))
            out.append(tuple(segs))
        return tuple(out)


@dataclasses.dataclass(frozen=True)
class PackPlan:
    """Static wire layout for every coded leaf of a parameter tree."""
    buckets: tuple[WireBucket, ...]
    align: int
    n: int                     # data-parallel degree (a2a chunk divisor)
    m: int                     # the code's group size (encoding = l/m elems)
    wire_dtype: str

    @property
    def padded_elems(self) -> int:
        """Total elements actually put on the wire per worker."""
        return sum(b.size for b in self.buckets)

    @property
    def unpadded_elems(self) -> int:
        """Total payload elements (sum of coded-leaf encoding sizes)."""
        return sum(b.unpadded for b in self.buckets)

    @property
    def num_coded_leaves(self) -> int:
        """Total coded leaves across every bucket's slot table."""
        return sum(len(b.slots) for b in self.buckets)

    def recv_elems_per_worker(self, schedule) -> float:
        """Padding-exact wire cost under ``schedule``'s own model: the
        schedule takes the pre-encoding gradient length l and divides by m
        internally, so feeding it l = padded_elems * m yields exactly what
        the padded buffers transmit (the per-leaf prediction summed over
        leaves, plus the explicit alignment padding)."""
        return schedule.recv_elems_per_worker(
            float(self.padded_elems * self.m), self.n, self.m)


def make_pack_plan(tree: PyTree, plans: PyTree, *, m: int, n: int,
                   specs: PyTree | None = None, model_size: int = 1,
                   align: int = WIRE_ALIGN,
                   wire_dtype="float32") -> PackPlan:
    """Compute the static wire layout from the leaf plans.

    tree:  params pytree (arrays or ShapeDtypeStructs);
    plans: matching ``LeafPlan`` tree (``plan_tree`` output);
    specs: optional PartitionSpec tree — only used for bucketing keys;
    model_size: size of the mesh's model axis (1 collapses every pattern).
    """
    flat, treedef = jax.tree.flatten(tree)
    flat_plans = treedef.flatten_up_to(plans)
    if specs is not None:
        flat_specs = treedef.flatten_up_to(specs)
    else:
        flat_specs = [None] * len(flat)
    dtype_name = str(jnp.dtype(wire_dtype))

    groups: dict[tuple, list[tuple[int, tuple[int, ...], LeafPlan]]] = {}
    for i, (x, pl, sp) in enumerate(zip(flat, flat_plans, flat_specs)):
        if pl is None or not pl.coded:
            continue
        es = enc_shape(tuple(x.shape), pl, m)
        pattern = sharding_pattern(
            tuple(sp) if sp is not None else None, pl, len(x.shape), model_size)
        groups.setdefault((dtype_name, pattern), []).append((i, es, pl))

    chunk = math.lcm(align, n)   # bucket length: aligned AND n-divisible
    buckets = []
    for key in sorted(groups):
        off = 0
        slots = []
        for i, es, pl in groups[key]:
            off = _round_up(off, align)
            size = int(np.prod(es))
            slots.append(LeafSlot(leaf_index=i, offset=off, size=size,
                                  enc_shape=es, plan=pl))
            off += size
        buckets.append(WireBucket(
            key=key, slots=tuple(slots),
            size=_round_up(off, chunk),
            unpadded=sum(s.size for s in slots)))
    return PackPlan(buckets=tuple(buckets), align=align, n=n, m=m,
                    wire_dtype=dtype_name)


# ------------------------------------------------------------ traced phases
def pack_bucket(flat_leaves: Sequence[jax.Array], bucket: WireBucket,
                dtype) -> jax.Array:
    """Concatenate the bucket's slot encodings (flattened, already in the
    wire dtype after ``Codec.to_wire``) with zero padding at the alignment
    gaps and the tail.  Pure reshape/concat — fused by XLA."""
    dtype = jnp.dtype(dtype)
    parts: list[jax.Array] = []
    pos = 0
    for s in bucket.slots:
        if s.offset > pos:
            parts.append(jnp.zeros((s.offset - pos,), dtype))
        parts.append(flat_leaves[s.leaf_index].reshape(-1).astype(dtype))
        pos = s.offset + s.size
    if bucket.size > pos:
        parts.append(jnp.zeros((bucket.size - pos,), dtype))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def psum_fallback(flat_leaves: Sequence[jax.Array], flat_plans,
                  axis_names) -> dict[int, jax.Array]:
    """Aggregate the non-coded leaves through ONE concatenated all-reduce
    (instead of one psum per leaf) and slice the sums back out.  Returns
    {leaf_index: summed leaf}; empty when every leaf is coded."""
    small_ix = [i for i, pl in enumerate(flat_plans)
                if pl is None or not pl.coded]
    if not small_ix:
        return {}
    sbuf = (jnp.concatenate([flat_leaves[i].reshape(-1) for i in small_ix])
            if len(small_ix) > 1 else flat_leaves[small_ix[0]].reshape(-1))
    ssum = wire.psum(sbuf, axis_names)
    out: dict[int, jax.Array] = {}
    off = 0
    for i in small_ix:
        sz = int(np.prod(flat_leaves[i].shape))
        out[i] = jax.lax.slice_in_dim(ssum, off, off + sz).reshape(
            flat_leaves[i].shape)
        off += sz
    return out


def pack_param_groups(flat_leaves: Sequence[jax.Array],
                      bucket: WireBucket, m: int) -> jax.Array:
    """Lay the bucket's *parameter* (or optimizer-state) leaves out in the
    decoded-buffer layout: an ``(m, bucket.size)`` f32 view whose columns
    ``[slot.offset, slot.offset + slot.size)`` hold leaf ``slot.leaf_index``
    exactly where ``unpack_bucket`` reads that leaf's decoded gradient.

    This is the fused decode-plus-apply path's input: with params and
    momentum in this layout, the per-bucket kernel can run the optimizer
    update right after the decode contraction without unpacking.  Columns
    in the alignment gaps and the tail are zeros (their decoded gradient is
    zero too, so the update fixes them at zero)."""
    parts: list[jax.Array] = []
    pos = 0
    for s in bucket.slots:
        if s.offset > pos:
            parts.append(jnp.zeros((m, s.offset - pos), jnp.float32))
        x = leaf_to_groups(
            flat_leaves[s.leaf_index].astype(jnp.float32), s.plan, m)
        parts.append(x.reshape(m, s.size))
        pos = s.offset + s.size
    if bucket.size > pos:
        parts.append(jnp.zeros((m, bucket.size - pos), jnp.float32))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def unpack_param_groups(buf: jax.Array, bucket: WireBucket,
                        flat_like: Sequence[Any]) -> dict[int, jax.Array]:
    """Invert ``pack_param_groups``: slice the updated ``(m, bucket.size)``
    buffer back into leaf layouts, cast to each leaf's original dtype
    (``flat_like`` supplies the dtypes).  Returns {leaf_index: leaf}."""
    out = unpack_bucket(buf, bucket)
    return {i: v.astype(flat_like[i].dtype) for i, v in out.items()}


def unpack_bucket(decoded: jax.Array, bucket: WireBucket) -> dict[int, jax.Array]:
    """Invert the packing on the decoded ``(m, bucket.size)`` buffer: static
    slices from the slot table, reshaped back through ``groups_to_leaf`` into
    each leaf's original layout.  Returns {leaf_index: gradient leaf}."""
    m = decoded.shape[0]
    out: dict[int, jax.Array] = {}
    for s in bucket.slots:
        seg = jax.lax.slice_in_dim(decoded, s.offset, s.offset + s.size,
                                   axis=1)                    # (m, size)
        x = seg.reshape(m, *s.enc_shape)                      # (m, V, *rest)
        out[s.leaf_index] = groups_to_leaf(x, s.plan)
    return out
