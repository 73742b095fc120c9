"""Static packing of coded-leaf encodings into bucketed wire buffers.

Decoding leaf by leaf would issue one ``all_gather``/``all_to_all`` (plus
one skinny contraction) *per coded parameter leaf*; for zoo configs with
dozens-to-hundreds of leaves the per-collective latency term alpha would
dominate exactly the way the paper's shifted-exponential T_comm model
(Sec. VI) predicts.  This module computes, once at step-build time, a
``PackPlan`` that lays every coded leaf's ``(V, *rest)`` encoding into a
few wire buffers, so each train step issues O(1) collectives per *bucket*
instead of per leaf and runs one large, aligned decode contraction over
each bucket.

A bucket is a 2-D ``(rows, lanes)`` f32 stack of rows, and each leaf's slot
is a range of whole rows: the leaf's own kernel plane
(``layout.slot_plane``).  So a leaf's gradient enters its slot, and leaves
the decoded ``(m, rows, lanes)`` buffer, by a row slice and a reshape — no
relayout of the leaf on either side.

Bucketing: leaves are grouped by (wire dtype, effective model-sharding
pattern of the encoding, lane width).  Axes of size 1 carry no data
movement, so their spec entries are dropped from the pattern ("effective"):
on a 1-sized model axis every leaf of one lane width lands in one
replicated bucket.  Leaves whose encodings really are model-sharded (>1
axis) form separate buckets per pattern.

Layout invariants (see DESIGN.md §7 for the wire-format diagram):
  - slot rows start on a multiple of ``ROW_ALIGN`` (16), so the encode
    kernel writes a slot in whole tiles;
  - each bucket's row count is divisible by lcm(16, n), so the gathered
    ``(n, rows, lanes)`` stack needs no pad and the a2a schedule can split
    it into n equal row chunks;
  - padding rows are zeros on the wire and are never read back — the
    unpack phase uses static row slices from the slot table.

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
from .layout import (ROW_ALIGN, from_slot_plane, groups_to_leaf, slot_plane,
                     to_slot_plane)
from .plan import LeafPlan

PyTree = Any

# element alignment of a flat (L,) wire, as the serving path lays out its
# own: one VPU lane row
WIRE_ALIGN = 128


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def enc_shape(shape: Sequence[int], plan: LeafPlan, m: int) -> tuple[int, ...]:
    """The ``(V, *rest)`` encoding shape of a coded leaf (the shape
    of its slot in a wire bucket: grouping dim first and split by m, the
    rest in device order)."""
    assert plan.coded
    moved = tuple(shape[d] for d in plan.group_perm(len(shape)))
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
    moved = [entries[d] for d in plan.group_perm(rank)]
    # moved[0] is the grouping dim — the planner only groups model-replicated
    # dims, so its entry never names the model axis
    return tuple(i for i, e in enumerate(moved)
                 if _mentions_model(e, model_axis))


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one coded leaf's encoding lives in its bucket: rows
    ``[row, row + rows)`` of the bucket's ``(rows, lanes)`` stack."""
    leaf_index: int            # position in the flattened (tree-order) leaves
    row: int                   # first row, a multiple of ROW_ALIGN
    rows: int                  # rows of the slot's plane
    lanes: int                 # the bucket's lane width
    size: int                  # unpadded elements = prod(enc_shape)
    enc_shape: tuple[int, ...]  # (V, *rest)
    plan: LeafPlan

    @property
    def offset(self) -> int:
        """First element of the slot in the row-major flattened bucket; the
        slot's ``size`` elements follow contiguously in encoding order."""
        return self.row * self.lanes

    @property
    def free(self) -> bool:
        """Whether leaf -> slot -> leaf is a bitcast: grouped on the leading
        dim of its device order and planed as the group's own rows and
        lanes (no flat lift)."""
        return self.plan.leads and slot_plane(self.enc_shape)[2]


@dataclasses.dataclass(frozen=True)
class WireBucket:
    """One wire buffer: a slot table over a ``(rows, lanes)`` stack."""
    key: tuple                 # (wire dtype, model-sharding pattern, lanes)
    slots: tuple[LeafSlot, ...]
    rows: int                  # padded: ROW_ALIGN- and n-divisible
    lanes: int
    unpadded: int              # sum of slot sizes

    @property
    def shape(self) -> tuple[int, int]:
        """The bucket buffer's ``(rows, lanes)``."""
        return self.rows, self.lanes

    @property
    def size(self) -> int:
        """Padded elements on the wire."""
        return self.rows * self.lanes

    @property
    def padding(self) -> int:
        """Zero elements added for row alignment and the n-divisible tail."""
        return self.size - self.unpadded

    @functools.lru_cache(maxsize=None)
    def worker_chunk_slots(self, n: int) -> tuple[tuple, ...]:
        """Ragged per-worker view of the a2a chunking of this bucket.

        The a2a schedule splits the bucket's rows into ``n`` equal chunks
        and worker ``p`` decodes chunk ``p`` — but the *slot* boundaries do
        not align with the chunk boundaries, so each worker covers a ragged
        set of (possibly partial) leaf segments.  Returns, per worker, a
        tuple of ``(leaf_index, elem_lo, elem_hi)`` triples in that leaf's
        flattened-encoding coordinates.  The union over workers tiles every
        slot exactly once (asserted in tests) — the accounting used to
        attribute per-worker decode work under heterogeneous loads.

        Memoized (the dataclass is frozen and hashable): the O(n * slots)
        scan runs at Python trace time inside every step (re)trace and the
        tuning loop asks for the same (bucket, n) pair constantly.
        """
        assert self.rows % n == 0, \
            f"bucket rows {self.rows} not n={n}-divisible"
        chunk = self.rows // n
        out = []
        for p in range(n):
            lo_p, hi_p = p * chunk, (p + 1) * chunk
            segs = []
            for s in self.slots:
                lo = (max(s.row, lo_p) - s.row) * self.lanes
                hi = min((min(s.row + s.rows, hi_p) - s.row) * self.lanes,
                         s.size)
                if lo < hi:
                    segs.append((s.leaf_index, lo, hi))
            out.append(tuple(segs))
        return tuple(out)


@dataclasses.dataclass(frozen=True)
class PackPlan:
    """Static wire layout for every coded leaf of a parameter tree."""
    buckets: tuple[WireBucket, ...]
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

    @property
    def free_share(self) -> float:
        """Share of the coded gradient's elements whose path from backward
        to the optimizer holds no relayout: grouped on the leading dim and
        planed by a bitcast (``LeafSlot.free``)."""
        free = sum(s.size for b in self.buckets for s in b.slots if s.free)
        return free / max(self.unpadded_elems, 1)

    def recv_elems_per_worker(self, schedule) -> float:
        """Padding-exact wire cost under ``schedule``'s own model: the
        schedule takes the pre-encoding gradient length l and divides by m
        internally, so feeding it l = padded_elems * m yields exactly what
        the padded buffers transmit (the per-leaf prediction summed over
        leaves, plus the explicit row padding)."""
        return schedule.recv_elems_per_worker(
            float(self.padded_elems * self.m), self.n, self.m)


def make_pack_plan(tree: PyTree, plans: PyTree, *, m: int, n: int,
                   specs: PyTree | None = None, model_size: int = 1,
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
        lanes = slot_plane(es)[1]
        groups.setdefault((dtype_name, pattern, lanes), []).append((i, es, pl))

    chunk = math.lcm(ROW_ALIGN, n)   # bucket rows: aligned AND n-divisible
    buckets = []
    for key in sorted(groups):
        lanes = key[2]
        row = 0
        slots = []
        for i, es, pl in groups[key]:
            rows = slot_plane(es)[0]
            slots.append(LeafSlot(leaf_index=i, row=row, rows=rows,
                                  lanes=lanes, size=math.prod(es),
                                  enc_shape=es, plan=pl))
            row += _round_up(rows, ROW_ALIGN)
        buckets.append(WireBucket(
            key=key, slots=tuple(slots), rows=_round_up(row, chunk),
            lanes=lanes, unpadded=sum(s.size for s in slots)))
    return PackPlan(buckets=tuple(buckets), n=n, m=m, wire_dtype=dtype_name)


# ------------------------------------------------------------ traced phases
def pack_bucket(flat_leaves: Sequence[jax.Array], bucket: WireBucket,
                dtype) -> jax.Array:
    """Lay the bucket's slot encodings (``(V, *rest)``, already in the wire
    dtype after ``Codec.to_wire``) into its ``(rows, lanes)`` buffer with
    zero rows at the alignment gaps and the tail.  The train step encodes
    straight into the slots instead (``Codec.encode_into``); this is the
    spelling for encodings made elsewhere."""
    dtype = jnp.dtype(dtype)
    parts: list[jax.Array] = []
    pos = 0
    for s in bucket.slots:
        if s.row > pos:
            parts.append(jnp.zeros((s.row - pos, bucket.lanes), dtype))
        parts.append(to_slot_plane(flat_leaves[s.leaf_index], s.enc_shape,
                                   0).astype(dtype))
        pos = s.row + s.rows
    if bucket.rows > pos:
        parts.append(jnp.zeros((bucket.rows - pos, bucket.lanes), dtype))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-2)


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


def unpack_bucket(decoded: jax.Array, bucket: WireBucket) -> dict[int, jax.Array]:
    """Invert the packing on the decoded ``(m, rows, lanes)`` buffer: a
    static row slice per slot, viewed back through ``groups_to_leaf`` in
    each leaf's original layout.  Returns {leaf_index: gradient leaf}."""
    out: dict[int, jax.Array] = {}
    for s in bucket.slots:
        seg = jax.lax.slice_in_dim(decoded, s.row, s.row + s.rows, axis=1)
        out[s.leaf_index] = groups_to_leaf(
            from_slot_plane(seg, s.enc_shape, 1), s.plan)
    return out
