"""The ``Codec``: one object owning the per-leaf coded-aggregation lifecycle.

A codec binds a gradient code to an aggregation ``Schedule`` and a compute
``CodecBackend`` and exposes the four phases the train step needs:

  plan    — choose each leaf's grouping dimension (``plan_tree``),
  encode  — fold one subset's gradient into the l/m encoding (eq. 17/18),
  wire    — mask stragglers + cast to the wire dtype (u16-bitcast collectives),
  pack    — lay every coded encoding into bucketed flat wire buffers
            (``packing.py``; static ``PackPlan``, O(1) collectives/bucket),
  decode  — run the schedule's collective choreography + contraction (eq. 19-21),
  unpack  — static slices + ``groups_to_leaf`` back to leaf layouts.

New code families plug in by constructing a codec around their code object —
the heterogeneous-load ``repro.core.hetero.HeteroCode`` and the
partial-recovery least-squares weights both ride these same phases
unchanged: only the host-side weight solve differs
(``Codec.decode_weights(partial=True)`` returns the approximation plus its
error certificate).
"""
from __future__ import annotations

import dataclasses
import math
from typing import TYPE_CHECKING, Any

import jax
import jax.numpy as jnp

if TYPE_CHECKING:  # annotation-only: keeps repro.coding import-independent
    from repro.core.schemes import GradCode

from . import wire
from .backends import CodecBackend, RefBackend, resolve_backend
from .layout import flatten_rest, leaf_to_groups, unflatten_rest
from .packing import (PackPlan, make_pack_plan, pack_bucket,
                      pack_param_groups, unpack_bucket, unpack_param_groups)
from .plan import LeafPlan, coded_fraction, plan_tree
from .schedules import Schedule, get_schedule

PyTree = Any
_REF = RefBackend()


# --------------------------------------------------- functional encode layer
def encode_leaf(g: jax.Array, coef: jax.Array, plan: LeafPlan,
                backend: CodecBackend = _REF) -> jax.Array:
    """Fold one subset's gradient leaf into the l/m-sized encoding.

    g: (..., Dg, ...);  coef: (m,)  ->  (Dg/m, *rest) contribution.
    The fold is the d=1 slice of the canonical (d, m, V[, R]) contraction, so
    both backends serve it.
    """
    assert plan.coded
    m = coef.shape[0]
    x = leaf_to_groups(g, plan, m)                  # (m, V, *rest)
    rest = x.shape[2:]
    G = flatten_rest(x, 2)[None]                    # (1, m, V[, R])
    out = backend.encode(G, coef.reshape(1, m), out_dtype=g.dtype)
    return unflatten_rest(out, 1, rest)             # (V, *rest)


def encode_tree(grads: PyTree, coef: jax.Array, plans: PyTree,
                backend: CodecBackend = _REF) -> tuple[PyTree, PyTree]:
    """Split one subset-gradient tree into (coded contributions, psum leaves).

    coef: (m,) — the C[i, j, :] row for this worker/subset.
    Returns (encoded_tree_or_None_per_leaf, smalls_tree_or_None_per_leaf).
    """
    enc = jax.tree.map(
        lambda g, p: encode_leaf(g, coef, p, backend) if p.coded else None,
        grads, plans)
    small = jax.tree.map(
        lambda g, p: None if p.coded else g, grads, plans)
    return enc, small


def decode_tree(enc: PyTree, smalls: PyTree, W: jax.Array, rho_i: jax.Array,
                plans: PyTree, axis_names, n: int, schedule: str = "gather",
                backend: CodecBackend = _REF) -> PyTree:
    """Aggregate: decode coded leaves, rho-weighted psum for small leaves.

    enc   : pytree with (Dg/m, *rest) arrays at coded leaves, None elsewhere
    smalls: pytree with summed rho-weighted small-leaf grads, None elsewhere
    W     : (n, m); rho_i applied upstream (see coded_step).
    """
    sched = get_schedule(schedule)

    def dec_one(e, sm, p):
        if p.coded:
            return sched.decode_leaf(e, W, p, axis_names, n, backend)
        return wire.psum(sm, axis_names)

    return jax.tree.map(dec_one, enc, smalls, plans,
                        is_leaf=lambda x: x is None)


# -------------------------------------------------------------- the subsystem
@dataclasses.dataclass(frozen=True)
class Codec:
    """Gradient code + schedule + backend, with the leaf lifecycle methods."""
    code: GradCode
    schedule: Schedule
    backend: CodecBackend
    wire_dtype: Any = jnp.float32

    # ---- planning
    def plan(self, tree: PyTree, specs: PyTree | None = None) -> PyTree:
        """Choose every leaf's grouping dimension (``plan_tree``), honouring
        the schedule's extra divisibility (a2a slices encodings n ways)."""
        return plan_tree(tree, specs, self.code.m,
                         self.schedule.n_split(self.code.n))

    def coded_fraction(self, tree: PyTree, plans: PyTree) -> float:
        """Fraction of gradient bytes covered by the code (rest -> psum)."""
        return coded_fraction(tree, plans)

    # ---- encode
    def encode_leaf(self, g: jax.Array, coef: jax.Array,
                    plan: LeafPlan) -> jax.Array:
        """Fold one subset's gradient leaf into the l/m encoding with this
        worker's coefficient row (paper eq. 17/18) on the bound backend."""
        return encode_leaf(g, coef, plan, self.backend)

    def encoding_zero(self, p, plan: LeafPlan) -> jax.Array:
        """f32 zero accumulator in the encoding layout of leaf ``p``."""
        if not plan.coded:
            return jnp.zeros(p.shape, jnp.float32)
        x = jnp.moveaxis(jnp.zeros(p.shape, jnp.float32), plan.group_dim, 0)
        return jnp.zeros((x.shape[0] // self.code.m, *x.shape[1:]), jnp.float32)

    # ---- fused encode (encode straight into the wire layout)
    def bucket_acc_zeros(self, pplan: PackPlan) -> list[jax.Array]:
        """Flat f32 zero accumulators, one per wire bucket — the fused
        encode fold's carry.  Alignment gaps and the n-divisible tail are
        never written, so they stay exactly zero on the wire (matching
        ``pack_bucket``'s explicit zero padding bit-for-bit)."""
        return [jnp.zeros((b.size,), jnp.float32) for b in pplan.buckets]

    def encode_into(self, buf: jax.Array, g: jax.Array, coef: jax.Array,
                    slot) -> jax.Array:
        """Fold one subset's gradient leaf straight into its bucket slot:
        ``buf[slot] += encode(g, coef)`` via the backend's accumulating
        encode, skipping the materialise-then-pack copy of the sync path.
        ``g`` must already be f32 (the fold accumulates in f32, exactly like
        the per-leaf path's ``encoding_zero`` carry); returns the updated
        flat buffer."""
        m = coef.shape[0]
        x = leaf_to_groups(g, slot.plan, m)             # (m, V, *rest)
        rest = x.shape[2:]
        G = flatten_rest(x, 2)[None]                    # (1, m, V[, R])
        acc = jax.lax.slice_in_dim(buf, slot.offset, slot.offset + slot.size)
        if rest:
            acc = acc.reshape(slot.enc_shape[0], math.prod(rest))
        acc = self.backend.encode_acc(acc, G, coef.reshape(1, m))
        return buf.at[slot.offset:slot.offset + slot.size].set(
            acc.reshape(-1))

    # ---- wire
    def to_wire(self, e: jax.Array, mask_i: jax.Array) -> jax.Array:
        """Mask the straggler payload (transmits nothing) + cast to the wire."""
        return (e * mask_i).astype(jnp.dtype(self.wire_dtype))

    # ---- pack / unpack
    def pack_plan(self, tree: PyTree, plans: PyTree, *,
                  specs: PyTree | None = None,
                  model_size: int = 1) -> PackPlan:
        """Static wire layout of every coded leaf (see ``packing.py``)."""
        return make_pack_plan(tree, plans, m=self.code.m, n=self.code.n,
                              specs=specs, model_size=model_size,
                              wire_dtype=self.wire_dtype)

    def pack(self, flat_leaves, pplan: PackPlan) -> list[jax.Array]:
        """Flattened (tree-order) wire-masked leaves -> one flat buffer per
        bucket."""
        return [pack_bucket(flat_leaves, b, self.wire_dtype)
                for b in pplan.buckets]

    def unpack(self, decoded_bufs, pplan: PackPlan) -> dict[int, jax.Array]:
        """Per-bucket (m, L) decoded buffers -> {leaf_index: gradient leaf}."""
        out: dict[int, jax.Array] = {}
        for dec, b in zip(decoded_bufs, pplan.buckets):
            out.update(unpack_bucket(dec, b))
        return out

    def pack_params(self, flat_leaves, pplan: PackPlan) -> list[jax.Array]:
        """Param/momentum leaves -> one (m, L) f32 bucket-layout view per
        bucket, row-aligned with the decoded gradient buffers (the fused
        decode-plus-apply operands; see ``packing.pack_param_groups``)."""
        return [pack_param_groups(flat_leaves, b, self.code.m)
                for b in pplan.buckets]

    def unpack_params(self, bufs, pplan: PackPlan,
                      flat_like) -> dict[int, jax.Array]:
        """Updated (m, L) buffers -> {leaf_index: leaf}, cast back to each
        leaf's dtype (``flat_like`` supplies the originals)."""
        out: dict[int, jax.Array] = {}
        for buf, b in zip(bufs, pplan.buckets):
            out.update(unpack_param_groups(buf, b, flat_like))
        return out

    # ---- decode
    def decode_weights(self, responders, *, partial: bool = False):
        """Host-side float64 decode-weight solve for a responder set.

        With ``partial=False`` (the paper's regime) the exact weights are
        returned and fewer than ``n - s`` responders raise.  With
        ``partial=True`` *any* responder set is accepted: returns the
        ``(W, err_factor)`` pair of the least-squares approximation, where
        ``err_factor * sqrt(sum_j ||g_j||^2)`` upper-bounds the L2 decode
        error (see :mod:`repro.core.hetero`).  The runtime decode phases
        below consume ``W`` unchanged either way — degradation is purely a
        property of the weights.
        """
        if partial:
            return self.code.partial_decode_weights(responders)
        return self.code.decode_weights(responders)

    def decode_leaf(self, f_leaf: jax.Array, W: jax.Array, plan: LeafPlan,
                    axis_names) -> jax.Array:
        """Decode one coded leaf via the bound schedule's choreography
        (see ``Schedule.decode_leaf``)."""
        return self.schedule.decode_leaf(f_leaf, W, plan, axis_names,
                                         self.code.n, self.backend)

    def decode_packed(self, buf: jax.Array, W: jax.Array,
                      axis_names) -> jax.Array:
        """One bucket's collective + fused contraction: (L,) -> (m, L) f32."""
        return self.schedule.decode_packed(buf, W, axis_names, self.code.n,
                                           self.backend)

    def decode_apply_packed(self, buf: jax.Array, W: jax.Array, P: jax.Array,
                            MU: jax.Array, axis_names, *, lr: float,
                            momentum: float, scale: float):
        """One bucket's collective + fused decode-and-SGD-momentum apply on
        its (m, L) param/momentum views: returns (p', mu', sum(g*g)).  See
        ``Schedule.decode_apply_packed``."""
        return self.schedule.decode_apply_packed(
            buf, W, P, MU, axis_names, self.code.n, self.backend, lr=lr,
            momentum=momentum, scale=scale)


def make_codec(code: GradCode, *, schedule: str | Schedule = "gather",
               backend: str | CodecBackend = "auto",
               wire_dtype="float32") -> Codec:
    """Resolve names to objects; ``backend='auto'`` -> pallas on TPU, ref
    elsewhere (see ``backends.resolve_backend``)."""
    return Codec(code=code, schedule=get_schedule(schedule),
                 backend=resolve_backend(backend),
                 wire_dtype=jnp.dtype(wire_dtype))
