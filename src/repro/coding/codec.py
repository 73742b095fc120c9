"""The ``Codec``: one object owning the coded-aggregation lifecycle.

A codec binds a gradient code to an aggregation ``Schedule`` and a compute
``CodecBackend`` and exposes the four phases the train step needs:

  plan    — choose each leaf's grouping dimension (``plan_tree``),
  pack    — lay every coded encoding into bucketed ``(rows, lanes)`` wire
            buffers (``packing.py``; static ``PackPlan``, O(1)
            collectives/bucket),
  encode  — fold one subset's gradient leaf into its l/m encoding rows of
            a wire bucket (eq. 17/18),
  wire    — mask stragglers + cast to the wire dtype (u16-bitcast collectives),
  decode  — run the schedule's collective choreography + contraction (eq. 19-21),
  unpack  — static row slices + ``groups_to_leaf`` back to leaf layouts.

New code families plug in by constructing a codec around their code object —
the heterogeneous-load ``repro.core.hetero.HeteroCode`` and the
partial-recovery least-squares weights both ride these same phases
unchanged: only the host-side weight solve differs
(``Codec.decode_weights(partial=True)`` returns the approximation plus its
error certificate).
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Any

import jax
import jax.numpy as jnp

if TYPE_CHECKING:  # annotation-only: keeps repro.coding import-independent
    from repro.core.schemes import GradCode

from .backends import CodecBackend, resolve_backend
from .layout import leaf_to_groups, to_slot_plane
from .packing import PackPlan, make_pack_plan, pack_bucket, unpack_bucket
from .plan import coded_fraction, plan_tree
from .schedules import Schedule, get_schedule

PyTree = Any


# -------------------------------------------------------------- the subsystem
@dataclasses.dataclass(frozen=True)
class Codec:
    """Gradient code + schedule + backend, with the leaf lifecycle methods."""
    code: GradCode
    schedule: Schedule
    backend: CodecBackend
    wire_dtype: Any = jnp.float32

    # ---- planning
    def plan(self, tree: PyTree, specs: PyTree | None = None,
             model_size: int | None = None, device=None) -> PyTree:
        """Choose every leaf's grouping dimension (``plan_tree``), honouring
        the schedule's extra divisibility (a2a slices encodings n ways);
        ``model_size`` is the mesh's model-axis size and ``device`` gives
        the leaves' device order (see ``plan_leaf``)."""
        return plan_tree(tree, specs, self.code.m,
                         self.schedule.n_split(self.code.n), model_size,
                         device)

    def coded_fraction(self, tree: PyTree, plans: PyTree) -> float:
        """Fraction of gradient bytes covered by the code (rest -> psum)."""
        return coded_fraction(tree, plans)

    # ---- fused encode (encode straight into the wire layout)
    def bucket_zeros(self, pplan: PackPlan) -> list[jax.Array]:
        """f32 zero ``(rows, lanes)`` accumulators, one per wire bucket —
        the encode fold's carry.  Padding rows are never written, so they
        stay exactly zero on the wire (matching ``pack_bucket``'s explicit
        zero rows bit-for-bit)."""
        return [jnp.zeros(b.shape, jnp.float32) for b in pplan.buckets]

    def encode_into(self, buf: jax.Array, g: jax.Array, coef: jax.Array,
                    slot) -> jax.Array:
        """Fold one subset's gradient leaf straight into its bucket slot:
        ``buf[slot rows] += encode(g, coef)`` via the backend's accumulating
        encode (in place, over the slot's rows alone), skipping the
        materialise-then-pack copy.  ``g`` must already be f32 (the fold
        accumulates in f32); returns the updated bucket."""
        m = coef.shape[0]
        x = leaf_to_groups(g, slot.plan, m)             # (m, V, *rest)
        G = to_slot_plane(x, slot.enc_shape, 1)[None]   # (1, m, rows, lanes)
        return self.backend.encode_acc(buf, G, coef.reshape(1, m),
                                       row=slot.row)

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
        """Flattened (tree-order) wire-masked encodings -> one buffer per
        bucket.  The train step never calls it (it encodes straight into
        the buckets); tests build packed buffers from known encodings with
        it."""
        return [pack_bucket(flat_leaves, b, self.wire_dtype)
                for b in pplan.buckets]

    def unpack(self, decoded_bufs, pplan: PackPlan) -> dict[int, jax.Array]:
        """Per-bucket (m, rows, lanes) decoded buffers -> {leaf_index:
        gradient leaf}."""
        out: dict[int, jax.Array] = {}
        for dec, b in zip(decoded_bufs, pplan.buckets):
            out.update(unpack_bucket(dec, b))
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

    def decode_packed(self, buf: jax.Array, W: jax.Array,
                      axis_names) -> jax.Array:
        """One bucket's collective + fused contraction: (rows, lanes) ->
        (m, rows, lanes) f32 (a flat (L,) wire -> (m, L) alike)."""
        return self.schedule.decode_packed(buf, W, axis_names, self.code.n,
                                           self.backend)


def make_codec(code: GradCode, *, schedule: str | Schedule = "gather",
               backend: str | CodecBackend = "auto",
               wire_dtype="float32") -> Codec:
    """Resolve names to objects; ``backend='auto'`` -> pallas on TPU, ref
    elsewhere (see ``backends.resolve_backend``)."""
    return Codec(code=code, schedule=get_schedule(schedule),
                 backend=resolve_backend(backend),
                 wire_dtype=jnp.dtype(wire_dtype))
