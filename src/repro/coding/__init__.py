"""Codec subsystem: the paper's gradient coding as a pluggable pipeline.

  plan     — per-leaf grouping-dimension choice (``plan.py``)
  encode   — fold subset gradients into l/m encodings in the wire buckets
             (``codec.py``)
  wire     — wire-dtype collectives with the u16 bitcast trick (``wire.py``)
  pack     — bucketed flat wire buffers, O(1) collectives/bucket (``packing.py``)
  decode   — gather / a2a / psum schedules (``schedules.py``)
  backends — ref einsum vs Pallas kernels, auto-dispatched (``backends.py``)

Entry points: ``make_codec(code, schedule=..., backend=..., wire_dtype=...)``
for the raw codec, and ``SchemeSpec`` (``spec.py``) — the frozen value object
consolidating every scheme lever — consumed by ``make_coded_train_step``,
the ``Trainer`` and the serving ``CodedServer`` alike.
"""
from .backends import (BACKEND_NAMES, CodecBackend, PallasBackend, RefBackend,
                       resolve_backend)
from .codec import Codec, make_codec
from .inputs import (admit_code, coding_worker_index, make_step_inputs,
                     uncovered_subsets)
from .layout import groups_to_leaf, leaf_to_groups
from .packing import (WIRE_ALIGN, LeafSlot, PackPlan, WireBucket, enc_shape,
                      make_pack_plan, pack_bucket, psum_fallback,
                      unpack_bucket)
from .plan import LeafPlan, coded_fraction, plan_leaf, plan_tree
from .schedules import (SCHEDULES, AllToAllSchedule, GatherSchedule,
                        PsumSchedule, Schedule, get_schedule)
from .spec import SchemeSpec
from .wire import all_gather_wire, all_to_all_wire

__all__ = [
    "Codec", "make_codec",
    "SchemeSpec",
    "CodecBackend", "RefBackend", "PallasBackend", "resolve_backend",
    "BACKEND_NAMES",
    "Schedule", "GatherSchedule", "AllToAllSchedule", "PsumSchedule",
    "SCHEDULES", "get_schedule",
    "LeafPlan", "plan_leaf", "plan_tree", "coded_fraction",
    "PackPlan", "WireBucket", "LeafSlot", "WIRE_ALIGN",
    "make_pack_plan", "pack_bucket", "unpack_bucket", "psum_fallback",
    "enc_shape",
    "all_gather_wire", "all_to_all_wire",
    "leaf_to_groups", "groups_to_leaf",
    "make_step_inputs", "coding_worker_index", "uncovered_subsets",
    "admit_code",
]
