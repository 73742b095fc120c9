"""Aggregation schedules over the data axes, as first-class objects.

- ``gather``  (paper-faithful): all_gather the l/m encodings, decode locally.
- ``a2a``     (beyond-paper):  all_to_all chunks of the encodings, decode the
              local 1/n slice, all_gather decoded slices.  ≈ l(1/m + 1) bytes
              received per worker vs ≈ 2l for plain all-reduce.
- ``psum``    (baseline / fallback): straggler-aware weighted all-reduce —
              carries no encoding, so its decode path is the train step's
              plain rho-weighted psum.

Each schedule's decode contraction is delegated to a ``CodecBackend`` so the
same collective choreography runs on the einsum reference or the Pallas
kernels.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import wire
from .backends import CodecBackend


def _gather_slices(dec: jax.Array, axis_names, dtype) -> jax.Array:
    """all_gather every worker's decoded ``(m, c, *rest)`` slice at the wire
    dtype and lay the slices back along V: -> ``(m, n * c, *rest)`` f32."""
    full = wire.all_gather_wire(dec.astype(dtype), axis_names)
    full = jnp.moveaxis(full.astype(jnp.float32), 0, 1)   # (m, n, c, *rest)
    return full.reshape(full.shape[0], -1, *full.shape[3:])


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Interface: how encoded leaves travel and get decoded."""
    name: str = "abstract"
    uses_encoding: bool = True

    def n_split(self, n: int) -> int:
        """Extra divisibility the planner must guarantee on the grouping dim
        (beyond m): 1 unless the schedule slices encodings n ways."""
        return 1

    def recv_elems_per_worker(self, l: int, n: int, m: int) -> float:
        """Wire-cost model: elements *received* per worker to aggregate one
        l-element gradient (multiply by the wire itemsize for bytes).  Used
        by the straggler bench to report predicted collective volume next to
        measured wall-clock."""
        raise NotImplementedError

    def decode_packed(self, buf: jax.Array, W: jax.Array, axis_names, n: int,
                      backend: CodecBackend) -> jax.Array:
        """Decode one packed wire bucket: ``buf`` is the ``(rows, lanes)``
        stack of leaf encodings (``repro.coding.packing``), rows a multiple
        of lcm(16, n) — or a flat ``(L,)`` wire, L a multiple of
        lcm(128, n).  Returns the ``(m, *buf.shape)`` decoded blocks in f32
        (paper eq. 19-21 per element), issued as ONE collective choreography
        and one large aligned contraction for the whole bucket."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class GatherSchedule(Schedule):
    """Paper-faithful master emulation: all_gather encodings, decode locally."""
    name: str = "gather"

    def recv_elems_per_worker(self, l: int, n: int, m: int) -> float:
        """all_gather of the (l/m)-element encodings: n-1 peer encodings."""
        return (n - 1) * l / m

    def decode_packed(self, buf, W, axis_names, n, backend):
        """One all_gather + one fused (n, ...) x (n, m) contraction for the
        whole bucket."""
        gathered = wire.all_gather_wire(buf, axis_names)     # (n, *buf.shape)
        return backend.decode(gathered, W, out_dtype=jnp.float32)


@dataclasses.dataclass(frozen=True)
class AllToAllSchedule(Schedule):
    """Beyond-paper TPU-native: all_to_all encoding chunks, decode the local
    1/n slice of the sum, all_gather decoded slices (second hop travels at the
    wire dtype too)."""
    name: str = "a2a"

    def n_split(self, n: int) -> int:
        """The a2a schedule slices encodings n ways along the grouping dim."""
        return n

    def recv_elems_per_worker(self, l: int, n: int, m: int) -> float:
        """all_to_all of the l/m encoding + all_gather of decoded slices."""
        return (n - 1) * l / (m * n) + (n - 1) * l / n

    def decode_packed(self, buf, W, axis_names, n, backend):
        """One all_to_all of the bucket's n row chunks, one fused
        (n, rows/n, ...) contraction, one all_gather of the decoded
        slices."""
        L = buf.shape[0]
        assert L % n == 0, f"a2a needs n | bucket rows, got {L} % {n}"
        ex = wire.all_to_all_wire(buf, axis_names)           # (L, ...)
        ex = ex.reshape(n, L // n, *buf.shape[1:])           # row p: peer p
        dec = backend.decode(ex, W, out_dtype=jnp.float32)   # (m, L/n, ...)
        return _gather_slices(dec, axis_names, buf.dtype)    # (m, L, ...)


@dataclasses.dataclass(frozen=True)
class PsumSchedule(Schedule):
    """Uncoded baseline: rho-weighted all-reduce, no encode/decode."""
    name: str = "psum"
    uses_encoding: bool = False

    def recv_elems_per_worker(self, l: int, n: int, m: int) -> float:
        """Ring all-reduce: reduce-scatter + all-gather phases, ~2l total."""
        return 2 * (n - 1) * l / n


SCHEDULES = {s.name: s for s in
             (GatherSchedule(), AllToAllSchedule(), PsumSchedule())}


def get_schedule(schedule: str | Schedule) -> Schedule:
    """Resolve a schedule name ("gather" | "a2a" | "psum") to its object;
    ``Schedule`` instances pass through unchanged."""
    if isinstance(schedule, Schedule):
        return schedule
    try:
        return SCHEDULES[schedule]
    except KeyError:
        raise ValueError(f"unknown schedule {schedule!r}; "
                         f"expected one of {tuple(SCHEDULES)}") from None
