"""`SchemeSpec`: one frozen value object naming a complete coding scheme.

The scheme levers — collective schedule, compute backend, partial recovery,
async pipelining, wire dtype — live in one hashable dataclass that every
consumer of the codec accepts, the train step and the serving engine alike:

>>> spec = SchemeSpec(schedule="a2a", encode_dtype="bfloat16")
>>> spec.replace(partial=True).partial
True

``make_coded_train_step(cfg, code, mesh, opt, spec=spec)``,
``Trainer(..., spec=spec)`` and ``CodedServer(..., spec=spec)`` all consume
the same instance.

What stays *out* of the spec: anything workload-specific (``grad_scale``)
or cluster-specific (the code object, the mesh) — a spec is the reusable
"how to aggregate", not the "what" or the "where".
"""
from __future__ import annotations

import dataclasses
from typing import Any

from .backends import CodecBackend
from .codec import Codec, make_codec
from .schedules import get_schedule


@dataclasses.dataclass(frozen=True)
class SchemeSpec:
    """Frozen bundle of every scheme lever shared by train and serve.

    schedule: collective choreography — "gather" | "a2a" | "psum" (the
    uncoded baseline; see ``repro.coding.schedules``).  The encoding
    schedules carry every coded leaf on the bucketed wire buffers of
    ``repro.coding.packing``.

    backend: codec compute backend — "auto" | "ref" | "pallas" |
    "interpret" or a :class:`~repro.coding.backends.CodecBackend` instance
    ("auto" resolves to the Pallas kernels on TPU, the einsum reference
    elsewhere).

    partial: build partial-recovery executables — straggler sets larger
    than the design ``s`` decode approximately with an ``err_factor``
    error certificate instead of raising.

    pipelined: the async stale-by-one train step (``repro.train.pipeline``);
    requires an encoding schedule.  Train-only: the serving forward has no
    gradient pipeline to overlap.

    encode_dtype: wire dtype of the transmitted encodings ("float32" |
    "bfloat16" | "float16").
    """

    schedule: str = "gather"
    backend: str | CodecBackend = "auto"
    partial: bool = False
    pipelined: bool = False
    encode_dtype: str = "float32"

    def __post_init__(self):
        """Reject structurally impossible lever combinations eagerly;
        backend resolution stays with the consumers."""
        if self.pipelined:
            if self.partial:
                raise ValueError(
                    "pipelined partial-recovery is unsupported: the "
                    "err_factor certificate is computed from the same "
                    "step's subset gradients and cannot ride the "
                    "stale-by-one wire")
            if not self.uses_encoding:
                raise ValueError(
                    "pipelined=True needs an encoding schedule (gather/"
                    "a2a); the psum baseline has no wire to double-buffer")

    def replace(self, **changes: Any) -> "SchemeSpec":
        """A copy with the given levers changed (``dataclasses.replace``)."""
        return dataclasses.replace(self, **changes)

    def make_codec(self, code) -> Codec:
        """Bind the spec's schedule/backend/wire-dtype levers to a code."""
        return make_codec(code, schedule=self.schedule, backend=self.backend,
                          wire_dtype=self.encode_dtype)

    @property
    def uses_encoding(self) -> bool:
        """Whether the schedule transmits coded encodings (psum does not)."""
        return get_schedule(self.schedule).uses_encoding
