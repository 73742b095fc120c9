"""Compute backends for the codec: the same encode/decode contractions in
interchangeable implementations.

Canonical shapes (the leaf <-> canonical reshaping lives in ``codec.py``):

  encode: G (d, m, A[, B]) x C (d, m)  ->  (A[, B])      (paper eq. 17/18)
  decode: F (n, A[, B])   x W (n, m)   ->  (m, A[, B])   (paper eq. 19-21)

with ``(A, B)`` a kernel plane (``layout.to_plane_view``) or a flat A.

The group axis m always leads: it is never the TPU's lane axis.

Backends:
  ``ref``       — pure jnp multiply-and-sum (``repro.kernels.ref``); runs
                  anywhere, XLA-fused.
  ``pallas``    — the TPU Mosaic kernels in ``repro.kernels``, compiled;
                  needs an attached TPU.
  ``interpret`` — the same kernels in Pallas interpret mode (same f32
                  sequence, slow — tests and kernel debugging off-TPU).

``resolve_backend`` implements the dispatch policy: ``auto`` -> pallas on TPU,
ref elsewhere; explicit names force a backend and never fall back.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# imported as modules (not the package's re-exported functions) so tests can
# monkeypatch the kernel entry points and observe the pallas path executing
import importlib

_encode_mod = importlib.import_module("repro.kernels.coded_encode")
_decode_mod = importlib.import_module("repro.kernels.coded_decode")
_ref_mod = importlib.import_module("repro.kernels.ref")

BACKEND_NAMES = ("auto", "ref", "pallas", "interpret")


@dataclasses.dataclass(frozen=True)
class CodecBackend:
    """Interface: subclasses implement the two canonical contractions."""
    name: str = "abstract"

    def encode(self, G: jax.Array, C: jax.Array, *, out_dtype=None) -> jax.Array:
        """Encode contraction: G (d, m, V[, R]) x C (d, m) -> (V[, R])."""
        raise NotImplementedError

    def decode(self, F: jax.Array, W: jax.Array, *, out_dtype=None) -> jax.Array:
        """Decode contraction: F (n, V[, R]) x W (n, m) -> (m, V[, R])."""
        raise NotImplementedError

    def encode_acc(self, acc: jax.Array, G: jax.Array, C: jax.Array, *,
                   row: int = 0) -> jax.Array:
        """Accumulating encode into a wire bucket: rows ``[row, row + A)``
        of the f32 ``(rows, B)`` stack ``acc`` += ``encode(G, C)``, G the
        slot's ``(d, m, A, B)`` plane.

        The packed step's encode fold — one call per (subset, leaf) writes
        straight into the leaf's slot of the wire bucket instead of
        materialising the per-leaf encoding for a later pack copy.  Must be
        bit-identical to the two-step spelling.
        """
        raise NotImplementedError

    def decode_apply(self, F: jax.Array, W: jax.Array, P: jax.Array,
                     MU: jax.Array, *, lr: float, momentum: float,
                     scale: float):
        """Fused decode + SGD-momentum apply over one packed bucket.

        F (n, rows, B) x W (n, m) -> g = scale * decode; then
        ``mu' = momentum * MU + g``, ``p' = P - lr * mu'`` on the
        (m, rows, B) f32 bucket-layout views.  Returns
        ``(p', mu', sum(g*g))`` — the
        gradient-norm partial rides along so the step never rebuilds g.
        """
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class RefBackend(CodecBackend):
    """Pure-jnp reference backend (``repro.kernels.ref``): runs anywhere,
    XLA-fused, and serves as the numerical oracle for the Pallas kernels."""
    name: str = "ref"

    def encode(self, G, C, *, out_dtype=None):
        """Encode via multiply-and-sum, f32 accumulation, cast to
        ``out_dtype``."""
        return _ref_mod.coded_encode_ref(G, C, out_dtype)

    def decode(self, F, W, *, out_dtype=None):
        """Decode via multiply-and-sum, f32 accumulation, cast to
        ``out_dtype``."""
        return _ref_mod.coded_decode_ref(F, W, out_dtype)

    def encode_acc(self, acc, G, C, *, row=0):
        """``acc + encode(G, C)`` over the slot's rows (an in-place
        dynamic-update-slice of the bucket)."""
        enc = self.encode(G, C, out_dtype=jnp.float32)
        cur = jax.lax.slice_in_dim(acc, row, row + enc.shape[0])
        return jax.lax.dynamic_update_slice_in_dim(acc, cur + enc, row, 0)

    def decode_apply(self, F, W, P, MU, *, lr, momentum, scale):
        """Reference decode + elementwise SGD-momentum apply (see
        interface)."""
        g = self.decode(F, W, out_dtype=jnp.float32) * scale
        mu = momentum * MU + g
        return P - lr * mu, mu, jnp.sum(g * g)


@dataclasses.dataclass(frozen=True)
class PallasBackend(CodecBackend):
    """The TPU Mosaic kernels in ``repro.kernels``; ``interpret=True`` runs
    the same kernels in Pallas interpret mode (same f32 sequence, slow —
    tests and kernel debugging)."""
    name: str = "pallas"
    interpret: bool = False

    def encode(self, G, C, *, out_dtype=None):
        """Encode via the ``coded_encode`` Pallas kernel."""
        return _encode_mod.coded_encode(G, C, interpret=self.interpret,
                                        out_dtype=out_dtype)

    def decode(self, F, W, *, out_dtype=None):
        """Decode via the ``coded_decode`` Pallas kernel."""
        return _decode_mod.coded_decode(F, W, interpret=self.interpret,
                                        out_dtype=out_dtype)

    def encode_acc(self, acc, G, C, *, row=0):
        """Accumulate via the ``coded_encode_acc`` Pallas kernel (in-place
        through ``input_output_aliases``, its grid on the slot's rows)."""
        return _encode_mod.coded_encode_acc(acc, G, C, row=row,
                                            interpret=self.interpret)

    def decode_apply(self, F, W, P, MU, *, lr, momentum, scale):
        """Fuse via the ``coded_decode_apply`` Pallas kernel."""
        return _decode_mod.coded_decode_apply(
            F, W, P, MU, lr=lr, momentum=momentum, scale=scale,
            interpret=self.interpret)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str | CodecBackend | None) -> CodecBackend:
    """Dispatch policy.  ``auto``: pallas on TPU, ref elsewhere.  ``pallas``:
    the compiled kernels — raises when no TPU is attached.  ``interpret``:
    the kernels in interpret mode (tests and kernel debugging)."""
    if isinstance(backend, CodecBackend):
        return backend
    name = backend or "auto"
    if name == "auto":
        return PallasBackend() if _on_tpu() else RefBackend()
    if name == "ref":
        return RefBackend()
    if name == "pallas":
        if not _on_tpu():
            raise RuntimeError(
                f"backend='pallas' compiles the Mosaic kernels and needs a "
                f"TPU, but JAX's default backend is "
                f"{jax.default_backend()!r}; use 'interpret' to run the "
                f"kernels in interpret mode or 'ref' for the einsum path")
        return PallasBackend()
    if name == "interpret":
        return PallasBackend(interpret=True)
    raise ValueError(f"unknown codec backend {backend!r}; "
                     f"expected one of {BACKEND_NAMES}")
