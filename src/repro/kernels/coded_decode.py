"""Pallas TPU kernel: coded gradient DECODE (paper eq. 19-21).

After the all-gather, every chip holds the (n, V[, R]) stack of worker
encodings and contracts it with the (n, m) decode-weight matrix W (zero rows
at stragglers) to reconstruct the (m, V[, R]) groups of the summed gradient.
This is a skinny contraction (m <= 8 outputs): memory-bound on the F read,
so the kernel is tiled like the encode — one pass over F:

- W lives in SMEM as ``n * m`` scalars; each of the m output planes is a
  multiply-and-add over the n encodings on the VPU (exact f32),
- the output is ``(m, V[, R])`` so m is a leading (untiled) axis, never a
  lane; operands are laid out as ``coded_encode.to_plane`` planes,
- blocks come from ``coded_encode.block_tiles`` (aligned or whole-dim,
  sized for the 16 MiB scoped VMEM, ragged last block masked).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .coded_encode import (block_tiles, from_plane, smem_spec, to_plane,
                           weighted_sum)


def _decode_kernel(n, m, rolled, w_ref, f_ref, o_ref):
    """w: (n*m,) SMEM, f: (n, TA, TB), o: (m, TA, TB)."""
    for u in range(m):
        o_ref[u] = weighted_sum(w_ref, f_ref, n, col=u, stride=m,
                                rolled=rolled).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def coded_decode(F: jax.Array, W: jax.Array, *, interpret: bool = False,
                 out_dtype=None) -> jax.Array:
    """F: (n, V) or (n, V, R); W: (n, m) -> (m, V) or (m, V, R).

    Serves both aggregation schedules: ``gather`` passes the full (n, V[, R])
    stack, ``a2a`` passes the exchanged (n, V/n[, R]) slice — the contraction
    is identical.  out_dtype: in-kernel accumulation is f32; the result is
    written in this dtype (default F's dtype; the train step asks for f32 so a
    bf16 wire still decodes exactly once into the f32 gradient).
    """
    n, m = W.shape
    out_dtype = jnp.dtype(out_dtype) if out_dtype is not None else F.dtype
    X, rest = to_plane(F, 1)
    _, A, B = X.shape
    ta, tb = block_tiles(A, B, n * X.dtype.itemsize + m * out_dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, n, m, interpret),
        grid=(pl.cdiv(A, ta), pl.cdiv(B, tb)),
        in_specs=[smem_spec(),
                  pl.BlockSpec((n, ta, tb), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((m, ta, tb), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((m, A, B), out_dtype),
        interpret=interpret,
        name="coded_decode",
    )(W.reshape(-1).astype(jnp.float32), X)
    return from_plane(out, rest)

