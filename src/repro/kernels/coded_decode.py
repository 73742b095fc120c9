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


# ---------------------------------------------------------------- fused path
def _decode_apply_kernel(n, m, rows, lr, momentum, scale, rolled,
                         w_ref, f_ref, p_ref, mu_ref,
                         pn_ref, mun_ref, ss_ref):
    """w: (n*m,) SMEM, f: (n, TA, TB), p/mu: (m, TA, TB) -> p', mu', and
    this block's (1, TB) partial of sum(g^2) (rows past ``rows`` — the
    ragged last block's padding — are masked out of it; lanes past the
    plane's are dropped when the partials are written)."""
    ta = f_ref.shape[1]
    row = (pl.program_id(0) * ta
           + jax.lax.broadcasted_iota(jnp.int32, f_ref.shape[1:], 0))
    ss = jnp.zeros((1, f_ref.shape[2]), jnp.float32)
    for u in range(m):
        g = weighted_sum(w_ref, f_ref, n, col=u, stride=m,
                         rolled=rolled) * scale
        mu = momentum * mu_ref[u] + g                   # SGD-momentum state
        pn_ref[u] = p_ref[u] - lr * mu
        mun_ref[u] = mu
        g = jnp.where(row < rows, g, 0.0)
        ss = ss + jnp.sum(g * g, axis=0, keepdims=True)
    ss_ref[0] = ss


@functools.partial(jax.jit,
                   static_argnames=("lr", "momentum", "scale", "interpret"))
def coded_decode_apply(F: jax.Array, W: jax.Array, P: jax.Array,
                       MU: jax.Array, *, lr: float, momentum: float,
                       scale: float, interpret: bool = False):
    """Fused decode + SGD-momentum apply for one packed wire bucket.

    F: (n, L) or (n, rows, B) gathered wire stack; W: (n, m) decode
    weights; P / MU: (m, L) or (m, rows, B) f32 bucket-layout views of the
    params and momentum state (``repro.coding.packing.pack_param_groups``).
    One pass computes

        g   = scale * (W^T F)        (paper eq. 19-21 + grad scaling)
        mu' = momentum * mu + g
        p'  = p - lr * mu'

    and returns ``(p', mu', sum(g*g))`` — the decode, the unpack-free
    optimizer apply and the gradient-norm partial in a single kernel per
    bucket, instead of decode -> unpack -> tree-wise update.  The decode
    shares :func:`coded_decode`'s f32 sequence; the update matches the
    unfused path's up to where the compiler fuses a multiply-add into one
    FMA.  P/MU are aliased to the outputs (donated by the pipelined step).
    """
    n, m = W.shape
    (X, rest), (Pl, _), (Ml, _) = (to_plane(F, 1), to_plane(P, 1),
                                   to_plane(MU, 1))
    _, A, B = X.shape
    ta, tb = block_tiles(A, B, n * X.dtype.itemsize + 4 * m * 4)
    grid = (pl.cdiv(A, ta), pl.cdiv(B, tb))
    kern = functools.partial(_decode_apply_kernel, n, m, A, float(lr),
                             float(momentum), float(scale), interpret)
    plane = pl.BlockSpec((m, ta, tb), lambda i, j: (0, i, j))
    pn, mun, ss = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[smem_spec(),
                  pl.BlockSpec((n, ta, tb), lambda i, j: (0, i, j)),
                  plane, plane],
        out_specs=[plane, plane,
                   pl.BlockSpec((1, 1, tb), lambda i, j: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((m, A, B), jnp.float32),
                   jax.ShapeDtypeStruct((m, A, B), jnp.float32),
                   jax.ShapeDtypeStruct((grid[0], 1, B), jnp.float32)],
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
        name="coded_decode_apply",
    )(W.reshape(-1).astype(jnp.float32), X, Pl, Ml)
    return from_plane(pn, rest), from_plane(mun, rest), jnp.sum(ss)
