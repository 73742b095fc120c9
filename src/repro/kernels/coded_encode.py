"""Pallas TPU kernel: coded gradient ENCODE (paper eq. 17/18).

The encode is the per-step device hot-spot the paper's scheme adds on the
critical path between backprop and the collective: contract the worker's
``(d, m)`` coefficient rows against the grouped gradient ``(d, m, V[, R])``
to produce the ``(V[, R])`` transmitted vector.  Arithmetic intensity is
low (~1 FLOP/byte) — a pure streaming kernel, so the design goal is VMEM
tiling that keeps HBM traffic at exactly one read of G:

- the ``d * m`` coefficients live in SMEM as scalars; the contraction is a
  multiply-and-add over the leading ``K = d * m`` axis on the VPU (exact
  f32, no MXU pass, no multi-dimension contraction for Mosaic to refuse),
- ``m`` is never a minor axis: every block is ``(K, TA, TB)`` over a
  plane (``to_plane``): the ``(V, R)`` plane when R spans at least 128
  lanes, else the flattened tail lifted to ``(K, V*R/128, 128)`` so the
  lane axis is full,
- blocks are (16, 128)-aligned or span a whole dim; a ragged last block is
  masked by Pallas (out-of-bounds writes are dropped), and ``block_tiles``
  sizes them so double-buffered operands fit the 16 MiB scoped VMEM.

``coded_encode_acc`` is the same contraction folded into an f32
accumulator in place (the pipelined step's encode-into-wire path).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 16            # bf16 sublane tile; a multiple of f32's 8
BLOCK_BYTES = 3 << 20    # one grid step's blocks; x2 double-buffered


def block_tiles(A: int, B: int, bytes_per_elem: int,
                budget: int = BLOCK_BYTES) -> tuple[int, int]:
    """Tile ``(TA, TB)`` of an ``(A, B)`` plane whose blocks total
    ``bytes_per_elem`` bytes per plane element across all operands.

    Each tile dim is either the whole dim or a multiple of the TPU's
    (16, 128) tiling, and ``TA * TB * bytes_per_elem <= budget`` whenever
    the minimum aligned tile allows it."""
    rows_min = min(A, SUBLANES)
    if B * rows_min * bytes_per_elem <= budget:
        tb = B
    else:
        tb = max(LANES, budget // (rows_min * bytes_per_elem) // LANES * LANES)
    rows = max(SUBLANES, budget // (tb * bytes_per_elem) // SUBLANES * SUBLANES)
    return (A if rows >= A else rows), tb


def to_plane(x: jax.Array, lead: int):
    """``(*L, V[, R])`` -> ``(*L, A, B)`` kernel plane under ``lead``
    leading dims, plus the trailing shape :func:`from_plane` restores.

    A ``(V, R)`` tail with at least 128 lanes of R is the plane as it is
    (R stays the lane axis; the trailing shape is None).  A narrower tail
    is flattened onto 128-lane rows, zero-padded to whole (16, 128) tiles:
    a lane axis of a few elements would pad every vector register, a flat
    tail puts each element in the same place of its row as in a packed
    bucket (a leaf and its bucket slot are contracted alike), and the TPU
    compiler takes minutes over a bucket-sized plane whose rows are no
    multiple of 8, against a second for an aligned one."""
    rest = x.shape[lead:]
    if len(rest) == 2 and rest[1] >= LANES:
        return x, None
    x = x.reshape(*x.shape[:lead], -1)
    pad = -x.shape[-1] % (SUBLANES * LANES)
    if pad:
        x = jnp.pad(x, [(0, 0)] * lead + [(0, pad)])
    return x.reshape(*x.shape[:lead], -1, LANES), rest


def from_plane(y: jax.Array, rest) -> jax.Array:
    """Inverse of :func:`to_plane` on ``y``'s trailing two dims."""
    if rest is None:
        return y
    flat = y.reshape(*y.shape[:-2], -1)[..., :math.prod(rest)]
    return flat.reshape(*y.shape[:-2], *rest)


def smem_spec() -> pl.BlockSpec:
    """Whole-array SMEM operand (the scalar coefficient / weight table)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def weighted_sum(coef_ref, x_ref, K: int, col: int = 0, stride: int = 1, *,
                 rolled: bool = False):
    """``sum_k coef[k * stride + col] * x[k]`` over the leading block axis,
    accumulated in f32 from zero in a fixed k order (every kernel that must
    agree bit-for-bit with another shares this one sequence).

    ``rolled`` keeps the k loop a loop (interpret mode): unrolled, XLA:CPU
    fuses the first two products into one FMA with either one as the addend,
    and which one it picks depends on the block shape; a rolled loop adds
    each product to the running sum the same way at every shape.  The
    compiled kernels unroll it."""
    def body(k, acc):
        return acc + coef_ref[k * stride + col] * x_ref[k].astype(jnp.float32)

    return jax.lax.fori_loop(0, K, body,
                             jnp.zeros(x_ref.shape[1:], jnp.float32),
                             unroll=not rolled)


def _encode_kernel(K, rolled, c_ref, g_ref, o_ref):
    """c: (K,) SMEM, g: (K, TA, TB), o: (TA, TB)."""
    o_ref[...] = weighted_sum(c_ref, g_ref, K,
                              rolled=rolled).astype(o_ref.dtype)


def _encode_acc_kernel(K, rolled, c_ref, a_ref, g_ref, o_ref):
    """c: (K,) SMEM, a/o: (TA, TB) f32, g: (K, TA, TB) — o = a + encode."""
    o_ref[...] = a_ref[...] + weighted_sum(c_ref, g_ref, K, rolled=rolled)


def _encode_call(kernel, K, A, B, tiles, out_dtype, interpret, alias):
    """The encode's ``pallas_call``, named for its kernel (``coded_encode``
    or ``coded_encode_acc``) so a trace finds it by that name."""
    ta, tb = tiles
    grid = (pl.cdiv(A, ta), pl.cdiv(B, tb))
    plane = pl.BlockSpec((ta, tb), lambda i, j: (i, j))
    in_specs = [smem_spec()]
    if alias:
        in_specs.append(plane)
    in_specs.append(pl.BlockSpec((K, ta, tb), lambda i, j: (0, i, j)))
    return pl.pallas_call(
        functools.partial(kernel, K, interpret),
        grid=grid,
        in_specs=in_specs,
        out_specs=plane,
        out_shape=jax.ShapeDtypeStruct((A, B), out_dtype),
        input_output_aliases={1: 0} if alias else {},
        interpret=interpret,
        name="coded_encode_acc" if alias else "coded_encode",
    )


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def coded_encode(G: jax.Array, C: jax.Array, *, interpret: bool = False,
                 out_dtype=None) -> jax.Array:
    """G: (d, m, V) or (d, m, V, R); C: (d, m) -> (V,) or (V, R).

    out_dtype: accumulation happens in f32 in-kernel; the result is written in
    this dtype (default: G's dtype, matching the ref oracle).
    """
    out_dtype = jnp.dtype(out_dtype) if out_dtype is not None else G.dtype
    X, rest = to_plane(G.reshape(-1, *G.shape[2:]), 1)
    K, A, B = X.shape
    bpe = K * X.dtype.itemsize + out_dtype.itemsize
    out = _encode_call(_encode_kernel, K, A, B, block_tiles(A, B, bpe),
                       out_dtype, interpret, alias=False)(
        C.reshape(-1).astype(jnp.float32), X)
    return from_plane(out, rest)


@functools.partial(jax.jit, static_argnames=("interpret",))
def coded_encode_acc(acc: jax.Array, G: jax.Array, C: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """Accumulating encode: ``acc + coded_encode(G, C)`` in one pass.

    acc: (V,) or (V, R) f32 — one leaf's 128-aligned slot of a wire-bucket
    accumulator (``repro.coding.packing``); G: (d, m, V[, R]); C: (d, m).
    The pipelined step's fused encode path calls this once per (subset,
    leaf) so the wire buffer fills as gradient leaves materialise, instead
    of materialising every per-leaf encoding and concatenating in a later
    pack copy.  ``input_output_aliases`` updates the accumulator in place
    (the slot is consumed each fold); accumulation stays f32 in-kernel in
    :func:`weighted_sum`'s order, so the fold is bit-identical to
    ``acc + coded_encode(G, C)``.
    """
    assert acc.dtype == jnp.float32, "wire accumulators are f32"
    X, rest = to_plane(G.reshape(-1, *G.shape[2:]), 1)
    K, A, B = X.shape
    a, _ = to_plane(acc, 0)
    bpe = K * X.dtype.itemsize + 2 * 4
    out = _encode_call(_encode_acc_kernel, K, A, B, block_tiles(A, B, bpe),
                       jnp.float32, interpret, alias=True)(
        C.reshape(-1).astype(jnp.float32), a, X)
    return from_plane(out, rest)
