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
accumulator in place: the packed step's encode into its leaf's rows of a
wire bucket, with element-offset blocks that tile the slot exactly
(``slot_tiles``) and a grid over the slot's rows alone.
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
    ta, tb = block_tiles(A, B, K * X.dtype.itemsize + out_dtype.itemsize)
    plane = pl.BlockSpec((ta, tb), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_encode_kernel, K, interpret),
        grid=(pl.cdiv(A, ta), pl.cdiv(B, tb)),
        in_specs=[smem_spec(),
                  pl.BlockSpec((K, ta, tb), lambda i, j: (0, i, j))],
        out_specs=plane,
        out_shape=jax.ShapeDtypeStruct((A, B), out_dtype),
        interpret=interpret,
        name="coded_encode",
    )(C.reshape(-1).astype(jnp.float32), X)
    return from_plane(out, rest)


def slot_tiles(A: int, B: int, bytes_per_elem: int,
               budget: int = BLOCK_BYTES) -> tuple[int, int]:
    """Tile ``(TA, TB)`` that divides an ``(A, B)`` slot plane exactly:
    ``TA`` a multiple of 8 dividing ``A``, ``TB`` the whole ``B`` or a
    multiple of 128 dividing it, the largest such within ``budget`` bytes
    of blocks (a whole-``B`` lane axis of no 128-multiple may exceed it).
    A slot's blocks then never leave its rows, nor the bucket's lanes."""
    assert A % 8 == 0, f"slot rows {A} are no multiple of 8"
    lanes = [B] + [t for t in range(B // LANES * LANES, 0, -LANES)
                   if t < B and B % t == 0]
    tb = next((t for t in lanes if 8 * t * bytes_per_elem <= budget),
              lanes[-1])
    rows = max(8, budget // (tb * bytes_per_elem))
    ta = max(t for t in range(8, min(A, rows) + 1, 8) if A % t == 0)
    return ta, tb


@functools.partial(jax.jit, static_argnames=("row", "interpret"))
def coded_encode_acc(acc: jax.Array, G: jax.Array, C: jax.Array, *,
                     row: int = 0, interpret: bool = False) -> jax.Array:
    """Accumulating encode into a wire bucket: rows ``[row, row + A)`` of
    ``acc`` += ``coded_encode(G, C)``, in one pass, every other row left
    as it was.

    acc: a bucket's ``(R, B)`` f32 stack (``repro.coding.packing``); G: the
    slot's ``(d, m, A, B)`` plane, A and ``row`` multiples of 8; C: (d, m).
    The step calls this once per (subset, leaf), so the wire buffer fills
    as gradient leaves materialise, instead of materialising every per-leaf
    encoding and concatenating in a later pack copy.
    ``input_output_aliases`` updates the bucket in place and the grid
    covers the slot's rows alone, in element-offset blocks that tile the
    slot exactly (``slot_tiles``).  Accumulation stays f32 in-kernel in
    :func:`weighted_sum`'s order, so the fold is bit-identical to
    ``acc[slot] + coded_encode(G, C)``.
    """
    d, m, A, B = G.shape
    R = acc.shape[0]
    assert acc.dtype == jnp.float32 and acc.shape == (R, B), \
        f"a wire bucket is an f32 (rows, {B}) stack, got {acc.shape}"
    assert row % 8 == 0 and row + A <= R, (row, A, R)
    K = d * m
    bpe = K * G.dtype.itemsize + 2 * 4
    ta, tb = slot_tiles(A, B, bpe)
    slot = pl.BlockSpec(
        (pl.Element(ta), pl.Element(tb)),
        lambda i, j: (pl.multiple_of(row + i * ta, 8),
                      pl.multiple_of(j * tb, LANES)))
    vmem = 2 * ta * tb * bpe                  # double-buffered blocks
    params = (pltpu.CompilerParams(vmem_limit_bytes=vmem + (4 << 20))
              if vmem > (12 << 20) else None)
    return pl.pallas_call(
        functools.partial(_encode_acc_kernel, K, interpret),
        grid=(A // ta, B // tb),
        in_specs=[smem_spec(), slot,
                  pl.BlockSpec((K, ta, tb), lambda i, j: (0, i, j))],
        out_specs=slot,
        out_shape=jax.ShapeDtypeStruct((R, B), jnp.float32),
        input_output_aliases={1: 0},
        compiler_params=params,
        interpret=interpret,
        name="coded_encode_acc",
    )(C.reshape(-1).astype(jnp.float32), acc, G.reshape(K, A, B))
