"""Leaf-layout <-> canonical-shape conversions.

The backends contract canonical tensors (``(d, m, A[, B])`` encode,
``(n, A[, B])`` decode); parameter leaves are arbitrary-rank with a planned
grouping dimension.  These helpers take the leaf's dims in device order with
the grouping dim first (a no-op when it leads, as it usually does: see
``repro.coding.plan``), split that dim into m contiguous blocks of V (the
paper's g = [g^(1); ...; g^(m)]), and view each group as a kernel plane:

- a group ``(V, *rest)`` whose last dim spans at least 128 lanes is the
  plane ``(V * prod(rest[:-1]), rest[-1])``: the leaf's own rows and lanes,
  so the view is free wherever the tiled layout allows it;
- a narrower tail (a 1-D gain) is flattened, and the kernels lift it
  onto 128-lane rows.

In a packed wire bucket (``repro.coding.packing``) every slot is such a
plane, a range of whole rows; a leaf whose plane would not be a free view
(its second-minor extent no multiple of 8) takes the flat lift there.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.coded_encode import (LANES, SUBLANES, from_plane,
                                       to_plane)

# rows of a wire-bucket slot start on a multiple of this (one bf16 tile)
ROW_ALIGN = SUBLANES


def _permute(x: jax.Array, perm: tuple[int, ...]) -> jax.Array:
    return x if perm == tuple(range(x.ndim)) else jnp.transpose(x, perm)


def leaf_to_groups(g: jax.Array, plan, m: int) -> jax.Array:
    """(..., Dg, ...) -> (m, V, *rest): the grouping dim split first, the
    rest in the leaf's device order (``LeafPlan.group_perm``)."""
    x = _permute(g, plan.group_perm(g.ndim))
    return x.reshape(m, x.shape[0] // m, *x.shape[1:])


def groups_to_leaf(decoded: jax.Array, plan) -> jax.Array:
    """(m, V, *rest) -> original leaf layout (inverse of ``leaf_to_groups``)."""
    m, V = decoded.shape[:2]
    x = decoded.reshape(m * V, *decoded.shape[2:])
    perm = plan.group_perm(x.ndim)
    return _permute(x, tuple(int(i) for i in np.argsort(perm)))


def to_plane_view(x: jax.Array, lead: int) -> jax.Array:
    """``(*L, V, *rest)`` -> ``(*L, V * prod(rest[:-1]), rest[-1])`` when
    the last dim spans at least 128 lanes, else ``(*L, V * prod(rest))``."""
    tail = x.shape[lead:]
    if len(tail) >= 2 and tail[-1] >= LANES:
        return x.reshape(*x.shape[:lead], math.prod(tail[:-1]), tail[-1])
    return x.reshape(*x.shape[:lead], math.prod(tail))


def slot_plane(enc_shape: tuple[int, ...]) -> tuple[int, int, bool]:
    """``(rows, lanes, free)`` of an encoding's slot in a wire bucket.

    ``free`` means the slot is the group's own plane and the view is a
    bitcast: the last dim spans at least 128 lanes and the second-minor dim
    (``rest[-2]``, or V for a 2-D group) is a multiple of 8.  Otherwise the
    encoding is flattened onto 128-lane rows, padded to whole
    ``ROW_ALIGN``-row tiles."""
    V, *rest = enc_shape
    second_minor = rest[-2] if len(rest) > 1 else V
    if rest and rest[-1] >= LANES and second_minor % 8 == 0:
        return V * math.prod(rest[:-1]), rest[-1], True
    size = math.prod(enc_shape)
    rows = -(-size // LANES)
    return -(-rows // ROW_ALIGN) * ROW_ALIGN, LANES, False


def to_slot_plane(x: jax.Array, enc_shape: tuple[int, ...],
                  lead: int) -> jax.Array:
    """``(*L, V, *rest)`` -> ``(*L, rows, lanes)`` per :func:`slot_plane`
    (a reshape, or a flatten and zero pad for the flat lift)."""
    rows, lanes, free = slot_plane(enc_shape)
    if free:
        return x.reshape(*x.shape[:lead], rows, lanes)
    return to_plane(x.reshape(*x.shape[:lead], -1), lead)[0]


def from_slot_plane(y: jax.Array, enc_shape: tuple[int, ...],
                    lead: int) -> jax.Array:
    """Inverse of :func:`to_slot_plane` on ``(*L, rows, lanes)``."""
    if slot_plane(enc_shape)[2]:
        return y.reshape(*y.shape[:lead], *enc_shape)
    return from_plane(y, enc_shape)
