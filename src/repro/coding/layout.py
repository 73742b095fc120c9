"""Leaf-layout <-> canonical-shape conversions.

The backends contract canonical tensors (``(d, m, V[, R])`` encode,
``(n, V[, R])`` decode); parameter leaves are arbitrary-rank with a planned
grouping dimension.  These helpers move the grouping dim first, split it into
m contiguous blocks of V (the paper's g = [g^(1); ...; g^(m)]), and flatten
any trailing (possibly model-sharded) dims into the single R axis the
kernels tile over.  Splitting the leading dim keeps the trailing (lane)
layout of the leaf, so the split is free and m is never a minor axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .plan import LeafPlan


def leaf_to_groups(g: jax.Array, plan: LeafPlan, m: int) -> jax.Array:
    """(..., Dg, ...) -> (m, V, *rest) with the grouping dim split first."""
    x = jnp.moveaxis(g, plan.group_dim, 0)
    Dg = x.shape[0]
    return x.reshape(m, Dg // m, *x.shape[1:])


def groups_to_leaf(decoded: jax.Array, plan: LeafPlan) -> jax.Array:
    """(m, V, *rest) -> original leaf layout (inverse of ``leaf_to_groups``)."""
    m, V = decoded.shape[:2]
    x = decoded.reshape(m * V, *decoded.shape[2:])
    return jnp.moveaxis(x, 0, plan.group_dim)


def flatten_rest(x: jax.Array, lead: int) -> jax.Array:
    """Collapse all dims after the first ``lead`` into one trailing R axis
    (no-op when there are none)."""
    rest = x.shape[lead:]
    if not rest:
        return x
    return x.reshape(*x.shape[:lead], int(np.prod(rest)))


def unflatten_rest(x: jax.Array, lead: int, rest: tuple[int, ...]) -> jax.Array:
    """Inverse of ``flatten_rest``."""
    if not rest:
        return x
    return x.reshape(*x.shape[:lead], *rest)
