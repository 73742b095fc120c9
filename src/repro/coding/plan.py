"""Per-leaf participation planning for the coded aggregation.

The paper groups the flat gradient's coordinates as (v*m + u).  Flattening
model-sharded tensors would trigger resharding, so we pick, per parameter
leaf, a *grouping dimension* that is replicated over the model axes and
divisible by m (and by n for the all-to-all schedule).

The dims are taken in the leaf's *device order*: the order, major to minor,
in which the device lays the array out (its default layout).  That is the
leaf's own order, except where the TPU moves a dim that is a multiple of 128
into the lanes — an f32 ``(2048, 18992)`` lives as ``(18992, 2048)``.  The
leading dim of that order is taken whenever it is usable: the split into m
groups is then a reshape of the array as it lies in memory, and its trailing
(tiled) dims stay as they are from backward to the optimizer.  Otherwise the
largest usable dim is taken, at the price of a transpose.  A model axis of
size one shards nothing, so a spec entry naming only it counts as replicated
once the axis size is known.  Leaves with no usable dimension (norm gains,
biases — a negligible byte fraction) are aggregated by a straggler-aware
weighted psum instead.  See DESIGN.md §3.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import numpy as np
from jax.experimental.layout import Layout

PyTree = Any


@dataclasses.dataclass(frozen=True)
class LeafPlan:
    """How one parameter leaf participates in the coded aggregation."""
    coded: bool          # False -> weighted-psum fallback
    group_dim: int = -1  # dimension whose coordinates are grouped by m
    order: tuple[int, ...] = ()  # the dims in device order; () = row-major

    def group_perm(self, ndim: int) -> tuple[int, ...]:
        """The leaf's dims as its encoding ``(Dg, *rest)`` takes them: the
        grouping dim first, the rest in device order."""
        order = self.order or tuple(range(ndim))
        return (self.group_dim,) + tuple(d for d in order
                                         if d != self.group_dim)

    @property
    def leads(self) -> bool:
        """Whether the grouping dim leads the device order, so that the
        split into groups moves no data."""
        return self.group_dim == (self.order[0] if self.order else 0)


def device_order(shape: Sequence[int], device=None,
                 dtype="float32") -> tuple[int, ...]:
    """Dims of a ``dtype`` array of ``shape`` in the order, major to minor,
    of ``device``'s default layout for it; the row-major order without a
    device."""
    if device is None or not shape:
        return tuple(range(len(shape)))
    pjrt = device.client.get_default_layout(np.dtype(dtype), tuple(shape),
                                            device)
    return tuple(Layout.from_pjrt_layout(pjrt).major_to_minor)


def _replicated(entry, model_size: int | None) -> bool:
    """Whether a spec entry leaves its dim whole on every device: no axis,
    or only the model axis when that axis is known to have size one."""
    if entry is None:
        return True
    if model_size != 1:
        return False
    names = entry if isinstance(entry, tuple) else (entry,)
    return all(a == "model" for a in names)


def plan_leaf(shape: Sequence[int], spec: Sequence[Any] | None, m: int,
              n_split: int = 1, model_size: int | None = None,
              order: Sequence[int] | None = None) -> LeafPlan:
    """Choose a grouping dimension: replicated and divisible by m * n_split.
    Takes the leading dim of the device ``order`` (default: row-major) when
    it qualifies, else the largest dim that does.

    ``model_size`` is the size of the mesh's model axis; at 1, entries that
    name only that axis count as replicated.  ``None`` (unknown) treats every
    named entry as sharded."""
    order = tuple(order) if order is not None else tuple(range(len(shape)))
    usable = [dim for dim, size in enumerate(shape)
              if size > 0 and size % (m * n_split) == 0
              and _replicated(None if spec is None or dim >= len(spec)
                              else spec[dim], model_size)]
    if not usable:
        return LeafPlan(coded=False)
    kept = () if order == tuple(range(len(shape))) else order
    lead = order[0]
    dim = lead if lead in usable else max(usable, key=lambda d: shape[d])
    return LeafPlan(coded=True, group_dim=dim, order=kept)


def plan_tree(tree: PyTree, specs: PyTree | None, m: int, n_split: int = 1,
              model_size: int | None = None, device=None) -> PyTree:
    """Map ``plan_leaf`` over a pytree of arrays/ShapeDtypeStructs (+ optional
    PartitionSpecs, a tree with the same structure whose leaves are specs).
    ``device``: the device whose default layouts give each leaf's order
    (``device_order``); without one every leaf is taken row-major."""
    flat, treedef = jax.tree.flatten(tree)
    flat_sp = (treedef.flatten_up_to(specs) if specs is not None
               else [None] * len(flat))
    plans = [plan_leaf(tuple(x.shape),
                       tuple(sp) if sp is not None else None, m, n_split,
                       model_size,
                       order=device_order(x.shape, device, x.dtype))
             for x, sp in zip(flat, flat_sp)]
    return treedef.unflatten(plans)


def coded_fraction(tree: PyTree, plans: PyTree) -> float:
    """Fraction of gradient bytes covered by the code (rest falls back to psum)."""
    tot = cod = 0
    for x, p in zip(jax.tree.leaves(tree), jax.tree.leaves(
            plans, is_leaf=lambda v: isinstance(v, LeafPlan))):
        size = int(np.prod(x.shape))
        tot += size
        if p.coded:
            cod += size
    return cod / max(tot, 1)
