"""Partition-spec rules for params, optimizer state, batches, and caches.

The mesh has data axes (``('data',)`` single-pod or ``('pod', 'data')``
multi-pod) and one ``'model'`` axis.  Params are replicated over the data
axes (pure DP + TP baseline; an FSDP variant shards the largest dim over
data — a §Perf lever) and tensor-parallel over ``'model'`` by name-based
rules (Megatron-style: shard attention heads / ffn columns / vocab).  Dims
not divisible by the axis size are replicated — e.g. GQA kv-heads (8) on a
16-way model axis.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

PyTree = Any

def manual_axes(mesh, data_axes, backend=None) -> set[str]:
    """The axes a coded step's shard_map makes manual: the data axes, and
    every other axis of size one.  A wider model axis stays GSPMD-auto, and
    there the compiled Pallas kernels cannot run (a Mosaic call compiles
    only where no axis is left to the compiler), so ``backend`` — the
    codec's — is refused."""
    manual = set(data_axes) | {a for a in mesh.axis_names
                               if mesh.shape[a] == 1}
    auto = set(mesh.axis_names) - manual
    if auto and backend is not None and backend.name == "pallas" \
            and not backend.interpret:
        raise ValueError(
            f"the compiled Pallas kernels need every mesh axis manual, but "
            f"{sorted(auto)} (size > 1) is left to GSPMD; use a model axis "
            f"of one or the 'ref' backend")
    return manual


# leaves that live under a stacked-layer container get one leading stack dim
_STACKS = ("layers", "dense_layers", "pairs", "mamba", "enc_layers",
           "dec_layers")


def _key_names(path) -> list[str]:
    out = []
    for e in path:
        if isinstance(e, jax.tree_util.DictKey):
            out.append(str(e.key))
        elif isinstance(e, jax.tree_util.GetAttrKey):
            out.append(e.name)
    return out


def _rule(names: list[str], shape: tuple[int, ...], ms: int, ax: str):
    """PartitionSpec entries for the *unstacked* trailing dims."""
    name = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    nd = len(shape)

    def ok(d):
        return shape[d] % ms == 0 and shape[d] >= ms

    def spec(*entries):
        return list(entries)

    if nd <= 1:
        # gains, biases (1-d), scalars: replicate (negligible bytes)
        return spec(*([None] * nd))
    if name == "embed":
        return spec(ax if ok(0) else None, None)
    if name == "unembed":
        return spec(None, ax if ok(1) else None)
    if name in ("enc_pos", "dec_pos"):
        return spec(None, None)
    if name == "beta":
        return spec(ax if ok(0) else None)
    if parent in ("attn", "xattn") or (name in ("wq", "wk", "wv") and nd == 3):
        if name == "wq":
            return spec(None, ax if ok(1) else None, None)
        if name in ("wk", "wv"):
            return spec(None, ax if ok(1) else None, None)
        if name == "wo":
            return spec(ax if ok(0) else None, None, None)
        if name in ("bq", "bk", "bv"):
            return spec(ax if ok(0) else None, None)
        if name == "wkv_a":              # (D, latent + rope): every head's
            return spec(None, None)
        if name == "wkv_b":              # (latent, H, nope + v)
            return spec(None, ax if ok(1) else None, None)
    if parent == "moe":
        if name in ("w_gate", "w_up"):   # (E, D, F)
            if ok(0):
                return spec(ax, None, None)
            return spec(None, None, ax if ok(2) else None)
        if name == "w_down":             # (E, F, D)
            if ok(0):
                return spec(ax, None, None)
            return spec(None, ax if ok(1) else None, None)
    if name == "router":
        return spec(None, None)
    if name in ("w_gate", "w_up"):       # (D, F) mlp
        return spec(None, ax if ok(1) else None)
    if name == "w_down":                 # (F, D)
        return spec(ax if ok(0) else None, None)
    # xlstm inner projections (2-d): shard the output column
    if name in ("wz", "wi", "wf", "wo", "wq", "wk", "wv", "w_up2") and nd == 2:
        return spec(None, ax if ok(1) else None)
    if name == "r":                      # (4, H, hd, hd) recurrent block-diag
        return spec(None, None, None, None)
    if name == "in_proj":                # (D, X)
        return spec(None, ax if ok(1) else None)
    if name == "out_proj":               # (Di, D)
        return spec(ax if ok(0) else None, None)
    if name in ("conv_w", "conv_b"):
        return spec(*([None] * nd))
    # fallback: shard the largest divisible dim
    order = sorted(range(nd), key=lambda d: -shape[d])
    for d in order:
        if ok(d):
            e = [None] * nd
            e[d] = ax
            return spec(*e)
    return spec(*([None] * nd))


def param_specs(shapes: PyTree, model_size: int, model_axis: str = "model",
                fsdp_axes: tuple[str, ...] = (), fsdp_size: int = 1) -> PyTree:
    """PartitionSpec tree for a param pytree of ShapeDtypeStructs/arrays.

    ``fsdp_axes``: if set, additionally shard the largest still-replicated,
    divisible dim over the data axes (ZeRO-3-ish; §Perf option).
    """

    def leaf(path, x):
        names = _key_names(path)
        shape = tuple(x.shape)
        stacked = any(n in _STACKS for n in names)
        body = shape[1:] if stacked else shape
        entries = _rule(names, body, model_size, model_axis)
        if stacked:
            entries = [None] + entries
        if fsdp_axes:
            used = {e for e in entries if e is not None}
            if model_axis in used or not used:
                for d in sorted(range(len(shape)), key=lambda i: -shape[i]):
                    if entries[d] is None and shape[d] % fsdp_size == 0 \
                            and shape[d] >= fsdp_size:
                        entries[d] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
                        break
        return P(*entries)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def opt_state_specs(opt_state_shapes: PyTree, pspecs: PyTree) -> PyTree:
    """Optimizer states mirror the param tree under known keys; scalars are
    replicated."""

    def top(key, sub):
        if key in ("x_prev", "mu", "m", "v"):
            return pspecs
        return P()

    return {k: top(k, v) for k, v in opt_state_shapes.items()}


def batch_specs(batch_shapes: PyTree, data_axes: tuple[str, ...]) -> PyTree:
    """Coded-layout batches (n, d, b, ...) shard dim 0 over the data axes."""
    ax = data_axes if len(data_axes) > 1 else data_axes[0]
    return jax.tree.map(lambda x: P(ax, *([None] * (len(x.shape) - 1))),
                        batch_shapes)


def serve_batch_specs(batch_shapes: PyTree, data_axes: tuple[str, ...],
                      data_size: int) -> PyTree:
    """Serving batches (B, ...) shard dim 0 when divisible, else replicate."""
    ax = data_axes if len(data_axes) > 1 else data_axes[0]

    def leaf(x):
        if len(x.shape) >= 1 and x.shape[0] % data_size == 0 and x.shape[0] >= data_size:
            return P(ax, *([None] * (len(x.shape) - 1)))
        return P(*([None] * len(x.shape)))

    return jax.tree.map(leaf, batch_shapes)


def cache_specs(cache_shapes: PyTree, data_axes: tuple[str, ...],
                data_size: int, model_size: int,
                model_axis: str = "model") -> PyTree:
    """Decode-state leaves: (L, B, ...) — shard B over data if divisible,
    then the largest remaining divisible dim over model."""
    ax = data_axes if len(data_axes) > 1 else data_axes[0]

    def leaf(x):
        shape = tuple(x.shape)
        nd = len(shape)
        entries = [None] * nd
        if nd >= 2 and shape[1] % data_size == 0 and shape[1] >= data_size:
            entries[1] = ax
        cands = sorted(range(2, nd), key=lambda d: -shape[d])
        for d in cands:
            if shape[d] % model_size == 0 and shape[d] >= model_size:
                entries[d] = model_axis
                break
        return P(*entries)

    return jax.tree.map(leaf, cache_shapes)


def count_params(shapes: PyTree) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(shapes)))
