"""The coded forward pass: gradient-coding codes repurposed for inference.

Training encodes per-subset *gradients* so the master can decode their sum
from any ``n - s`` responders.  Serving wants something subtly different —
each request's own output, not a sum — and gets it from the *same* code
objects: the decode identity behind ``repro.coding`` is per subset
(``sum_{i in holders(j)} W_i C_ij^T = I_m``), so placing each subset's
coded forward output in a *disjoint block* of the wire makes the blockwise
decode exact per block, not just in aggregate.

Layout.  The engine batch is ``B = k * b`` requests; the coded data
pipeline (:class:`repro.data.CodedBatcher`) places subset ``j`` = rows
``j*b:(j+1)*b`` redundantly on its ``d``-cyclic holders — the same
``(n, d, b, ...)`` layout training uses.  Each replica runs the family's
batched forward on its ``d`` assigned subsets (compute redundancy ``d``,
the paper's intended price), flattens subset ``j``'s output to
``S_out = b * prod(out_shape)`` values, zero-pads to ``q * m`` rows of
``m`` (``q = ceil(S_out / m)``) and folds it through the backend's encode
contraction with its coefficient row ``C[i, j] in R^m`` — an ``m``-fold
smaller payload, the paper's communication reduction applied to
activations.  The ``(q,)`` encoding lands at block offset ``j * q`` of a
flat ``(L,)`` wire buffer (``L = k * q`` rounded up to
``lcm(WIRE_ALIGN, n)`` so the a2a schedule can slice it ``n`` ways);
non-holders leave other blocks zero.  One ``Codec.decode_packed``
collective + fused contraction recovers every block: decoded rows
``j*q:(j+1)*q`` are exactly subset ``j``'s ``(q, m)`` output matrix.

Hedging.  ``W`` is the host float64 solve with zero rows at stragglers
(:func:`repro.coding.make_step_inputs`) and the wire masks straggler
payloads to exact zero, so the decode is *bit-for-bit independent of the
straggler replicas' payloads*: waiting for only the fastest ``n - s``
replicas returns the same bits as waiting for all ``n``.  That is the
serving engine's hedge — and the acceptance test's contract.

Past-``s`` failures reuse the PR 4 partial-recovery certificate: the
least-squares ``W`` plus ``err_factor * sqrt(sum_j ||y_j||^2)`` bounds the
L2 decode error across covered subsets, and subsets with no live holder
are reported as failed request rows instead of poisoning the batch.

The ``psum`` schedule degenerates to replicated serving (each live holder
contributes its subset's raw output, rho-weighted so duplicates average
exactly) — the bench's like-for-like replicated baseline.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import coding
from repro.core import GradCode
from repro.models import api as model_api
from repro.train import sharding

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ForwardArtifacts:
    """Everything the serving engine needs to run one coded forward.

    ``step(batch_shapes) -> (fn, in_specs, out_specs)`` builds the
    shard_map'd forward for one coded-batch signature; the jitted
    executable takes ``(params, batch, W, mask, rho)`` (plus a trailing
    ``err_factor`` scalar when built with ``spec.partial``) and returns the
    replicated ``(B, *out_shape)`` decoded outputs — with ``partial`` a
    ``(outputs, err_bound)`` pair.  ``compiled`` memoizes the jit per batch
    signature and ``step_inputs`` maps straggler patterns to device inputs,
    mirroring :class:`repro.train.coded_step.StepArtifacts` so drivers
    treat train and serve steps uniformly.
    """

    step: Callable
    codec: coding.Codec
    spec: coding.SchemeSpec
    out_shape: tuple[int, ...]     # per-request output shape (sans batch)
    batch_per_subset: int          # b: requests per data subset
    partial: bool = False
    _exe_cache: dict = dataclasses.field(default_factory=dict, init=False,
                                         repr=False, compare=False)

    @property
    def code(self) -> GradCode:
        """The bound gradient code (n, d, s, m)."""
        return self.codec.code

    def compiled(self, batch):
        """Memoized ``jax.jit`` of the forward for a coded batch's shapes."""
        flat, treedef = jax.tree.flatten(batch)
        key = (tuple((tuple(x.shape), str(x.dtype)) for x in flat),
               str(treedef))
        if key not in self._exe_cache:
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
            fn, _, _ = self.step(shapes)
            self._exe_cache[key] = jax.jit(fn)
        return self._exe_cache[key]

    def step_inputs(self, stragglers=()) -> dict[str, jax.Array]:
        """Device-ready ``W``/``mask``/``rho`` for a straggler pattern
        (plus ``err_factor`` when the step was built ``partial``)."""
        inp = coding.make_step_inputs(self.codec.code, stragglers,
                                      partial=self.partial)
        return {k: jnp.asarray(v) for k, v in inp.items()}


def _data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def make_coded_forward(cfg, code: GradCode, mesh, *,
                       spec: coding.SchemeSpec | None = None,
                       batch_per_subset: int = 1,
                       seq_len: int = 128,
                       window: int = 0) -> ForwardArtifacts:
    """Build the shard_map'd coded forward for one architecture.

    ``spec`` is the same :class:`repro.coding.SchemeSpec` instance
    :func:`repro.train.coded_step.make_coded_train_step` accepts — one
    value object drives the scheme at train and serve time.  Serving
    rejects the training-only lever ``pipelined``: a forward pass has no
    optimizer update to overlap.

    ``batch_per_subset`` is ``b``, the requests per data subset; the
    engine batch is ``B = k * b`` with ``k = code.num_subsets`` and
    arrives in the coded ``(n, d, b, ...)`` layout of
    :class:`repro.data.CodedBatcher`.  ``seq_len`` fixes the LM families'
    prompt length (requests are padded to it; ignored by ``linear``).
    """
    spec = spec if spec is not None else coding.SchemeSpec()
    if spec.pipelined:
        raise ValueError(
            "pipelined is a train-step lever (it overlaps the optimizer "
            "update); the serving forward has none — build the CodedServer "
            "from a spec without it")
    data_axes = _data_axes(mesh)
    n = int(np.prod([mesh.shape[a] for a in data_axes]))
    if code.n != n:
        raise ValueError(f"code.n={code.n} != data-parallel degree {n}")
    ms = mesh.shape["model"]
    partial = spec.partial
    codec = spec.make_codec(code)
    manual = sharding.manual_axes(mesh, data_axes, codec.backend)
    forward_fn = model_api.make_forward(cfg, window=window)

    k = getattr(code, "num_subsets", n)
    b = int(batch_per_subset)
    d = code.d
    m = code.m

    # per-request output shape from one subset's abstract forward
    pshapes = jax.eval_shape(lambda: model_api.init(jax.random.PRNGKey(0),
                                                    cfg))
    pspecs = sharding.param_specs(pshapes, ms)
    sub_shapes = _subset_batch_shapes(cfg, b, seq_len)
    out_abs = jax.eval_shape(forward_fn, pshapes, sub_shapes)
    out_shape = tuple(out_abs.shape[1:])
    s_out = b * int(np.prod(out_shape, dtype=np.int64))
    q = -(-s_out // m)                       # ceil: m blocks of q per subset
    align = math.lcm(coding.WIRE_ALIGN, n)   # a2a slices the wire n ways
    L = -(-(k * q) // align) * align

    C = jnp.asarray(code.C, jnp.float32)                      # (n, d, m)
    blk = jnp.asarray(code.placement(), jnp.int32)            # (n, d)
    valid = jnp.asarray(code.slot_mask(), jnp.float32)        # (n, d)

    def run_subsets(f, lb):
        """Map ``f(sub, slot)`` over the d subset slots (unrolled: serving
        slots carry different wire offsets, so a lax.scan would retrace the
        dynamic-update anyway; d is small by design)."""
        return [f(jax.tree.map(lambda x: x[i], lb), i) for i in range(d)]

    def body(params, batch, W, mask, rho, Csh, blksh, vsh, ef=None):
        lb = jax.tree.map(lambda x: x[0], batch)   # (d, b, ...)
        Ci = Csh[0]          # (d, m)
        rho_i = rho[0]       # (d,)
        mask_i = mask[0]     # ()
        blk_i = blksh[0]     # (d,) subset id per slot
        valid_i = vsh[0]     # (d,) 0.0 at padded (hetero) slots

        def enc_slot(sub, slot):
            y = forward_fn(params, sub).astype(jnp.float32)       # (b, *out)
            flat = y.reshape(-1)
            G = jnp.pad(flat, (0, q * m - s_out)).reshape(1, m, q)
            enc = codec.backend.encode(G, Ci[slot][None],
                                       out_dtype=jnp.float32)     # (q,)
            ss = rho_i[slot] * jnp.sum(flat * flat)
            return enc * valid_i[slot], ss

        buf = jnp.zeros((L,), jnp.float32)
        ss_acc = jnp.zeros((), jnp.float32)
        for slot, (enc, ss) in enumerate(run_subsets(enc_slot, lb)):
            # scatter-add at the subset's block (duplicated hetero padding
            # slots carry zero valid weight, so double-adds are zero-adds)
            off = blk_i[slot] * q
            cur = jax.lax.dynamic_slice(buf, (off,), (q,))
            buf = jax.lax.dynamic_update_slice(buf, cur + enc, (off,))
            ss_acc = ss_acc + ss
        wire = codec.to_wire(buf, mask_i)
        dec = codec.decode_packed(wire, W, data_axes)             # (m, L)
        flat = dec[:, :k * q].reshape(m, k, q).transpose(1, 0, 2)
        flat = flat.reshape(k, m * q)[:, :s_out]
        out = flat.reshape(k * b, *out_shape)
        if partial:
            bound = ef * jnp.sqrt(jax.lax.psum(ss_acc, data_axes))
            return out, bound
        return out

    def body_psum(params, batch, W, mask, rho, Csh, blksh, vsh,
                  ef=None):
        # replicated baseline: live holders contribute raw outputs, the rho
        # equal-split makes duplicated subsets average exactly (matching the
        # train step's straggler-aware psum body)
        lb = jax.tree.map(lambda x: x[0], batch)
        rho_i = rho[0]
        blk_i = blksh[0]

        def raw_slot(sub, slot):
            y = forward_fn(params, sub).astype(jnp.float32)
            return y.reshape(-1) * rho_i[slot]

        buf = jnp.zeros((k * s_out,), jnp.float32)
        for slot, flat in enumerate(run_subsets(raw_slot, lb)):
            off = blk_i[slot] * s_out
            cur = jax.lax.dynamic_slice(buf, (off,), (s_out,))
            buf = jax.lax.dynamic_update_slice(buf, cur + flat, (off,))
        total = jax.lax.psum(buf, data_axes)
        out = total.reshape(k * b, *out_shape)
        if partial:
            return out, jnp.zeros((), jnp.float32)  # rho drops exactly
        return out

    fn = body_psum if not codec.schedule.uses_encoding else body

    def make(batch_shapes):
        bspecs = sharding.batch_specs(batch_shapes, data_axes)
        dspec = P(data_axes if len(data_axes) > 1 else data_axes[0])
        in_specs = (pspecs, bspecs, P(), P(), P())
        out_specs = P() if not partial else (P(), P())
        smapped = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(_strip_data(pspecs, data_axes),
                      _strip_data(bspecs, data_axes), P())
                     + (dspec,) * 5      # mask rho C blk valid
                     + ((P(),) if partial else ()),
            out_specs=out_specs, axis_names=manual, check_vma=False)

        if partial:
            def stepfn(params, batch, W, mask, rho, err_factor):
                return smapped(params, batch, W, mask, rho, C, blk,
                               valid, err_factor)
        else:
            def stepfn(params, batch, W, mask, rho):
                return smapped(params, batch, W, mask, rho, C, blk, valid)

        return stepfn, in_specs, out_specs

    return ForwardArtifacts(step=make, codec=codec, spec=spec,
                            out_shape=out_shape, batch_per_subset=b,
                            partial=partial)


def _strip_data(tree, data_axes):
    """Drop non-data axis entries from PartitionSpecs (shard_map manual
    region only knows the data axes; 'model' stays GSPMD-auto)."""
    keep = set(data_axes)

    def f(s):
        def ok(e):
            if e is None:
                return None
            if isinstance(e, tuple):
                return e if all(x in keep for x in e) else None
            return e if e in keep else None
        return P(*[ok(e) for e in s])

    return jax.tree.map(f, tree, is_leaf=lambda x: isinstance(x, P))


def _subset_batch_shapes(cfg, b: int, seq: int) -> dict:
    """Abstract one-subset batch (the forward's per-slot operand shapes)."""
    if cfg.family == "linear":
        return {"x": jax.ShapeDtypeStruct((b, cfg.d_model), jnp.float32)}
    shapes = {"tokens": jax.ShapeDtypeStruct((b, seq), jnp.int32)}
    if cfg.family == "vlm":
        shapes["embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.n_frontend_tokens, cfg.d_model),
            jnp.dtype(cfg.compute_dtype))
    if cfg.family == "encdec":
        shapes = {"embeds": jax.ShapeDtypeStruct(
            (b, seq, cfg.d_model), jnp.dtype(cfg.compute_dtype))}
    return shapes


def failed_request_rows(code: GradCode, stragglers, batch_per_subset: int,
                        ) -> list[int]:
    """Batch rows whose subset lost every holder (unrecoverable requests).

    Only non-empty past the design ``s`` in partial mode: subset ``j``
    covers rows ``j*b:(j+1)*b`` of the engine batch.
    """
    st = set(int(i) for i in stragglers)
    placement, valid = code.placement(), code.slot_mask()
    covered: set[int] = set()
    for i in range(code.n):
        if i in st:
            continue
        covered.update(int(j) for slot, j in enumerate(placement[i])
                       if valid[i, slot])
    b = batch_per_subset
    return [r for j in range(code.num_subsets) if j not in covered
            for r in range(j * b, (j + 1) * b)]
