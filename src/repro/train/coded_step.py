"""The coded train step: the paper's gradient coding wired into a generic
shard_map train step usable by every zoo architecture.

Layout: batch arrives in the redundant coded layout (n, d, b, ...) — dim 0
sharded over the data axes (n workers), dim 1 the worker's d assigned
subsets.  The step (manual over data axes, GSPMD-auto over 'model'):

  1. scans the d subsets, computing each subset's gradient with
     ``jax.value_and_grad`` (activation memory = 1 subset; compute
     redundancy d is the paper's intended cost),
  2. folds each subset gradient into the l/m encoding on the fly with the
     worker's coefficient rows C[i, j, :] (paper eq. 17/18 — never
     materializes the (d, l) partial-gradient matrix),
  3. multiplies by the responder mask (stragglers transmit nothing; proves
     the decode is independent of straggler payloads),
  4. packs the coded encodings into the static ``PackPlan``'s bucketed flat
     wire buffers (default; ``packed=False`` keeps the per-leaf escape
     hatch) and decodes the summed gradient with the host-computed float64
     weights W (zero rows at stragglers) via the gather or a2a schedule —
     one collective choreography + one fused contraction per bucket,
  5. runs the optimizer update (replicated over data axes, model-sharded).

Each phase runs under a ``jax.named_scope``: ``coded.grad`` (forward and
backward), ``coded.encode`` (fold, mask, pack), ``coded.exchange`` (every
collective, set in ``repro.coding.wire``), ``coded.decode`` and
``coded.apply`` (the optimizer).  The scopes are op metadata only; a device
trace carries them in each op's name path, so its time can be put down to a
phase.

A family whose loss reports counters (``repro.models.api.counters``, e.g.
the expert layer's routed slots) has them summed over the worker's subsets
and returned, one per worker (dim 0 over the data axes), beside the loss in
the synchronous and psum steps' metrics.

All coding phases are delegated to a ``repro.coding.Codec``: ``schedule``
picks the collective choreography (gather / a2a / psum — see
``repro.coding.schedules``), ``backend`` the encode/decode implementation
("auto" -> Pallas kernels on TPU, einsum reference elsewhere; "pallas" forces
the compiled kernels and needs a TPU; "interpret" runs them interpreted).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import coding
from repro.coding import wire
from repro.core import GradCode
from repro.models import api as model_api
from repro.optim import Optimizer

from . import sharding
from .pipeline import CompiledPipeline, PipelineFns

PyTree = Any

# §Perf lever: pin the coded encodings to their model sharding before the
# manual collective (see _enc_spec below).  Default False = recorded baseline;
# flipped by the dry-run's --opt enc_constraint.
ENC_CONSTRAINT = False


@dataclasses.dataclass(frozen=True)
class StepArtifacts:
    """Everything the launcher needs to run one coded train step.

    Carries the jit-able step builder (``step(batch_shapes) -> (fn, in_specs,
    out_specs)``), the per-leaf coding plans, the bound ``Codec``, the static
    ``PackPlan`` of the packed wire (None on the per-leaf path), the
    per-worker subset-load vector (uniform codes: ``(d,) * n``; hetero codes:
    the plan's ragged loads), and whether the step was built in
    partial-recovery mode (the executable then takes a 7th ``err_factor``
    input and emits a ``decode_err_bound`` metric).
    """
    step: Callable
    in_specs: tuple
    out_specs: tuple
    plans: PyTree
    coded_fraction: float
    codec: coding.Codec | None = None
    pack_plan: coding.PackPlan | None = None
    loads: tuple[int, ...] = ()
    partial: bool = False
    pipelined: bool = False
    fuse_apply: bool = False
    spec: "coding.SchemeSpec | None" = None  # the resolved scheme levers
    pipeline: Callable | None = None   # (batch_shapes) -> PipelineFns
    # memoized jitted executables, keyed by (batch signature, donate): the
    # bench's donated steady-state step and the autotuner's telemetry step
    # share ONE executable instead of tracing twice
    _exe_cache: dict = dataclasses.field(default_factory=dict, init=False,
                                         repr=False, compare=False)

    # ---- benchmark / driver hooks --------------------------------------
    @staticmethod
    def _batch_sig(batch) -> tuple:
        flat, treedef = jax.tree.flatten(batch)
        return (tuple((tuple(x.shape), str(x.dtype)) for x in flat),
                str(treedef))

    def compiled(self, batch, donate: bool = False):
        """Jit the step for a batch (arrays or ShapeDtypeStructs).

        Collapses the `arts.step(shapes) -> jax.jit(fn)` dance every driver
        repeats; straggler patterns stay *inputs* to the returned callable
        (`fn(params, opt_state, batch, W, mask, rho)`), so one executable
        serves every drop pattern.

        donate=True donates params/opt_state (`donate_argnums=(0, 1)`,
        matching the Trainer's jit) so steady-state timing loops reuse the
        update buffers — callers must then thread the returned params/state
        into the next call instead of replaying the originals.

        Memoized per (batch shapes, donate): repeat callers — the bench's
        timing loop, HLO dumps — all receive the same jitted callable, so the step is traced and
        compiled at most once per signature.
        """
        key = self._batch_sig(batch) + (bool(donate),)
        if key not in self._exe_cache:
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
            fn, _, _ = self.step(shapes)
            self._exe_cache[key] = (jax.jit(fn, donate_argnums=(0, 1))
                                    if donate else jax.jit(fn))
        return self._exe_cache[key]

    def compiled_pipeline(self, batch, donate: bool = True) -> CompiledPipeline:
        """Jit the pipelined fill/steady/drain triple for a batch.

        donate=True donates params/opt-state AND the wire-state buffers of
        ``steady``/``drain`` (the double-buffer swap reuses the retired
        buffer's memory); ``fill`` never donates — its params are reused by
        the first steady call.  Memoized like :meth:`compiled`.
        """
        if self.pipeline is None:
            raise ValueError("step was not built with pipelined=True")
        key = ("pipeline",) + self._batch_sig(batch) + (bool(donate),)
        if key not in self._exe_cache:
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
            fns: PipelineFns = self.pipeline(shapes)
            B = fns.num_buffers
            if donate:
                steady = jax.jit(fns.steady,
                                 donate_argnums=(0, 1) + tuple(range(6, 6 + B)))
                drain = jax.jit(fns.drain,
                                donate_argnums=(0, 1) + tuple(range(3, 3 + B)))
            else:
                steady, drain = jax.jit(fns.steady), jax.jit(fns.drain)
            self._exe_cache[key] = CompiledPipeline(
                fill=jax.jit(fns.fill), steady=steady, drain=drain,
                num_buffers=B)
        return self._exe_cache[key]

    def lowered(self, batch, cfg, optimizer):
        """Lower (don't execute) the step for abstract inputs: returns the
        jax ``Lowered`` — ``.compile().as_text()`` feeds HLO analysis such
        as the collective-count guards (`repro.launch.hlo_cost.analyze`).
        Collapses the pshapes/oshapes/W/mask/rho ShapeDtypeStruct dance the
        HLO test and the coding_packed bench would otherwise both hand-roll.
        """
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
        fn, _, _ = self.step(shapes)
        pshapes = jax.eval_shape(
            lambda: model_api.init(jax.random.PRNGKey(0), cfg))
        oshapes = jax.eval_shape(optimizer.init, pshapes)
        code = self.codec.code
        args = [pshapes, oshapes, shapes,
                jax.ShapeDtypeStruct((code.n, code.m), jnp.float32),
                jax.ShapeDtypeStruct((code.n,), jnp.float32),
                jax.ShapeDtypeStruct((code.n, code.d), jnp.float32)]
        if self.partial:
            args.append(jax.ShapeDtypeStruct((), jnp.float32))
        return jax.jit(fn).lower(*args)

    def step_inputs(self, stragglers=()) -> dict[str, jax.Array]:
        """Drop-pattern hook: device-ready `W`/`mask`/`rho` for a straggler
        set (the host-side float64 solve for this responder pattern).  On a
        partial-recovery step the dict also carries the pattern's
        ``err_factor`` certificate scalar (the executable's 7th input)."""
        assert self.codec is not None
        inp = coding.make_step_inputs(self.codec.code, stragglers,
                                      partial=self.partial)
        return {k: jnp.asarray(v) for k, v in inp.items()}


def _data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def _axis_prod(mesh, axes) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


def pipelining_supported(mesh, schedule: str = "gather") -> bool:
    """Whether the async pipelined step is available for this scheme: the
    schedule must carry an encoding (psum has no wire to double-buffer)."""
    from repro.coding import get_schedule
    return get_schedule(schedule).uses_encoding


def make_coded_train_step(cfg, code: GradCode, mesh, optimizer: Optimizer,
                          *, spec: coding.SchemeSpec | None = None,
                          grad_scale: float | None = None,
                          schedule: str | None = None,
                          encode_dtype: str | None = None,
                          backend: str | coding.CodecBackend | None = None,
                          packed: bool | None = None,
                          partial: bool | None = None,
                          pipelined: bool | None = None,
                          fuse_apply: bool | None = None) -> StepArtifacts:
    """Build the shard_map'd coded train step for one architecture.

    code: a uniform :class:`~repro.core.schemes.GradCode` or a heterogeneous
    :class:`~repro.core.hetero.HeteroCode` — the batch layout's subset-slot
    count is ``code.d`` (the max per-worker load for hetero plans, whose
    padded slots carry zero encode/rho weight).

    spec: a :class:`repro.coding.SchemeSpec` bundling every scheme lever —
    the same instance a ``CodedServer`` accepts, so train and serve run one
    scheme from one value.  The per-lever kwargs below are the deprecated
    spelling (``DeprecationWarning``; cannot be combined with ``spec=``)
    and produce bitwise-identical artifacts to the equivalent spec.

    grad_scale: decoded gradients are multiplied by this (default 1/k with
    k = ``code.num_subsets`` so the update equals uncoded *mean*-gradient
    descent when per-subset losses are means; the paper's linear workload
    uses sum losses and scale 1).  Workload-specific, hence not a spec
    lever.

    encode_dtype: wire dtype of the transmitted encodings (the paper uses
    f32; "bfloat16" halves the collective bytes at ~3 decimal digits of
    gradient precision — a beyond-paper lever recorded in §Perf).

    backend: codec compute backend — "auto" | "ref" | "pallas" | "interpret"
    or a ``coding.CodecBackend`` instance.  (The pre-PR-1 ``use_kernels``
    boolean is gone; ``SchemeSpec.backend`` is the one spelling.)

    packed (default True): aggregate coded leaves through the bucketed flat
    wire buffers of ``repro.coding.packing`` — O(1) collectives and one
    fused decode contraction per bucket per step, and the psum-fallback
    leaves ride a single flat all-reduce.  ``packed=False`` is the per-leaf
    escape hatch (one collective + one skinny contraction per coded leaf),
    bit-identical by construction.

    partial (default False): build the step in partial-recovery mode — the
    executable takes a 7th scalar input ``err_factor`` (from
    ``make_step_inputs(..., partial=True)``, which then accepts straggler
    sets *larger* than the design ``s`` instead of raising) and emits a
    ``decode_err_bound`` metric: ``err_factor * sqrt(sum_j ||g_j||^2)``,
    an upper bound on the L2 error of the least-squares decoded gradient
    over the subsets that kept at least one live holder.

    pipelined (default False): additionally build the async three-phase
    step (``StepArtifacts.pipeline`` / ``compiled_pipeline``): fill
    encodes one batch into double-buffered wire-bucket state, steady
    decodes the in-flight buffers (stale-by-one) while encoding the
    current batch at pre-update params — the decode collective and the
    encode compute are dataflow-independent, so XLA overlaps them — and
    drain retires the last buffers.  The encode folds each subset gradient
    straight into the 128-aligned wire layout (``Codec.encode_into``, the
    accumulating encode kernel) instead of materialise-then-pack.
    Requires ``packed=True``, an encoding schedule (not psum) and
    ``partial=False``; the synchronous executable is still built and is
    byte-identical to the non-pipelined build.  Parity contract: fill
    immediately followed by drain == the synchronous step, bit for bit.

    fuse_apply: fuse the per-bucket decode contraction with the optimizer
    update (``Codec.decode_apply_packed``: decode + SGD-momentum + param
    write in one kernel on the gather schedule).  Only valid for
    ``optimizer.kind == "sgd"``.  Params and momentum stay bit-identical
    to the synchronous step (the kernel replicates its op sequence), but
    the ``grad_norm`` metric sums squares in bucket order instead of leaf
    order (~1e-6 relative drift), so the default (None) resolves to False
    and the fully bit-exact path stays the default.  Pipelined-only.
    """
    spec = coding.resolve_scheme_spec(
        spec, dict(schedule=schedule, backend=backend, packed=packed,
                   partial=partial, pipelined=pipelined,
                   fuse_apply=fuse_apply, encode_dtype=encode_dtype),
        caller="make_coded_train_step")
    schedule, backend = spec.schedule, spec.backend
    packed, partial, pipelined = spec.packed, spec.partial, spec.pipelined
    encode_dtype, fuse_apply = spec.encode_dtype, spec.fuse_apply
    data_axes = _data_axes(mesh)
    n = _axis_prod(mesh, data_axes)
    if code.n != n:
        raise ValueError(f"code.n={code.n} != data-parallel degree {n}")
    ms = mesh.shape["model"]
    loss_fn = model_api.make_loss(cfg)
    loss_counts_fn = model_api.make_loss_and_counts(cfg)
    counter_names = model_api.counters(cfg)
    k_subsets = getattr(code, "num_subsets", n)
    if grad_scale is None:
        grad_scale = 1.0 if cfg.family == "linear" else 1.0 / k_subsets

    codec = coding.make_codec(code, schedule=schedule, backend=backend,
                              wire_dtype=encode_dtype)
    manual = sharding.manual_axes(mesh, data_axes, codec.backend)

    if pipelined:
        if not codec.schedule.uses_encoding:
            raise ValueError(
                "pipelined=True needs an encoding schedule (gather/a2a); "
                "the psum baseline has no wire to double-buffer")
        if not packed:
            raise ValueError(
                "pipelined=True requires packed=True: the wire state IS the "
                "PackPlan's bucketed flat buffers")
        if partial:
            raise ValueError(
                "pipelined partial-recovery is unsupported: the err_factor "
                "certificate is computed from the same step's subset "
                "gradients and cannot ride the stale-by-one wire")
    fuse = False if fuse_apply is None else bool(fuse_apply)
    if fuse and not pipelined:
        raise ValueError("fuse_apply is a pipelined-step lever; "
                         "pass pipelined=True")
    if fuse and optimizer.kind != "sgd":
        raise ValueError(
            f"fuse_apply supports optimizer.kind='sgd' only (the fused "
            f"kernel replicates the SGD-momentum rule); got "
            f"{optimizer.kind or 'opaque'!r}")

    # --- shapes / specs ------------------------------------------------
    pshapes = jax.eval_shape(lambda: model_api.init(jax.random.PRNGKey(0), cfg))
    pspecs = sharding.param_specs(pshapes, ms)
    oshapes = jax.eval_shape(optimizer.init, pshapes)
    ospecs = sharding.opt_state_specs(oshapes, pspecs)
    plans = codec.plan(pshapes, pspecs)
    coded_frac = codec.coded_fraction(pshapes, plans)
    # §Tentpole (packed wire): static layout of every coded leaf's encoding
    # into bucketed 128-aligned flat buffers (bucket key: wire dtype x
    # effective model sharding).  Computed once here; the step then issues
    # one collective choreography + one fused contraction per bucket.
    pplan = (codec.pack_plan(pshapes, plans, specs=pspecs, model_size=ms)
             if packed and codec.schedule.uses_encoding else None)
    flat_plans = jax.tree.leaves(
        plans, is_leaf=lambda x: isinstance(x, coding.LeafPlan))

    # §Perf lever (enc_constraint): the encoding of a model-sharded leaf can
    # silently lose its 'model' sharding at the manual-collective boundary
    # (GSPMD resharding — grok's 10 TB all-gather).  This computes the spec
    # each encoding *should* keep: dims = [group_dim] + rest, model entries
    # preserved.
    def _enc_spec(pl, spec):
        if not pl.coded:
            return None
        entries = [e if e == "model" else None for e in tuple(spec)]
        del entries[pl.group_dim]
        return P(*([None] + entries))

    enc_specs = jax.tree.map(
        _enc_spec, plans, pspecs,
        is_leaf=lambda x: isinstance(x, coding.LeafPlan))

    C = jnp.asarray(code.C, jnp.float32)           # (n, d, m) host constant

    def counts_zero():
        return {n: jnp.zeros((), jnp.float32) for n in counter_names}

    # The per-worker rows of C/mask/rho enter the shard_map body sharded over
    # the data axes (dim 0), so each worker reads its own row locally — no
    # axis_index/dynamic gather in the step (axis_index lowers to PartitionId,
    # which SPMD partitioning rejects when GSPMD-auto axes remain).
    def body(params, opt_state, batch, W, mask, rho, Csh, ef=None):
        # local batch leaves: (1, d, b, ...) -> (d, b, ...)
        lb = jax.tree.map(lambda x: x[0], batch)
        Ci = Csh[0]       # (d, m)   this worker's coefficient rows
        rho_i = rho[0]    # (d,)
        mask_i = mask[0]  # ()

        def per_subset(carry, xs):
            if partial:
                enc, cnt, loss_acc, gss_acc = carry
            else:
                enc, cnt, loss_acc = carry
            sub, cj, rj = xs
            with jax.named_scope("coded.grad"):
                (lval, c), g = jax.value_and_grad(loss_counts_fn,
                                                  has_aux=True)(params, sub)
            cnt = jax.tree.map(jnp.add, cnt, c)

            def fold(e, gleaf, pl):
                if not pl.coded:
                    return e + rj * gleaf.astype(jnp.float32)
                contrib = codec.encode_leaf(gleaf.astype(jnp.float32), cj, pl)
                # contribution arrives as (Dg/m, *rest-moved); match e's layout
                return e + contrib

            with jax.named_scope("coded.encode"):
                enc = jax.tree.map(fold, enc, g, plans)
            if partial:
                # rho-weighted subset gradient sumsq: psummed it becomes
                # sum_j ||g_j||^2 over covered subsets — the certificate's
                # gradient-norm term
                gss = sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                          for l in jax.tree.leaves(g))
                return (enc, cnt, loss_acc + rj * lval,
                        gss_acc + rj * gss), None
            return (enc, cnt, loss_acc + rj * lval), None

        init = (jax.tree.map(codec.encoding_zero, params, plans),
                counts_zero(), jnp.zeros((), jnp.float32))
        if partial:
            init = init + (jnp.zeros((), jnp.float32),)
            (enc, cnt, loss_sum, gss_sum), _ = jax.lax.scan(
                per_subset, init, (lb, Ci, rho_i))
        else:
            (enc, cnt, loss_sum), _ = jax.lax.scan(per_subset, init,
                                                   (lb, Ci, rho_i))

        # stragglers transmit nothing — zero the payload to prove independence
        with jax.named_scope("coded.encode"):
            enc = jax.tree.map(
                lambda e, pl: codec.to_wire(e, mask_i) if pl.coded else e,
                enc, plans)
        if ENC_CONSTRAINT:
            flat_e, td = jax.tree.flatten(enc)
            flat_s = td.flatten_up_to(enc_specs)
            flat_p = [p for p in jax.tree.leaves(
                plans, is_leaf=lambda x: isinstance(x, coding.LeafPlan))]
            flat_e = [jax.lax.with_sharding_constraint(e, s)
                      if (pl.coded and s is not None and "model" in tuple(s))
                      else e
                      for e, s, pl in zip(flat_e, flat_s, flat_p)]
            enc = td.unflatten(flat_e)

        if pplan is not None:
            # packed path: coded leaves ride the plan's flat buckets (one
            # collective + one fused (n, L) contraction each); the psum
            # fallback leaves are summed through a single concatenated
            # all-reduce instead of one per leaf.
            flat_enc, td = jax.tree.flatten(enc)
            flat_grads = list(flat_enc)
            with jax.named_scope("coded.encode"):
                bufs = codec.pack(flat_enc, pplan)
            with jax.named_scope("coded.decode"):
                decs = [codec.decode_packed(b, W, data_axes) for b in bufs]
                for i, g_ in codec.unpack(decs, pplan).items():
                    flat_grads[i] = g_
            for i, g_ in coding.psum_fallback(flat_enc, flat_plans,
                                              data_axes).items():
                flat_grads[i] = g_
            grads = td.unflatten(flat_grads)
        else:
            def dec_one(e, pl):
                if not pl.coded:
                    return wire.psum(e, data_axes)
                return codec.decode_leaf(e, W, pl, data_axes)

            with jax.named_scope("coded.decode"):
                grads = jax.tree.map(dec_one, enc, plans)
        grads = jax.tree.map(lambda g_: g_ * grad_scale, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(g_ * g_) for g_ in jax.tree.leaves(grads)))
        # responders' view, normalised by the subset count (= n uniformly)
        loss_global = wire.psum(loss_sum * mask_i, data_axes) / k_subsets

        with jax.named_scope("coded.apply"):
            new_params, new_opt = optimizer.update(grads, opt_state, params)
        metrics = {"loss": loss_global[None], "grad_norm": gnorm[None],
                   **{n: v[None] for n, v in cnt.items()}}
        if partial:
            bound = ef * jnp.sqrt(wire.psum(gss_sum, data_axes))
            metrics["decode_err_bound"] = bound[None]
        return new_params, new_opt, metrics

    # psum baseline: plain rho-weighted all-reduce (uncoded / straggler-aware)
    def body_psum(params, opt_state, batch, W, mask, rho, Csh, ef=None):
        lb = jax.tree.map(lambda x: x[0], batch)
        rho_i = rho[0]
        mask_i = mask[0]

        def per_subset(carry, xs):
            acc, cnt, loss_acc = carry
            sub, rj = xs
            with jax.named_scope("coded.grad"):
                (lval, c), g = jax.value_and_grad(loss_counts_fn,
                                                  has_aux=True)(params, sub)
            acc = jax.tree.map(lambda a, g_: a + rj * g_.astype(jnp.float32), acc, g)
            cnt = jax.tree.map(jnp.add, cnt, c)
            return (acc, cnt, loss_acc + rj * lval), None

        init = (jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
                counts_zero(), jnp.zeros((), jnp.float32))
        (acc, cnt, loss_sum), _ = jax.lax.scan(per_subset, init, (lb, rho_i))
        grads = jax.tree.map(lambda a: wire.psum(a, data_axes) * grad_scale, acc)
        gnorm = jnp.sqrt(sum(jnp.sum(g_ * g_) for g_ in jax.tree.leaves(grads)))
        loss_global = wire.psum(loss_sum * mask_i, data_axes) / k_subsets
        with jax.named_scope("coded.apply"):
            new_params, new_opt = optimizer.update(grads, opt_state, params)
        metrics = {"loss": loss_global[None], "grad_norm": gnorm[None],
                   **{n: v[None] for n, v in cnt.items()}}
        if partial:
            # the psum baseline carries no code: rho already drops uncovered
            # subsets exactly, so the certificate term is identically zero
            metrics["decode_err_bound"] = jnp.zeros((1,), jnp.float32)
        return new_params, new_opt, metrics

    fn = body_psum if not codec.schedule.uses_encoding else body

    # --- pipelined three-phase bodies -----------------------------------
    # Shared static tables: where every coded leaf lands in the wire
    # buckets (fused-encode fold targets) and how the psum-fallback leaves
    # + the masked loss scalar lay out in the flat (S,) side buffer.
    if pipelined:
        flat_pshapes = jax.tree.leaves(pshapes)
        slot_items = [(bi, s) for bi, b in enumerate(pplan.buckets)
                      for s in b.slots]
        small_ix = [i for i, pl_ in enumerate(flat_plans) if not pl_.coded]
        small_shapes = [tuple(flat_pshapes[i].shape) for i in small_ix]
        small_sizes = [int(np.prod(sh)) for sh in small_shapes]

    def _encode_wire(params, lb, Ci, rho_i, mask_i):
        """One batch's backward + fused encode: scan the d subsets, folding
        each subset gradient straight into the per-bucket f32 wire
        accumulators (``Codec.encode_into`` — no materialise-then-pack
        copy) and the rho-weighted psum-fallback accumulators.  Returns
        (per-bucket wire buffers in the wire dtype, (S,) f32 side buffer =
        concat(small-leaf flats) + [masked loss]).  Bit-identical to the
        synchronous body's fold -> to_wire -> pack_bucket: the add order
        per element is the same and the padding gaps stay exactly zero."""
        def per_subset(carry, xs):
            accs, smalls, loss_acc = carry
            sub, cj, rj = xs
            with jax.named_scope("coded.grad"):
                lval, g = jax.value_and_grad(loss_fn)(params, sub)
            flat_g = jax.tree.leaves(g)
            accs = list(accs)
            with jax.named_scope("coded.encode"):
                for bi, slot in slot_items:
                    accs[bi] = codec.encode_into(
                        accs[bi], flat_g[slot.leaf_index].astype(jnp.float32),
                        cj, slot)
                smalls = tuple(sm + rj * flat_g[i].astype(jnp.float32)
                               for sm, i in zip(smalls, small_ix))
            return (tuple(accs), smalls, loss_acc + rj * lval), None

        init = (tuple(jnp.zeros((b.size,), jnp.float32)
                      for b in pplan.buckets),
                tuple(jnp.zeros(sh, jnp.float32) for sh in small_shapes),
                jnp.zeros((), jnp.float32))
        (accs, smalls, loss_sum), _ = jax.lax.scan(per_subset, init,
                                                   (lb, Ci, rho_i))
        with jax.named_scope("coded.encode"):
            wires = tuple(codec.to_wire(a, mask_i) for a in accs)
            side = jnp.concatenate([s_.reshape(-1) for s_ in smalls]
                                   + [(loss_sum * mask_i)[None]])
        return wires, side

    def _decode_update(params, opt_state, W, wires, side):
        """Decode the in-flight wire + side buffers and apply the update:
        the synchronous step's phases 4-5 operating on state instead of
        locally produced encodings.  Op-for-op identical to the sync body
        (bitwise parity) on the default path; with ``fuse_apply`` the coded
        leaves ride the fused decode-plus-apply kernel instead."""
        side_sum = wire.psum(side, data_axes)
        loss_global = side_sum[-1] / k_subsets
        flat_params, ptd = jax.tree.flatten(params)
        small_grads: dict[int, jax.Array] = {}
        off = 0
        for i, sz, sh in zip(small_ix, small_sizes, small_shapes):
            small_grads[i] = (jax.lax.slice_in_dim(side_sum, off, off + sz)
                              .reshape(sh) * grad_scale)
            off += sz

        if not fuse:
            flat_grads: list = [None] * len(flat_params)
            with jax.named_scope("coded.decode"):
                decs = [codec.decode_packed(w, W, data_axes) for w in wires]
                for i, g_ in codec.unpack(decs, pplan).items():
                    flat_grads[i] = g_ * grad_scale
            for i, g_ in small_grads.items():
                flat_grads[i] = g_
            grads = ptd.unflatten(flat_grads)
            gnorm = jnp.sqrt(sum(jnp.sum(g_ * g_)
                                 for g_ in jax.tree.leaves(grads)))
            with jax.named_scope("coded.apply"):
                new_params, new_opt = optimizer.update(grads, opt_state,
                                                       params)
        else:
            hy = optimizer.hyper
            flat_mu = ptd.flatten_up_to(opt_state["mu"])
            p_bufs = codec.pack_params(flat_params, pplan)
            mu_bufs = codec.pack_params(flat_mu, pplan)
            new_p_bufs, new_mu_bufs, ss_parts = [], [], []
            for w, pb, mb in zip(wires, p_bufs, mu_bufs):
                with jax.named_scope("coded.decode"):
                    pn, mn, ss = codec.decode_apply_packed(
                        w, W, pb, mb, data_axes, lr=hy["lr"],
                        momentum=hy["momentum"], scale=grad_scale)
                new_p_bufs.append(pn)
                new_mu_bufs.append(mn)
                ss_parts.append(ss)
            # small leaves ride the plain optimizer update (zero grads at
            # coded positions — their state is overwritten from the fused
            # buffers right below)
            flat_gz = [small_grads.get(i,
                                       jnp.zeros(flat_params[i].shape,
                                                 jnp.float32))
                       for i in range(len(flat_params))]
            with jax.named_scope("coded.apply"):
                new_params, new_opt = optimizer.update(
                    ptd.unflatten(flat_gz), opt_state, params)
            flat_np = ptd.flatten_up_to(new_params)
            flat_nmu = ptd.flatten_up_to(new_opt["mu"])
            for i, v in codec.unpack_params(new_p_bufs, pplan,
                                            flat_params).items():
                flat_np[i] = v
            for i, v in codec.unpack_params(new_mu_bufs, pplan,
                                            flat_mu).items():
                flat_nmu[i] = v
            new_params = ptd.unflatten(flat_np)
            new_opt = {"mu": ptd.unflatten(flat_nmu)}
            gnorm = jnp.sqrt(sum(ss_parts)
                             + sum(jnp.sum(g_ * g_)
                                   for g_ in small_grads.values()))

        metrics = {"loss": loss_global[None], "grad_norm": gnorm[None]}
        return new_params, new_opt, metrics

    def body_fill(params, batch, mask, rho, Csh):
        """Pipeline fill: encode one batch, emit wire state, no update."""
        lb = jax.tree.map(lambda x: x[0], batch)
        wires, side = _encode_wire(params, lb, Csh[0], rho[0], mask[0])
        return tuple(w[None] for w in wires) + (side[None],)

    def body_steady(params, opt_state, batch, W, mask, rho, Csh,
                    *wire_state):
        """Steady state: decode the in-flight wire (pattern of the PREVIOUS
        call — its W arrives now) and apply the stale-by-one update, while
        encoding the current batch at the pre-update params; the collective
        and the backward pass share no data dependency, so XLA overlaps
        them."""
        lb = jax.tree.map(lambda x: x[0], batch)
        prev_wires = tuple(w[0] for w in wire_state[:-1])
        prev_side = wire_state[-1][0]
        new_params, new_opt, metrics = _decode_update(
            params, opt_state, W, prev_wires, prev_side)
        wires, side = _encode_wire(params, lb, Csh[0], rho[0], mask[0])
        return ((new_params, new_opt, metrics)
                + tuple(w[None] for w in wires) + (side[None],))

    def body_drain(params, opt_state, W, *wire_state):
        """Drain: retire the last in-flight buffers — decode + update only."""
        prev_wires = tuple(w[0] for w in wire_state[:-1])
        prev_side = wire_state[-1][0]
        return _decode_update(params, opt_state, W, prev_wires, prev_side)

    # --- wrap in shard_map over the data axes (a model axis wider than one
    # stays auto/GSPMD; see sharding.manual_axes) --------------------------
    # shard_map's in/out_specs mention only the data axes; the 'model'
    # placement is carried by the jit in_shardings (GSPMD auto).
    def _strip(tree):
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

    def make(batch_shapes):
        bspecs = sharding.batch_specs(batch_shapes, data_axes)
        # worker-row operands: dim 0 split over the (flattened) data axes
        dspec = P(data_axes if len(data_axes) > 1 else data_axes[0])
        in_specs = (pspecs, ospecs, bspecs, P(), P(), P())
        mspecs = {"loss": P(), "grad_norm": P(),
                  **{n: dspec for n in counter_names}}
        if partial:
            in_specs = in_specs + (P(),)          # the err_factor scalar
            mspecs["decode_err_bound"] = P()
        out_specs = (pspecs, ospecs, mspecs)
        smapped = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(_strip((pspecs, ospecs, bspecs, P()))
                      + (dspec, dspec, dspec)
                      + ((P(),) if partial else ())),
            out_specs=_strip(out_specs),
            axis_names=manual, check_vma=False)

        # W enters replicated (decode needs all n rows); mask/rho/C are
        # split so each worker sees only its own row
        if partial:
            def stepfn(params, opt_state, batch, W, mask, rho, err_factor):
                return smapped(params, opt_state, batch, W, mask, rho, C,
                               err_factor)
        else:
            def stepfn(params, opt_state, batch, W, mask, rho):
                return smapped(params, opt_state, batch, W, mask, rho, C)

        return stepfn, in_specs, out_specs

    def make_pipeline(batch_shapes) -> PipelineFns:
        """Build the un-jitted fill/steady/drain triple for one batch shape.

        Wire-state arrays are (n, L_b) / (n, S) with dim 0 split over the
        data axes — each worker's shard is its own wire buffer, so the
        state round-trips through jit without resharding.
        """
        bspecs = sharding.batch_specs(batch_shapes, data_axes)
        dspec = P(data_axes if len(data_axes) > 1 else data_axes[0])
        mspecs = {"loss": P(), "grad_norm": P()}
        nbuf = len(pplan.buckets) + 1          # bucket buffers + side buffer
        wire_specs = (dspec,) * nbuf

        fill_sm = jax.shard_map(
            body_fill, mesh=mesh,
            in_specs=_strip((pspecs, bspecs)) + (dspec, dspec, dspec),
            out_specs=wire_specs,
            axis_names=manual, check_vma=False)
        steady_sm = jax.shard_map(
            body_steady, mesh=mesh,
            in_specs=(_strip((pspecs, ospecs, bspecs, P()))
                      + (dspec, dspec, dspec) + wire_specs),
            out_specs=_strip((pspecs, ospecs, mspecs)) + wire_specs,
            axis_names=manual, check_vma=False)
        drain_sm = jax.shard_map(
            body_drain, mesh=mesh,
            in_specs=_strip((pspecs, ospecs, P())) + wire_specs,
            out_specs=_strip((pspecs, ospecs, mspecs)),
            axis_names=manual, check_vma=False)

        def fillfn(params, batch, mask, rho):
            return fill_sm(params, batch, mask, rho, C)

        def steadyfn(params, opt_state, batch, W, mask, rho, *wire):
            return steady_sm(params, opt_state, batch, W, mask, rho, C,
                             *wire)

        def drainfn(params, opt_state, W, *wire):
            return drain_sm(params, opt_state, W, *wire)

        return PipelineFns(fill=fillfn, steady=steadyfn, drain=drainfn,
                           num_buffers=nbuf)

    return StepArtifacts(step=make, in_specs=(pspecs, ospecs), out_specs=None,
                         plans=plans, coded_fraction=coded_frac, codec=codec,
                         pack_plan=pplan,
                         loads=tuple(getattr(code, "loads", (code.d,) * n)),
                         partial=partial, pipelined=pipelined,
                         fuse_apply=fuse, spec=spec,
                         pipeline=make_pipeline if pipelined else None)
