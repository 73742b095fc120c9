"""Serving layer: sharded prefill / decode steps, a small batched-request
engine for the examples, and the coded inference server.

The pjit/GSPMD surface (``build_serve_artifacts`` / ``BatchedEngine``)
exercises the model zoo's decode path on the production mesh.  The
:class:`CodedServer` is the paper's scheme applied to *inference*: batched
forward passes ride the coded replica layout of
:mod:`repro.serving.coded`, the engine decodes from the fastest ``n - s``
replicas (hedging — straggler payloads provably never reach the output),
and the same telemetry -> MLE -> re-plan loop that adapts training
(:mod:`repro.tune`) re-ranks ``(d, s, m) x schedule`` by modeled p99 under
a Poisson arrival process at serve time.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import coding
from repro.core import make_code
from repro.data import CodedBatcher
from repro.models import api as model_api
from repro.train import sharding

from .batcher import Request, RequestBatcher
from .coded import ForwardArtifacts, failed_request_rows, make_coded_forward

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ServeArtifacts:
    """Jitted pjit serving surface for one arch x shape: prefill + decode
    callables and the shardings/shapes drivers need to feed them."""

    prefill: Callable | None
    decode: Callable
    param_shardings: PyTree
    cache_shardings: PyTree
    cache_shapes: PyTree
    token_sharding: Any


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_serve_artifacts(cfg, mesh, *, batch: int, seq_len: int,
                          window: int = 0) -> ServeArtifacts:
    """Sharded decode (and prefill where sensible) for one arch x shape."""
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    dsize = int(np.prod([mesh.shape[a] for a in data_axes]))
    msize = mesh.shape["model"]

    pshapes = jax.eval_shape(lambda: model_api.init(jax.random.PRNGKey(0), cfg))
    pspecs = sharding.param_specs(pshapes, msize)
    cshapes = model_api.cache_spec(cfg, batch, seq_len, window=window)
    cspecs = sharding.cache_specs(cshapes, data_axes, dsize, msize)
    ax = data_axes if len(data_axes) > 1 else data_axes[0]
    tok_spec = P(ax) if batch % dsize == 0 and batch >= dsize else P(None)

    decode_fn = model_api.make_decode(cfg, window=window)
    decode = jax.jit(decode_fn,
                     in_shardings=(_ns(mesh, pspecs), _ns(mesh, cspecs),
                                   NamedSharding(mesh, tok_spec)),
                     out_shardings=(NamedSharding(mesh, tok_spec),
                                    _ns(mesh, cspecs)),
                     donate_argnums=(1,))

    if True:
        pre_fn = model_api.make_prefill(cfg, seq_len, window=window)
        if cfg.family == "encdec":
            bshapes = {"embeds": jax.ShapeDtypeStruct(
                (batch, seq_len, cfg.d_model), jnp.dtype(cfg.compute_dtype))}
        elif cfg.family == "vlm":
            bshapes = {
                "tokens": jax.ShapeDtypeStruct(
                    (batch, max(seq_len - cfg.n_frontend_tokens, 16)), jnp.int32),
                "embeds": jax.ShapeDtypeStruct(
                    (batch, cfg.n_frontend_tokens, cfg.d_model),
                    jnp.dtype(cfg.compute_dtype)),
            }
        else:
            bshapes = {"tokens": jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)}
        bspecs = sharding.serve_batch_specs(bshapes, data_axes, dsize)
        logit_spec = P(ax, None) if batch % dsize == 0 and batch >= dsize \
            else P(None, None)
        # out_shardings pin the cache to the decode layout so the prefill
        # output feeds decode without a reshard-mismatch
        prefill = jax.jit(pre_fn,
                          in_shardings=(_ns(mesh, pspecs), _ns(mesh, bspecs)),
                          out_shardings=(NamedSharding(mesh, logit_spec),
                                         _ns(mesh, cspecs)))

    return ServeArtifacts(prefill=prefill, decode=decode,
                          param_shardings=_ns(mesh, pspecs),
                          cache_shardings=_ns(mesh, cspecs),
                          cache_shapes=cshapes,
                          token_sharding=NamedSharding(mesh, tok_spec))


# ------------------------------------------------------------ toy engine
class BatchedEngine:
    """Minimal batched-request serving loop for the examples: fixed batch
    slots, greedy decoding, per-slot stop lengths."""

    def __init__(self, cfg, mesh, params, *, batch: int, seq_len: int,
                 window: int = 0):
        self.cfg = cfg
        self.mesh = mesh
        self.arts = build_serve_artifacts(cfg, mesh, batch=batch,
                                          seq_len=seq_len, window=window)
        # reshard to the serving layout (params may arrive replicated or in
        # the training layout)
        self.params = jax.device_put(params, self.arts.param_shardings)
        self.batch = batch
        self.seq_len = seq_len
        self.window = window

    def generate(self, prompts: np.ndarray, max_new: int) -> np.ndarray:
        """prompts: (batch, prompt_len) int32 -> (batch, max_new)."""
        with jax.sharding.set_mesh(self.mesh):
            batch = {"tokens": jnp.asarray(prompts)}
            if self.cfg.family in ("vlm", "encdec"):
                batch["embeds"] = jnp.zeros(
                    (prompts.shape[0], self.cfg.n_frontend_tokens, self.cfg.d_model),
                    jnp.dtype(self.cfg.compute_dtype))
            if self.cfg.family == "encdec":
                batch = {"embeds": jnp.zeros(
                    (prompts.shape[0], self.seq_len, self.cfg.d_model),
                    jnp.dtype(self.cfg.compute_dtype))}
            logits, cache = self.arts.prefill(self.params, batch)
            outs = []
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            for _ in range(max_new):
                outs.append(np.asarray(tok))
                logits, cache = self.arts.decode(self.params, cache, tok)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return np.stack(outs, axis=1)


# ------------------------------------------------------------ coded server
@dataclasses.dataclass(frozen=True)
class ServeSLO:
    """The bounded-error service-level objective for degraded serving.

    Inside the design budget (``<= s`` stragglers) decode is exact and the
    SLO is trivially met.  Past it, a ``partial`` server returns the
    least-squares decode and its error certificate; a batch is within SLO
    iff the certified L2 bound stays under ``max_decode_err`` — callers
    decide whether out-of-SLO batches are retried or surfaced degraded.
    """

    max_decode_err: float = float("inf")


@dataclasses.dataclass(frozen=True)
class BatchResult:
    """One served batch: decoded outputs + the hedge/degradation evidence.

    ``outputs`` is ``(valid, *out_shape)`` — padding rows already dropped;
    ``requests`` aligns row-for-row when the batch came through the
    request queue (empty for raw ``serve_batch`` calls).  ``stragglers``
    is the replica set the engine did *not* wait for; ``failed_rows`` the
    request rows whose subset lost every holder (only possible past the
    design ``s`` in partial mode — exact serves always return it empty).
    """

    outputs: np.ndarray
    requests: tuple[Request, ...]
    stragglers: tuple[int, ...]
    err_bound: float
    within_slo: bool
    failed_rows: tuple[int, ...]
    wall_s: float


class CodedServer:
    """Batched coded-inference engine over the replica mesh.

    Construction mirrors the ``Trainer``: one
    :class:`repro.coding.SchemeSpec` instance (the *same* object a
    ``make_coded_train_step`` call accepts) fixes the scheme levers, and a
    :class:`repro.tune.StragglerSource` supplies per-batch straggler sets
    — at serve time that is the hedging decision: the engine decodes from
    the fastest ``n - len(stragglers)`` replicas and the stragglers'
    payloads provably never influence the output bits.

    With ``autotune=``\\ :class:`repro.tune.ServingPolicy` the server runs
    the serving twin of the training auto-tuner: every served batch feeds
    a :class:`~repro.tune.StepRecord` (per-replica timings from the timed
    source + measured forward wall-clock) to a
    :class:`~repro.tune.ServingAutotuner`, which re-fits the Section-VI
    model and re-ranks the uniform ``(d, s, m) x schedule`` family by
    modeled p99 sojourn under the policy's Poisson arrival process.
    Adopted plans swap the code/codec through a per-scheme artifact cache
    (uniform family only: ``k = n`` is pinned so the engine batch
    ``B = k * b`` never changes mid-flight).
    """

    def __init__(self, cfg, code, mesh, params, *,
                 spec: coding.SchemeSpec | None = None,
                 batch_per_subset: int = 1,
                 straggler_source=None,
                 slo: ServeSLO | None = None,
                 autotune=None,
                 seq_len: int = 128,
                 window: int = 0):
        """Bind model, code, mesh and scheme; build the first codec."""
        from repro.tune import ServingAutotuner, as_straggler_source
        self.cfg = cfg
        self.mesh = mesh
        self.params = params
        self.spec = spec if spec is not None else coding.SchemeSpec()
        self.slo = slo if slo is not None else ServeSLO()
        self.seq_len = seq_len
        self.window = window
        self.b = int(batch_per_subset)
        self.code = code
        self._source = as_straggler_source(straggler_source)
        if autotune is not None and not self._source.provides_times:
            raise ValueError(
                "autotune needs per-worker timings: pass a timed "
                "straggler_source= (e.g. a repro.tune.ShiftedExpSampler or "
                "a replica heartbeat feed)")
        k = getattr(code, "num_subsets", code.n)
        self.batch_requests = k * self.b
        self.batcher = RequestBatcher(self.batch_requests)
        self._arts: dict[tuple, ForwardArtifacts] = {}
        self._placer = CodedBatcher(code)
        self._tuner = (ServingAutotuner(autotune, self.batch_requests)
                       if autotune is not None else None)
        self._served = 0
        self._next_id = 0

    # ---- scheme plumbing ------------------------------------------------
    def _scheme_key(self) -> tuple:
        code = self.code
        return (code.n, code.d, code.s, code.m, self.spec.schedule,
                self.spec.packed, self.spec.partial, str(self.spec.backend),
                self.spec.encode_dtype)

    @property
    def artifacts(self) -> ForwardArtifacts:
        """The active scheme's forward artifacts (built once per scheme —
        returning to a previously served scheme does not retrace)."""
        key = self._scheme_key()
        if key not in self._arts:
            self._arts[key] = make_coded_forward(
                self.cfg, self.code, self.mesh, spec=self.spec,
                batch_per_subset=self.b, seq_len=self.seq_len,
                window=self.window)
        return self._arts[key]

    def _apply_plan(self, plan) -> None:
        """Adopt a ranked serve plan: swap code + schedule, keep B fixed."""
        n = self.code.n
        self.code = make_code(n, plan.d, plan.s, plan.m)
        self.spec = self.spec.replace(schedule=plan.schedule)
        self._placer = CodedBatcher(self.code)

    # ---- request-queue surface -----------------------------------------
    def submit(self, payload: dict, arrival_s: float = 0.0) -> int:
        """Enqueue one request payload; returns its request id."""
        self._next_id += 1
        self.batcher.add(Request(self._next_id, payload, arrival_s))
        return self._next_id

    def step(self) -> BatchResult | None:
        """Serve one batch from the queue (None when nothing is queued)."""
        if not len(self.batcher):
            return None
        reqs, batch, valid = self.batcher.next_batch()
        res = self.serve_batch(batch, valid=valid)
        return dataclasses.replace(res, requests=tuple(reqs))

    # ---- the coded forward ---------------------------------------------
    def serve_batch(self, batch: dict, valid: int | None = None,
                    stragglers=None) -> BatchResult:
        """Run one coded forward over a ``(B, ...)`` batch dict.

        ``stragglers`` overrides the straggler source (tests drive exact
        patterns through it); ``valid`` trims padding rows from the
        returned outputs.  Per-batch telemetry feeds the serving
        auto-tuner when one is configured.
        """
        from repro.tune import record_from_times
        arts = self.artifacts
        code = arts.codec.code
        times = None
        if stragglers is None:
            draw = self._source.draw(self._served, code)
            stragglers, times = list(draw.stragglers), draw.times
        else:
            stragglers = list(stragglers)
        inp = arts.step_inputs(stragglers)
        placed = jax.tree.map(jnp.asarray, self._placer.place(batch))
        fn = arts.compiled(placed)
        args = (self.params, placed, inp["W"], inp["mask"], inp["rho"])
        if arts.partial:
            args = args + (inp["err_factor"],)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        wall = time.perf_counter() - t0
        if arts.partial:
            out, bound = out
            err_bound = float(bound)
        else:
            err_bound = 0.0
        failed = tuple(failed_request_rows(code, stragglers, self.b))
        self._served += 1
        if self._tuner is not None and times is not None:
            self._tuner.record(record_from_times(
                self._served, code, self.spec.schedule, self.spec.packed,
                times, n_drop=len(stragglers), measured_step_s=wall))
            plan = self._tuner.maybe_replan(self._served)
            if plan is not None:
                self._apply_plan(plan)
        nvalid = self.batch_requests if valid is None else int(valid)
        return BatchResult(
            outputs=np.asarray(out)[:nvalid],
            requests=(),
            stragglers=tuple(int(i) for i in stragglers),
            err_bound=err_bound,
            within_slo=err_bound <= self.slo.max_decode_err,
            failed_rows=failed,
            wall_s=wall)
