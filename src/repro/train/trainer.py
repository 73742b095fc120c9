"""High-level training driver: wires the data pipeline, coded step, straggler
simulation, telemetry, and (optional) checkpointing + auto-tuning into a run
loop.

Scheme levers arrive as one ``repro.coding.SchemeSpec``
(``Trainer(spec=...)`` — the same instance a ``repro.serving.CodedServer``
accepts); ``Trainer.spec`` is the active scheme and the only place the
trainer reads its levers from.

Stragglers: each step draws a straggler set from the trainer's
``straggler_source`` (the ``repro.tune.StragglerSource`` protocol shared
with the serving engine's hedging loop: ``NoStragglers`` default,
``FixedStragglers``, ``RandomStragglers``, or a timings-backed
``TimedSource``), computes the host-side float64 decode weights for that
responder pattern, and feeds them to the jitted step (the device graph is
static across patterns).  The legacy ``straggler_mode``/
``fixed_stragglers``/``injector`` fields map onto the protocol with a
``DeprecationWarning``.

Auto-tuning (``autotune=AutotunePolicy(...)``): the trainer records per-step
telemetry — per-worker compute/communication durations from a timed
straggler source (wrapping a ``(step, code) -> WorkerTimes`` callable such
as ``repro.tune.DriftingSampler``; on a real cluster, worker heartbeats), the
induced straggler set, and the measured step wall-clock — and every
``policy.interval`` steps refits the Section-VI shifted-exponential model
and re-ranks the feasible (d, s, m) x schedule space (``repro.tune``).
When the winning plan beats the active one past the hysteresis margin the
trainer swaps codecs in place: code, schedule, wire layout and batcher are
replaced, and both the ``StepArtifacts`` and the
jitted executables are held in caches keyed by the scheme signature, so
switching back to a previously used scheme reuses its compiled step instead
of retracing.  ``partial=True`` is preserved across swaps (every cached
artifact is built in the trainer's partial mode).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.coding import SchemeSpec, make_step_inputs
from repro.core import GradCode, make_code
from repro.data import CodedBatcher
from repro.optim import Optimizer

from .coded_step import make_coded_train_step
from .pipeline import PipelineDriver


@dataclasses.dataclass
class Trainer:
    cfg: Any
    code: GradCode
    mesh: Any
    optimizer: Optimizer
    # the scheme levers: one SchemeSpec (shared with CodedServer); the
    # autotuner and the elastic trainer replace it as they swap schemes
    spec: SchemeSpec = dataclasses.field(default_factory=SchemeSpec)
    # the straggler process: one StragglerSource (shared with CodedServer's
    # hedging loop) — the three legacy fields map onto it
    straggler_source: Any | None = None
    straggler_mode: str | None = None  # deprecated: none | random | fixed
    fixed_stragglers: tuple = ()       # deprecated: FixedStragglers(...)
    injector: Callable | None = None   # deprecated: TimedSource(injector)
    autotune: Any | None = None        # repro.tune.AutotunePolicy
    seed: int = 0
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0

    def __post_init__(self):
        import warnings

        from repro.models import api as model_api
        from repro.tune.stragglers import (FixedStragglers, NoStragglers,
                                           RandomStragglers, TimedSource,
                                           as_straggler_source)
        legacy_straggler = (self.straggler_mode is not None
                            or bool(self.fixed_stragglers)
                            or self.injector is not None)
        if self.straggler_source is not None and legacy_straggler:
            raise ValueError(
                "pass either straggler_source= or the deprecated "
                "straggler_mode=/fixed_stragglers=/injector= fields, "
                "not both")
        if (self.injector is not None
                and self.straggler_mode not in (None, "none")):
            raise ValueError(
                "injector= is its own straggler source (the slowest s "
                "workers of each draw are dropped); it cannot be combined "
                f"with straggler_mode={self.straggler_mode!r}")
        if self.straggler_source is not None:
            self._source = as_straggler_source(self.straggler_source)
        elif self.injector is not None:
            warnings.warn(
                "Trainer(injector=...) is deprecated; pass "
                "straggler_source=repro.tune.TimedSource(injector) (or the "
                "injector itself as straggler_source=)",
                DeprecationWarning, stacklevel=3)
            self._source = TimedSource(self.injector)
        elif legacy_straggler:
            warnings.warn(
                "Trainer(straggler_mode=/fixed_stragglers=) is deprecated; "
                "pass straggler_source= (repro.tune.NoStragglers / "
                "FixedStragglers / RandomStragglers)",
                DeprecationWarning, stacklevel=3)
            mode = self.straggler_mode or "fixed"
            if mode == "none":
                self._source = NoStragglers()
            elif mode == "fixed":
                self._source = FixedStragglers(self.fixed_stragglers)
            elif mode == "random":
                # same RNG discipline as the legacy inline draw: a private
                # Generator seeded at seed + 1
                self._source = RandomStragglers(self.seed + 1)
            else:
                raise ValueError(f"unknown straggler_mode {mode!r}")
        else:
            self._source = NoStragglers()
        if self.autotune is not None and not self._source.provides_times:
            raise ValueError(
                "autotune needs per-worker timings: pass a timed "
                "straggler_source= (e.g. a repro.tune.ShiftedExpSampler or "
                "a cluster heartbeat feed — the deprecated injector= "
                "spelling also works)")
        self._arts_cache: dict[tuple, Any] = {}
        self.arts = self._get_arts(self.code, self.spec.schedule,
                                   self.spec.pipelined)
        self._driver: PipelineDriver | None = None
        self.batcher = CodedBatcher(self.code)
        key = jax.random.PRNGKey(self.seed)
        with jax.sharding.set_mesh(self.mesh):
            self.params = model_api.init(key, self.cfg)
            self.opt_state = self.optimizer.init(self.params)
        self._jitted = {}
        self._step_count = 0
        self._data_cursor = 0   # batches consumed (for trajectory resume)
        self._tuner = None
        self.telemetry = None
        if self.autotune is not None:
            from repro.tune import Autotuner
            self._tuner = Autotuner(self.autotune,
                                    current=self._current_plan())
            self.telemetry = self._tuner.telemetry
        elif self._source.provides_times:
            from repro.tune import TelemetryLog
            self.telemetry = TelemetryLog()
        self._ckpt = None
        if self.checkpoint_dir:
            from repro.checkpoint import CheckpointManager
            self._ckpt = CheckpointManager(self.checkpoint_dir)
            restored = self._ckpt.restore_latest(
                {"params": self.params, "opt_state": self.opt_state})
            if restored is not None:
                state, meta = restored
                with jax.sharding.set_mesh(self.mesh):
                    self.params = jax.tree.map(jnp.asarray, state["params"])
                    self.opt_state = jax.tree.map(jnp.asarray, state["opt_state"])
                self._step_count = int(meta.get("step", 0))
                # trajectory-exact resume state: where the data stream was
                # (skip_to_cursor replays a fresh stream to this point) and
                # which seed/scheme produced the snapshot — a mismatch means
                # the resumed run would silently diverge, so warn loudly.
                self._data_cursor = int(
                    meta.get("data_cursor", self._step_count))
                if "seed" in meta and int(meta["seed"]) != self.seed:
                    warnings.warn(
                        f"checkpoint was written with seed "
                        f"{meta['seed']}, trainer has seed {self.seed}: "
                        f"the resumed trajectory will not match the "
                        f"original run", stacklevel=3)
                if ("scheme_sig" in meta
                        and meta["scheme_sig"] != repr(self._scheme_sig)):
                    warnings.warn(
                        f"checkpoint scheme {meta['scheme_sig']} differs "
                        f"from the trainer's {self._scheme_sig!r}: resuming "
                        f"with a different codec changes the straggler/"
                        f"decode trajectory", stacklevel=3)

    # ------------------------------------------------------- codec swapping
    @staticmethod
    def _code_key(code) -> tuple:
        """Hashable scheme identity for the artifact/executable caches."""
        from repro.tune import scheme_k, scheme_loads
        return (type(code).__name__, code.n, code.d, code.s, code.m,
                scheme_k(code), scheme_loads(code),
                getattr(code, "kind", ""), getattr(code, "seed", 0))

    def _sig(self, partial: bool | None = None,
             pipelined: bool | None = None) -> tuple:
        """Scheme signature with optional per-step overrides.

        ``partial`` joins the signature (and hence the jitted-executable
        key): the partial step takes an extra ``err_factor`` argument, so
        an executable compiled for one mode must never serve the other.
        """
        return (self._code_key(self.code), self.spec.schedule,
                self.spec.partial if partial is None else bool(partial),
                self.spec.pipelined if pipelined is None
                else bool(pipelined))

    @property
    def _scheme_sig(self) -> tuple:
        return self._sig()

    def _get_arts(self, code, schedule: str, pipelined: bool = False,
                  partial: bool | None = None):
        """StepArtifacts for a scheme, built once per signature (the compile
        cache's first layer; the jitted executables are the second).

        ``partial`` overrides the trainer's mode for this build — the
        elastic failover path compiles a partial twin of the active scheme
        so a past-budget straggler step can decode approximately instead
        of raising.  Partial artifacts are always synchronous
        (``SchemeSpec`` rejects pipelined+partial).
        """
        part = self.spec.partial if partial is None else bool(partial)
        key = (self._code_key(code), schedule, part, pipelined)
        if key not in self._arts_cache:
            self._arts_cache[key] = make_coded_train_step(
                self.cfg, code, self.mesh, self.optimizer,
                spec=self.spec.replace(schedule=schedule,
                                       pipelined=pipelined, partial=part))
        return self._arts_cache[key]

    def _current_plan(self):
        """The active scheme as a `repro.tune.Plan` (seed for hysteresis)."""
        from repro.core.approx import ExpanderCode, FractionalRepetitionCode
        from repro.core.stable import BlockCompositeCode
        from repro.tune import Plan, scheme_k, scheme_loads
        k = scheme_k(self.code)
        loads = scheme_loads(self.code)
        n0 = None
        if isinstance(self.code, FractionalRepetitionCode):
            fam = "frc"
        elif isinstance(self.code, ExpanderCode):
            fam = "expander"
        elif isinstance(self.code, BlockCompositeCode):
            fam = "block"
            n0 = self.code.n0
        elif getattr(self.code, "kind", "") in ("chebyshev", "rotation"):
            fam = self.code.kind
        else:
            fam = ("uniform" if k == self.code.n and len(set(loads)) == 1
                   else "hetero")
        return Plan(family=fam, d=self.code.d, s=self.code.s, m=self.code.m,
                    k=k, loads=loads, schedule=self.spec.schedule,
                    predicted_wait_s=0.0, predicted_step_s=0.0,
                    predicted_total_s=0.0, pipelined=self.spec.pipelined,
                    n0=n0)

    def _code_for_plan(self, plan):
        """Materialise the scheme object a ranked plan selects."""
        n = len(plan.loads)
        if plan.family == "uniform":
            return make_code(n, plan.d, plan.s, plan.m)
        if plan.family in ("frc", "expander"):
            # the construction is recoverable from (family, d, m) alone:
            # both approx families use d = m * replication, and the
            # expander graph seed is pinned to the planner's default (0)
            # so the materialised graph is the one that was ranked
            from repro.core.approx import make_approx
            return make_approx(plan.family, n, plan.d // plan.m, plan.m)
        if plan.family in ("chebyshev", "rotation", "block"):
            # stable families are recoverable from (family, d, s, m) plus
            # the plan's tile size n0 for block composites; the rotation
            # basis seed is pinned to the planner's default (0), matching
            # the construction whose conditioning certificate was ranked
            from repro.core.stable import make_stable
            return make_stable(plan.family, n, plan.d, plan.s, plan.m,
                               n0=plan.n0)
        # hetero plans carry their exact load assignment (which may encode
        # elastic zero-load holes at departed workers) — build the code
        # from those loads directly rather than re-deriving from speeds,
        # so the materialised scheme always matches what was ranked
        from repro.core.hetero import HeteroCode, HeteroPlan
        speeds = ((1.0,) * n if self._tuner is None
                  or self._tuner.last_fit is None
                  or len(self._tuner.last_fit.speeds) != n
                  else tuple(float(x) for x in self._tuner.last_fit.speeds))
        hp = HeteroPlan(n=n, s=plan.s, m=plan.m, k=plan.k,
                        speeds=speeds, loads=tuple(plan.loads))
        return HeteroCode(plan=hp, kind="poly" if n <= 20 else "random")

    def _swap_code(self, code, schedule: str, pipelined: bool) -> None:
        """Swap the active codec in place (code, schedule, wire, batcher).

        A pipelined swap first drains the in-flight wire (its buffers were
        encoded under the outgoing scheme's pack plan and cannot be decoded
        by the incoming one), applying the pending gradient before the new
        codec takes over."""
        if self._driver is not None and self._driver.in_flight:
            self.params, self.opt_state, _ = self._driver.drain(
                self.params, self.opt_state)
        self._driver = None
        self.code = code
        self.spec = self.spec.replace(schedule=schedule, pipelined=pipelined)
        self.arts = self._get_arts(code, schedule, pipelined)
        self.batcher = CodedBatcher(code)

    def _apply_plan(self, plan) -> None:
        """Adopt a ranked plan: materialise its code and swap it in.

        An approx plan whose drop budget exceeds the code's structural
        tolerance (``plan.s > code.s`` — the planner traded bounded decode
        error for wall-clock) flips the trainer to partial mode: the step
        must decode a certified estimate instead of raising past ``s``.
        """
        code = self._code_for_plan(plan)
        if plan.family in ("frc", "expander") and plan.s > code.s:
            # approx plans are never pipelined; drop the flag in the same
            # replace (SchemeSpec rejects partial+pipelined)
            self.spec = self.spec.replace(partial=True, pipelined=False)
        self._swap_code(code, plan.schedule, getattr(plan, "pipelined", False))

    @property
    def autotune_events(self) -> list[dict]:
        """The tuner's decision log (empty when autotune is off)."""
        return [] if self._tuner is None else self._tuner.events

    @property
    def cached_schemes(self) -> int:
        """Number of distinct scheme signatures with built step artifacts
        (the compile cache's population — revisits don't rebuild)."""
        return len(self._arts_cache)

    def maybe_checkpoint(self, force: bool = False) -> None:
        if self._ckpt is None:
            return
        if force or (self.checkpoint_every
                     and self._step_count % self.checkpoint_every == 0):
            # data_cursor/seed/scheme_sig make the resume trajectory-exact:
            # a fresh run restoring this snapshot can replay its data stream
            # to the same batch (skip_to_cursor) and verify it runs the same
            # seed and codec the snapshot was written under
            with jax.profiler.TraceAnnotation("trainer.checkpoint"):
                self._ckpt.save(self._step_count,
                                {"params": self.params,
                                 "opt_state": self.opt_state},
                                {"arch": self.cfg.name,
                                 "data_cursor": self._data_cursor,
                                 "seed": self.seed,
                                 "scheme_sig": repr(self._scheme_sig)})

    def skip_to_cursor(self, stream: Iterator, consumed: int = 0) -> Iterator:
        """Advance a data stream to the restored batch cursor.

        After a checkpoint restore ``self._data_cursor`` batches of the
        original run are already inside the restored parameters; a resumed
        run feeding a *fresh* stream must discard exactly that many batches
        or every post-resume step trains on the wrong data (the trajectory
        silently forks).  ``consumed`` says how many batches the caller
        already pulled from this particular stream.  Returns the stream for
        chaining.
        """
        for _ in range(max(0, self._data_cursor - int(consumed))):
            next(stream)
        return stream

    # ---------------------------------------------------------------- hooks
    def _step_partial(self, stragglers) -> bool:
        """Whether THIS step decodes partially (subclass failover hook).

        The base trainer simply runs its configured mode;
        :class:`~repro.elastic.ElasticTrainer` overrides this to force
        ``True`` when the straggler set exceeds the design budget ``s`` —
        the past-budget step then fails over to the approximate decode
        (with its ``decode_err_bound`` certificate) instead of raising.
        """
        return self.spec.partial

    def _departed_workers(self) -> tuple[int, ...]:
        """Departed worker indices for the re-planner (subclass hook)."""
        return ()

    # ---------------------------------------------------------------- steps
    def step(self, batch: dict[str, np.ndarray]) -> dict[str, float]:
        """One training step, its host work in profiler spans, in order:
        ``trainer.inputs`` (batch placement, straggler draw, float64
        decode-weight solve, device puts), ``trainer.dispatch`` (the jitted
        call; a fresh executable's trace and compile fall inside it),
        ``trainer.sync`` (waiting on the metrics), ``trainer.readback``
        (metrics to floats), and ``trainer.telemetry`` (timed straggler
        sources only).  ``maybe_checkpoint`` adds ``trainer.checkpoint``
        when it saves."""
        with jax.profiler.TraceAnnotation("trainer.inputs"):
            placed = self.batcher.place(batch)
            draw = self._source.draw(self._step_count,
                                     self.code).restrict(self.code.n)
            stragglers = list(draw.stragglers)
            times = draw.times
            part = self._step_partial(stragglers)
            # a forced-partial step cannot ride the pipelined wire (the
            # partial executable is synchronous by construction), so it
            # drops to the sync path for this step only; when the trainer
            # is *configured* partial, pipelining is already off
            # (SchemeSpec rejects the combo)
            pipelined = self.spec.pipelined and not part
            if (self.spec.pipelined and not pipelined
                    and self._driver is not None
                    and self._driver.in_flight):
                # retire the in-flight update before stepping synchronously
                # — its buffers are valid under the unchanged codec
                self.params, self.opt_state, _ = self._driver.drain(
                    self.params, self.opt_state)
                self._driver = None
            arts = (self.arts if part == self.spec.partial
                    and pipelined == self.spec.pipelined
                    else self._get_arts(self.code, self.spec.schedule,
                                        pipelined=pipelined, partial=part))
            fn = None
            fresh = False
            if not pipelined:
                shapes = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), placed)
                keyshape = (self._sig(partial=part, pipelined=pipelined),
                            tuple(sorted((k, v.shape)
                                         for k, v in placed.items())))
                fresh = keyshape not in self._jitted
                if fresh:
                    smapped, in_specs, _ = arts.step(shapes)
                    self._jitted[keyshape] = jax.jit(smapped,
                                                     donate_argnums=(0, 1))
                fn = self._jitted[keyshape]
            inp = make_step_inputs(self.code, stragglers, partial=part)
            args = [jnp.asarray(inp["W"]), jnp.asarray(inp["mask"]),
                    jnp.asarray(inp["rho"])]
            if part:
                args.append(jnp.asarray(inp["err_factor"]))
            t0 = time.perf_counter()
            with jax.sharding.set_mesh(self.mesh):
                dev_batch = jax.tree.map(jnp.asarray, placed)
        with (jax.profiler.TraceAnnotation("trainer.dispatch"),
              jax.sharding.set_mesh(self.mesh)):
            if pipelined:
                # the driver fills on first use (metrics None — no update
                # retired yet) and runs overlapped steady steps after; its
                # metrics describe the PREVIOUS batch, whose gradient is
                # the one applied (stale-by-one)
                if self._driver is None:
                    self._driver = PipelineDriver(arts)
                self.params, self.opt_state, metrics = self._driver.step(
                    self.params, self.opt_state, dev_batch, *args)
                fresh = self._driver.last_fresh
            else:
                self.params, self.opt_state, metrics = fn(
                    self.params, self.opt_state, dev_batch, *args)
        if metrics is not None:
            with jax.profiler.TraceAnnotation("trainer.sync"):
                jax.block_until_ready(metrics)
        wall = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("trainer.readback"):
            out = ({"loss": float("nan"), "grad_norm": float("nan")}
                   if metrics is None
                   else {k: float(v[0]) for k, v in metrics.items()})
        if times is not None:
            with jax.profiler.TraceAnnotation("trainer.telemetry"):
                from repro.tune import record_from_times
                # a fresh executable's first call pays one-time
                # trace+compile: keep it out of the step-cost calibration
                # (measured_step_s <= 0 is ignored by StepCostBook) while
                # still recording the worker timings the estimator fits on
                # — and hand the compile wall to the record so the
                # planner's recompile-amortization charge is calibrated
                # from real traces.  The returned "step_time_s" stays the
                # real wall either way.  A pipelined fill call (metrics
                # None) retires no update, so its wall is not a steady step
                # cost either.
                uncal = fresh or metrics is None
                rec = record_from_times(self._step_count, self.code,
                                        self.spec.schedule, times,
                                        measured_step_s=0.0 if uncal
                                        else wall,
                                        pipelined=pipelined,
                                        compile_s=wall if fresh else 0.0)
                out["step_time_s"] = wall
                out["modeled_wait_s"] = rec.wait_s
                if self._tuner is not None:
                    self._tuner.record(rec)
                    new_plan = self._tuner.maybe_replan(
                        self._step_count,
                        departed=self._departed_workers())
                    if new_plan is not None:
                        self._apply_plan(new_plan)
                elif self.telemetry is not None:
                    self.telemetry.append(rec)
        self._step_count += 1
        self._data_cursor += 1
        self.maybe_checkpoint()
        return out

    def run(self, stream: Iterator[dict[str, np.ndarray]], steps: int,
            log_every: int = 10, log_path: str | None = None) -> list[dict]:
        logs = []
        t0 = time.time()
        for i in range(steps):
            m = self.step(next(stream))
            m["step"] = i
            m["wall"] = time.time() - t0
            logs.append(m)
            if log_every and i % log_every == 0:
                print(f"step {i:5d} loss {m['loss']:.4f} "
                      f"gnorm {m['grad_norm']:.3e} t {m['wall']:.1f}s")
        if log_path:
            pathlib.Path(log_path).write_text(json.dumps(logs))
        return logs
