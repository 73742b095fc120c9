"""One run of one cell: set-up, the measured (or traced) window, the
comparison with the reference, and the result.

The cell's model type (its configuration's ``model_type``) is resolved
once, to the modules of ``models/<model_type>/`` (``model_of``).
Set-up builds the cell's ``Trainer`` (``program.py``), drives it through
its first three steps with the run's own feed (these compile the step and
give the comparison its readings), then hands the same trainer to the
window.  ``--trace 0`` times back-to-back steps for the window's length;
``--trace 1`` traces a few steps and reduces the trace (``reduce_trace.py``) to
the cell's per-layer metrics, each read by ``metrics/<name>.py``.  After
the window the trainer is freed and the reference (``reference.py``
around the model type's own) runs on the first chip.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import gzip
import importlib.util
import json
import math
import os
import pathlib
import platform
import re
import shutil
import sys
import time
from typing import Callable

import numpy as np

import check
import counts
import program
import reference
import reduce_trace as tracing

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
MODELS = HERE / "models"
MODEL_PARTS = ("program", "reference", "counts")
# a fixed path inside the checkout: JAX's persistent cache keys on it
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = HERE / ".traces"
FIRST_STEPS = 3
TRACED_STEPS = 5


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass(frozen=True, eq=False)
class Model:
    """What the benchmark knows of one model type, from the modules of
    ``models/<model_type>/`` (``run.py`` says what each defines).  Hashed
    by identity: the reference keeps its compiled programs per model."""
    model_type: str
    model_config: Callable      # program.model_config(config)
    dims: Callable              # reference.Dims.from_config(config)
    init_params: Callable       # reference.init_params(seed, dims)
    sequence_loss: Callable     # reference.sequence_loss(params, tokens,
                                #     labels, dims, quant=None)
    toy: dict                   # reference.TOY
    total_params: Callable      # counts.total_params(config)
    flops_per_token: Callable   # counts.flops_per_token(config, seq_len)
    kernels: Callable           # counts.kernels(config, traffic), or none


def _module(path: pathlib.Path):
    name = "chipbench_models_" + re.sub(r"\W", "_", str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod     # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _model_at(where: pathlib.Path) -> Model:
    prog, ref, cnt = (_module(where / f"{p}.py") for p in MODEL_PARTS)
    return Model(where.name, prog.model_config, ref.Dims.from_config,
                 ref.init_params, ref.sequence_loss, ref.TOY,
                 cnt.total_params, cnt.flops_per_token,
                 getattr(cnt, "kernels", lambda config, traffic: {}))


def model_of(config: dict, root: pathlib.Path = MODELS) -> Model:
    """The configuration's model type: the directory ``root/<model_type>/``
    and its modules, loaded once; raises where there is none."""
    kind = config["model_type"]
    where = root / kind
    if not (re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", kind)
            and all((where / f"{p}.py").is_file() for p in MODEL_PARTS)):
        raise KeyError(f"{config['name']}: no model type {kind!r}: "
                       f"{where} holds no "
                       + ", ".join(f"{p}.py" for p in MODEL_PARTS))
    return _model_at(where)


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its files read and its model
    type resolved."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    model: Model


def read_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str, b: dict | None = None) -> Cell:
    """The workload ``name`` of ``b`` (default: ``BENCHMARK.json``) and its
    configuration, model type, traffic, limits and metrics, found by
    name."""
    b = b or read_benchmark()
    work = {w["name"]: w for w in b["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in the benchmark; "
                       f"have {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in b["configs"]}[w["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits = json.loads((HERE / "limits" / f"{name}.json").read_text())
    e2e = [m for m in b["end_to_end"] if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in b["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    if traffic["chips"] != w["chips"]:
        raise ValueError(f"{name}: traffic {w['traffic']} runs on "
                         f"{traffic['chips']} chips, the cell asks {w['chips']}")
    return Cell(name, w["chips"], config, traffic, limits, e2e, per_layer,
                model_of(config))


def derive_seeds(seed: int) -> tuple[int, int, int]:
    """(weights, data, stragglers) seeds from the run's seed; the weights'
    seed fits a 31-bit PRNG key."""
    w, d, s = np.random.SeedSequence(seed % 2**64).generate_state(3)
    return int(w) & 0x7FFFFFFF, int(d), int(s)


class Feed:
    """The cell's batches from the data seed: rows of uniform tokens over
    the ``vocab`` rows, labels the next token.  Every step gets new rows."""

    def __init__(self, vocab: int, traffic: dict, seed: int):
        self.rows = traffic["code"]["n"] * traffic["sequences_per_subset"]
        self.seq = traffic["seq_len"]
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)

    def next(self) -> dict[str, np.ndarray]:
        toks = self.rng.integers(0, self.vocab, (self.rows, self.seq),
                                 dtype=np.int32)
        return {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}


class CompileLog:
    """Backend-compile seconds, compiles and persistent-cache hits, from
    JAX's monitoring events."""

    def __init__(self):
        import jax

        self.compile_s, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def capture_env() -> dict:
    """What makes two runs comparable: versions, backend and devices."""
    import jax

    devices = jax.devices()
    return {"python": platform.python_version(), "jax": jax.__version__,
            "numpy": np.__version__, "backend": jax.default_backend(),
            "device_count": len(devices),
            "device_kind": devices[0].device_kind,
            "xla_flags": os.environ.get("XLA_FLAGS", "")}


def enable_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_or_fail(cell: Cell, require_chip: bool):
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def program_readings(trainer, feed: Feed, model: Model, k, weight_seed: int,
                     b1: float) -> reference.Readings:
    """Set-up's first steps through the window's own call and feed: each
    step's loss, the first gradient from AdamW's state after one step, and
    each leaf's change after the last."""
    import jax

    norms = jax.jit(reference.leaf_norms)
    losses, grads = [], None
    for t in range(FIRST_STEPS):
        losses.append(trainer.step(feed.next())["loss"])
        if t == 0:
            grads = {n: float(v) / (1 - b1) for n, v in
                     norms(program.first_moment(trainer)).items()}
    changes = reference.change_norms(model, weight_seed, k,
                                     program.params(trainer))
    return reference.Readings(losses, grads, changes)


class GcLog:
    """Seconds the interpreter's garbage collector held the process, and
    its collections by generation, until ``close``."""

    def __init__(self):
        self.pause_s, self.by_gen, self._t = 0.0, [0, 0, 0], 0.0
        gc.callbacks.append(self._callback)

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pause_s += time.perf_counter() - self._t
            self.by_gen[info["generation"]] += 1

    def close(self) -> None:
        gc.callbacks.remove(self._callback)


def measure(trainer, feed: Feed, seconds: float, log: CompileLog,
            tokens_per_step: int, err) -> tuple[dict, int, int]:
    """Back-to-back steps for ``seconds``: tokens/s over the whole window
    and the 90th percentile of the steps' wall times."""
    compiles = log.compiles
    steps, failed = [], 0
    gcs = GcLog()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        a = time.perf_counter()
        loss = trainer.step(feed.next())["loss"]
        steps.append(time.perf_counter() - a)
        failed += not math.isfinite(loss)
    program.block(trainer)
    window = time.perf_counter() - t0
    gcs.close()
    ordered = sorted(steps)
    median = ordered[len(ordered) // 2]
    p90 = ordered[math.ceil(0.9 * len(ordered)) - 1]
    beyond = sum(s > p90 for s in steps)
    print(f"window: {len(steps)} steps in {window:.6f} s; step median "
          f"{1e3 * median:.3f} ms, p90 {1e3 * p90:.3f} "
          f"ms with {beyond} steps beyond it; compiles inside the window "
          f"{log.compiles - compiles}", file=err)
    print(f"window: outside the steps {window - sum(steps):.6f} s; steps' "
          f"excess over the median {sum(steps) - len(steps) * median:.6f} s; "
          f"slowest (ms) {[round(1e3 * s, 3) for s in ordered[-5:]]} at "
          f"{sorted(sorted(range(len(steps)), key=steps.__getitem__)[-5:])}; "
          f"gc {gcs.pause_s:.6f} s in {gcs.by_gen} collections by generation",
          file=err)
    return ({"tokens_per_s": len(steps) * tokens_per_step / window,
             "step_ms_p90": 1e3 * p90}, len(steps), failed)


class ReadContext:
    """What a per-layer metric reader gets: the reduced trace and the
    cell's counts and peaks."""

    def __init__(self, reduced, cell: Cell, peak: dict):
        self.trace = reduced
        self.n_chips = cell.chips
        self.peak = peak
        self.tokens_per_step = program.unique_tokens(cell.traffic)
        self.flops_per_token = cell.model.flops_per_token(
            cell.config, cell.traffic["seq_len"])
        self.kernels = counts.kernel_work(cell.model.total_params(cell.config),
                                          cell.traffic["code"])
        self.model_kernels = cell.model.kernels(cell.config, cell.traffic)

    def _share(self, nbytes: float, flops: float, times: list[float]):
        """Mean over the chips that spent time on a kernel of the least
        time its bytes or flops take a step, over that time."""
        least = max(nbytes / self.peak["hbm_bytes_per_s"],
                    flops / self.peak["bf16_flops_per_s"]) * self.trace.steps
        shares = [least / t for t in times if t > 0]
        return 100.0 * sum(shares) / len(shares) if shares else None

    def roofline(self, kind: str):
        """Share of the roofline of a codec kernel, mean over the chips
        whose trace holds it; None where none does."""
        nbytes, flops = self.kernels[kind]
        return self._share(nbytes, flops,
                           [c.by_kind[kind] for c in self.trace.chips])

    def roofline_of(self, name: str):
        """Share of the roofline of a kernel that the model type's
        ``counts.kernels`` declares, its time the ops whose names match the
        declared pattern; None where the model declares no such kernel or
        no chip's trace holds it."""
        if name not in self.model_kernels:
            return None
        pattern, nbytes, flops = self.model_kernels[name]
        match = re.compile(pattern).search
        return self._share(nbytes, flops, [
            sum(t for op, t in c.by_op.items() if match(op))
            for c in self.trace.chips])

    def time_in_scope(self, scope: str):
        """Milliseconds a step, mean over chips, of the ops whose innermost
        named scope is ``scope`` (``<layer>.<phase>``) or, for a bare
        ``<layer>``, any of its phases; None where no chip's trace holds
        one."""
        times = [sum(t for s, t in c.by_scope.items()
                     if s == scope or s.split(".")[0] == scope)
                 for c in self.trace.chips]
        if not any(times) or self.trace.steps == 0:
            return None
        return 1e3 * sum(times) / len(times) / self.trace.steps


def peaks_for(kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def read_metric(name: str, ctx: ReadContext):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def traced(trainer, feed: Feed, cell: Cell, seed: int, err,
           keep: str | None) -> tuple[dict, dict, dict, int, int]:
    """A few steps under the profiler, reduced to the per-layer metrics."""
    import jax

    where = TRACE_DIR / f"{cell.name}-{seed}"
    shutil.rmtree(where, ignore_errors=True)
    failed = 0
    jax.profiler.start_trace(str(where))
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            for i in range(TRACED_STEPS):
                with jax.profiler.StepTraceAnnotation("train", step_num=i):
                    failed += not math.isfinite(
                        trainer.step(feed.next())["loss"])
            program.block(trainer)
    finally:
        jax.profiler.stop_trace()
    (path,) = where.glob("plugins/profile/*/*.xplane.pb")
    extracted = tracing.extract(str(path))
    reduced = tracing.reduce(extracted, TRACED_STEPS)
    kind = jax.devices()[0].device_kind
    ctx = ReadContext(reduced, cell, peaks_for(kind))
    metrics, missing = {}, []
    for m in cell.per_layer:
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif "workloads" in m:
            missing.append(m["name"])
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(path, pathlib.Path(keep) / f"{cell.name}.xplane.pb")
        kept = dict(tracing.clip(extracted), workload=cell.name,
                    device_kind=kind, steps=TRACED_STEPS,
                    metrics={n: v["value"] for n, v in metrics.items()})
        with gzip.open(pathlib.Path(keep) / f"{cell.name}.events.json.gz",
                       "wt") as f:
            json.dump(kept, f)
    shutil.rmtree(where, ignore_errors=True)
    if missing:
        # these metrics name this cell: finding nothing means the trace's
        # names no longer match the reduction's, not that the work is gone
        raise RuntimeError(
            f"{cell.name}: the trace holds nothing for {missing}; time by "
            f"kind {[c.by_kind for c in reduced.chips]}")
    device = {"busy_s": sum(c.busy_s for c in reduced.chips)
              / max(1, len(reduced.chips)),
              "window_s": reduced.window_s}
    for c_i, c in enumerate(reduced.chips):
        print(f"trace chip {c_i}: busy {c.busy_s:.6f} s of "
              f"{reduced.window_s:.6f} s; " + ", ".join(
                  f"{k} {v:.6f} s" for k, v in c.by_kind.items()), file=err)
    return metrics, device, tracing.breakdown(reduced), TRACED_STEPS, failed


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, backend: str | None = None,
        keep_trace: str | None = None, err=sys.stderr) -> dict:
    """One run; returns the result object the last stdout line carries."""
    devices = chips_or_fail(cell, require_chip)
    enable_cache()
    log = CompileLog()
    print(f"env: {json.dumps(capture_env())}", file=err)
    wseed, dseed, sseed = derive_seeds(seed)
    model = cell.model
    k = model.dims(cell.config)
    opt = cell.traffic["optimizer"]
    trainer = program.build_trainer(model.model_config(cell.config),
                                    cell.traffic, weight_seed=wseed,
                                    straggler_seed=sseed, backend=backend)
    feed = Feed(k.vocab, cell.traffic, dseed)
    prog = program_readings(trainer, feed, model, k, wseed, opt["b1"])
    tokens = program.unique_tokens(cell.traffic)
    setup_s = time.perf_counter() - t_start
    print(f"set-up: {setup_s:.6f} s; backend compile {log.compile_s:.6f} s "
          f"in {log.compiles} compiles; persistent cache hits "
          f"{log.cache_hits}; first losses {prog.losses}", file=err)
    breakdown = None
    if trace:
        metrics, extra, breakdown, attempted, failed = traced(
            trainer, feed, cell, seed, err, keep_trace)
    else:
        e2e, attempted, failed = measure(trainer, feed, seconds, log, tokens,
                                         err)
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {n: {"value": e2e[n], "unit": units[n]} for n in units}
        extra = {}
    used = devices[:cell.chips]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes(used), **extra}
    print(f"peak_bytes_in_use (fullest chip): {device['memory_peak_bytes']}",
          file=err)
    del trainer
    gc.collect()

    t_ref = time.perf_counter()
    replay = Feed(k.vocab, cell.traffic, dseed)
    batches = [replay.next() for _ in range(FIRST_STEPS)]
    ref = reference.train_readings(model, wseed, k, batches, opt,
                                   device=used[0])
    numbers = check.gaps(prog, ref)
    correct, shown = check.verdict(numbers, cell.limits)
    correct = correct and failed == 0
    print(f"reference: {time.perf_counter() - t_ref:.3f} s; losses "
          f"{ref.losses}", file=err)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {n: {"value": min(v["value"], 1e300),
                              "limit": v["limit"]} for n, v in shown.items()}
    for n, v in shown.items():
        print(f"{n} {v['value']:.6e} limit {v['limit']:.6e}", file=err)
    return result
