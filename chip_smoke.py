"""Smoke test of coded data-parallel training on TPU chips.

It drives the launcher's own ``repro.launch.train.build`` — the config cut,
the mesh over the chips present, the ``Trainer`` and its coded step — at the
published widths of qwen3-1.7b (d_model 2048, 16/8 heads of 128, d_ff 6144,
qk-norm), cut to 4 layers and 1/8 of the vocabulary rows (18992), with AdamW
and random weights from a seed.

  python chip_smoke.py             # one chip: code (1,1,0,1) on the compiled
                                   # Pallas kernels, 4 steps of 4 x 2048
                                   # tokens; the first step's gradient is
                                   # checked against the ref backend and the
                                   # psum schedule
  python chip_smoke.py --chips 4   # four chips: code (4,3,1,2), worker 2
                                   # dropped, gather and a2a on the Pallas
                                   # kernels, each checked against psum

The gradient of a step is read from AdamW's first moment after the first
step from zero state, m = (1 - b1) * g, the same scaling on every path.
At the TPU's default precision an f32 matmul takes bf16 passes: one
subset's gradient moves by 1.6e-2 (relative L2, measured on a v5e), so two
compiled programs of the same step may differ by far more than any codec
error.  On one chip the three programs agreed to ~1e-7 at default
precision; the four-chip psum and coded programs differed by 4.2e-3, so
the four-chip phase runs at ``jax.default_matmul_precision("highest")``
(f32 as the config states) and its gaps measure the codec alone.
Every check that fails exits nonzero.  The last line of a run that passes
is one JSON object naming the device.  Compiles go to JAX's persistent
cache (``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
# relative L2 gap of the decoded gradient to its reference: the f32 codec
# kernels land at ~1e-7 of an f64 reference on a v5e; the same contraction
# on bf16 operands lands at ~1e-3, so a codec that dropped to bf16 fails
GRAD_TOL = 1e-4
MODEL = ["--size", "cut", "--seq", "2048", "--optimizer", "adamw",
         "--lr", "3e-4"]


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


class CompileLog:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def first_moment(trainer) -> list[np.ndarray]:
    """AdamW's first moment, (1 - b1) * g after one step from zero state."""
    import jax

    return [np.asarray(x) for x in
            jax.tree.leaves(jax.device_get(trainer.opt_state["m"]))]


def rel_gap(got: list[np.ndarray], want: list[np.ndarray]) -> float:
    """||got - want|| / ||want|| over every leaf, in float64."""
    num = sum(float(np.sum((g.astype(np.float64) - w) ** 2))
              for g, w in zip(got, want))
    den = sum(float(np.sum(w.astype(np.float64) ** 2)) for w in want)
    return math.sqrt(num / den)


def custom_calls(trainer, batch) -> int:
    """``tpu_custom_call`` ops (compiled Pallas kernels) in the lowered
    step: interpret mode would lower to plain HLO loops instead."""
    import jax

    placed = trainer.batcher.place(batch)
    with jax.sharding.set_mesh(trainer.mesh):
        lowered = trainer.arts.lowered(placed, trainer.cfg, trainer.optimizer)
    return lowered.as_text().count("tpu_custom_call")


def run_one_step(flags: list[str], batch, label: str, log: CompileLog):
    """A fresh launcher ``Trainer`` for ``flags``, one step on ``batch``
    (None: the launcher stream's first batch): returns (gradient moment,
    loss, the trainer, the batch)."""
    from repro.launch.train import build, parse_args

    trainer, stream = build(parse_args(MODEL + flags))
    batch = next(stream) if batch is None else batch
    c0, t0 = log.compile_s, time.perf_counter()
    out = trainer.step(batch)
    wall = time.perf_counter() - t0
    print(f"{label}: step 0 loss {out['loss']:.6f}; wall time of this one "
          f"step {wall:.3f} s, of which backend compile "
          f"{log.compile_s - c0:.3f} s")
    if not math.isfinite(out["loss"]):
        fail(f"{label}: loss {out['loss']} is not finite")
    return first_moment(trainer), out["loss"], trainer, batch


def check_gap(label: str, got, want) -> None:
    gap = rel_gap(got, want)
    print(f"gap {label}: relative L2 {gap:.3e} (tolerance {GRAD_TOL:.0e})")
    if not gap <= GRAD_TOL:
        fail(f"{label}: relative gap {gap:.3e} exceeds {GRAD_TOL:.0e}")


def peak_bytes() -> int:
    import jax

    return max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def one_chip(log: CompileLog, steps: int = 4) -> None:
    """Code (1,1,0,1) on the compiled kernels: `steps` AdamW steps of
    4 x 2048 tokens, then the first step's gradient against the ref
    backend and the psum schedule on the same batch."""
    from repro.launch.train import build, parse_args

    flags = ["--n-data", "1", "--d", "1", "--s", "0", "--m", "1",
             "--batch-per-subset", "4", "--stragglers", "none"]
    trainer, stream = build(parse_args(
        MODEL + flags + ["--backend", "pallas", "--schedule", "gather"]))
    print(f"config {trainer.cfg.name}: d_model {trainer.cfg.d_model}, "
          f"heads {trainer.cfg.n_heads}/{trainer.cfg.n_kv_heads} of "
          f"{trainer.cfg.head_dim_}, d_ff {trainer.cfg.d_ff}, layers "
          f"{trainer.cfg.n_layers}, vocab {trainer.cfg.vocab}; code "
          f"(n,d,s,m)=(1,1,0,1); batch 4 x 2048 tokens per step")
    batches = [next(stream) for _ in range(steps)]
    calls = custom_calls(trainer, batches[0])
    print(f"pallas step: {calls} tpu_custom_call ops in the lowered step")
    if calls < 2:
        fail("the pallas step holds no compiled encode and decode kernels")
    losses, walls = [], []
    moment = None
    for i, batch in enumerate(batches):
        c0, t0 = log.compile_s, time.perf_counter()
        out = trainer.step(batch)
        walls.append(time.perf_counter() - t0)
        losses.append(out["loss"])
        what = (f"of which backend compile {log.compile_s - c0:.3f} s"
                if i == 0 else "no compile" if log.compile_s == c0
                else f"recompiled {log.compile_s - c0:.3f} s")
        print(f"pallas step {i}: loss {out['loss']:.6f}; wall time of this "
              f"one step {walls[-1]:.3f} s ({what})")
        if not math.isfinite(out["loss"]):
            fail(f"pallas step {i}: loss {out['loss']} is not finite")
        if i == 0:
            moment = first_moment(trainer)
    print(f"peak_bytes_in_use after the pallas steps: {peak_bytes()}")
    del trainer
    gc.collect()

    ref, ref_loss, tr, _ = run_one_step(
        flags + ["--backend", "ref", "--schedule", "gather"], batches[0],
        "ref backend", log)
    del tr
    gc.collect()
    psum, psum_loss, tr, _ = run_one_step(
        flags + ["--schedule", "psum"], batches[0], "psum schedule", log)
    del tr
    gc.collect()
    check_gap("pallas vs ref backend", moment, ref)
    check_gap("pallas vs psum schedule", moment, psum)
    print(f"losses: pallas {losses}; first-step ref {ref_loss:.6f}, "
          f"psum {psum_loss:.6f}")


def four_chips(log: CompileLog) -> None:
    """Code (4,3,1,2) over four chips with worker 2 dropped: the gather and
    a2a steps on the compiled kernels against the uncoded psum step."""
    flags = ["--n-data", "4", "--d", "3", "--s", "1", "--m", "2",
             "--batch-per-subset", "2", "--stragglers", "fixed",
             "--drop", "2"]
    print("config qwen3-1.7b cut to 4 layers, vocab 18992; code "
          "(n,d,s,m)=(4,3,1,2), worker 2 dropped; batch 4 subsets x "
          "2 x 2048 tokens; matmul precision highest")
    psum, psum_loss, tr, batch = run_one_step(
        flags + ["--schedule", "psum"], None, "psum schedule", log)
    del tr
    gc.collect()
    for schedule in ("gather", "a2a"):
        got, loss, tr, _ = run_one_step(
            flags + ["--schedule", schedule, "--backend", "pallas"], batch,
            f"{schedule} pallas", log)
        calls = custom_calls(tr, batch)
        print(f"{schedule} pallas: {calls} tpu_custom_call ops in the "
              f"lowered step")
        if calls < 2:
            fail(f"the {schedule} step holds no compiled kernels")
        del tr
        gc.collect()
        check_gap(f"{schedule} coded vs psum", got, psum)
        print(f"loss {schedule} {loss:.6f} vs psum {psum_loss:.6f}")
    print(f"peak_bytes_in_use over the four chips (max): {peak_bytes()}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} chips")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    log = CompileLog()
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(log)
    else:
        with jax.default_matmul_precision("highest"):
            four_chips(log)
    print(f"backend compile {log.compile_s:.3f} s in all; persistent cache "
          f"hits {log.cache_hits}; whole run {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
