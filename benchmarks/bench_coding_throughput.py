"""Micro-benchmark of the coding layer itself: encode / decode throughput vs
gradient dimension l for each codec backend (ref einsum vs the Pallas
kernels — interpret mode off-TPU, so the kernel numbers on CPU measure the
interpreter, not Mosaic), plus the host-side decode-weight solve time (the
master's O(n^3) per-pattern cost the paper argues is negligible).

  PYTHONPATH=src python benchmarks/bench_coding_throughput.py --backend both
  PYTHONPATH=src python benchmarks/bench_coding_throughput.py --backend ref
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench import (
    BenchResult,
    BenchSpec,
    TimerPolicy,
    capture_env,
    register,
    time_callable,
)
from repro.coding import resolve_backend
from repro.core import make_code


def _bench_backend(name: str, quick: bool) -> BenchResult:
    code = make_code(16, 4, 1, 3)
    bk = resolve_backend(name)
    interp = bool(getattr(bk, "interpret", False))
    # the Pallas interpreter is orders of magnitude slower than compiled
    # Mosaic — keep its problem sizes honest-but-small off TPU
    if quick:
        sizes = (1 << 12,)
        policy = TimerPolicy(warmup=1, reps=2 if interp else 5)
    elif interp:
        sizes = (1 << 12, 1 << 14)
        policy = TimerPolicy(warmup=1, reps=5)
    else:
        sizes = (1 << 16, 1 << 20, 1 << 22)
        policy = TimerPolicy(warmup=1, reps=20)
    enc = jax.jit(lambda G, C: bk.encode(G, C))
    dec = jax.jit(lambda F, W: bk.decode(F, W))
    rng = np.random.default_rng(0)
    metrics: dict[str, float] = {}
    lines = []
    for l in sizes:
        V = l // code.m
        G = jnp.asarray(rng.standard_normal((code.d, code.m, V)), jnp.float32)
        C = jnp.asarray(code.C[0], jnp.float32)
        F = jnp.asarray(rng.standard_normal((code.n, V)), jnp.float32)
        W = jnp.asarray(code.decode_weights(range(1, 16)), jnp.float32)
        t_enc = time_callable(enc, G, C, policy=policy).mean_s * 1e6
        t_dec = time_callable(dec, F, W, policy=policy).mean_s * 1e6
        gbps_enc = G.size * 4 / (t_enc / 1e6) / 1e9
        gbps_dec = F.size * 4 / (t_dec / 1e6) / 1e9
        metrics[f"encode_us_l{l}"] = round(t_enc, 1)
        metrics[f"decode_us_l{l}"] = round(t_dec, 1)
        metrics[f"encode_GBps_l{l}"] = round(gbps_enc, 3)
        metrics[f"decode_GBps_l{l}"] = round(gbps_dec, 3)
        lines.append(f"coding_throughput,backend={bk.name}"
                     f"{',interpret' if interp else ''},l={l},"
                     f"encode_us={t_enc:.0f},decode_us={t_dec:.0f},"
                     f"enc_GBps={gbps_enc:.1f},dec_GBps={gbps_dec:.1f}")
    return BenchResult(
        name=f"coding_throughput_{bk.name}",
        metrics=metrics,
        params={"code": {"n": 16, "d": 4, "s": 1, "m": 3},
                "sizes": list(sizes), "interpret": interp, "quick": quick},
        env=capture_env(),
        timing={"warmup": policy.warmup, "reps": policy.reps},
        # raw wall-clock: CI hardware varies too much to gate these
        gates={},
        extra={"lines": lines},
    )


def _bench_fused_decode(quick: bool) -> BenchResult:
    """The packed wire's compute-side claim, isolated from collectives: one
    fused (n, K*V) decode contraction vs K skinny per-leaf (n, V) decodes at
    identical total elements (K pallas_call/einsum launches vs one)."""
    n, m = 4, 2
    K, V = (8, 512) if quick else (64, 4096)
    bk = resolve_backend("ref")
    rng = np.random.default_rng(1)
    leaves = [jnp.asarray(rng.standard_normal((n, V)), jnp.float32)
              for _ in range(K)]
    packed = jnp.concatenate(leaves, axis=1)               # (n, K*V)
    W = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
    per_leaf = jax.jit(lambda fs, Wm: [bk.decode(f, Wm) for f in fs])
    fused = jax.jit(lambda F, Wm: bk.decode(F, Wm))
    policy = TimerPolicy(warmup=2, reps=5 if quick else 20)
    t_leaf = time_callable(per_leaf, leaves, W, policy=policy).mean_s * 1e6
    t_fused = time_callable(fused, packed, W, policy=policy).mean_s * 1e6
    speedup = t_leaf / t_fused
    line = (f"fused_decode,K={K},V={V},per_leaf_us={t_leaf:.0f},"
            f"fused_us={t_fused:.0f},speedup={speedup:.2f}x")
    return BenchResult(
        name="fused_decode",
        metrics={"per_leaf_us": round(t_leaf, 1),
                 "fused_us": round(t_fused, 1),
                 "fused_speedup": round(speedup, 3)},
        params={"n": n, "m": m, "K": K, "V": V, "quick": quick},
        env=capture_env(),
        timing={"warmup": policy.warmup, "reps": policy.reps},
        gates={},   # wall-clock ratio: too hardware-dependent to gate
        extra={"lines": [line]},
    )


def _bench_solve(quick: bool) -> BenchResult:
    metrics: dict[str, float] = {}
    lines = []
    reps = 20 if quick else 100
    for n in (16, 32):
        c = make_code(n, 4, 1, 3)
        resp = list(range(1, n))
        t0 = time.perf_counter()
        for _ in range(reps):
            c.decode_weights(resp)
        t = (time.perf_counter() - t0) / reps * 1e6
        metrics[f"solve_us_n{n}"] = round(t, 1)
        lines.append(f"decode_weight_solve,n={n},us={t:.0f}")
    return BenchResult(
        name="decode_weight_solve",
        metrics=metrics,
        params={"reps": reps, "quick": quick},
        env=capture_env(),
        timing={"warmup": 0, "reps": reps},
        gates={},
        extra={"lines": lines},
    )


def _kernel_backend() -> str:
    """The Pallas kernels: compiled on a TPU, interpreted elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def bench_results(quick: bool = False,
                  backends: tuple[str, ...] | None = None) -> list[BenchResult]:
    if quick:
        backends = ("ref",)
    elif backends is None:
        backends = ("ref", _kernel_backend())
    out = [_bench_backend(name, quick) for name in backends]
    out.append(_bench_fused_decode(quick))
    out.append(_bench_solve(quick))
    return out


register(BenchSpec(
    name="throughput",
    description="encode/decode microbench",
    fn=bench_results,
    tags=("kernels",),
))


def run(backends: tuple[str, ...] | None = None) -> list[str]:
    out: list[str] = []
    for r in bench_results(False, backends=backends):
        out.extend(r.extra["lines"])
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="both",
                    choices=["ref", "pallas", "interpret", "both"])
    args = ap.parse_args()
    names = None if args.backend == "both" else (args.backend,)
    for line in run(names):
        print(line)
