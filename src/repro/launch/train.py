"""Coded-training launcher over the devices present (TPU chips, or host
devices on the CPU).

One TPU chip, qwen3-1.7b at its published widths cut to 4 layers and 1/8 of
the vocabulary rows (``--size cut``: ``ModelConfig.cut(4, 8)``), uncoded
(1, 1, 0, 1) on the compiled Pallas kernels:

  PYTHONPATH=src python -m repro.launch.train --size cut --d 1 --s 0 \\
      --m 1 --backend pallas --batch-per-subset 4 --seq 2048 --steps 3

The CPU, with four forced host devices and the tiny reduced model:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
      PYTHONPATH=src python -m repro.launch.train --size reduced \\
      --d 3 --s 1 --m 2 --steps 20 --schedule gather

The mesh is ``(n_data, n_model)`` over the first devices; ``--n-data``
defaults to every device the model axis leaves.  ``chip_smoke.py`` drives
the same :func:`build`.
"""
from __future__ import annotations

import argparse
from typing import Iterator

# --size cut: (layers, vocabulary share) of the published model that one
# v5e chip holds with AdamW state and 4 x 2048 tokens of activations
CHIP_CUT = (4, 8)


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--size", default="reduced",
                    choices=["reduced", "cut", "full"],
                    help="reduced: the tiny CPU smoke model; cut: published "
                         "widths, 4 layers and 1/8 of the vocabulary rows "
                         "(one v5e chip's share); full: the whole model")
    ap.add_argument("--n-data", type=int, default=None,
                    help="data-parallel workers (default: every device)")
    ap.add_argument("--n-model", type=int, default=1)
    ap.add_argument("--d", type=int, default=3)
    ap.add_argument("--s", type=int, default=1)
    ap.add_argument("--m", type=int, default=2)
    ap.add_argument("--schedule", default="gather",
                    choices=["gather", "a2a", "psum"])
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "ref", "pallas", "interpret"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-per-subset", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--stragglers", default="random",
                    choices=["none", "random", "fixed"])
    ap.add_argument("--drop", type=int, nargs="*", default=[],
                    help="the workers that straggle every step "
                         "(--stragglers fixed)")
    ap.add_argument("--log", default=None)
    return ap.parse_args(argv)


def build(args: argparse.Namespace) -> tuple["Trainer", Iterator[dict]]:
    """The config, code, mesh, ``Trainer`` and synthetic LM stream that
    ``args`` describe."""
    import jax

    from repro import coding
    from repro.configs import get_config
    from repro.core import make_code
    from repro.data import synthetic_lm_stream
    from repro.launch.mesh import make_local_mesh
    from repro.optim import get_optimizer
    from repro.train import Trainer
    from repro.tune import FixedStragglers, NoStragglers, RandomStragglers

    cfg = get_config(args.arch)
    if args.size == "reduced":
        cfg = cfg.reduced()
    elif args.size == "cut":
        cfg = cfg.cut(*CHIP_CUT)
    n_data = args.n_data or max(1, jax.device_count() // args.n_model)
    mesh = make_local_mesh(n_data, args.n_model)
    code = make_code(n_data, args.d, args.s, args.m)
    source = {"none": NoStragglers(), "random": RandomStragglers(seed=1),
              "fixed": FixedStragglers(args.drop)}[args.stragglers]
    trainer = Trainer(cfg, code, mesh, get_optimizer(args.optimizer, args.lr),
                      spec=coding.SchemeSpec(schedule=args.schedule,
                                             backend=args.backend),
                      straggler_source=source)
    stream = synthetic_lm_stream(cfg, code.n * args.batch_per_subset, args.seq)
    return trainer, stream


def main(argv: list[str] | None = None) -> None:
    from repro.compile_cache import enable_compile_cache

    args = parse_args(argv)
    enable_compile_cache()
    trainer, stream = build(args)
    logs = trainer.run(stream, args.steps, log_every=max(1, args.steps // 10),
                       log_path=args.log)
    print(f"{trainer.cfg.name}: final loss {logs[-1]['loss']:.4f} "
          f"(coded fraction {trainer.arts.coded_fraction:.3f})")


if __name__ == "__main__":
    main()
