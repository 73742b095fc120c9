"""End-to-end driver: train a ~100M-parameter dense LM with coded gradient
aggregation for a few hundred steps on the host mesh.

Default invocation trains a scaled-down model for a fast demo; pass
``--full-100m`` for the ~100M configuration (slow on CPU — this is the
deliverable's end-to-end driver and runs unattended):

  PYTHONPATH=src python examples/train_lm_coded.py --steps 300 --full-100m
  PYTHONPATH=src python examples/train_lm_coded.py --steps 40        # demo
"""
import argparse
import dataclasses
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--full-100m", action="store_true")
    ap.add_argument("--schedule", default="gather",
                    choices=["gather", "a2a", "psum"])
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "ref", "pallas", "interpret"],
                    help="codec compute backend (pallas = the TPU kernels)")
    ap.add_argument("--n-data", type=int, default=4)
    ap.add_argument("--n-model", type=int, default=2)
    ap.add_argument("--d", type=int, default=3)
    ap.add_argument("--s", type=int, default=1)
    ap.add_argument("--m", type=int, default=2)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch-per-subset", type=int, default=4)
    ap.add_argument("--log", default="results/train_lm_coded.json")
    args = ap.parse_args()

    ndev = args.n_data * args.n_model
    os.environ.setdefault("XLA_FLAGS",
                          f"--xla_force_host_platform_device_count={ndev}")

    from repro import coding
    from repro.configs import get_config
    from repro.core import make_code
    from repro.data import synthetic_lm_stream
    from repro.launch.mesh import make_local_mesh
    from repro.optim import get_optimizer
    from repro.train import Trainer
    from repro.tune import RandomStragglers

    base = get_config("qwen3-1.7b")
    if args.full_100m:
        # ~100M params: 12L, d_model 768, 12 heads, vocab 32k
        cfg = dataclasses.replace(
            base, name="coded-lm-100m", n_layers=12, d_model=768, n_heads=12,
            n_kv_heads=4, d_ff=2048, vocab=32000, head_dim=64)
    else:
        cfg = dataclasses.replace(
            base.reduced(), name="coded-lm-demo", n_layers=4, d_model=256,
            vocab=2048)

    code = make_code(args.n_data, args.d, args.s, args.m)
    mesh = make_local_mesh(args.n_data, args.n_model)
    trainer = Trainer(cfg, code, mesh, get_optimizer("adamw", 3e-4),
                      spec=coding.SchemeSpec(schedule=args.schedule,
                                             backend=args.backend),
                      straggler_source=RandomStragglers(seed=1))
    import jax
    n_params = sum(x.size for x in jax.tree.leaves(trainer.params))
    print(f"model {cfg.name}: {n_params / 1e6:.1f}M params; {code.describe()}")
    gb = args.n_data * args.batch_per_subset
    stream = synthetic_lm_stream(cfg, gb, args.seq)
    os.makedirs("results", exist_ok=True)
    logs = trainer.run(stream, args.steps, log_every=10, log_path=args.log)
    print(f"done: loss {logs[0]['loss']:.3f} -> {logs[-1]['loss']:.3f} "
          f"in {logs[-1]['wall']:.0f}s")


if __name__ == "__main__":
    main()
