"""Quickstart: the paper's gradient coding end to end, then the
beyond-paper levers — heterogeneous loads, partial recovery, and online
auto-tuning — on the same 4-worker host mesh (runs on the CPU CI
container).

1. uniform (d=3, s=1, m=2) code, GQA transformer, random straggler per step;
2. heterogeneous plan: per-worker loads from a cluster speed vector, same
   decode, same trainer;
3. partial recovery: s+1 fixed stragglers — the step completes and reports
   a certified L2 gradient-error bound instead of aborting;
4. auto-tuning: the straggler distribution drifts mid-run and the trainer
   re-fits the Sec-VI model from telemetry, re-plans (d, s, m), and swaps
   codecs (docs/autotune.md).

  PYTHONPATH=src python examples/quickstart.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")


from repro import coding  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import make_code, make_hetero_code  # noqa: E402
from repro.data import synthetic_lm_stream  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.optim import get_optimizer  # noqa: E402
from repro.train import Trainer  # noqa: E402
from repro.tune import FixedStragglers, RandomStragglers  # noqa: E402


def main() -> None:
    n, d, s, m = 4, 3, 1, 2
    code = make_code(n, d, s, m)
    print(code.describe())
    # -> each worker computes 3/4 of the data, sends l/2 floats, and the
    #    master (here: every chip, SPMD) tolerates any 1 straggler.

    cfg = get_config("qwen3-1.7b").reduced()   # 2-layer, d_model=256 smoke model
    mesh = make_local_mesh(n_data=4, n_model=2)
    spec = coding.SchemeSpec(schedule="gather")   # paper-faithful decode
    trainer = Trainer(cfg, code, mesh,
                      optimizer=get_optimizer("adamw", 3e-3), spec=spec,
                      straggler_source=RandomStragglers(seed=1))  # <= s/step
    stream = synthetic_lm_stream(cfg, global_batch=8, seq_len=64)
    logs = trainer.run(stream, steps=20, log_every=5)
    print(f"\ncoded fraction of gradient bytes: {trainer.arts.coded_fraction:.3f}")
    print(f"loss: {logs[0]['loss']:.3f} -> {logs[-1]['loss']:.3f} "
          f"(with random stragglers every step)")

    # ---- lever 1: heterogeneous cluster -------------------------------
    # workers run at different speeds: give each a load proportional to its
    # speed (k=8 subsets instead of n=4), same decode, same trainer.
    hcode = make_hetero_code(speeds=[0.5, 1.0, 1.0, 1.5], s=1, m=2)
    print(f"\n{hcode.describe()}")
    htrainer = Trainer(cfg, hcode, mesh,
                       optimizer=get_optimizer("adamw", 3e-3), spec=spec,
                       straggler_source=RandomStragglers(seed=1))
    logs = htrainer.run(stream, steps=10, log_every=5)
    print(f"hetero loads {hcode.loads}: loss {logs[0]['loss']:.3f} -> "
          f"{logs[-1]['loss']:.3f}")

    # ---- lever 2: partial recovery past the straggler budget ----------
    # kill s+1 = 2 fixed workers every step: exact decode would raise; the
    # partial step completes and certifies its gradient error instead.
    ptrainer = Trainer(cfg, hcode, mesh,
                       optimizer=get_optimizer("adamw", 3e-3),
                       spec=spec.replace(partial=True),
                       straggler_source=FixedStragglers((0, 3)))
    metrics = ptrainer.step(next(stream))
    print(f"\npartial step with {2} stragglers (s={hcode.s}): "
          f"loss {metrics['loss']:.3f}, certified gradient error bound "
          f"{metrics['decode_err_bound']:.3f}")

    # ---- lever 3: online auto-tuning under drift ----------------------
    # the cluster starts communication-bound (the paper's regime, optimum
    # (4,2,2)) and drifts computation-bound at step 10 (optimum (1,0,1)).
    # The injector stands in for worker heartbeats; the trainer re-fits
    # the shifted-exponential model every 5 steps, re-ranks the (d,s,m) x
    # schedule space, and swaps codecs through its compile cache.
    from repro.core.runtime_model import RuntimeParams
    from repro.tune import AutotunePolicy, DriftingSampler
    comm_heavy = RuntimeParams(n=n, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0)
    comp_heavy = RuntimeParams(n=n, lambda1=0.5, lambda2=0.2, t1=16.0, t2=0.5)
    atrainer = Trainer(cfg, make_code(n, 4, 2, 2), mesh,
                       optimizer=get_optimizer("adamw", 3e-3), spec=spec,
                       straggler_source=DriftingSampler([(0, comm_heavy),
                                                         (10, comp_heavy)],
                                                        seed=3),
                       autotune=AutotunePolicy(interval=5, window=10,
                                               min_samples=5,
                                               schedules=("gather",)))
    atrainer.run(stream, steps=22, log_every=0)
    print(f"\nautotune: (4,2,2) -> "
          f"(d={atrainer.code.d},s={atrainer.code.s},m={atrainer.code.m}) "
          f"after drift; {sum(e['switched'] for e in atrainer.autotune_events)}"
          f" codec swap(s), {atrainer.cached_schemes} cached step builds")
    for e in atrainer.autotune_events:
        tag = "switch" if e["switched"] else "hold"
        print(f"  step {e['step']:3d} {tag:6s} -> {e['best']}")


if __name__ == "__main__":
    main()
