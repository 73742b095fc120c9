"""The system under test, built from a cell's files.

This module and each model type's ``models/<model_type>/program.py`` are
the benchmark's only imports of the program (``repro``).  It builds the
``Trainer`` the way ``repro.launch.train.build`` does, from the model
type's ``ModelConfig`` and the traffic file instead of flags, and reads
back what the comparison needs from the trainer's own state.  Everything
else the benchmark measures with is its own.
"""
from __future__ import annotations

import jax


def build_trainer(model_config, traffic: dict, *, weight_seed: int,
                  straggler_seed: int, backend: str | None = None):
    """The cell's ``Trainer`` of the program's ``model_config``: its code,
    mesh over the first ``code.n`` devices, AdamW and straggler source.
    ``backend`` overrides the traffic's codec backend (tests on the CPU)."""
    from repro import coding
    from repro.core import make_code
    from repro.launch.mesh import make_local_mesh
    from repro.optim.optimizers import adamw
    from repro.train import Trainer
    from repro.tune import NoStragglers, RandomStragglers

    c = traffic["code"]
    code = make_code(c["n"], c["d"], c["s"], c["m"])
    o = traffic["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"optimizer {o['name']!r}: the benchmark runs adamw")
    source = {"none": NoStragglers,
              "random": lambda: RandomStragglers(seed=straggler_seed)}[
        traffic["stragglers"]]()
    return Trainer(model_config, code, make_local_mesh(c["n"], 1),
                   adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"]),
                   spec=coding.SchemeSpec(
                       schedule=traffic["schedule"],
                       backend=backend or traffic["backend"]),
                   straggler_source=source, seed=weight_seed)


def first_moment(trainer):
    """AdamW's first moment: after one step from zero state it is
    (1 - b1) times the gradient the optimizer was given."""
    return trainer.opt_state["m"]


def params(trainer):
    """The trainer's parameters as they stand."""
    return trainer.params


def block(trainer) -> None:
    """Wait until the trainer's last update has landed on the device."""
    jax.block_until_ready((trainer.params, trainer.opt_state))


def unique_tokens(traffic: dict) -> int:
    """Tokens a step trains on, each counted once however many workers
    recompute it."""
    return (traffic["code"]["n"] * traffic["sequences_per_subset"]
            * traffic["seq_len"])
