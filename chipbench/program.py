"""The system under test, built from a cell's files.

This is the one module of the benchmark that imports the program
(``repro``).  It builds the ``Trainer`` the way ``repro.launch.train.build``
does, from the configuration and traffic files instead of flags, and reads
back what the comparison needs from the trainer's own state.  Everything
else the benchmark measures with is its own.
"""
from __future__ import annotations

import jax


def model_config(config: dict):
    """The program's ``ModelConfig`` for a Qwen3 configuration file, at the
    precision the file states."""
    from repro.configs import ModelConfig

    if config["model_type"] != "qwen3" or config["hidden_act"] != "silu" \
            or config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError(f"{config['name']}: the program runs untied qwen3 "
                         f"SwiGLU decoders without attention bias")
    if float(config["rms_norm_eps"]) != 1e-6:
        raise ValueError(f"{config['name']}: the program's RMSNorm has "
                         f"eps 1e-6")
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        head_dim=config["head_dim"], qk_norm=True,
        rope_theta=float(config["rope_theta"]),
        param_dtype=config["precision"]["params"],
        compute_dtype=config["precision"]["activations"].split()[0],
        source=config["source"])


def build_trainer(config: dict, traffic: dict, *, weight_seed: int,
                  straggler_seed: int, backend: str | None = None):
    """The cell's ``Trainer``: its model, code, mesh over the first
    ``code.n`` devices, AdamW and straggler source.  ``backend`` overrides
    the traffic's codec backend (tests on the CPU)."""
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
    return Trainer(model_config(config), code, make_local_mesh(c["n"], 1),
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

