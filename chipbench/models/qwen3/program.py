"""The program's model for a Qwen3 configuration file: the one file of this
directory that imports the program (``repro``)."""
from __future__ import annotations


def model_config(config: dict):
    """The program's ``ModelConfig`` for the configuration, at the precision
    the file states."""
    from repro.configs import ModelConfig

    if config["hidden_act"] != "silu" or config["attention_bias"] \
            or config["tie_word_embeddings"]:
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
