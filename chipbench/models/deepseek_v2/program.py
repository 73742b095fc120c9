"""The program's model for a DeepSeek-V2 configuration file: the one file of
this directory that imports the program (``repro``)."""
from __future__ import annotations


def _checked(config: dict) -> None:
    fixed = {"hidden_act": "silu", "attention_bias": False,
             "tie_word_embeddings": False, "q_lora_rank": None,
             "scoring_func": "softmax", "topk_method": "greedy",
             "n_group": 1, "topk_group": 1, "norm_topk_prob": False,
             "routed_scaling_factor": 1, "seq_aux": True,
             "moe_layer_freq": 1, "rms_norm_eps": 1e-6}
    wrong = {k: config[k] for k, v in fixed.items() if config[k] != v}
    if wrong or config["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"{config['name']}: the program runs DeepSeek-V2 "
                         f"with {fixed} and YaRN; the file has {wrong}")


def model_config(config: dict):
    """The program's ``ModelConfig`` for the configuration, at the precision
    the file states: every layer but the first ``first_k_dense_replace`` an
    expert layer holding the file's ``n_routed_experts`` (share 0) of the
    router's published count."""
    try:
        from repro.models import deepseek  # noqa: F401
    except ImportError as e:
        raise RuntimeError(f"{config['name']}: the program has no model "
                           f"family 'deepseek' (latent attention, experts "
                           f"held here)") from e
    from repro.configs import ModelConfig
    from repro.configs.base import Yarn

    _checked(config)
    routed = config["reduced"]["n_routed_experts"]["published"]
    held = config["n_routed_experts"]
    y = config["rope_scaling"]
    return ModelConfig(
        name=config["name"], family="deepseek",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        yarn=Yarn(factor=float(y["factor"]),
                  original_len=y["original_max_position_embeddings"],
                  beta_fast=float(y["beta_fast"]),
                  beta_slow=float(y["beta_slow"]),
                  mscale=float(y["mscale"]),
                  mscale_all_dim=float(y["mscale_all_dim"])),
        n_experts=routed, top_k=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        n_dense_layers=config["first_k_dense_replace"],
        expert_shares=routed // held, expert_share_index=0,
        aux_loss_alpha=float(config["aux_loss_alpha"]),
        param_dtype=config["precision"]["params"],
        compute_dtype=config["precision"]["activations"].split()[0],
        source=config["source"])
