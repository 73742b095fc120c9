"""Model FLOPs, parameters and the expert kernels' work of a DeepSeek-V2
configuration, from its shapes alone.

Model FLOPs per trained token (``flops_per_token``), the PaLM appendix B
convention: 6 x the matmul weights a token passes through on this chip
(every projection, the router, the shared experts, the routed experts at
their expected share, top-k x held / routed, and the unembedding; not the
embedding table, which is a lookup, nor the norm gains) plus
6 x layers x heads x sequence length x (q/k head dim + v head dim) for
latent attention's two products, with no causal halving.  Recomputation
(rematerialised layers, the coded step's d subsets) is not counted.

Every parameter is coded (``total_params``, the codec's l), the norm gains
too.  The model's own kernels (``kernels``) are the held experts' grouped
matmuls.
"""
from __future__ import annotations


def _attn_params(c: dict) -> int:
    D, H, R = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return D * H * (nope + rope) + D * (R + rope) + R * H * (nope + v) \
        + H * v * D


def _expert_params(c: dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _routed(c: dict) -> int:
    """The router's outputs: the published count of routed experts."""
    return c["reduced"]["n_routed_experts"]["published"]


def _moe_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def _moe_fixed(c: dict) -> int:
    """An expert layer's router and shared experts."""
    return (c["hidden_size"] * _routed(c)
            + c["n_shared_experts"] * _expert_params(c))


def matmul_params(c: dict) -> int:
    """Projection, router, expert and unembedding weights a token passes
    through, the routed experts at their expected share."""
    D, L = c["hidden_size"], c["num_hidden_layers"]
    routed_here = (c["num_experts_per_tok"] * c["n_routed_experts"]
                   / _routed(c))
    dense = 3 * D * c["intermediate_size"]
    moe = _moe_fixed(c) + routed_here * _expert_params(c)
    return (L * _attn_params(c) + c["first_k_dense_replace"] * dense
            + _moe_layers(c) * moe + D * c["vocab_size"])


def total_params(c: dict) -> int:
    """Every parameter: matmul weights (every held expert's), the embedding
    and the norm gains (two a layer, the latent's, the final one)."""
    D, L = c["hidden_size"], c["num_hidden_layers"]
    dense = 3 * D * c["intermediate_size"]
    moe = _moe_fixed(c) + c["n_routed_experts"] * _expert_params(c)
    gains = L * (2 * D + c["kv_lora_rank"]) + D
    return (L * _attn_params(c) + c["first_k_dense_replace"] * dense
            + _moe_layers(c) * moe + 2 * D * c["vocab_size"] + gains)


def flops_per_token(c: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token (forward and backward)."""
    attn = (6 * c["num_hidden_layers"] * c["num_attention_heads"] * seq_len
            * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
               + c["v_head_dim"]))
    return 6.0 * matmul_params(c) + attn


def expected_slots(c: dict, traffic: dict) -> int:
    """(token, expert) slots a step routes to the held experts of one
    expert layer under a uniform router."""
    tokens = (traffic["code"]["d"] * traffic["sequences_per_subset"]
              * traffic["seq_len"])
    return (tokens * c["num_experts_per_tok"] * c["n_routed_experts"]
            // _routed(c))


def kernels(c: dict, traffic: dict) -> dict[str, tuple[str, float, float]]:
    """The held experts' grouped matmuls a step and chip: for the expected
    slots, each expert layer's nine products (gate, up and down forward;
    each one's input gradient and weight gradient backward), each reading
    its operands and writing its result once in bfloat16, and doing
    2 x slots x d_model x width operations; timed by the ops that implement
    them (megablox's gmm and tgmm)."""
    s = expected_slots(c, traffic)
    D, F = c["hidden_size"], c["moe_intermediate_size"]
    E = c["n_routed_experts"]
    per_product_bytes = 2.0 * (s * D + s * F + E * D * F)
    per_product_flops = 2.0 * s * D * F
    n = 9 * _moe_layers(c)
    return {"expert_gmm": (r"gmm", n * per_product_bytes,
                           n * per_product_flops)}
