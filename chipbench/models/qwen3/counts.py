"""Model FLOPs and parameters of a Qwen3 configuration, from its shapes
alone.

Model FLOPs per trained token (``flops_per_token``), the PaLM appendix B
convention: 6 x the matmul weights (every projection and the unembedding;
not the embedding table, which is a lookup, nor the norm gains) plus
12 x layers x heads x head_dim x sequence length for attention, with no
causal halving.  Recomputation (the coded step's d subsets per worker,
rematerialised layers) is not counted.

Every parameter is coded (``total_params``, the codec's l): the norm gains
too, a few ten-thousandths of l, though they go through an all-reduce.
The model has no kernels of its own beside the codec's.
"""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Projection and unembedding weights."""
    D, F = c["hidden_size"], c["intermediate_size"]
    H, Hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    attn = D * H * hd * 2 + D * Hkv * hd * 2
    mlp = 3 * D * F
    return c["num_hidden_layers"] * (attn + mlp) + D * c["vocab_size"]


def total_params(c: dict) -> int:
    """Every parameter: matmul weights, the embedding and the norm gains."""
    D, hd, L = c["hidden_size"], c["head_dim"], c["num_hidden_layers"]
    gains = L * (2 * D + 2 * hd) + D
    return matmul_params(c) + D * c["vocab_size"] + gains


def flops_per_token(c: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token (forward and backward)."""
    attn = (12 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * seq_len)
    return 6.0 * matmul_params(c) + attn
