"""Operations and bytes the measured work needs, from the configuration's
shapes alone.  The per-layer metrics divide these by time.

Model FLOPs per trained token (``flops_per_token``), the PaLM appendix B
convention: 6 x the matmul weights (every projection and the unembedding;
not the embedding table, which is a lookup, nor the norm gains) plus
12 x layers x heads x head_dim x sequence length for attention, with no
causal halving.  Recomputation (the coded step's d subsets per worker,
rematerialised layers) is not counted.

Codec kernel bytes per step and chip, in float32, at the algorithm's
minimum (every operand read once, every result written once; the
program's padding and layout copies count against the kernel):

- encode: each of the worker's d subset gradients (l elements) is read and
  folded into its l/m encoding, which is written: d * 4 * l * (1 + 1/m);
- decode: the n gathered l/m encodings are read and the l-element
  gradient written: 4 * l * (n/m + 1);

with l the number of parameters (every leaf is coded but the norm gains,
a few ten-thousandths of l, which go through an all-reduce instead).  Each
kernel does 2 flops per element read; against the chip's bytes/flops ratio
both are bound by bytes.
"""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Projection and unembedding weights of a Qwen3 configuration."""
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


def kernel_work(c: dict, code: dict) -> dict[str, tuple[float, float]]:
    """(bytes, flops) per step and chip of each codec kernel."""
    l = total_params(c)
    n, d, m = code["n"], code["d"], code["m"]
    enc_read, dec_read = d * l, n * l / m
    return {
        "encode": (4.0 * (enc_read + d * l / m), 2.0 * enc_read),
        "decode": (4.0 * (dec_read + l), 2.0 * dec_read),
    }
