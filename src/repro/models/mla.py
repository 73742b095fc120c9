"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section 2.1)
with YaRN-stretched RoPE on its rope dims, without query compression:

- ``q = W_q h``: per head ``qk_nope_dim`` + ``qk_rope_dim``;
- ``[c_kv, k_pe] = W_kva h``: the ``kv_lora_rank`` latent and one rope key
  that every head shares; ``c_kv`` goes through its own RMSNorm;
- ``[k_nope, v] = W_kvb c_kv``: per head ``qk_nope_dim`` + ``v_head_dim``;
- RoPE on ``q``'s and ``k_pe``'s rope dims only, then causal attention over
  the concatenated ``[nope, rope]`` heads, logits times
  ``mscale(factor, mscale_all_dim)^2 / sqrt(qk_head_dim)``, and
  ``W_o`` over the heads' values.

Departure: RoPE rotates the first half of the rope dims with the second, as
the repo's other models do; the published checkpoint rotates interleaved
pairs.  The two are one fixed permutation of the rope columns of ``W_q``
and ``W_kva`` apart (``tests/test_mla.py``).

The projections run under the named scope ``mla.proj``, the attention
kernel under ``mla.attend``; on a TPU at whole ``FUSED_BLOCK`` lengths the
attention is the splash kernels at q/k head dim 192 and v head dim 128.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as cm


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(rotations: float, dim: int, theta: float,
                    length: int) -> float:
    """The rope dim whose wavelength turns ``rotations`` times over
    ``length`` positions."""
    return (dim * math.log(length / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_inv_freq(cfg) -> np.ndarray:
    """Inverse frequencies of the rope dims, (qk_rope_dim/2,): YaRN's blend
    of the original and the ``factor``-slowed frequencies where ``cfg.yarn``
    is set, else plain RoPE."""
    dim, theta, y = cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn
    extra = cm.rope_freqs(dim, theta)
    if y is None:
        return extra
    low = max(math.floor(_correction_dim(y.beta_fast, dim, theta,
                                         y.original_len)), 0)
    high = min(math.ceil(_correction_dim(y.beta_slow, dim, theta,
                                         y.original_len)), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp             # 1: the original frequency, 0: slowed
    return extra / y.factor * (1.0 - keep) + extra * keep


def rope_scale(cfg) -> float:
    """YaRN's factor on cos and sin (1 where mscale = mscale_all_dim)."""
    y = cfg.yarn
    if y is None:
        return 1.0
    return (_yarn_mscale(y.factor, y.mscale)
            / _yarn_mscale(y.factor, y.mscale_all_dim))


def softmax_scale(cfg) -> float:
    s = 1.0 / math.sqrt(cfg.qk_head_dim)
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        s *= _yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def init(key, cfg, dtype) -> dict:
    D, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, hv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": cm.dense_init(ks[0], (D, H, nope + rope), D, dtype),
        "wkv_a": cm.dense_init(ks[1], (D, R + rope), D, dtype),
        "kv_norm": jnp.ones((R,), dtype),
        "wkv_b": cm.dense_init(ks[2], (R, H, nope + hv), R, dtype),
        "wo": cm.dense_init(ks[3], (H, hv, D), H * hv, dtype),
    }


def project(p: dict, cfg, x: jax.Array, pos: jax.Array):
    """x: (B, S, D) -> q, k (B, S, H, nope + rope) and v (B, S, H, hv)."""
    H, R = cfg.n_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    inv_freq, scale = rope_inv_freq(cfg), rope_scale(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
    c_kv = cm.rms_norm(kv[..., :R], p["kv_norm"])
    k_pe = cm.rotate_halves(kv[..., None, R:], pos, inv_freq, scale)
    kvb = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"].astype(x.dtype))
    q_pe = cm.rotate_halves(q[..., nope:], pos, inv_freq, scale)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_pe, k_pe.shape[:2] + (H, rope))],
        axis=-1)
    return q, k, kvb[..., nope:]


def self_attention(p: dict, cfg, x: jax.Array, pos: jax.Array) -> jax.Array:
    """Causal latent attention of x: (B, S, D) on the path
    ``common.attention_path`` picks."""
    with jax.named_scope("mla.proj"):
        q, k, v = project(p, cfg, x, pos)
    with jax.named_scope("mla.attend"):
        path = cm.attention_path(cfg, x.shape[1], "causal")
        out = cm.attend(q, k, v, cfg, path, "causal", 0,
                        scale=softmax_scale(cfg))
    with jax.named_scope("mla.proj"):
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
