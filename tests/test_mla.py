"""Multi-head latent attention with YaRN (``repro.models.mla``) against a
plain float32 MLA written from the published description, in the published
checkpoint's convention: RoPE on interleaved pairs of the rope dims."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import common as cm, mla

PUBLISHED = get_config("deepseek-v2-lite")


def _cfg():
    return dataclasses.replace(PUBLISHED.reduced(), compute_dtype="float32")


def _hf_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """The published model's YaRN inverse frequencies, written out as its
    modelling code computes them."""
    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    return inter * (1 - mask) + extra * mask


def _rope_pairs(x, pos, inv):
    """Rotation of interleaved pairs (2i, 2i+1) by pos * inv[i]."""
    ang = pos[:, None] * inv[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    out = np.empty_like(x)
    out[..., 0::2] = a * cos - b * sin
    out[..., 1::2] = b * cos + a * sin
    return out


def _plain_mla(p, cfg, x):
    """One sequence x: (S, D), float64 numpy, causal."""
    S = x.shape[0]
    H, R = cfg.n_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    y = cfg.yarn
    inv = _hf_yarn(rope, cfg.rope_theta, y.factor, y.original_len,
                   y.beta_fast, y.beta_slow)
    pos = np.arange(S, dtype=np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    q = np.einsum("sd,dhk->shk", x, w["wq"])
    kv = x @ w["wkv_a"]
    c = kv[:, :R]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + 1e-6) * w["kv_norm"]
    k_pe = _rope_pairs(kv[:, None, R:], pos, inv)
    kvb = np.einsum("sr,rhk->shk", c, w["wkv_b"])
    qq = np.concatenate([q[..., :nope], _rope_pairs(q[..., nope:], pos, inv)],
                        -1)
    kk = np.concatenate([kvb[..., :nope],
                         np.broadcast_to(k_pe, (S, H, rope))], -1)
    m = 0.1 * y.mscale_all_dim * math.log(y.factor) + 1.0
    s = np.einsum("qhk,shk->hqs", qq, kk) * m * m / math.sqrt(nope + rope)
    s = np.where(np.tril(np.ones((S, S), bool))[None], s, -np.inf)
    e = np.exp(s - s.max(-1, keepdims=True))
    o = np.einsum("hqs,shk->qhk", e / e.sum(-1, keepdims=True),
                  kvb[..., nope:])
    return np.einsum("shk,hkd->sd", o, w["wo"])


def test_published_yarn_numbers():
    """The published configuration's softmax scale and cos/sin factor."""
    assert mla.softmax_scale(PUBLISHED) == pytest.approx(0.11472, abs=2e-5)
    assert mla.rope_scale(PUBLISHED) == 1.0
    y = PUBLISHED.yarn
    np.testing.assert_allclose(
        mla.rope_inv_freq(PUBLISHED),
        _hf_yarn(64, 1e4, y.factor, y.original_len, y.beta_fast,
                 y.beta_slow), rtol=1e-12)


def test_mla_equals_the_interleaved_checkpoint_convention():
    """The program rotates halves of the rope dims; the checkpoint rotates
    interleaved pairs.  With the rope columns of W_q and W_kva permuted
    (evens, then odds) the program gives the plain model's output."""
    cfg = _cfg()
    p = mla.init(jax.random.PRNGKey(3), cfg, jnp.float32)
    S = 24
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(4),
                                     (S, cfg.d_model)), np.float64)
    want = _plain_mla(p, cfg, x)
    nope, R, rope = cfg.qk_nope_dim, cfg.kv_lora_rank, cfg.qk_rope_dim
    perm = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    q = dict(p, wq=p["wq"].at[..., nope:].set(p["wq"][..., nope + perm]),
             wkv_a=p["wkv_a"].at[:, R:].set(p["wkv_a"][:, R + perm]))
    with jax.default_matmul_precision("highest"):
        got = mla.self_attention(q, cfg, jnp.asarray(x[None], jnp.float32),
                                 jnp.arange(S)[None])[0]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    # unpermuted, the two conventions differ: the pairing is not a no-op
    with jax.default_matmul_precision("highest"):
        other = mla.self_attention(p, cfg, jnp.asarray(x[None], jnp.float32),
                                   jnp.arange(S)[None])[0]
    assert np.max(np.abs(np.asarray(other) - want)) > 1e-3


@pytest.mark.parametrize("S,path", [(64, "materialized"), (4096, "fused")])
def test_mla_takes_the_splash_path_at_qk_192_v_128(S, path, monkeypatch):
    monkeypatch.setattr(cm, "_on_tpu", lambda: True)
    cfg = PUBLISHED.cut(5, 8, 8)
    assert (cfg.qk_head_dim, cfg.v_head_dim_) == (192, 128)
    assert cm.attention_path(cfg, S, "causal") == path
    # query/key heads off the 64-lane halves stay off the kernels
    odd = dataclasses.replace(cfg, qk_rope_dim=48)
    assert cm.attention_path(odd, 4096, "causal") == "online"


def test_online_path_matches_materialized_with_v_narrower_than_qk():
    cfg = _cfg()
    p = mla.init(jax.random.PRNGKey(5), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
    q, k, v = mla.project(p, cfg, x, pos)
    assert q.shape[-1] == k.shape[-1] != v.shape[-1]
    s = mla.softmax_scale(cfg)
    with jax.default_matmul_precision("highest"):
        a = cm.attend(q, k, v, cfg, "materialized", "causal", 0, scale=s)
        b = cm.online_attention(q, k, v, 1, chunk_q=16, chunk_kv=32,
                                scale=s)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-5)
