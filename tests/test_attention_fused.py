"""The fused (splash) attention path: its numbers against the materialized
scores, and which path ``self_attention`` takes where.

The kernels run in Pallas interpret mode here; ``tests/test_tpu_compile.py``
compiles them for a described v5e inside a model's forward and backward.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import common as cm


def _rel(x, ref):
    x, ref = np.asarray(x, np.float32), np.asarray(ref, np.float32)
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


@pytest.mark.parametrize("q_per_kv,mask_kind,window",
                         [(2, "causal", 0), (4, "causal", 0),
                          (2, "window", 100)])
def test_fused_matches_materialized(q_per_kv, mask_kind, window,
                                    monkeypatch):
    """Output and q/k/v gradients of the fused path (interpret mode, blocks
    of 128) against the scores computed in f32 at ``highest``, from the same
    bf16 operands: within bf16 rounding, and no further off than the
    materialized bf16 path."""
    monkeypatch.setattr(cm, "FUSED_BLOCK", 128)
    B, S, Hkv, hd = 2, 256, 2, 128
    H = Hkv * q_per_kv
    ks = jax.random.split(jax.random.PRNGKey(q_per_kv), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd)).astype(jnp.bfloat16)
    dout = jax.random.normal(ks[3], (B, S, H, hd))
    mask = (cm.causal_mask(S) if mask_kind == "causal"
            else cm.sliding_causal_mask(S, window))

    def exact(q, k, v):
        with jax.default_matmul_precision("highest"):
            return cm.gqa_scores_attend(*(t.astype(jnp.float32)
                                          for t in (q, k, v)), mask, q_per_kv)

    def materialized(q, k, v):
        return cm.gqa_scores_attend(q, k, v, mask, q_per_kv)

    def fused(q, k, v):
        return cm.fused_attention(q, k, v, q_per_kv, mask_kind=mask_kind,
                                  window=window)

    def out_and_grads(f):
        def pullback(q, k, v):
            out, vjp = jax.vjp(f, q, k, v)
            return (out,) + vjp(dout.astype(out.dtype))
        return jax.jit(pullback)(q, k, v)

    want = out_and_grads(exact)
    got = out_and_grads(fused)
    base = out_and_grads(materialized)
    for name, w, g, b in zip(("out", "dq", "dk", "dv"), want, got, base):
        assert g.shape == w.shape and g.dtype == jnp.bfloat16, name
        err = _rel(g, w)
        assert err < 1e-2, (name, err)
        assert err <= 1.25 * _rel(b, w), (name, err, _rel(b, w))


def test_attention_path():
    """Materialized or online on the CPU whatever the shape; fused on a TPU
    for a causal or sliding-window mask in whole blocks at head_dim 128."""
    cfg = get_config("qwen3-1.7b").cut(4, 8)
    S = cm.CHUNK_THRESHOLD                     # 2048, whole blocks
    assert cfg.head_dim_ == 128 and S % cm.FUSED_BLOCK == 0
    assert cm.attention_path(cfg, S, "causal") == "materialized"
    assert cm.attention_path(cfg, S, "window") == "materialized"
    assert cm.attention_path(cfg, 4 * S, "causal") == "online"

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cm, "_on_tpu", lambda: True)
        assert cm.attention_path(cfg, S, "causal") == "fused"
        assert cm.attention_path(cfg, S, "window") == "fused"
        assert cm.attention_path(cfg, 4 * S, "causal") == "fused"
        # a mask with no blocks to skip, part of a block, a narrow head
        assert cm.attention_path(cfg, S, "full") == "materialized"
        assert cm.attention_path(cfg, S - 128, "causal") == "materialized"
        assert cm.attention_path(cfg, 4 * S + 128, "causal") == "online"
        narrow = dataclasses.replace(cfg, head_dim=64)
        assert cm.attention_path(narrow, S, "causal") == "materialized"
