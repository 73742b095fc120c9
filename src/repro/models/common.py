"""Shared model components: norms, RoPE, GQA attention (full / KV-cache /
sliding-window), SwiGLU MLP, losses, and scan-over-layers helpers.

Conventions
-----------
- Params are plain nested dicts of jnp arrays; layer stacks carry a leading
  ``L`` axis and are consumed by ``jax.lax.scan`` (remat'd) so the HLO stays
  small for 88-layer configs under 512 fake devices.
- ``cfg.compute_dtype`` is used for activations; params stay in
  ``cfg.param_dtype``.  Logits / losses are computed in float32.
- KV caches are dicts ``{"k": (L, B, S, Hkv, hd), "v": ..., "pos": ()}``; the
  sliding-window variant stores a ring buffer of size ``cfg.sliding_window``.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def cdtype(cfg):
    return jnp.dtype(cfg.compute_dtype)


def pdtype(cfg):
    return jnp.dtype(cfg.param_dtype)


# ------------------------------------------------------------------ norms
def rms_norm(x: jax.Array, gain: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * gain.astype(x.dtype)


def layer_norm(x: jax.Array, gain: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * gain.astype(x.dtype) + bias.astype(x.dtype)


# ------------------------------------------------------------------- RoPE
def rope_freqs(hd: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))


def apply_rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd), pos: (..., S) int -> rotated x (same dtype)."""
    return rotate_halves(x, pos, rope_freqs(x.shape[-1], theta))


def rotate_halves(x: jax.Array, pos: jax.Array, inv_freq: np.ndarray,
                  scale: float = 1.0) -> jax.Array:
    """RoPE with the given inverse frequencies (hd/2,), the first half of
    the head dims paired with the second; cos and sin times ``scale``."""
    freqs = jnp.asarray(inv_freq, jnp.float32)                       # (hd/2,)
    ang = pos.astype(jnp.float32)[..., None] * freqs                 # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------- init
def dense_init(key, shape, in_axis_size, dtype):
    scale = 1.0 / np.sqrt(in_axis_size)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def attn_params(key, cfg, dtype) -> dict:
    D, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H, hd), D, dtype),
        "wk": dense_init(ks[1], (D, Hkv, hd), D, dtype),
        "wv": dense_init(ks[2], (D, Hkv, hd), D, dtype),
        "wo": dense_init(ks[3], (H, hd, D), H * hd, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((Hkv, hd), dtype)
        p["bv"] = jnp.zeros((Hkv, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def mlp_params(key, cfg, dtype, d_ff=None) -> dict:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (D, F), D, dtype),
        "w_up": dense_init(ks[1], (D, F), D, dtype),
        "w_down": dense_init(ks[2], (F, D), F, dtype),
    }


# -------------------------------------------------------------- attention
def qkv_project(p: dict, cfg, x: jax.Array, pos: jax.Array):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,Hkv,hd) with bias/qk_norm/rope."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    return q, k, v


def gqa_scores_attend(q, k, v, mask, q_per_kv: int, scale=None):
    """q/k: (B,Sq,H,hd), (B,Sk,Hkv,hd), v: (B,Sk,Hkv,hv), mask: (B,Sq,Sk) or
    (Sq,Sk) bool; logits times ``scale`` (default 1/sqrt(hd))."""
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, Sq, Hkv, q_per_kv, hd)
    logits = jnp.einsum("bqhgk,bshk->bhgqs", qg, k).astype(jnp.float32)
    logits = logits / np.sqrt(hd) if scale is None else logits * scale
    if mask.ndim == 2:
        mask = mask[None]
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqs,bshk->bqhgk", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def attention(p: dict, cfg, x: jax.Array, pos: jax.Array,
              mask: jax.Array) -> jax.Array:
    """Full (training / prefill) self-attention.  x: (B, S, D)."""
    q, k, v = qkv_project(p, cfg, x, pos)
    out = gqa_scores_attend(q, k, v, mask, cfg.q_per_kv)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


# ----------------------------------------------- chunked (online-softmax)
CHUNK_THRESHOLD = 2048  # switch to the chunked path above this seq length
CHUNK_Q = 256
CHUNK_KV = 1024

# §Perf lever: remat the kv-chunk body so backward recomputes the softmax
# probabilities per chunk instead of storing the full (Sq x Sk) p residuals
# (flash-attention-style memory behaviour).  Default False = the recorded
# baseline; flipped by the dry-run's --opt attn_remat and by EXPERIMENTS
# §Perf iteration 1.
REMAT_KV_STEP = False


def online_attention(q, k, v, q_per_kv: int, *, mask_kind: str = "causal",
                     window: int = 0, chunk_q: int = CHUNK_Q,
                     chunk_kv: int = CHUNK_KV, kv_pos0: int = 0,
                     scale=None) -> jax.Array:
    """Flash-style attention in pure JAX: never materializes (Sq, Sk).

    q: (B, Sq, H, hd); k: (B, Sk, Hkv, hd); v: (B, Sk, Hkv, hv); logits
    times ``scale`` (default 1/sqrt(hd)).
    mask_kind: "causal" | "full" | "window" (causal with a back-window).
    Query positions are ``kv_pos0 + arange(Sq)`` relative to kv positions
    ``arange(Sk)`` (self-attention uses kv_pos0=Sk-Sq=0).
    Memory per step: O(B * chunk_q * H * chunk_kv).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    cq = min(chunk_q, Sq)
    while Sq % cq:
        cq -= 1
    ckv = min(chunk_kv, Sk)
    while Sk % ckv:
        ckv -= 1
    nq, nk = Sq // cq, Sk // ckv
    hv = v.shape[-1]
    scale = 1.0 / np.sqrt(hd) if scale is None else scale
    qr = q.reshape(B, nq, cq, Hkv, q_per_kv, hd)
    kr = k.reshape(B, nk, ckv, Hkv, hd)
    vr = v.reshape(B, nk, ckv, Hkv, hv)

    def q_block(qi_qc):
        qi, qc = qi_qc                     # qc: (B, cq, Hkv, g, hd)
        qpos = kv_pos0 + qi * cq + jnp.arange(cq)

        def kv_step(carry, kj_kc):
            m_acc, l_acc, o_acc = carry
            kj, kc, vc = kj_kc
            kpos = kj * ckv + jnp.arange(ckv)
            s = jnp.einsum("bqhgk,bshk->bhgqs", qc, kc).astype(jnp.float32) * scale
            if mask_kind == "causal":
                valid = kpos[None, :] <= qpos[:, None]
            elif mask_kind == "window":
                valid = (kpos[None, :] <= qpos[:, None]) & \
                        (kpos[None, :] > qpos[:, None] - window)
            else:
                valid = jnp.ones((cq, ckv), bool)
            s = jnp.where(valid[None, None, None], s, -1e30)
            m_new = jnp.maximum(m_acc, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_acc - m_new)
            l_new = l_acc * corr + p.sum(-1)
            o_new = o_acc * corr[..., None] + jnp.einsum(
                "bhgqs,bshk->bhgqk", p.astype(qc.dtype), vc).astype(jnp.float32)
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, Hkv, q_per_kv, cq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, q_per_kv, cq), jnp.float32)
        o0 = jnp.zeros((B, Hkv, q_per_kv, cq, hv), jnp.float32)
        body = jax.remat(kv_step) if REMAT_KV_STEP else kv_step
        (m, l, o), _ = jax.lax.scan(
            body, (m0, l0, o0),
            (jnp.arange(nk), jnp.moveaxis(kr, 1, 0), jnp.moveaxis(vr, 1, 0)))
        o = o / jnp.maximum(l, 1e-30)[..., None]
        return jnp.einsum("bhgqk->bqhgk", o)

    blocks = jax.lax.map(q_block, (jnp.arange(nq), jnp.moveaxis(qr, 1, 0)))
    out = jnp.moveaxis(blocks, 0, 1).reshape(B, Sq, Hkv, q_per_kv, hv)
    return out.reshape(B, Sq, H, hv).astype(q.dtype)


# ------------------------------------------------- fused (TPU) attention
# Rows of q and of kv in one block of the splash kernels, in the forward and
# in both backward kernels (dq, dkv).  On a TPU v5e at S = 2048 (the
# qwen3-8b cell), 1024 took 1.1 ms of kernel time a step less than 512, and
# 256 19 ms more (PERF.md).
FUSED_BLOCK = 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention_path(cfg, S: int, mask_kind: str) -> str:
    """The path ``self_attention`` takes at sequence length ``S``:

    - ``"fused"``: JAX's splash attention kernels, blockwise with their own
      backward, so no (S, S) scores reach HBM.  On a TPU, for a causal or
      sliding-window mask, in whole ``FUSED_BLOCK`` blocks, with value heads
      that fill the 128 lanes and query/key heads in whole halves of them
      (latent attention's 128 + 64);
    - ``"materialized"``: the (S, S) scores, up to ``CHUNK_THRESHOLD``;
    - ``"online"``: the chunked online softmax beyond it.
    """
    if (_on_tpu() and mask_kind in ("causal", "window")
            and S % FUSED_BLOCK == 0 and cfg.qk_head_dim % 64 == 0
            and cfg.v_head_dim_ % 128 == 0):
        return "fused"
    return "materialized" if S <= CHUNK_THRESHOLD else "online"


@functools.lru_cache(maxsize=None)
def _splash_kernel(S: int, g: int, mask_kind: str, window: int, block: int,
                   interpret: bool):
    """``make_splash_mqa``'s kernel with its block maps left in numpy: they
    become constants of whatever program calls it, where ``jnp.array``
    would place them on a device while that program is being traced."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm,
        splash_attention_mask_info as mi)
    if mask_kind == "causal":
        head = sm.CausalMask((S, S))
    else:                                    # j in (i - window, i]
        head = sm.LocalMask((S, S), (window - 1, 0), offset=0)
    mask = sm.MultiHeadMask([head] * g)
    fwd, mask_fn = mi.process_mask(mask, (block, block))
    dkv, _ = mi.process_mask_dkv(mask, (block, block))
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    return sk.SplashAttentionKernel(
        fwd, fwd, dkv, block_sizes=sizes, is_mqa=True, save_residuals=False,
        mask_value=sk.DEFAULT_MASK_VALUE, attn_logits_soft_cap=None,
        residual_checkpoint_name=None, mask_function=mask_fn,
        interpret=interpret)


def fused_attention(q, k, v, q_per_kv: int, *, mask_kind: str = "causal",
                    window: int = 0, scale=None) -> jax.Array:
    """GQA self-attention through the splash kernels: one MQA kernel per KV
    head (its ``q_per_kv`` query heads share it), vmapped over batch and KV
    heads, in ``FUSED_BLOCK`` blocks.  Blocks the mask leaves empty are
    skipped.  Logits and softmax statistics are f32 in the kernel; q is
    scaled by ``scale`` (default 1/sqrt(hd)) before it.  Off a TPU the
    kernels run in Pallas interpret mode.

    q: (B, S, H, hd); k: (B, S, Hkv, hd); v: (B, S, Hkv, hv) -> (B, S, H, hv).
    """
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    kernel = _splash_kernel(S, q_per_kv, mask_kind, window, FUSED_BLOCK,
                            not _on_tpu())
    scale = 1.0 / np.sqrt(hd) if scale is None else scale
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qs = qs.reshape(B, S, Hkv, q_per_kv, hd).transpose(0, 2, 3, 1, 4)
    kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    out = jax.vmap(jax.vmap(lambda a, b, c: kernel(a, b, c)))(qs, kt, vt)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, v.shape[-1])


def attend(q, k, v, cfg, path: str, mask_kind: str, window: int,
           scale=None):
    """Attention of projected heads on ``path`` (``attention_path``);
    logits times ``scale``, default 1/sqrt(head dim)."""
    if path == "fused":
        return fused_attention(q, k, v, cfg.q_per_kv, mask_kind=mask_kind,
                               window=window, scale=scale)
    if path == "online":
        return online_attention(q, k, v, cfg.q_per_kv, mask_kind=mask_kind,
                                window=window, scale=scale)
    S = q.shape[1]
    if mask_kind == "causal":
        mask = causal_mask(S)
    elif mask_kind == "window":
        mask = sliding_causal_mask(S, window)
    else:
        mask = jnp.ones((S, S), bool)
    return gqa_scores_attend(q, k, v, mask, cfg.q_per_kv, scale)


def self_attention(p: dict, cfg, x: jax.Array, pos: jax.Array, *,
                   mask_kind: str = "causal", window: int = 0) -> jax.Array:
    """Mask-kind self-attention on the path ``attention_path`` picks."""
    S = x.shape[1]
    q, k, v = qkv_project(p, cfg, x, pos)
    out = attend(q, k, v, cfg, attention_path(cfg, S, mask_kind), mask_kind,
                 window)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def self_attention_with_kv(p: dict, cfg, x: jax.Array, pos: jax.Array, *,
                           mask_kind: str = "causal", window: int = 0):
    """Like self_attention but also returns (k, v) for prefill caching; it
    takes the materialized or the online path, never the fused one."""
    S = x.shape[1]
    q, k, v = qkv_project(p, cfg, x, pos)
    path = "materialized" if S <= CHUNK_THRESHOLD else "online"
    out = attend(q, k, v, cfg, path, mask_kind, window)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, k, v


def causal_mask(S: int) -> jax.Array:
    return jnp.tril(jnp.ones((S, S), bool))


def sliding_causal_mask(S: int, window: int) -> jax.Array:
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    return (j <= i) & (j > i - window)


# ------------------------------------------------------- KV-cache decoding
def attention_decode(p: dict, cfg, x: jax.Array, k_cache, v_cache,
                     pos: jax.Array, *, window: int = 0):
    """One-token decode.  x: (B, 1, D); k/v_cache: (B, S, Hkv, hd) already
    containing this step's k/v is returned updated.

    ``window == 0``: dense cache of length S (pos indexes absolutely).
    ``window  > 0``: ring buffer of length ``window`` (pos % window slot).
    """
    B = x.shape[0]
    q, k, v = qkv_project(p, cfg, x, jnp.broadcast_to(pos, (B, 1)))
    S = k_cache.shape[1]
    slot = (pos % S) if window else pos
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, slot, axis=1)  # noqa: broadcast over B
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, slot, axis=1)
    j = jnp.arange(S)
    if window:
        valid = (j <= pos % S) | (pos >= S)          # ring buffer fullness
    else:
        valid = j <= pos
    mask = jnp.broadcast_to(valid[None, None, :], (B, 1, S))
    out = gqa_scores_attend(q, k_cache, v_cache, mask, cfg.q_per_kv)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, k_cache, v_cache


def pack_cache(k: jax.Array, slots: int, window: int) -> jax.Array:
    """Place prefill-time keys/values (B, S, H, hd), ordered by position,
    into a cache of ``slots`` entries so that ``attention_decode``'s slot
    arithmetic (``pos`` for dense, ``pos % slots`` for ring) lines up.

    - dense (window == 0): position p lives at slot p; requires S <= slots,
      padded with zeros at the end.
    - ring (window > 0, slots == window): position p lives at slot p % slots;
      keep the last ``slots`` positions and roll them into place.
    """
    B, S = k.shape[:2]
    if S <= slots:
        pad = slots - S
        return jnp.pad(k, ((0, 0), (0, pad)) + ((0, 0),) * (k.ndim - 2))
    if not window:
        raise ValueError(f"dense cache too small: S={S} > slots={slots}")
    last = k[:, S - slots:]
    return jnp.roll(last, S % slots, axis=1)


# ------------------------------------------------------------------- MLP
def swiglu(p: dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"].astype(x.dtype))


# ------------------------------------------------------------------ loss
def softmax_xent(logits: jax.Array, labels: jax.Array,
                 mask: jax.Array | None = None) -> jax.Array:
    """Mean next-token cross-entropy in float32.  logits: (..., V)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


# -------------------------------------------------- scan-over-layers glue
def stacked_init(per_layer_init, key, n_layers: int):
    """vmap a single-layer init over a leading L axis."""
    keys = jax.random.split(key, n_layers)
    return jax.vmap(per_layer_init)(keys)


def scan_layers(body, x, stacked_params, *extra):
    """Remat'd scan of ``body(x, layer_params, *extra) -> x`` over the stack."""
    def step(carry, lp):
        return jax.remat(body)(carry, lp, *extra), None
    out, _ = jax.lax.scan(step, x, stacked_params)
    return out


def embed_tokens(emb: jax.Array, tokens: jax.Array, dtype) -> jax.Array:
    return emb.astype(dtype)[tokens]


def unembed(x: jax.Array, emb_out: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,dv->bsv", x, emb_out.astype(x.dtype))
