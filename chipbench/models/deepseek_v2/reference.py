"""Plain reference of a DeepSeek-V2 decoder, float32 in straightforward
``jax.numpy``; the training around it (AdamW, the per-sequence gradient,
the readings) is the benchmark's shared ``reference.py``.

It imports nothing of the program under test.  Weights come from the seed
by the same convention the program uses (the key splits and the
N(0, 1/fan_in) scaling of ``repro.models``), so the reference rebuilds them
itself, in one compiled call.

The model follows the DeepSeek-V2 paper (arXiv:2405.04434, sections
2.1-2.2) and the published configuration:

- RMSNorm (eps from the configuration) before attention and before the
  feed-forward part, a final RMSNorm and an untied unembedding; the loss is
  the mean next-token cross-entropy plus ``aux_loss_alpha`` times the
  sequence-wise balance loss of every expert layer;
- multi-head latent attention without query compression: ``q = h W_q``
  (nope and rope parts per head), ``[c_kv, k_pe] = h W_kva``, ``c_kv``
  through its own RMSNorm, ``[k_nope, v] = c_kv W_kvb``, one rope key
  shared by the heads; YaRN RoPE on the rope dims (inverse frequencies
  blended by the linear ramp between the beta_fast and beta_slow correction
  dims, cos and sin times mscale(mscale) / mscale(mscale_all_dim)); causal
  softmax scaled by mscale(mscale_all_dim)^2 / sqrt(nope + rope), computed
  in blocks of queries;
- the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; the others DeepSeekMoE: softmax scores over the
  router's published number of experts, greedy top-k, the gates the scores
  themselves; every held expert's SwiGLU computed for every token and
  weighted by its gate where the token chose it and 0 elsewhere; the shared
  experts one SwiGLU of ``n_shared_experts`` x ``moe_intermediate_size``.

It holds the same share as the program: the configuration's
``n_routed_experts`` experts, the first share of the router's; slots routed
elsewhere add nothing.  Departure, as in the program: RoPE rotates the
first half of the rope dims with the second (the checkpoint rotates
interleaved pairs, a fixed permutation of W_q's and W_kva's rope columns).

``quant="fp8"`` rounds, where the program holds an activation in bfloat16
(the embedding's output, every matmul's operands and output, the residual
stream after each addition), as the shared ``reference.act`` and
``reference.mm`` say; the router stays float32, as in the program.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

import reference as shared

# toy sizes under this model type's keys, for the benchmark's CPU tests
TOY = {"hidden_size": 128, "intermediate_size": 256,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "kv_lora_rank": 32, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
       "v_head_dim": 32, "moe_intermediate_size": 64,
       "num_hidden_layers": 3, "vocab_size": 512}
QUERY_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference needs, read from a configuration file."""
    d_model: int
    n_heads: int
    nope: int
    rope: int
    v_dim: int
    latent: int
    d_ff: int
    moe_ff: int
    shared_ff: int
    n_layers: int
    n_dense: int
    n_experts: int      # the router's outputs
    n_held: int         # experts 0 .. n_held - 1 held here
    top_k: int
    vocab: int
    rope_theta: float
    yarn: tuple         # (factor, original length, beta_fast, beta_slow,
                        #  mscale, mscale_all_dim)
    eps: float
    aux_alpha: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        y = c["rope_scaling"]
        return cls(
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
            v_dim=c["v_head_dim"], latent=c["kv_lora_rank"],
            d_ff=c["intermediate_size"], moe_ff=c["moe_intermediate_size"],
            shared_ff=c["n_shared_experts"] * c["moe_intermediate_size"],
            n_layers=c["num_hidden_layers"],
            n_dense=c["first_k_dense_replace"],
            n_experts=c["reduced"]["n_routed_experts"]["published"],
            n_held=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
            vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
            yarn=(float(y["factor"]), int(y["original_max_position_embeddings"]),
                  float(y["beta_fast"]), float(y["beta_slow"]),
                  float(y["mscale"]), float(y["mscale_all_dim"])),
            eps=float(c["rms_norm_eps"]), aux_alpha=float(c["aux_loss_alpha"]))


# ------------------------------------------------------------------ weights
def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (1.0 / np.sqrt(fan_in))


def _swiglu_weights(key, D, F):
    q = jax.random.split(key, 3)
    return {"w_gate": _normal(q[0], (D, F), D),
            "w_up": _normal(q[1], (D, F), D),
            "w_down": _normal(q[2], (F, D), F)}


def init_params(seed, k: Dims) -> dict:
    """The weights the program makes from ``seed`` (its key layout and
    scaling), in float32."""
    D, H, R = k.d_model, k.n_heads, k.latent
    kd, kl, ke, ko = jax.random.split(jax.random.PRNGKey(seed), 4)

    def attn(key):
        q = jax.random.split(key, 4)
        return {"wq": _normal(q[0], (D, H, k.nope + k.rope), D),
                "wkv_a": _normal(q[1], (D, R + k.rope), D),
                "kv_norm": jnp.ones((R,), jnp.float32),
                "wkv_b": _normal(q[2], (R, H, k.nope + k.v_dim), R),
                "wo": _normal(q[3], (H, k.v_dim, D), H * k.v_dim)}

    def dense_layer(key):
        ka, km = jax.random.split(key)
        return {"ln1": jnp.ones((D,), jnp.float32), "attn": attn(ka),
                "ln2": jnp.ones((D,), jnp.float32),
                "mlp": _swiglu_weights(km, D, k.d_ff)}

    def moe_layer(key):
        ka, kr, ks, k1, k2, k3 = jax.random.split(key, 6)
        E, F = k.n_held, k.moe_ff
        return {"ln1": jnp.ones((D,), jnp.float32), "attn": attn(ka),
                "ln2": jnp.ones((D,), jnp.float32),
                "router": _normal(kr, (D, k.n_experts), D),
                "moe": {"w_gate": _normal(k1, (E, D, F), D),
                        "w_up": _normal(k2, (E, D, F), D),
                        "w_down": _normal(k3, (E, F, D), F)},
                "shared": _swiglu_weights(ks, D, k.shared_ff)}

    return {"embed": _normal(ke, (k.vocab, D), D),
            "dense_layers": jax.vmap(dense_layer)(
                jax.random.split(kd, k.n_dense)),
            "layers": jax.vmap(moe_layer)(
                jax.random.split(kl, k.n_layers - k.n_dense)),
            "ln_f": jnp.ones((D,), jnp.float32),
            "unembed": _normal(ko, (D, k.vocab), D)}


# ------------------------------------------------------------------ model
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn(k: Dims):
    """(inverse frequencies (rope/2,), cos/sin factor, softmax scale)."""
    factor, length, fast, slow, ms, ms_all = k.yarn
    dim, theta = k.rope, k.rope_theta
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return dim * math.log(length / (rot * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = extra / factor * ramp + extra * (1 - ramp)
    scale = _mscale(factor, ms_all) ** 2 / math.sqrt(k.nope + k.rope)
    return inv, _mscale(factor, ms) / _mscale(factor, ms_all), scale


def _rope(x, inv, m):
    """x: (S, H, rope), position = row index; halves rotated."""
    S, half = x.shape[0], x.shape[-1] // 2
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * m, jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang) * m, jnp.float32)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(x, a, k: Dims, mm):
    S = x.shape[0]
    H, R = k.n_heads, k.latent
    inv, m, scale = _yarn(k)
    q = mm("sd,dhk->shk", x, a["wq"])
    kv = mm("sd,dr->sr", x, a["wkv_a"])
    c_kv = _rms(kv[:, :R], a["kv_norm"], k.eps)
    k_pe = _rope(kv[:, None, R:], inv, m)
    kvb = mm("sr,rhk->shk", c_kv, a["wkv_b"])
    q = jnp.concatenate([q[..., :k.nope], _rope(q[..., k.nope:], inv, m)], -1)
    kk = jnp.concatenate(
        [kvb[..., :k.nope], jnp.broadcast_to(k_pe, (S, H, k.rope))], -1)
    v = kvb[..., k.nope:]

    def rows(q_blk, lo):
        s = mm("qhk,shk->hqs", q_blk, kk) * scale
        causal = (np.arange(S)[None, :]
                  <= lo + np.arange(q_blk.shape[0])[:, None])
        s = jnp.where(causal[None], s, -jnp.inf)
        return mm("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v)

    blk = min(QUERY_BLOCK, S)
    o = jnp.concatenate([jax.checkpoint(rows, static_argnums=1)(
        q[lo:lo + blk], lo) for lo in range(0, S, blk)])
    return mm("shk,hkd->sd", o, a["wo"])


def _swiglu(h, w, mm):
    u = jax.nn.silu(mm("sd,df->sf", h, w["w_gate"])) * mm("sd,df->sf", h,
                                                          w["w_up"])
    return mm("sf,fd->sd", u, w["w_down"])


def _experts(h, p, k: Dims, mm):
    """(the held experts' part of the expert layer plus the shared
    experts', the sequence's balance loss)."""
    S = h.shape[0]
    scores = jax.nn.softmax(jnp.einsum("sd,de->se", h, p["router"]), -1)
    top, idx = jax.lax.top_k(scores, k.top_k)
    chosen = jax.nn.one_hot(idx, k.n_experts, dtype=jnp.float32)  # (S,K,E)
    gates = jnp.einsum("sk,ske->se", top, chosen)      # 0 where not chosen
    y = _swiglu(h, p["shared"], mm)
    m = p["moe"]
    for e in range(k.n_held):
        w = {n: m[n][e] for n in ("w_gate", "w_up", "w_down")}
        y = y + gates[:, e:e + 1] * _swiglu(h, w, mm)
    f = jax.lax.stop_gradient(chosen.sum((0, 1))) * (
        k.n_experts / (k.top_k * S))
    return y, jnp.sum(f * scores.mean(0))


def _layer(x, p, k: Dims, mm, act, moe: bool):
    x = act(x + _attention(_rms(x, p["ln1"], k.eps), p["attn"], k, mm))
    h = _rms(x, p["ln2"], k.eps)
    if not moe:
        return act(x + _swiglu(h, p["mlp"], mm)), jnp.zeros((), jnp.float32)
    y, aux = _experts(h, p, k, mm)
    return act(x + act(y)), aux


def sequence_loss(params, tokens, labels, k: Dims, quant=None):
    """Mean next-token cross-entropy of one sequence (tokens: (S,)) plus
    alpha times its balance loss summed over the expert layers."""
    mm, act = shared.mm(quant), shared.act(quant)
    x = act(params["embed"][tokens])

    def stack(moe):
        def body(carry, lp):
            h, aux = carry
            h, a = jax.checkpoint(
                lambda h_, lp_: _layer(h_, lp_, k, mm, act, moe))(h, lp)
            return (h, aux + a), None
        return body

    zero = jnp.zeros((), jnp.float32)
    (x, _), _ = jax.lax.scan(stack(False), (x, zero), params["dense_layers"])
    (x, aux), _ = jax.lax.scan(stack(True), (x, zero), params["layers"])
    logits = mm("sd,dv->sv", _rms(x, params["ln_f"], k.eps), params["unembed"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked) + k.aux_alpha * aux
