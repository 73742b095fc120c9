"""Plain reference of a Qwen3 decoder, float32 in straightforward
``jax.numpy``; the training around it (AdamW, the per-sequence gradient,
the readings) is the benchmark's shared ``reference.py``.

It imports nothing of the program under test.  Weights come from the seed
by the same convention the program uses (the key splits and the
N(0, 1/fan_in) scaling of ``repro.models``), so the reference rebuilds them
itself, in one compiled call; ``tests/test_reference.py`` checks they match
the program's to an ulp (XLA fuses the scaling into the sampler) at a small
size.

The model follows the published Qwen3 description: RMSNorm (eps from the
configuration) before attention and MLP, grouped-query attention with
RMSNorm on each query and key head before RoPE (halves rotated, theta from
the configuration), causal softmax scaled by 1/sqrt(head_dim), SwiGLU MLP,
a final RMSNorm and the unembedding; the loss is the mean next-token
cross-entropy.  Departure, as in the program: the unembedding is its own
matrix (Qwen3-1.7B ties it to the embedding).

``quant="fp8"`` rounds, where the program holds an activation in bfloat16
(the embedding's output, every matmul's operands and output, the residual
stream after each addition), as the shared ``reference.act`` and
``reference.mm`` say.
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
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
       "num_hidden_layers": 2, "vocab_size": 512}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference needs, read from a configuration file."""
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    n_layers: int
    vocab: int
    rope_theta: float
    eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
                   n_kv=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"],
                   n_layers=c["num_hidden_layers"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]))


# ------------------------------------------------------------------ weights
def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (1.0 / np.sqrt(fan_in))


def init_params(seed, k: Dims) -> dict:
    """The weights the program makes from ``seed`` (its key layout and
    scaling), in float32."""
    D, H, Hkv, hd, F = k.d_model, k.n_heads, k.n_kv, k.head_dim, k.d_ff
    kl, ke, ko = jax.random.split(jax.random.PRNGKey(seed), 3)

    def layer(key):
        ka, km = jax.random.split(key)
        qa = jax.random.split(ka, 4)
        qm = jax.random.split(km, 3)
        return {
            "ln1": jnp.ones((D,), jnp.float32),
            "attn": {"wq": _normal(qa[0], (D, H, hd), D),
                     "wk": _normal(qa[1], (D, Hkv, hd), D),
                     "wv": _normal(qa[2], (D, Hkv, hd), D),
                     "wo": _normal(qa[3], (H, hd, D), H * hd),
                     "q_norm": jnp.ones((hd,), jnp.float32),
                     "k_norm": jnp.ones((hd,), jnp.float32)},
            "ln2": jnp.ones((D,), jnp.float32),
            "mlp": {"w_gate": _normal(qm[0], (D, F), D),
                    "w_up": _normal(qm[1], (D, F), D),
                    "w_down": _normal(qm[2], (F, D), F)},
        }

    return {"embed": _normal(ke, (k.vocab, D), D),
            "layers": jax.vmap(layer)(jax.random.split(kl, k.n_layers)),
            "ln_f": jnp.ones((D,), jnp.float32),
            "unembed": _normal(ko, (D, k.vocab), D)}


# ------------------------------------------------------------------ model
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (S, H, hd), position = row index; halves rotated."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, p, k: Dims, mm, act):
    S = x.shape[0]
    a = p["attn"]
    h = _rms(x, p["ln1"], k.eps)
    q = _rms(mm("sd,dhk->shk", h, a["wq"]), a["q_norm"], k.eps)
    kk = _rms(mm("sd,dhk->shk", h, a["wk"]), a["k_norm"], k.eps)
    v = mm("sd,dhk->shk", h, a["wv"])
    q, kk = _rope(q, k.rope_theta), _rope(kk, k.rope_theta)
    group = k.n_heads // k.n_kv
    kk = jnp.repeat(kk, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = mm("qhk,shk->hqs", q, kk) / math.sqrt(k.head_dim)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = mm("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v)
    x = act(x + mm("shk,hkd->sd", o, a["wo"]))
    m = p["mlp"]
    h = _rms(x, p["ln2"], k.eps)
    u = jax.nn.silu(mm("sd,df->sf", h, m["w_gate"])) * mm("sd,df->sf", h,
                                                          m["w_up"])
    return act(x + mm("sf,fd->sd", u, m["w_down"]))


def sequence_loss(params, tokens, labels, k: Dims, quant=None):
    """Mean next-token cross-entropy of one sequence (tokens: (S,))."""
    mm, act = shared.mm(quant), shared.act(quant)
    x = act(params["embed"][tokens])

    def body(h, lp):
        return jax.checkpoint(
            lambda h_, lp_: _layer(h_, lp_, k, mm, act))(h, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    logits = mm("sd,dv->sv", _rms(x, params["ln_f"], k.eps), params["unembed"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)
