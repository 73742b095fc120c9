"""Plain reference of the cells' training: a Qwen3 decoder and AdamW in
straightforward ``jax.numpy``, float32 at ``highest`` matmul precision.

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

A step's gradient is the mean over its sequences of each sequence's
mean-loss gradient, computed one sequence at a time (``lax.scan``, each
layer rematerialised) so that an 8B-wide layer fits beside AdamW's state.

``quant="fp8"`` is the control: the configuration's activation precision
one step down.  Where the program holds an activation in bfloat16 (the
embedding's output, every matmul's operands and output, the residual
stream after each addition) the control rounds it to float8_e4m3fn under
a per-tensor scale (amax to 448); the backward passes through the rounding
unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


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


# -------------------------------------------------------------- precision
def _fp8(x):
    """x rounded to float8_e4m3fn under a per-tensor amax scale; the
    gradient passes through unchanged."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _act(quant):
    """Rounding of an activation the configuration holds in bfloat16."""
    return _fp8 if quant == "fp8" else (lambda x: x)


def _mm(quant):
    act = _act(quant)

    def mm(spec, a, b):
        return act(jnp.einsum(spec, act(a), act(b),
                              preferred_element_type=jnp.float32))
    return mm


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
    mm, act = _mm(quant), _act(quant)
    x = act(params["embed"][tokens])

    def body(h, lp):
        return jax.checkpoint(
            lambda h_, lp_: _layer(h_, lp_, k, mm, act))(h, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    logits = mm("sd,dv->sv", _rms(x, params["ln_f"], k.eps), params["unembed"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def batch_grad(params, tokens, labels, k: Dims, quant=None):
    """(mean loss, mean gradient) over the rows of tokens: (N, S)."""
    def body(carry, xs):
        gacc, lacc = carry
        lval, g = jax.value_and_grad(sequence_loss)(params, xs[0], xs[1], k,
                                                    quant)
        return (jax.tree.map(jnp.add, gacc, g), lacc + lval), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (gsum, lsum), _ = jax.lax.scan(body, (zero, jnp.zeros((), jnp.float32)),
                                   (tokens, labels))
    n = tokens.shape[0]
    return lsum / n, jax.tree.map(lambda g: g / n, gsum)


def adamw(params, grads, m, v, t, lr, b1, b2, eps):
    """One AdamW step (no weight decay); t counts from 1."""
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps),
        params, m, v)
    return params, m, v


# ---------------------------------------------------------------- readings
def leaf_norms(tree) -> dict[str, jax.Array]:
    """Per-leaf L2 norms keyed by the leaf's path ("layers/attn/wq")."""
    out = {}
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(e, "key", e)) for e in path)
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    return out


@functools.partial(jax.jit, static_argnums=1)
def _change(seed, k: Dims, params):
    return leaf_norms(jax.tree.map(jnp.subtract, params, init_params(seed, k)))


def change_norms(seed: int, k: Dims, params) -> dict[str, float]:
    """Per-leaf ||params - init(seed)||, with the initial weights rebuilt
    from the seed beside the params."""
    return {n: float(v) for n, v in _change(seed, k, params).items()}


@dataclasses.dataclass
class Readings:
    """What a training run's first steps give the comparison."""
    losses: list[float]
    grad_norms: dict[str, float]     # the first step's gradient, per leaf
    change_norms: dict[str, float]   # ||p_steps - p_0|| per leaf


@functools.lru_cache(maxsize=None)
def _programs(k: Dims, quant, lr: float, b1: float, b2: float, eps: float):
    """The jitted gradient, update and initial-weight programs, built once
    per process for each set of sizes."""
    grad_fn = jax.jit(lambda p, t, l: batch_grad(p, t, l, k, quant))
    step_fn = jax.jit(lambda p, g, m, v, t: adamw(p, g, m, v, t, lr, b1, b2,
                                                  eps),
                      donate_argnums=(0, 2, 3))
    init_fn = jax.jit(lambda s: init_params(s, k))
    return grad_fn, step_fn, init_fn


def train_readings(seed: int, k: Dims, batches, opt: dict, *, quant=None,
                   rows=None, device=None) -> Readings:
    """Run ``len(batches)`` AdamW steps of the reference from the seed's
    weights.  ``rows`` (a slice) keeps only those rows of every batch: the
    half-batch fault.  Everything runs on ``device`` (default: the first)
    at ``highest`` matmul precision."""
    device = device or jax.devices()[0]
    with jax.default_matmul_precision("highest"), jax.default_device(device):
        grad_fn, step_fn, init_fn = _programs(k, quant, opt["lr"], opt["b1"],
                                              opt["b2"], opt["eps"])
        params = init_fn(seed)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for t, batch in enumerate(batches, start=1):
            tok, lab = batch["tokens"], batch["labels"]
            if rows is not None:
                tok, lab = tok[rows], lab[rows]
            loss, g = grad_fn(params, jnp.asarray(tok), jnp.asarray(lab))
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = {n: float(x) for n, x in
                              jax.jit(leaf_norms)(g).items()}
            params, m, v = step_fn(params, g, m, v, jnp.float32(t))
            del g
        del m, v
        changes = change_norms(seed, k, params)
    return Readings(losses=losses, grad_norms=grad_norms,
                    change_norms=changes)
