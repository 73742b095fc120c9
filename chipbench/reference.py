"""Plain reference of the cells' training, shared by every model type:
the per-sequence gradient, AdamW and the readings the comparison takes, in
straightforward ``jax.numpy``, float32 at ``highest`` matmul precision.

It imports nothing of the program under test.  The model itself, its
weights from the seed (``init_params``) and one sequence's loss
(``sequence_loss``), is the reference of the configuration's model type
(``models/<model_type>/reference.py``), which the harness resolves once per
cell and passes in.

A step's gradient is the mean over its sequences of each sequence's
mean-loss gradient, computed one sequence at a time (``lax.scan``) so that
a wide layer fits beside AdamW's state; a model's loss rematerialises its
layers for the same reason.

``quant="fp8"`` is the control: the configuration's activation precision
one step down.  Where the program holds an activation in bfloat16 a model's
loss rounds it with ``act`` (and every matmul with ``mm``): to
float8_e4m3fn under a per-tensor scale (amax to 448); the backward passes
through the rounding unchanged.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


# -------------------------------------------------------------- precision
def fp8(x):
    """x rounded to float8_e4m3fn under a per-tensor amax scale; the
    gradient passes through unchanged."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def act(quant):
    """Rounding of an activation the configuration holds in bfloat16."""
    return fp8 if quant == "fp8" else (lambda x: x)


def mm(quant):
    """``einsum`` of two activations into float32, operands and result
    rounded as ``act`` says."""
    rnd = act(quant)

    def matmul(spec, a, b):
        return rnd(jnp.einsum(spec, rnd(a), rnd(b),
                              preferred_element_type=jnp.float32))
    return matmul


# ---------------------------------------------------------------- training
def batch_grad(model, params, tokens, labels, k, quant=None):
    """(mean loss, mean gradient) of ``model.sequence_loss`` over the rows
    of tokens: (N, S)."""
    def body(carry, xs):
        gacc, lacc = carry
        lval, g = jax.value_and_grad(model.sequence_loss)(
            params, xs[0], xs[1], k, quant)
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


@functools.partial(jax.jit, static_argnums=(0, 2))
def _change(model, seed, k, params):
    return leaf_norms(jax.tree.map(jnp.subtract, params,
                                   model.init_params(seed, k)))


def change_norms(model, seed: int, k, params) -> dict[str, float]:
    """Per-leaf ||params - init(seed)||, with the initial weights rebuilt
    from the seed by ``model.init_params`` beside the params."""
    return {n: float(v) for n, v in _change(model, seed, k, params).items()}


@dataclasses.dataclass
class Readings:
    """What a training run's first steps give the comparison."""
    losses: list[float]
    grad_norms: dict[str, float]     # the first step's gradient, per leaf
    change_norms: dict[str, float]   # ||p_steps - p_0|| per leaf


@functools.lru_cache(maxsize=None)
def _programs(model, k, quant, lr: float, b1: float, b2: float,
              eps: float):
    """The jitted gradient, update and initial-weight programs, built once
    per process for each model and set of sizes."""
    grad_fn = jax.jit(lambda p, t, l: batch_grad(model, p, t, l, k, quant))
    step_fn = jax.jit(lambda p, g, m, v, t: adamw(p, g, m, v, t, lr, b1, b2,
                                                  eps),
                      donate_argnums=(0, 2, 3))
    init_fn = jax.jit(lambda s: model.init_params(s, k))
    return grad_fn, step_fn, init_fn


def train_readings(model, seed: int, k, batches, opt: dict, *, quant=None,
                   rows=None, device=None) -> Readings:
    """Run ``len(batches)`` AdamW steps of the reference ``model`` (its
    ``init_params`` and ``sequence_loss``, at sizes ``k``) from the seed's
    weights.  ``rows`` (a slice) keeps only those rows of every batch: the
    half-batch fault.  Everything runs on ``device`` (default: the first)
    at ``highest`` matmul precision."""
    device = device or jax.devices()[0]
    with jax.default_matmul_precision("highest"), jax.default_device(device):
        grad_fn, step_fn, init_fn = _programs(model, k, quant, opt["lr"],
                                              opt["b1"], opt["b2"], opt["eps"])
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
        changes = change_norms(model, seed, k, params)
    return Readings(losses=losses, grad_norms=grad_norms,
                    change_norms=changes)
