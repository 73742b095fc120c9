"""The expert layer that holds a share of the experts
(``repro.models.moe.held_experts_ffn``) against a dense loop over the
experts, float32 on the CPU: every held expert computed for every token and
weighted by its gate where the token chose it."""
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import common as cm, moe

D, F, E, K = 32, 16, 8, 3


def _cfg(shares=1, index=0, experts=E, top_k=K):
    base = get_config("deepseek-v2-lite")
    return dataclasses.replace(
        base, d_model=D, moe_d_ff=F, n_experts=experts, top_k=top_k,
        n_shared_experts=2, expert_shares=shares, expert_share_index=index,
        compute_dtype="float32")


def _layer(seed, experts=E):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"router": cm.dense_init(ks[0], (D, experts), D, jnp.float32),
            "moe": {"w_gate": cm.dense_init(ks[1], (experts, D, F), D,
                                            jnp.float32),
                    "w_up": cm.dense_init(ks[2], (experts, D, F), D,
                                          jnp.float32),
                    "w_down": cm.dense_init(ks[3], (experts, F, D), F,
                                            jnp.float32)},
            "shared": cm.mlp_params(ks[4], _cfg(), jnp.float32, d_ff=2 * F)}


def _share(lp, cfg):
    """The layer as the share ``cfg`` holds it."""
    lo, n = cfg.first_expert_held, cfg.experts_held
    return dict(lp, moe={k: v[lo:lo + n] for k, v in lp["moe"].items()})


def _dense_loop(lp, cfg, x):
    """The shared experts plus every held expert, for every token, weighted
    by the token's gate (0 where it did not choose the expert)."""
    xt = np.asarray(x, np.float64).reshape(-1, D)
    logits = xt @ np.asarray(lp["router"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, axis=-1, kind="stable")[:, :cfg.top_k]

    def swiglu(h, g, u, d):
        a = h @ g
        return (a / (1 + np.exp(-a)) * (h @ u)) @ d

    sh = {k: np.asarray(v, np.float64) for k, v in lp["shared"].items()}
    y = swiglu(xt, sh["w_gate"], sh["w_up"], sh["w_down"])
    w = {k: np.asarray(v, np.float64) for k, v in lp["moe"].items()}
    for j in range(cfg.experts_held):
        e = cfg.first_expert_held + j
        gate = np.where((top == e).any(-1), p[:, e], 0.0)
        y += gate[:, None] * swiglu(xt, w["w_gate"][j], w["w_up"][j],
                                    w["w_down"][j])
    return y.reshape(x.shape), int(np.isin(
        top, np.arange(cfg.first_expert_held,
                       cfg.first_expert_held + cfg.experts_held)).sum())


def _run(lp, cfg, x):
    with jax.default_matmul_precision("highest"):
        return moe.held_experts_ffn(lp, cfg, x)


@pytest.mark.parametrize("shares,index", [(1, 0), (2, 0), (2, 1), (4, 3)])
def test_held_experts_equal_a_dense_loop(shares, index):
    cfg = _cfg(shares, index)
    lp = _share(_layer(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, D))
    y, aux, (here, most) = _run(lp, cfg, x)
    want, want_here = _dense_loop(lp, cfg, x)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    assert int(here) == want_here and 0 < int(most) <= want_here
    assert np.isfinite(float(aux))


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts of all shares, with the shared experts (which every
    chip computes alike) counted once, give the uncut layer's output; the
    balance loss is the same on every share."""
    uncut = _layer(3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, D))
    whole, aux, _ = _run(uncut, _cfg(), x)
    parts, auxes = [], []
    for r in range(4):
        cfg = _cfg(4, r)
        y, a, _ = _run(_share(uncut, cfg), cfg, x)
        parts.append(np.asarray(y))
        auxes.append(float(a))
    shared = np.asarray(cm.swiglu(uncut["shared"], x))
    np.testing.assert_allclose(sum(parts) - 3 * shared, np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(auxes, float(aux), rtol=1e-6)


def test_no_slot_dropped_under_forced_imbalance(monkeypatch):
    """Every token routes its k slots to the held experts, k x the
    uniform share: the slots overflow the first buffer, the chunks past it
    run, and nothing is dropped."""
    monkeypatch.setattr(moe, "GMM_ROW_TILE", 8)
    cfg = _cfg(4, 0, experts=16)           # experts 0-3 held of 16, top-3
    lp = _share(_layer(5, experts=16), cfg)
    bias = jnp.zeros((D, 16)).at[:, 4:].set(-30.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (2, 40, D)))
    lp["router"] = lp["router"] + bias / D   # held experts win every token
    T = x.shape[0] * x.shape[1]
    # a buffer holds twice the uniform share, T x 3 x 4/16 x 2 = 120 slots
    calls = []                             # the layer's own, not megablox's
    real_cond = jax.lax.cond

    def cond(p, *a):
        if sys._getframe(1).f_globals["__name__"] == moe.__name__:
            calls.append(p)
        return real_cond(p, *a)
    monkeypatch.setattr(jax.lax, "cond", cond)
    y, _, (here, most) = _run(lp, cfg, x)
    assert len(calls) == 1                 # one buffer past the first
    want, want_here = _dense_loop(lp, cfg, x)
    assert want_here == int(here) == T * K
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    # one expert takes every token: its slots exceed a whole buffer
    one = lp["router"].at[:, 0].add(40.0 / D)
    y, _, (here, most) = _run(dict(lp, router=one), cfg, x)
    assert int(most) == T
    want, _ = _dense_loop(dict(lp, router=one), cfg, x)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    _grads_agree(dict(lp, router=one), cfg, x)   # through every buffer


def _grads_agree(lp, cfg, x):
    """d sum(sin(y)) / d layer, the layer's and a dense loop's in jnp."""
    def dense(lp_):
        logits = jnp.einsum("td,de->te", x.reshape(-1, D), lp_["router"])
        p = jax.nn.softmax(logits, -1)
        gate, idx = jax.lax.top_k(p, cfg.top_k)
        y = cm.swiglu(lp_["shared"], x).reshape(-1, D)
        for j in range(cfg.experts_held):
            e = cfg.first_expert_held + j
            g = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)
            w = {n: v[j] for n, v in lp_["moe"].items()}
            y = y + g[:, None] * cm.swiglu(w, x).reshape(-1, D)
        return jnp.sum(jnp.sin(y))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p_: jnp.sum(jnp.sin(
            moe.held_experts_ffn(p_, cfg, x)[0])))(lp)
        want = jax.grad(dense)(lp)
    # f32 sums in another order, of gradients of order 10
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


def test_gradient_matches_the_dense_loop():
    cfg = _cfg(2, 1)
    _grads_agree(_share(_layer(7), cfg), cfg,
                 jax.random.normal(jax.random.PRNGKey(8), (1, 16, D)))


def test_rows_past_the_groups_reach_nothing(monkeypatch):
    """Whatever the grouped matmul leaves in the rows past its groups, in
    its result and in its input's gradient (NaN here), the layer's output
    and gradients are the dense loop's."""
    real = moe._grouped

    def nan_past(a, sizes):
        past = jnp.arange(a.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, a)

    @jax.custom_vjp
    def poisoned(x, w, sizes):
        return nan_past(real(x, w, sizes), sizes)

    def fwd(x, w, sizes):
        y, vjp = jax.vjp(lambda a, b: real(a, b, sizes), x, w)
        return nan_past(y, sizes), (vjp, sizes)

    def bwd(res, ct):
        vjp, sizes = res
        dx, dw = vjp(ct)
        return (nan_past(dx, sizes), dw,
                np.zeros(sizes.shape, jax.dtypes.float0))

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(moe, "_grouped", poisoned)
    cfg = _cfg(2, 1)
    lp = _share(_layer(11), cfg)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 24, D))
    y, _, (here, _) = _run(lp, cfg, x)
    assert 0 < int(here) < x.shape[0] * x.shape[1] * K   # rows past groups
    np.testing.assert_allclose(np.asarray(y), _dense_loop(lp, cfg, x)[0],
                               rtol=1e-4, atol=1e-5)
    _grads_agree(lp, cfg, x)


def test_sequence_wise_balance_loss():
    """Per sequence: sum over experts of (E / (K S)) x its slots times its
    mean score, averaged over the sequences (arXiv:2405.04434 eq. 23-25)."""
    B, S = 3, 10
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(9),
                                             (B * S, E)), -1)
    _, idx = jax.lax.top_k(probs, K)
    got = float(moe.seq_aux_loss(probs, idx, E, K, B))
    p, ix = np.asarray(probs).reshape(B, S, E), np.asarray(idx).reshape(B, -1)
    want = np.mean([sum(E / (K * S) * np.sum(ix[b] == e) * p[b, :, e].mean()
                        for e in range(E)) for b in range(B)])
    assert got == pytest.approx(want, rel=1e-6)


def test_megablox_equals_ragged_dot(monkeypatch):
    """The grouped matmul (megablox, in Pallas interpret mode here) against
    XLA's ragged dot, forward and gradient, on the rows the groups cover;
    the rows past the groups read zero."""
    monkeypatch.setattr(moe, "GMM_ROW_TILE", 8)
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    x = jax.random.normal(ks[0], (64, 32))
    w = jax.random.normal(ks[1], (4, 32, 16))
    sizes = jnp.asarray([9, 0, 20, 15], jnp.int32)      # 44 of 64 rows
    ct = jax.random.normal(ks[2], (64, 16))
    n = int(sizes.sum())

    def run(op):
        def f(x_, w_):
            return jnp.sum(op(x_, w_, sizes)[:n] * ct[:n])
        with jax.default_matmul_precision("highest"):
            return op(x, w, sizes), jax.grad(f, argnums=(0, 1))(x, w)

    (ya, (gxa, gwa)) = run(moe.grouped_matmul)
    (yb, (gxb, gwb)) = run(jax.lax.ragged_dot)
    np.testing.assert_allclose(ya[:n], yb[:n], rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(ya[n:]))
    np.testing.assert_allclose(gxa[:n], gxb[:n], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gwa, gwb, rtol=1e-5, atol=1e-5)
