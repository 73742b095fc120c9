"""Mixture-of-Experts decoder LM (olmoe / grok family), and the expert layer
that holds a share of the experts (DeepSeekMoE, ``repro.models.deepseek``).

Routing: top-k softmax router with capacity-based scatter dispatch (Switch
style, but gather/scatter instead of the (T, E, C) one-hot einsum so the
dispatch tensors stay O(T*k), not O(T*E*C)).  Tokens overflowing an expert's
capacity are dropped (standard); a load-balance auxiliary loss (Shazeer) keeps
the router spread.  Expert weights carry a leading E axis so the `model` mesh
axis can shard either E (olmoe: 64 experts) or d_ff (grok: 8 experts).

``held_experts_ffn`` is told which experts it holds: it routes over all of
them and computes, with no capacity and no dropped slot, the part of the
result its own experts give (``held_experts_ffn``'s docstring).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common as cm
from . import dense

AUX_LOSS_WEIGHT = 0.01

# §Perf lever: MoE dispatch implementation.
#   "scatter"  — gather/scatter capacity dispatch (baseline; O(T*k) dispatch
#                tensors but GSPMD partitions the scatter poorly: the (E,C,D)
#                buffers get replicated -> multi-GB all-reduces per layer).
#   "einsum"   — chunked Switch/GShard-style one-hot einsum dispatch: shards
#                cleanly over the expert axis (token chunks bound the one-hot
#                to (Tc, E, Cc)).  Flipped by the dry-run's --opt moe_einsum.
DISPATCH = "scatter"
TOKEN_CHUNK = 2048

# megablox row tile, and the grain of the held experts' buffer: 512
# overflows the v5e's 16 MiB of scoped VMEM in tgmm at d_model 2048 x
# expert width 1408
GMM_ROW_TILE = 256
# rows of the held experts' buffer, in multiples of the slots a uniform
# router sends to them
BUFFER_SHARE = 2


def init(key, cfg):
    kl, ke, ko = jax.random.split(key, 3)
    dt = cm.pdtype(cfg)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts

    def layer_init(k):
        ka, kr, k1, k2, k3 = jax.random.split(k, 5)
        return {
            "ln1": jnp.ones((D,), dt),
            "attn": cm.attn_params(ka, cfg, dt),
            "ln2": jnp.ones((D,), dt),
            "router": cm.dense_init(kr, (D, E), D, dt),
            "moe": {
                "w_gate": cm.dense_init(k1, (E, D, F), D, dt),
                "w_up": cm.dense_init(k2, (E, D, F), D, dt),
                "w_down": cm.dense_init(k3, (E, F, D), F, dt),
            },
        }

    return {
        "embed": cm.dense_init(ke, (cfg.vocab, D), D, dt),
        "layers": cm.stacked_init(layer_init, kl, cfg.n_layers),
        "ln_f": jnp.ones((D,), dt),
        "unembed": cm.dense_init(ko, (D, cfg.vocab), D, dt),
    }


# ------------------------------------------------------------------ MoE op
def moe_ffn(lp, cfg, x):
    """Dispatch-implementation switch (see DISPATCH above)."""
    if DISPATCH == "einsum":
        return moe_ffn_einsum(lp, cfg, x)
    return moe_ffn_scatter(lp, cfg, x)


def moe_ffn_einsum(lp, cfg, x):
    """Chunked one-hot einsum dispatch (Switch/GShard style).

    Tokens are processed in TOKEN_CHUNK chunks; capacity is per-chunk
    (Cc = ceil(Tc*K/E * capacity_factor)), so the dispatch one-hot stays
    (Tc, E, Cc).  All expert-indexed tensors contract through einsums, which
    GSPMD partitions over the expert (or d_ff) axis without replication.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, D)
    Tc = min(TOKEN_CHUNK, T)
    while T % Tc:
        Tc -= 1
    nc = T // Tc
    Cc = int(np.ceil(Tc * K / E * cfg.capacity_factor))
    w = lp["moe"]

    def chunk(carry, xc):
        me_sum, ce_sum = carry
        logits = jnp.einsum("td,de->te", xc.astype(jnp.float32),
                            lp["router"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)                  # (Tc, E)
        gate, eidx = jax.lax.top_k(probs, K)                     # (Tc, K)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        onehot_e = jax.nn.one_hot(eidx, E, dtype=jnp.float32)    # (Tc, K, E)
        # position of (t, k) within its expert, counted over the chunk
        pos = jnp.cumsum(onehot_e.reshape(Tc * K, E), axis=0) * \
            onehot_e.reshape(Tc * K, E)
        pos = (pos.sum(-1) - 1.0).reshape(Tc, K)                 # 0-based slot
        keep = pos < Cc
        onehot_c = jax.nn.one_hot(pos, Cc, dtype=jnp.float32) * \
            keep[..., None].astype(jnp.float32)                 # (Tc, K, Cc)
        disp = jnp.einsum("tke,tkc->tec", onehot_e, onehot_c)    # (Tc, E, Cc)
        comb = jnp.einsum("tke,tkc,tk->tec", onehot_e, onehot_c,
                          gate.astype(jnp.float32))
        buf = jnp.einsum("tec,td->ecd", disp.astype(x.dtype), xc)
        g = jnp.einsum("ecd,edf->ecf", buf, w["w_gate"].astype(x.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, w["w_up"].astype(x.dtype))
        out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                         w["w_down"].astype(x.dtype))
        yc = jnp.einsum("tec,ecd->td", comb.astype(x.dtype), out)
        me_sum = me_sum + probs.sum(0)
        ce_sum = ce_sum + jnp.einsum("tke->e", onehot_e)
        return (me_sum, ce_sum), yc

    carry0 = (jnp.zeros((E,), jnp.float32), jnp.zeros((E,), jnp.float32))
    (me_sum, ce_sum), ys = jax.lax.scan(
        lambda c, xc: jax.remat(chunk)(c, xc), carry0,
        xt.reshape(nc, Tc, D))
    aux = E * jnp.sum((me_sum / T) * (ce_sum / (T * K)))
    return ys.reshape(B, S, D), aux


def moe_ffn_scatter(lp, cfg, x):
    """x: (B, S, D) -> (y: (B, S, D), aux_loss: scalar f32)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        lp["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # (T, E)
    gate, eidx = jax.lax.top_k(probs, K)                          # (T, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)  # renorm

    # load-balance aux loss (Shazeer): E * sum_e f_e * p_e
    me = probs.mean(0)                                            # (T,E)->(E,)
    ce = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    # capacity-based dispatch
    C = int(np.ceil(T * K / E * cfg.capacity_factor))
    flat_e = eidx.reshape(-1)                                     # (T*K,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)           # (T*K, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) * onehot                # 1-based slot
    slot = jnp.sum(pos_in_e, axis=-1) - 1                         # (T*K,)
    keep = slot < C
    slot_c = jnp.where(keep, slot, C)                             # dropped -> pad row

    buf = jnp.zeros((E, C + 1, D), x.dtype)
    tok_of = jnp.repeat(jnp.arange(T), K)
    buf = buf.at[flat_e, slot_c].add(xt[tok_of])                  # (E, C+1, D)
    buf = buf[:, :C]

    w = lp["moe"]
    g = jnp.einsum("ecd,edf->ecf", buf, w["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, w["w_up"].astype(x.dtype))
    out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w["w_down"].astype(x.dtype))

    out = jnp.pad(out, ((0, 0), (0, 1), (0, 0)))                  # pad row reads 0
    picked = out[flat_e, slot_c]                                  # (T*K, D)
    picked = picked * (keep.astype(x.dtype) * gate.reshape(-1).astype(x.dtype))[:, None]
    y = jnp.zeros((T, D), x.dtype).at[tok_of].add(picked)
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------- forward
def _block(xa, lp, cfg, pos, mask_kind, window):
    x, aux = xa
    x = x + cm.self_attention(lp["attn"], cfg, cm.rms_norm(x, lp["ln1"]), pos,
                              mask_kind=mask_kind, window=window)
    y, a = moe_ffn(lp, cfg, cm.rms_norm(x, lp["ln2"]))
    return (x + y, aux + a)


def forward(params, cfg, tokens, *, window: int = 0):
    B, S = tokens.shape
    x = cm.embed_tokens(params["embed"], tokens, cm.cdtype(cfg))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    mk = "window" if window else "causal"
    (x, aux) = cm.scan_layers(lambda h, lp: _block(h, lp, cfg, pos, mk, window),
                              (x, jnp.zeros((), jnp.float32)), params["layers"])
    x = cm.rms_norm(x, params["ln_f"])
    return cm.unembed(x, params["unembed"]), aux


def loss(params, cfg, batch):
    logits, aux = forward(params, cfg, batch["tokens"])
    return cm.softmax_xent(logits, batch["labels"]) + AUX_LOSS_WEIGHT * aux


# ---------------------------------------------------------------- serving
cache_spec = dense.cache_spec
init_cache = dense.init_cache


def prefill(params, cfg, tokens, cache_len: int, *, window: int = 0):
    B, S = tokens.shape
    x = cm.embed_tokens(params["embed"], tokens, cm.cdtype(cfg))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    mk = "window" if window else "causal"
    slots = min(cache_len, window) if window else cache_len

    def block_with_cache(x, lp):
        h = cm.rms_norm(x, lp["ln1"])
        ya, k, v = cm.self_attention_with_kv(lp["attn"], cfg, h, pos,
                                             mask_kind=mk, window=window)
        x = x + ya
        y, _ = moe_ffn(lp, cfg, cm.rms_norm(x, lp["ln2"]))
        x = x + y
        kk = cm.pack_cache(k, slots, window)
        vv = cm.pack_cache(v, slots, window)
        return x, (kk, vv)

    x, (ks, vs) = jax.lax.scan(lambda c, lp: jax.remat(block_with_cache)(c, lp),
                               x, params["layers"])
    x = cm.rms_norm(x[:, -1:], params["ln_f"])
    logits = cm.unembed(x, params["unembed"])[:, 0]
    return logits, {"k": ks, "v": vs, "pos": jnp.asarray(S, jnp.int32)}


def decode_step(params, cfg, cache, token, *, window: int = 0):
    pos = cache["pos"]
    x = cm.embed_tokens(params["embed"], token[:, None], cm.cdtype(cfg))

    def block(x, lp_kv):
        lp, (kc, vc) = lp_kv
        h = cm.rms_norm(x, lp["ln1"])
        y, kc, vc = cm.attention_decode(lp["attn"], cfg, h, kc, vc, pos,
                                        window=window)
        x = x + y
        z, _ = moe_ffn(lp, cfg, cm.rms_norm(x, lp["ln2"]))
        return x + z, (kc, vc)

    x, (ks, vs) = jax.lax.scan(lambda c, lpkv: jax.remat(block)(c, lpkv),
                               x, (params["layers"], (cache["k"], cache["v"])))
    x = cm.rms_norm(x, params["ln_f"])
    logits = cm.unembed(x, params["unembed"])[:, 0]
    return logits, {"k": ks, "v": vs, "pos": pos + 1}


# ------------------------------------------------- experts held here
def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """megablox tiles: GMM_ROW_TILE rows; a width whole up to 1536, else
    1024 of it."""
    return (GMM_ROW_TILE,) + tuple(x if x <= 1536 else 1024 for x in (k, n))


def grouped_matmul(x, w, sizes):
    """Rows of x: (M, K) in consecutive groups of ``sizes`` (G,), each times
    its group's w[g]: (K, N) -> (M, N), in JAX's Pallas megablox ``gmm``
    (``tgmm`` for the weights' gradient; interpret mode off a TPU), which
    visits only the row tiles the groups cover.  The rows past the groups
    read 0 and pass no gradient back, whatever the op leaves there."""
    past = jnp.arange(x.shape[0]) >= jnp.sum(sizes)
    return jnp.where(past[:, None], 0, _grouped(x, w, sizes))


def _grouped(x, w, sizes):
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    # one more group, of the rows past w's groups, so that the sizes add up
    # to M as megablox assumes; it has no weights and is not computed
    rest = x.shape[0] - jnp.sum(sizes)
    return megablox.gmm(
        x, w, jnp.concatenate([sizes, rest[None]]).astype(jnp.int32),
        x.dtype, _gmm_tiling, jnp.zeros((), jnp.int32), None, False,
        not cm._on_tpu())


def seq_aux_loss(probs, eidx, n_experts: int, top_k: int, batch: int):
    """DeepSeek-V2's sequence-wise balance loss before its weight alpha
    (arXiv:2405.04434 eq. 23-25): per sequence, the sum over experts of
    f_i (E / (K S) times the slots routed to i) and P_i (i's mean score),
    averaged over the sequences.  probs: (B*S, E), eidx: (B*S, K)."""
    T = probs.shape[0]
    S = T // batch
    slots = jax.nn.one_hot(eidx.reshape(batch, S * top_k), n_experts,
                           dtype=jnp.float32).sum(1)                # (B, E)
    f = jax.lax.stop_gradient(slots) * (n_experts / (top_k * S))
    P = probs.reshape(batch, S, n_experts).mean(1)
    return jnp.mean(jnp.sum(f * P, axis=-1))


def held_experts_ffn(lp, cfg, x):
    """The routed experts' part of DeepSeekMoE's output from the experts
    held here, and the shared experts' (which every chip computes alike).

    The router scores all ``cfg.n_experts`` experts (f32 logits at
    ``HIGHEST`` precision, so a near tie does not flip on the bf16
    rounding of a product), softmax, greedy top-k, gates as scored.  Of the
    T x k (token, expert) slots, those whose expert lies in the held range
    ``[first_expert_held, + experts_held)`` are sorted by expert and run
    through grouped matmuls of the held experts' SwiGLUs, a static
    ``buffer`` of rows at a time: ``BUFFER_SHARE`` times the slots a uniform
    router sends here, in whole row tiles.  The buffers cover T x min(k, held) rows,
    every slot a token can send here, so no slot is ever dropped however
    uneven the routing; a buffer past the slots routed here is skipped
    (``lax.cond``, rematerialised in the backward), and the grouped
    matmuls visit only the rows their groups cover, so the matmul work
    follows the slots routed here (the gather and scatter the buffer).
    Each output row, times its gate, is added to its token.

    lp: the layer's ``router`` (D, E), ``moe`` {w_gate, w_up (held, D, F),
    w_down (held, F, D)} and ``shared`` (a SwiGLU); x: (B, S, D).
    Returns (y (B, S, D), the balance loss before alpha, and the slots
    routed here and the largest held expert's slots, both int32).
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    Eh, e0 = cfg.experts_held, cfg.first_expert_held
    T = B * S
    most = T * min(K, Eh)               # every slot of a token held here
    tile = GMM_ROW_TILE
    buffer = min(-(-BUFFER_SHARE * T * K * Eh // E // tile),
                 -(-most // tile)) * tile
    n_chunks = -(-most // buffer)
    xt = x.reshape(T, D)
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            lp["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
        gate, eidx = jax.lax.top_k(probs, K)                        # (T, K)
        aux = seq_aux_loss(probs, eidx, E, K, B)
    with jax.named_scope("moe.dispatch"):
        local = eidx.reshape(-1) - e0                               # (T*K,)
        key = jnp.where((local >= 0) & (local < Eh), local, Eh)
        order = jnp.argsort(key, stable=True)   # held slots first, by expert
        sizes = jnp.bincount(key, length=Eh + 1).astype(jnp.int32)[:Eh]
        here = jnp.sum(sizes)
        starts = jnp.cumsum(sizes) - sizes
        pad = (0, max(n_chunks * buffer - T * K, 0))
        tok = jnp.pad(order // K, pad)
        gate_sorted = jnp.pad(gate.reshape(-1)[order].astype(x.dtype), pad)
    w = {n: a.astype(x.dtype) for n, a in lp["moe"].items()}

    def chunk(c, y):
        lo = c * buffer
        with jax.named_scope("moe.dispatch"):
            t = jnp.where(lo + jnp.arange(buffer) < here,
                          tok[lo:lo + buffer], T)         # T: no slot, dropped
            # each held expert's rows within this chunk
            sz = (jnp.clip(starts + sizes, lo, lo + buffer)
                  - jnp.clip(starts, lo, lo + buffer))
            xs = xt.at[t].get(mode="fill", fill_value=0)
        with jax.named_scope("moe.experts"):
            h = (jax.nn.silu(grouped_matmul(xs, w["w_gate"], sz))
                 * grouped_matmul(xs, w["w_up"], sz))
            out = grouped_matmul(h, w["w_down"], sz)
        with jax.named_scope("moe.combine"):
            return y.at[t].add(out * gate_sorted[lo:lo + buffer, None],
                               mode="drop")

    y = chunk(0, jnp.zeros((T, D), x.dtype))
    for c in range(1, n_chunks):
        y = jax.lax.cond(c * buffer < here,
                         jax.checkpoint(functools.partial(chunk, c)),
                         lambda y_: y_, y)
    with jax.named_scope("moe.shared"):
        y = y + cm.swiglu(lp["shared"], x).reshape(T, D)
    return y.reshape(B, S, D), aux, (here, jnp.max(sizes))
