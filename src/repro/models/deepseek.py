"""DeepSeek-V2 decoder LM (family "deepseek"; arXiv:2405.04434): multi-head
latent attention (``repro.models.mla``) in every layer; ``n_dense_layers``
leading layers with a SwiGLU MLP of width ``d_ff``, the rest DeepSeekMoE
layers (``repro.models.moe.held_experts_ffn``: the router over all
``n_experts``, the routed experts held here, the shared experts); a final
RMSNorm and an untied unembedding.

The loss is the mean next-token cross-entropy plus ``aux_loss_alpha`` times
the sequence-wise balance loss summed over the expert layers.  Each
layer's expert part is this chip's share: the slots routed to experts held
elsewhere add nothing here (``ModelConfig.cut``'s ``expert_share``).

``loss_and_counts`` also returns the expert layers' counters, summed over
the layers: ``moe_slots_here``, the (token, expert) slots routed to the
experts held here, and ``moe_max_expert_slots``, the largest held expert's.
It trains only: there is no serving path (prefill, decode) for it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as cm
from . import mla, moe

COUNTERS = ("moe_slots_here", "moe_max_expert_slots")


def init(key, cfg):
    kd, kl, ke, ko = jax.random.split(key, 4)
    dt = cm.pdtype(cfg)
    D = cfg.d_model
    F, Eh = cfg.moe_d_ff, cfg.experts_held

    def dense_layer(k):
        ka, km = jax.random.split(k)
        return {"ln1": jnp.ones((D,), dt), "attn": mla.init(ka, cfg, dt),
                "ln2": jnp.ones((D,), dt), "mlp": cm.mlp_params(km, cfg, dt)}

    def moe_layer(k):
        ka, kr, ks, k1, k2, k3 = jax.random.split(k, 6)
        return {
            "ln1": jnp.ones((D,), dt),
            "attn": mla.init(ka, cfg, dt),
            "ln2": jnp.ones((D,), dt),
            "router": cm.dense_init(kr, (D, cfg.n_experts), D, dt),
            "moe": {"w_gate": cm.dense_init(k1, (Eh, D, F), D, dt),
                    "w_up": cm.dense_init(k2, (Eh, D, F), D, dt),
                    "w_down": cm.dense_init(k3, (Eh, F, D), F, dt)},
            "shared": cm.mlp_params(ks, cfg, dt,
                                    d_ff=cfg.n_shared_experts * F),
        }

    return {
        "embed": cm.dense_init(ke, (cfg.vocab, D), D, dt),
        "dense_layers": cm.stacked_init(dense_layer, kd, cfg.n_dense_layers),
        "layers": cm.stacked_init(moe_layer, kl,
                                  cfg.n_layers - cfg.n_dense_layers),
        "ln_f": jnp.ones((D,), dt),
        "unembed": cm.dense_init(ko, (D, cfg.vocab), D, dt),
    }


def forward(params, cfg, tokens):
    """tokens: (B, S) -> (logits (B, S, V), balance loss before alpha,
    (slots routed here, largest held expert's slots))."""
    B, S = tokens.shape
    x = cm.embed_tokens(params["embed"], tokens, cm.cdtype(cfg))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def dense_block(x, lp):
        x = x + mla.self_attention(lp["attn"], cfg, cm.rms_norm(x, lp["ln1"]),
                                   pos)
        return x + cm.swiglu(lp["mlp"], cm.rms_norm(x, lp["ln2"]))

    def moe_block(carry, lp):
        x, aux, (here, most) = carry
        x = x + mla.self_attention(lp["attn"], cfg, cm.rms_norm(x, lp["ln1"]),
                                   pos)
        y, a, (h, m) = moe.held_experts_ffn(lp, cfg, cm.rms_norm(x, lp["ln2"]))
        return x + y, aux + a, (here + h, most + m)

    x = cm.scan_layers(dense_block, x, params["dense_layers"])
    zero = jnp.zeros((), jnp.int32)
    x, aux, counts = cm.scan_layers(
        moe_block, (x, jnp.zeros((), jnp.float32), (zero, zero)),
        params["layers"])
    x = cm.rms_norm(x, params["ln_f"])
    return cm.unembed(x, params["unembed"]), aux, counts


def loss_and_counts(params, cfg, batch):
    logits, aux, counts = forward(params, cfg, batch["tokens"])
    loss = cm.softmax_xent(logits, batch["labels"]) + cfg.aux_loss_alpha * aux
    return loss, {n: c.astype(jnp.float32) for n, c in zip(COUNTERS, counts)}


def loss(params, cfg, batch):
    return loss_and_counts(params, cfg, batch)[0]
