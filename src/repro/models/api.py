"""Uniform model interface over the zoo.

- ``get_module(cfg)``: the family module (init / loss / forward / serving).
- ``make_loss(cfg)``: ``fn(params, batch) -> scalar``; ``batch`` is always a
  dict (tokens/labels, + embeds for vlm/audio, or x/y for linear).
- ``make_loss_and_counts(cfg)``: ``fn(params, batch) -> (scalar, counters)``,
  the family's named f32 counters (``counters(cfg)``; none for most).
- ``make_prefill(cfg, cache_len, window)`` / ``make_decode(cfg, window)``:
  uniform serving entry points.
- ``cache_spec(cfg, B, S, window)``: ShapeDtypeStructs of the decode state.
"""
from __future__ import annotations

import jax

from . import deepseek, dense, encdec, linear, mamba_hybrid, moe, vlm, xlstm

_FAMILY = {
    "dense": dense,
    "moe": moe,
    "deepseek": deepseek,
    "ssm": xlstm,
    "hybrid": mamba_hybrid,
    "encdec": encdec,
    "vlm": vlm,
    "linear": linear,
}


def get_module(cfg):
    return _FAMILY[cfg.family]


def init(key, cfg):
    return get_module(cfg).init(key, cfg)


def make_loss(cfg):
    mod = get_module(cfg)

    def fn(params, batch):
        return mod.loss(params, cfg, batch)

    return fn


def counters(cfg) -> tuple[str, ...]:
    """Names of the counters the family's loss reports beside it."""
    return getattr(get_module(cfg), "COUNTERS", ())


def make_loss_and_counts(cfg):
    mod = get_module(cfg)
    if counters(cfg):
        def fn(params, batch):
            return mod.loss_and_counts(params, cfg, batch)
    else:
        def fn(params, batch):
            return mod.loss(params, cfg, batch), {}
    return fn


def cache_spec(cfg, B: int, S: int, *, window: int = 0):
    mod = get_module(cfg)
    if cfg.family == "ssm":
        return mod.state_spec(cfg, B)
    if cfg.family == "hybrid":
        return mod.state_spec(cfg, B, S, window=window)
    return mod.cache_spec(cfg, B, S, window=window)


def init_cache(cfg, B: int, S: int, *, window: int = 0):
    import jax.numpy as jnp
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_spec(cfg, B, S, window=window))


def make_prefill(cfg, cache_len: int, *, window: int = 0):
    """Returns fn(params, batch) -> (last-token logits, cache).

    batch: {"tokens"} (+ {"embeds"} for vlm/encdec)."""
    mod = get_module(cfg)

    def fn(params, batch):
        if cfg.family == "encdec":
            return mod.prefill(params, cfg, batch["embeds"], cache_len)
        if cfg.family == "vlm":
            return mod.prefill(params, cfg, batch["tokens"], cache_len,
                               embeds=batch["embeds"], window=window)
        if cfg.family == "ssm":
            return mod.prefill(params, cfg, batch["tokens"])
        return mod.prefill(params, cfg, batch["tokens"], cache_len, window=window)

    return fn


def make_forward(cfg, *, window: int = 0):
    """Returns fn(params, batch) -> per-request output, for coded serving.

    One batched stateless forward pass: the unit of work the coded serving
    engine shards across replicas.  For the linear family the output is the
    ``(B,)`` logit vector; for LM families it is the ``(B, vocab)``
    last-token logits of a full-prompt prefill (the cache is discarded —
    coded serving replicates the *forward compute*, not decode state).
    ``batch`` uses the same keys as :func:`make_loss` / :func:`make_prefill`.
    """
    mod = get_module(cfg)
    if cfg.family == "linear":
        def fn(params, batch):
            return mod.logits(params, cfg, batch["x"])
        return fn

    def fn(params, batch):
        key = "embeds" if cfg.family == "encdec" else "tokens"
        cache_len = batch[key].shape[1]
        logits, _ = make_prefill(cfg, cache_len, window=window)(params, batch)
        return logits

    return fn


def make_decode(cfg, *, window: int = 0):
    """Returns fn(params, cache, token) -> (logits, new_cache)."""
    mod = get_module(cfg)

    def fn(params, cache, token):
        if cfg.family == "ssm":
            return mod.decode_step(params, cfg, cache, token)
        if cfg.family == "encdec":
            return mod.decode_step(params, cfg, cache, token)
        return mod.decode_step(params, cfg, cache, token, window=window)

    return fn
