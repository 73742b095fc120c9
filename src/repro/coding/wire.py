"""Wire-dtype handling for the coded collectives.

Sub-f32 payloads are bitcast to u16 around each collective: XLA's algebraic
simplifier otherwise hoists the later f32 upcast *above* the all-gather /
all-to-all (silently doubling wire bytes); integer operands block the hoist.

Every collective of the coded step runs under the ``coded.exchange`` named
scope, the innermost scope of its ops, so a device trace puts their time in
the exchange phase whichever phase issued them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def all_gather_wire(x: jax.Array, axis_names) -> jax.Array:
    """all_gather at the wire dtype (u16 bitcast trick for sub-f32)."""
    if x.dtype == jnp.float32:
        with jax.named_scope("coded.exchange"):
            return jax.lax.all_gather(x, axis_names)
    raw = jax.lax.bitcast_convert_type(x, jnp.uint16)
    with jax.named_scope("coded.exchange"):
        g = jax.lax.all_gather(raw, axis_names)
    return jax.lax.bitcast_convert_type(g, x.dtype)


def all_to_all_wire(x: jax.Array, axis_names) -> jax.Array:
    """Tiled all_to_all over dim 0 at the wire dtype (same u16 trick)."""
    if x.dtype == jnp.float32:
        with jax.named_scope("coded.exchange"):
            return jax.lax.all_to_all(x, axis_names, split_axis=0,
                                      concat_axis=0, tiled=True)
    raw = jax.lax.bitcast_convert_type(x, jnp.uint16)
    with jax.named_scope("coded.exchange"):
        ex = jax.lax.all_to_all(raw, axis_names, split_axis=0,
                                concat_axis=0, tiled=True)
    return jax.lax.bitcast_convert_type(ex, x.dtype)


def psum(x, axis_names):
    """``jax.lax.psum`` under the exchange scope."""
    with jax.named_scope("coded.exchange"):
        return jax.lax.psum(x, axis_names)
