"""Pure-jnp oracles for the Pallas kernels.  These are the ground truth every
kernel test asserts against, and the ``ref`` codec backend.  The group axis m
leads (see ``repro.coding.layout``): a gradient of l coordinates is m
contiguous blocks of V = l/m, as in the paper's split g = [g^(1); ...; g^(m)].

Both contractions are written as a broadcast multiply and a sum over the
contracted axes, in f32.  XLA reduces every output element in the same
order whatever the trailing shape, so a leaf decoded alone and the same leaf
decoded inside a packed bucket agree bit for bit (a dot's blocking depends
on the shape, and with it the rounding); on a TPU the multiply-and-sum also
stays exact f32 instead of taking the MXU's bf16 passes.
"""
from __future__ import annotations

import jax.numpy as jnp


def coded_encode_ref(G: jnp.ndarray, C: jnp.ndarray,
                     out_dtype=None) -> jnp.ndarray:
    """Fold d subset-gradient rows into one l/m encoding (paper eq. 17/18).

    G: (d, m, V[, R]) — grouped gradients (m blocks of V = l/m coordinates,
                        R a trailing, possibly model-sharded, dim)
    C: (d, m)         — the worker's coefficient rows C[i, j, :]
    returns (V[, R])  — the transmitted vector f_i, in ``out_dtype``
                        (default G's dtype)
    """
    c = C.astype(jnp.float32).reshape(C.shape + (1,) * (G.ndim - 2))
    out = jnp.sum(c * G.astype(jnp.float32), axis=(0, 1))
    return out.astype(out_dtype or G.dtype)


def coded_decode_ref(F: jnp.ndarray, W: jnp.ndarray,
                     out_dtype=None) -> jnp.ndarray:
    """Reconstruct the summed gradient from worker encodings (eq. 19-21).

    F: (n, V[, R])    — one l/m-dim encoding per worker (straggler rows
                        garbage)
    W: (n, m)         — decode weights, zero rows at stragglers
    returns (m, V[, R]) — decoded blocks in ``out_dtype`` (default F's
                        dtype); the caller reshapes them to (l,)
    """
    n, m = W.shape
    w = W.astype(jnp.float32).T.reshape((m, n) + (1,) * (F.ndim - 1))
    out = jnp.sum(w * F.astype(jnp.float32)[None], axis=1)
    return out.astype(out_dtype or F.dtype)
