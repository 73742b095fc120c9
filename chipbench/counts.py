"""Bytes and operations of the codec kernels, from the number of coded
parameters alone.  The per-layer metrics divide these by time.

A model type's own counts, its parameters and model FLOPs per token (and
the work of any kernel of its own), are ``models/<model_type>/counts.py``.

Codec kernel bytes per step and chip, in float32, at the algorithm's
minimum (every operand read once, every result written once; the
program's padding and layout copies count against the kernel):

- encode: each of the worker's d subset gradients (l elements) is read and
  folded into its l/m encoding, which is written: d * 4 * l * (1 + 1/m);
- decode: the n gathered l/m encodings are read and the l-element
  gradient written: 4 * l * (n/m + 1);

with l the number of coded parameters (the model's ``total_params``).  Each
kernel does 2 flops per element read; against the chip's bytes/flops ratio
both are bound by bytes.
"""
from __future__ import annotations


def kernel_work(l: int, code: dict) -> dict[str, tuple[float, float]]:
    """(bytes, flops) per step and chip of each codec kernel, for ``l``
    coded parameters under ``code`` (n, d, m)."""
    n, d, m = code["n"], code["d"], code["m"]
    enc_read, dec_read = d * l, n * l / m
    return {
        "encode": (4.0 * (enc_read + d * l / m), 2.0 * enc_read),
        "decode": (4.0 * (dec_read + l), 2.0 * dec_read),
    }
