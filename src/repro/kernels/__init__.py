"""Pallas TPU kernels for the paper's per-step hot spots: coded encode
(eq. 17/18) and coded decode (eq. 19-21), each with a pure-jnp oracle in
ref.py.  The train step reaches them through ``repro.coding.backends``
(``pallas`` compiled on a TPU, ``interpret`` anywhere)."""
from . import ref
from .coded_decode import coded_decode
from .coded_encode import coded_encode, coded_encode_acc
from .flash_attn import flash_attention, flash_attention_gqa

__all__ = ["ref", "coded_encode", "coded_encode_acc", "coded_decode",
           "flash_attention", "flash_attention_gqa"]
