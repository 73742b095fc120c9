"""Dry-run lowering builders: for one (arch x input-shape x mesh) produce the
jitted step, its ShapeDtypeStruct arguments, and shardings — then
``.lower().compile()`` proves the distribution config is coherent and yields
``memory_analysis()`` / ``cost_analysis()`` / the collective-bytes breakdown
for §Roofline.  No arrays are ever allocated.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import coding
from repro.configs import get_config
from repro.core import make_code
from repro.models import api as model_api
from repro.optim import get_optimizer
from repro.serving.engine import build_serve_artifacts
from repro.train.coded_step import make_coded_train_step

from .mesh import data_degree
from .shapes import SHAPES, applicability

PyTree = Any


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def dryrun_config(arch: str):
    """Full config in bf16 compute (the roofline target numerics)."""
    cfg = get_config(arch)
    return dataclasses.replace(cfg, param_dtype="bfloat16",
                               compute_dtype="bfloat16")


def default_code(n: int, *, d: int = 3, s: int = 1, m: int = 2, kind=None):
    return make_code(n, d, s, m, kind=kind)


# ------------------------------------------------------------- train batch
def train_batch_shapes(cfg, n: int, d: int, shape, k: int | None = None) -> dict:
    """ShapeDtypeStructs of the (n, d, b_subset, ...) coded batch layout.

    ``k`` is the number of data subsets (defaults to n — the uniform
    scheme; hetero codes decouple it)."""
    gb, S = shape.global_batch, shape.seq_len
    b = gb // (k or n)
    assert b >= 1, f"global_batch {gb} < number of subsets {k or n}"
    out = {}
    if cfg.family == "linear":
        out["x"] = _sds((n, d, b, cfg.d_model), "float32")
        out["y"] = _sds((n, d, b), "int32")
        return out
    if cfg.family == "encdec":
        S_tok = cfg.dec_ctx
        out["embeds"] = _sds((n, d, b, S, cfg.d_model), cfg.compute_dtype)
    elif cfg.family == "vlm":
        S_tok = S - cfg.n_frontend_tokens
        out["embeds"] = _sds((n, d, b, cfg.n_frontend_tokens, cfg.d_model),
                             cfg.compute_dtype)
    else:
        S_tok = S
    out["tokens"] = _sds((n, d, b, S_tok), "int32")
    out["labels"] = _sds((n, d, b, S_tok), "int32")
    return out


# --------------------------------------------------------------- builders
def build_train_lowering(arch: str, shape_name: str, mesh, *,
                         schedule: str = "gather", code=None,
                         optimizer: str = "adamw",
                         encode_dtype: str = "float32",
                         backend: str = "auto", packed: bool = True,
                         partial: bool = False):
    """Returns (jitted_fn, args) ready for .lower(*args)."""
    cfg = dryrun_config(arch)
    shape = SHAPES[shape_name]
    n = data_degree(mesh)
    code = code or default_code(n)
    opt = get_optimizer(optimizer, 1e-3)
    spec = coding.SchemeSpec(schedule=schedule, encode_dtype=encode_dtype,
                             backend=backend, packed=packed, partial=partial)
    arts = make_coded_train_step(cfg, code, mesh, opt, spec=spec)

    pshapes = jax.eval_shape(lambda: model_api.init(jax.random.PRNGKey(0), cfg))
    oshapes = jax.eval_shape(opt.init, pshapes)
    bshapes = train_batch_shapes(cfg, n, code.d, shape,
                                 k=getattr(code, "num_subsets", n))
    smapped, in_specs, out_specs = arts.step(bshapes)

    args = (pshapes, oshapes, bshapes,
            _sds((n, code.m), "float32"), _sds((n,), "float32"),
            _sds((n, code.d), "float32"))
    if partial:
        args = args + (_sds((), "float32"),)
    def ns(t):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))
    fn = jax.jit(smapped, in_shardings=ns(in_specs), out_shardings=ns(out_specs),
                 donate_argnums=(0, 1))
    return fn, args, {"coded_fraction": arts.coded_fraction,
                      "codec_backend": arts.codec.backend.name,
                      "wire_buckets": (len(arts.pack_plan.buckets)
                                       if arts.pack_plan else 0),
                      "copy_free_share": round(arts.copy_free_share, 6),
                      "loads": list(arts.loads),
                      "partial": partial}


def build_prefill_lowering(arch: str, shape_name: str, mesh):
    cfg = dryrun_config(arch)
    shape = SHAPES[shape_name]
    arts = build_serve_artifacts(cfg, mesh, batch=shape.global_batch,
                                 seq_len=shape.seq_len, window=shape.window)
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        bshapes = {"embeds": _sds((B, S, cfg.d_model), cfg.compute_dtype)}
    elif cfg.family == "vlm":
        bshapes = {"tokens": _sds((B, max(S - cfg.n_frontend_tokens, 16)), "int32"),
                   "embeds": _sds((B, cfg.n_frontend_tokens, cfg.d_model),
                                  cfg.compute_dtype)}
    else:
        bshapes = {"tokens": _sds((B, S), "int32")}
    pshapes = jax.eval_shape(lambda: model_api.init(jax.random.PRNGKey(0), cfg))
    return arts.prefill, (pshapes, bshapes), {}


def build_decode_lowering(arch: str, shape_name: str, mesh):
    cfg = dryrun_config(arch)
    shape = SHAPES[shape_name]
    arts = build_serve_artifacts(cfg, mesh, batch=shape.global_batch,
                                 seq_len=shape.seq_len, window=shape.window)
    pshapes = jax.eval_shape(lambda: model_api.init(jax.random.PRNGKey(0), cfg))
    tok = _sds((shape.global_batch,), "int32")
    return arts.decode, (pshapes, arts.cache_shapes, tok), {}


def build_lowering(arch: str, shape_name: str, mesh, **kw):
    runs, reason = applicability(arch, shape_name)
    if not runs:
        raise SkipLowering(reason)
    kind = SHAPES[shape_name].kind
    if kind == "train":
        return build_train_lowering(arch, shape_name, mesh, **kw)
    if kind == "prefill":
        return build_prefill_lowering(arch, shape_name, mesh)
    return build_decode_lowering(arch, shape_name, mesh)


class SkipLowering(Exception):
    pass


# ------------------------------------------------------- HLO introspection
def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Loop-aware collective byte totals by op kind (see hlo_cost)."""
    from . import hlo_cost
    return {k: int(v) for k, v in
            hlo_cost.analyze(hlo_text)["collective_bytes"].items()}
