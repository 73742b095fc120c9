"""HLO-level collective regression guard for the packed codec.

Compiles the real coded train step for a multi-leaf LM (14 coded leaves) on
a (4 data x 1 model) host mesh and reads its collectives from the optimized
HLO via ``repro.launch.hlo_cost``: the packed step must issue at most 2
``all-gather``/``all-to-all`` ops *per wire bucket* per step — one gather
for the gather schedule, one all_to_all + one gather for a2a — and each
worker's encodings on the wire must stay within 1/m of what the uncoded
psum step all-reduces, plus the buckets' padding.
"""
import functools

import jax
import numpy as np
import pytest

import repro.coding as coding
from repro.configs import get_config
from repro.core import make_code
from repro.data import CodedBatcher, make_synthetic_batch
from repro.launch import hlo_cost
from repro.launch.mesh import make_local_mesh
from repro.optim import get_optimizer
from repro.train.coded_step import make_coded_train_step

N = 4
CODE = make_code(N, 3, 1, 2)
ARCH = "qwen3-1.7b"


@functools.lru_cache(maxsize=None)
def _analysis(schedule: str):
    """``hlo_cost.analyze`` of the compiled step, with its artifacts."""
    if len(jax.devices()) < N:
        pytest.skip(f"needs {N} devices")
    cfg = get_config(ARCH).reduced()
    mesh = make_local_mesh(N, 1)
    opt = get_optimizer("sgd", 1e-2)
    arts = make_coded_train_step(
        cfg, CODE, mesh, opt, spec=coding.SchemeSpec(schedule=schedule))
    rng = np.random.default_rng(0)
    placed = CodedBatcher(CODE).place(make_synthetic_batch(rng, cfg, 8, 16))
    txt = arts.lowered(placed, cfg, opt).compile().as_text()
    return hlo_cost.analyze(txt), arts


def _collective_counts(schedule: str):
    cost, arts = _analysis(schedule)
    counts = dict(cost["collective_counts"])
    n_buckets = len(arts.pack_plan.buckets) if arts.pack_plan else 0
    n_coded = sum(
        p.coded for p in jax.tree.leaves(
            arts.plans, is_leaf=lambda x: hasattr(x, "coded")))
    return counts, n_buckets, n_coded


def test_packed_gather_at_most_one_collective_per_bucket():
    counts, n_buckets, n_coded = _collective_counts("gather")
    assert n_buckets >= 1 and n_coded > 1          # a real multi-leaf model
    assert counts.get("all-gather", 0) <= n_buckets
    assert counts.get("all-to-all", 0) == 0


def test_packed_a2a_at_most_two_collectives_per_bucket():
    counts, n_buckets, _ = _collective_counts("a2a")
    assert counts.get("all-to-all", 0) <= n_buckets
    assert counts.get("all-gather", 0) <= n_buckets


@pytest.mark.parametrize("schedule", ["gather", "a2a"])
def test_packed_no_worse_than_per_leaf(schedule):
    """Each worker puts only its l/m encodings on the wire: the collective
    that carries them (gather: the all-gather, whose result holds all n
    workers' encodings; a2a: the all-to-all) moves at most 1/m of the bytes
    the psum step all-reduces, plus the buckets' padding rows."""
    cost, arts = _analysis(schedule)
    psum, _ = _analysis("psum")
    op = {"gather": "all-gather", "a2a": "all-to-all"}[schedule]
    sent = cost["collective_bytes"][op] / (N if schedule == "gather" else 1)
    reduced = psum["collective_bytes"]["all-reduce"]
    pp = arts.pack_plan
    assert pp.padded_elems * 4 <= sent        # every bucket rides the wire
    assert sent <= reduced / CODE.m * pp.padded_elems / pp.unpadded_elems
