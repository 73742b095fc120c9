"""The ``deepseek_v2`` model type: its counts, its reference against the
program, its faults and its metrics."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import bench
import reduce_trace as rt
import reference

CELL = "deepseek-v2-lite.worker"


def test_counts_are_pinned():
    """535,060,992 coded parameters: per layer attention 13,762,560 and the
    latent's norm 512; the dense layer's MLP 67,239,936; per expert layer
    the router 131,072, the shared experts 17,301,504 and 8 held experts
    69,206,016; the embedding and unembedding 2 x 26,214,400; the gains.
    A token passes through 257,949,696 matmul weights (the routed experts
    at 6 x 8/64), so 6 x that plus MLA's 6 x 5 x 16 x 4096 x 320 is
    2,176,843,776 FLOPs a token at 4096 positions."""
    cell = bench.load_cell(CELL)
    m, c = cell.model, cell.config
    assert m.total_params(c) == 535_060_992
    assert m.flops_per_token(c, 4096) == 2_176_843_776
    assert cell.traffic["seq_len"] == 4096
    counts = bench._module(bench.MODELS / "deepseek_v2" / "counts.py")
    assert counts.expected_slots(c, cell.traffic) == 12_288
    ((pattern, nbytes, flops),) = m.kernels(c, cell.traffic).values()
    s, D, F = 12_288, 2048, 1408
    assert flops == 36 * 2 * s * D * F                 # 2.551 TFLOP
    assert nbytes == 36 * 2 * (s * D + s * F + 8 * D * F)
    for op in ("gmm.3", "transpose_jvp_jit_tgmm___.1"):
        assert re.search(pattern, op)
    assert not re.search(pattern, "fusion.12")


def test_the_program_holds_what_the_file_states():
    cell = bench.load_cell(CELL)
    cfg = cell.model.model_config(cell.config)
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert_held) == \
        (64, 8, 0)
    assert (cfg.top_k, cfg.n_dense_layers, cfg.n_layers) == (6, 1, 5)
    assert (cfg.qk_head_dim, cfg.v_head_dim_, cfg.kv_lora_rank) == \
        (192, 128, 512)
    bad = dict(cell.config, norm_topk_prob=True)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        cell.model.model_config(bad)


def test_loss_and_gradient_match_the_program_at_f32():
    """At float32 the reference and the program (its own path on the CPU)
    give the same loss and gradient to a few float32 roundings of each
    leaf's largest element: three layers of latent attention, the router's
    softmax and the scatter of the expert outputs sum in other orders."""
    from repro.models import api

    c = tiny.cell(CELL, seq_len=32)
    cfg = dataclasses.replace(c.model.model_config(c.config),
                              compute_dtype="float32")
    k = c.model.dims(c.config)
    params = c.model.init_params(5, k)
    batch = bench.Feed(k.vocab, c.traffic, 9).next()
    with jax.default_matmul_precision("highest"):
        want_l, want_g = jax.value_and_grad(api.make_loss(cfg))(
            params, jax.tree.map(jnp.asarray, batch))
        got_l, got_g = reference.batch_grad(c.model, params, batch["tokens"],
                                            batch["labels"], k)
    assert abs(float(got_l) - float(want_l)) < 1e-6 * abs(float(want_l))
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        g, w = np.asarray(g), np.asarray(w)
        assert np.max(np.abs(g - w)) <= 2e-5 * np.max(np.abs(w))


def test_half_batch_fault_is_not_correct(monkeypatch):
    """Each subset's loss, and so its gradient, over the first half of its
    rows only: the comparison refuses it."""
    from repro.models import deepseek

    real = deepseek.loss_and_counts

    def half(params, cfg, batch):
        n = batch["tokens"].shape[0] // 2
        return real(params, cfg, {k: v[:n] for k, v in batch.items()})
    monkeypatch.setattr(deepseek, "loss_and_counts", half)
    result = tiny.run(tiny.cell(CELL))
    assert not result["correct"], result["compared"]


def test_new_metrics_read_nothing_without_their_scopes():
    """On a trace with no ``moe``/``mla`` scope and no grouped matmul the
    cell's three metrics read None (the parent's program, for one)."""
    ops = [["while.1", 0, 300, "", "coded.grad"],
           ["fusion.4", 300, 340, "", ""]]
    ext = {"window": [0, 1000], "host": [],
           "chips": {"0": {"ops": ops, "modules": [["jit_stepfn", 0, 400]]}}}
    cell = bench.load_cell(CELL)
    ctx = bench.ReadContext(rt.reduce(ext, steps=2), cell,
                            {"hbm_bytes_per_s": 1e12,
                             "bf16_flops_per_s": 1e15})
    for name in ("moe_ms", "mla_ms", "expert_gmm_roofline"):
        assert bench.read_metric(name, ctx) is None, name
    # and with them, each reads its own
    ops += [["fusion.2", 10, 60, "", "moe.route"],
            ["gmm.3", 60, 160, "", "moe.experts"],
            ["fusion.5", 160, 200, "", "mla.attend"]]
    ctx = bench.ReadContext(rt.reduce(ext, steps=2), cell,
                            {"hbm_bytes_per_s": 1e12,
                             "bf16_flops_per_s": 1e15})
    assert bench.read_metric("moe_ms", ctx) == pytest.approx(1e3 * 150e-9 / 2)
    assert bench.read_metric("mla_ms", ctx) == pytest.approx(1e3 * 40e-9 / 2)
    assert bench.read_metric("expert_gmm_roofline", ctx) > 0
