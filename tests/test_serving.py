"""Serving engine integration: sharded prefill feeds sharded decode (layout
pinned by out_shardings), greedy generation runs for dense (window and
dense-cache), SSM, and encdec families on a live mesh."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_local_mesh
from repro.models import api
from repro.serving.engine import BatchedEngine


@pytest.mark.parametrize("arch,window", [("qwen3-1.7b", 0), ("qwen3-1.7b", 16),
                                         ("xlstm-350m", 0),
                                         ("zamba2-1.2b", 0)])
def test_engine_generate(arch, window):
    cfg = get_config(arch).reduced()
    mesh = make_local_mesh(4, 2)
    with jax.sharding.set_mesh(mesh):
        params = api.init(jax.random.PRNGKey(0), cfg)
    engine = BatchedEngine(cfg, mesh, params, batch=4, seq_len=40,
                           window=window)
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (4, 12),
                                                dtype=np.int32)
    out = engine.generate(prompts, max_new=4)
    assert out.shape == (4, 4)
    assert (out >= 0).all() and (out < cfg.vocab).all()


def test_engine_non_divisible_batch_uses_replicated_tokens():
    """batch=3 on a 4-way data mesh cannot shard the token axis: the
    engine must fall back to the P(None) replicated token layout and still
    generate correctly (the serving edge case the coded engine's fixed
    B = k*b batching sidesteps)."""
    cfg = get_config("qwen3-1.7b").reduced()
    mesh = make_local_mesh(4, 2)
    with jax.sharding.set_mesh(mesh):
        params = api.init(jax.random.PRNGKey(0), cfg)
    engine = BatchedEngine(cfg, mesh, params, batch=3, seq_len=32)
    spec = engine.arts.token_sharding.spec
    assert tuple(spec) == (None,)
    prompts = np.random.default_rng(2).integers(0, cfg.vocab, (3, 8),
                                                dtype=np.int32)
    out = engine.generate(prompts, max_new=3)
    assert out.shape == (3, 3)
    assert (out >= 0).all() and (out < cfg.vocab).all()


def test_serve_artifacts_window_cache_shapes():
    """Windowed serving allocates the sliding-window cache: the artifact's
    cache shapes match the model's cache_spec for that window, and differ
    from the dense-cache shapes."""
    import jax as _jax
    from repro.serving.engine import build_serve_artifacts
    cfg = get_config("qwen3-1.7b").reduced()
    mesh = make_local_mesh(4, 2)
    win, dense = 16, 0
    arts_w = build_serve_artifacts(cfg, mesh, batch=4, seq_len=40,
                                   window=win)
    want = api.cache_spec(cfg, 4, 40, window=win)
    got_shapes = _jax.tree.map(lambda s: tuple(s.shape), arts_w.cache_shapes)
    want_shapes = _jax.tree.map(lambda s: tuple(s.shape), want)
    assert got_shapes == want_shapes
    arts_d = build_serve_artifacts(cfg, mesh, batch=4, seq_len=40,
                                   window=dense)
    dense_shapes = _jax.tree.map(lambda s: tuple(s.shape),
                                 arts_d.cache_shapes)
    assert got_shapes != dense_shapes


def test_engine_deterministic_across_batch_slots():
    """Greedy decode of identical prompts must agree across batch slots
    (catches cross-slot leakage through sharded caches)."""
    cfg = get_config("qwen3-1.7b").reduced()
    mesh = make_local_mesh(4, 2)
    with jax.sharding.set_mesh(mesh):
        params = api.init(jax.random.PRNGKey(1), cfg)
    engine = BatchedEngine(cfg, mesh, params, batch=4, seq_len=32)
    prompt = np.random.default_rng(1).integers(0, cfg.vocab, (1, 8),
                                               dtype=np.int32)
    prompts = np.repeat(prompt, 4, axis=0)
    out = engine.generate(prompts, max_new=4)
    for b in range(1, 4):
        np.testing.assert_array_equal(out[0], out[b])
