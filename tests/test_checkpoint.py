"""Checkpoint subsystem: roundtrip fidelity, atomicity conventions,
retention, torn-file fallback, trainer resume, and crash recovery."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_tree, save_tree
from repro.checkpoint import store as ckpt_store
from repro.configs import get_config
from repro.core import make_code
from repro.data import make_synthetic_batch
from repro.launch.mesh import make_local_mesh
from repro.models import api as model_api
from repro.optim import get_optimizer
from repro.train import Trainer


def test_save_restore_roundtrip(tmp_path):
    cfg = get_config("qwen3-1.7b").reduced()
    params = model_api.init(jax.random.PRNGKey(0), cfg)
    p = tmp_path / "ckpt.npz"
    save_tree(p, params, {"note": "hi"})
    restored, meta = restore_tree(p, params)
    assert meta["note"] == "hi"
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_shape_mismatch_rejected(tmp_path):
    tree = {"w": jnp.ones((4, 4))}
    p = tmp_path / "c.npz"
    save_tree(p, tree)
    with pytest.raises(ValueError):
        restore_tree(p, {"w": jnp.ones((4, 5))})
    with pytest.raises(KeyError):
        restore_tree(p, {"w2": jnp.ones((4, 4))})


def test_manager_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": jnp.full((2,), s)})
    assert mgr.steps() == [3, 4]
    restored, meta = mgr.restore_latest({"x": jnp.zeros((2,))})
    assert meta["step"] == 4
    assert float(restored["x"][0]) == 4.0


def test_manager_rejects_keep_below_one(tmp_path):
    # keep=0 is the list[:-0] footgun: retention would delete every
    # snapshot immediately after writing it
    with pytest.raises(ValueError, match="keep"):
        CheckpointManager(tmp_path, keep=0)
    with pytest.raises(ValueError, match="keep"):
        CheckpointManager(tmp_path, keep=-2)


@pytest.mark.parametrize("corruption", ["truncated", "empty", "garbage"])
def test_restore_latest_falls_back_past_torn_newest(tmp_path, corruption):
    mgr = CheckpointManager(tmp_path, keep=3)
    for s in (1, 2, 3):
        mgr.save(s, {"x": jnp.full((2,), s)})
    p = tmp_path / "ckpt_00000003.npz"
    if corruption == "truncated":
        p.write_bytes(p.read_bytes()[: p.stat().st_size // 2])
    elif corruption == "empty":
        p.write_bytes(b"")
    else:
        p.write_bytes(b"this is not an npz archive at all")
    with pytest.warns(UserWarning, match="unreadable"):
        restored, meta = mgr.restore_latest({"x": jnp.zeros((2,))})
    assert meta["step"] == 2
    assert float(restored["x"][0]) == 2.0


def test_restore_latest_all_torn_returns_none(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    for s in (1, 2):
        mgr.save(s, {"x": jnp.zeros((2,))})
    for f in tmp_path.glob("ckpt_*.npz"):
        f.write_bytes(b"")
    with pytest.warns(UserWarning, match="starting fresh"):
        assert mgr.restore_latest({"x": jnp.zeros((2,))}) is None


def test_restore_latest_shape_mismatch_still_raises(tmp_path):
    # a structure mismatch is a caller bug, not corruption: silently
    # resuming an older snapshot would mask it
    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(1, {"x": jnp.zeros((2,))})
    mgr.save(2, {"x": jnp.zeros((2,))})
    with pytest.raises(ValueError, match="shape mismatch"):
        mgr.restore_latest({"x": jnp.zeros((5,))})


def test_failed_save_never_prunes_older_snapshots(tmp_path, monkeypatch):
    mgr = CheckpointManager(tmp_path, keep=1)
    mgr.save(1, {"x": jnp.zeros((2,))})

    def torn_save(path, tree, metadata=None):
        path.write_bytes(b"torn")   # lands under the final name, unreadable

    monkeypatch.setattr(ckpt_store, "save_tree", torn_save)
    with pytest.raises(Exception):
        mgr.save(2, {"x": jnp.zeros((2,))})   # verification open fails
    monkeypatch.undo()
    # the failed save ran before pruning: step 1 must have survived
    (tmp_path / "ckpt_00000002.npz").unlink()
    restored, meta = mgr.restore_latest({"x": jnp.zeros((2,))})
    assert meta["step"] == 1


def test_trainer_resume(tmp_path):
    cfg = get_config("qwen3-1.7b").reduced()
    code = make_code(4, 3, 1, 2)
    mesh = make_local_mesh(4, 2)
    kw = dict(checkpoint_dir=str(tmp_path), checkpoint_every=2, seed=0)
    tr = Trainer(cfg, code, mesh, get_optimizer("sgd", 1e-2), **kw)
    rng = np.random.default_rng(0)
    batch = make_synthetic_batch(rng, cfg, 8, 16)
    for _ in range(4):
        tr.step(batch)
    assert tr._ckpt.latest_step() == 4
    # a fresh trainer resumes from step 4 with identical params
    tr2 = Trainer(cfg, code, mesh, get_optimizer("sgd", 1e-2), **kw)
    assert tr2._step_count == 4
    for a, b in zip(jax.tree.leaves(tr.params), jax.tree.leaves(tr2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_crash_recovery_trajectory_exact(tmp_path):
    """Kill mid-save (torn newest snapshot), resume, and land on the
    bitwise-identical trajectory.

    The original run checkpoints at steps 2/4/6 and "crashes" while
    writing step 6 (simulated by tearing the file).  The resumed run must
    fall back to step 4, use the restored ``data_cursor`` to skip the
    4 batches already inside the parameters (``skip_to_cursor``), replay
    batches 5 and 6, and reach the original run's step-6 parameters
    exactly.
    """
    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=32)
    code = make_code(4, 3, 1, 2)
    mesh = make_local_mesh(4, 1)
    kw = dict(checkpoint_dir=str(tmp_path), checkpoint_every=2, seed=0)

    def batches():
        rng = np.random.default_rng(123)
        while True:
            yield make_synthetic_batch(rng, cfg, 8, 0)

    tr = Trainer(cfg, code, mesh, get_optimizer("sgd", 1e-2), **kw)
    stream = batches()
    for _ in range(6):
        tr.step(next(stream))
    final = [np.asarray(x).copy() for x in jax.tree.leaves(tr.params)]
    assert tr._ckpt.steps() == [2, 4, 6]

    # the crash: step 6's snapshot landed torn (power cut mid-write on a
    # filesystem that reordered the rename ahead of the data blocks)
    p6 = tmp_path / "ckpt_00000006.npz"
    p6.write_bytes(p6.read_bytes()[: p6.stat().st_size // 3])

    with pytest.warns(UserWarning, match="unreadable"):
        tr2 = Trainer(cfg, code, mesh, get_optimizer("sgd", 1e-2), **kw)
    assert tr2._step_count == 4             # fell back past the torn file
    assert tr2._data_cursor == 4
    stream2 = tr2.skip_to_cursor(batches())
    for _ in range(2):
        tr2.step(next(stream2))
    for a, b in zip(final, jax.tree.leaves(tr2.params)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_resume_warns_on_seed_and_scheme_mismatch(tmp_path):
    cfg = dataclasses.replace(get_config("logistic-paper"), d_model=32)
    mesh = make_local_mesh(4, 1)
    kw = dict(checkpoint_dir=str(tmp_path), checkpoint_every=1)
    tr = Trainer(cfg, make_code(4, 3, 1, 2), mesh,
                 get_optimizer("sgd", 1e-2), seed=0, **kw)
    rng = np.random.default_rng(0)
    tr.step(make_synthetic_batch(rng, cfg, 8, 0))
    with pytest.warns(UserWarning, match="seed"):
        Trainer(cfg, make_code(4, 3, 1, 2), mesh,
                get_optimizer("sgd", 1e-2), seed=1, **kw)
    with pytest.warns(UserWarning, match="scheme"):
        Trainer(cfg, make_code(4, 2, 1, 1), mesh,
                get_optimizer("sgd", 1e-2), seed=0, **kw)
