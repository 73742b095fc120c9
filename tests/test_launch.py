"""The launcher's pieces on the CPU: the config cut that keeps every width,
the mesh over the devices present, the compile-cache placement, and one
step through ``repro.launch.train.build`` (the path ``chip_smoke.py``
drives on the chip)."""
import math

import jax
import pytest

from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
from repro.configs import get_config
from repro.launch.mesh import make_local_mesh
from repro.launch.train import build, parse_args


def test_cut_keeps_every_width_and_names_the_cut():
    full = get_config("qwen3-1.7b")
    cut = full.cut(4, 8)
    assert (cut.n_layers, cut.vocab) == (4, 18992)
    for key in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                "qk_norm", "rope_theta", "family"):
        assert getattr(cut, key) == getattr(full, key), key
    assert cut.name == "qwen3-1.7b-4l-vocab18992"


@pytest.mark.parametrize("layers,share", [(0, 8), (29, 8), (4, 7), (4, 0)])
def test_cut_rejects_what_is_not_a_share(layers, share):
    with pytest.raises(ValueError):
        get_config("qwen3-1.7b").cut(layers, share)


def test_local_mesh_needs_enough_devices():
    have = jax.device_count()
    assert make_local_mesh(have, 1).devices.size == have
    with pytest.raises(ValueError, match=f"needs {2 * have} devices"):
        make_local_mesh(have, 2)


def test_compile_cache_goes_where_the_env_says(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(DEFAULT_DIR)
        assert DEFAULT_DIR.name == ".jax_cache"
        assert (DEFAULT_DIR.parent / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_launcher_build_trains_one_step():
    """``build`` on four of the host devices: a (4, 3, 1, 2) gather step of
    the reduced model with a fixed straggler gives a finite loss."""
    args = parse_args(["--size", "reduced", "--n-data", "4", "--steps", "1",
                       "--seq", "16", "--backend", "ref",
                       "--stragglers", "fixed", "--drop", "2"])
    trainer, stream = build(args)
    assert trainer.mesh.shape == {"data": 4, "model": 1}
    assert (trainer.code.n, trainer.code.d, trainer.code.s,
            trainer.code.m) == (4, 3, 1, 2)
    out = trainer.step(next(stream))
    assert math.isfinite(out["loss"])
