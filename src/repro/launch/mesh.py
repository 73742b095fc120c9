"""Mesh builders.  Functions (not module constants) so importing never
touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """The target v5e meshes: single pod (16, 16) = ('data', 'model'),
    two pods (2, 16, 16) = ('pod', 'data', 'model').  Requires 256 / 512
    devices (the dry-run forces host-platform placeholders)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(n_data: int = 1, n_model: int = 1):
    """Small ``('data', 'model')`` mesh over the first ``n_data * n_model``
    devices present (chips, or forced host devices on the CPU).  Raises a
    clear error when fewer devices exist."""
    need = n_data * n_model
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"a ({n_data} data x {n_model} model) mesh needs {need} devices "
            f"but JAX sees {have} ({jax.default_backend()}); lower "
            f"--n-data/--n-model, or on the CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need}")
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:need])


def data_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def data_degree(mesh) -> int:
    out = 1
    for a in data_axes_of(mesh):
        out *= mesh.shape[a]
    return out
