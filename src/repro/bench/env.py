"""Environment capture for benchmark records.

Everything that makes two measurements comparable (or not): interpreter and
library versions, the JAX backend and device inventory, and the XLA flags in
effect.  Keys are
stable so JSON diffs stay readable.
"""

from __future__ import annotations

import os
import platform
from typing import Any


def capture_env(mesh: Any | None = None) -> dict[str, Any]:
    """Snapshot the software/hardware context of a benchmark run."""
    import jax

    devices = jax.devices()
    env: dict[str, Any] = {
        "python": platform.python_version(),
        "platform": platform.system().lower(),
        "jax": jax.__version__,
        "jaxlib": _jaxlib_version(),
        "numpy": _numpy_version(),
        "backend": jax.default_backend(),
        "device_count": len(devices),
        "device_kind": devices[0].device_kind if devices else "none",
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }
    if mesh is not None:
        env["mesh_axes"] = {str(a): int(mesh.shape[a]) for a in mesh.axis_names}
    return env


def _jaxlib_version() -> str:
    try:
        import jaxlib

        return getattr(jaxlib, "__version__", "unknown")
    except ImportError:  # pragma: no cover - jaxlib ships with jax
        return "absent"


def _numpy_version() -> str:
    import numpy

    return numpy.__version__
