"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import jax
import numpy as np

__all__ = ["make_production_mesh", "make_small_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds the 2-pod DCN axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(jax.devices())}. "
            "The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count."
        )
    return _mesh(shape, axes, n)


def make_small_mesh(data: int = 2, model: int = 2, pod: int | None = None):
    """Reduced mesh for tests (requires ≥ data·model·(pod or 1) devices)."""
    if pod:
        shape, axes = (pod, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    return _mesh(shape, axes, int(np.prod(shape)))


def _mesh(shape, axes, n):
    # Auto axes: the models pin activations with with_sharding_constraint,
    # which refuses the Explicit axes jax.make_mesh now makes by default
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
