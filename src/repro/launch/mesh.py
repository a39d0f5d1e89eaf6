"""Production mesh construction (function, not module-level constant, so
importing this module never touches jax device state)."""
from __future__ import annotations

import jax


def _mk(shape, axes):
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(n: int = 8, *, multi_pod: bool = False):
    """Small virtual-device mesh for CI-scale distribution tests."""
    if multi_pod:
        assert n % 2 == 0
        return _mk((2, n // 4, 2), ("pod", "data", "model"))
    return _mk((n // 2, 2), ("data", "model"))
