"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (required so smoke tests see 1 device while the dry-run sees
512 placeholder devices via XLA_FLAGS set before any jax import).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod single-pod; (2, 16, 16) = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh for tests/examples (e.g. (2, 4) on 8 CPU devices).

    Auto axes: the models place work by sharding rules and let the
    compiler propagate, which ``jax.make_mesh``'s default of explicit axes
    would reject at the first sharded gather or contraction.
    """
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)
