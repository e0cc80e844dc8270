"""Production mesh construction.

Single pod : (16, 16) = 256 v5e chips, axes (data, model)
Multi pod  : (2, 16, 16) = 512 chips, axes (pod, data, model); `pod` is the
             outer DCN-connected pure-DP axis.

FUNCTIONS, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax init).

``make_mesh`` is the constructor every mesh in the repo goes through: all
axes are ``Auto`` (sharding propagated by the compiler), never ``Explicit``.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """Build a Mesh whose axes are all ``AxisType.Auto``."""
    return jax.make_mesh(
        tuple(shape),
        tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        if n >= 8:
            shape, axes = (2, 2, n // 4), ("pod", "data", "model")
        elif n >= 4:
            shape, axes = (2, n // 2), ("data", "model")
        else:
            shape, axes = (1, n), ("data", "model")
    return make_mesh(shape, axes)
