"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax initialization.

Every axis is ``AxisType.Auto``: the sharding rules annotate arrays and
leave the partitioning to the compiler.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: single pod (16, 16) = 256 chips as ("data", "model"); two pods
    (2, 16, 16) = 512 chips with the leading "pod" axis crossing DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist locally, as a 1D ("data",) mesh (tests/examples)."""
    n = len(jax.devices())
    return _auto_mesh((n,), ("data",))


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
