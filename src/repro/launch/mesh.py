"""Production meshes.

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state — required because the dry-run
must set XLA_FLAGS *before* the first device query.
"""

from __future__ import annotations

import jax


def make_mesh_auto(shape, axes):
    """``jax.make_mesh`` with Auto axis types.

    ``jax.make_mesh`` without ``axis_types`` builds Explicit axes, under
    which jitted code must carry sharding-typed values; every caller here
    shards through ``NamedSharding`` and ``with mesh:`` instead.
    """
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    return make_mesh_auto((data, model), ("data", "model"))
