"""Production mesh factory (TPU v5e pods).

Function, not module-level constant: importing this module never touches
jax device state (device count is locked at first jax init, and only
dryrun.py requests 512 placeholder host devices).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """A mesh whose axes the compiler shards by ``with_sharding_constraint``
    hints (``jax.make_mesh`` otherwise makes them Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_sim_mesh(shards: int | None = None):
    """1-D ``devices`` mesh for the sharded sim engine (``repro.sim``).

    Lays SDCA bucket groups data-parallel across local accelerators.
    ``shards`` defaults to every visible device and is floored to a
    power of two so it always divides the engine's power-of-two group
    padding (a 1-device host degenerates to the bucketed layout, which
    is exactly what the differential tests exploit on CPU).
    """
    n = len(jax.devices())
    shards = n if shards is None else max(1, min(shards, n))
    shards = 1 << (shards.bit_length() - 1)  # floor to a power of two
    return jax.make_mesh((shards,), ("devices",))


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many real devices exist (tests)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _auto_mesh((data, model), ("data", "model"))


def mesh_chips(mesh) -> int:
    import numpy as np

    return int(np.prod(mesh.devices.shape))
