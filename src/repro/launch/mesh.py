"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""

from __future__ import annotations

import jax


def _auto(n: int):
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(data: int = 1, model: int = 1):
    """Mesh over the first data·model local devices."""
    return jax.make_mesh((data, model), ("data", "model"), axis_types=_auto(2))


def device_info() -> dict:
    """The device a run lands on, as JAX reports it."""
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}

