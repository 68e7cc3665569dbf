"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips.
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis carries
pure data parallelism over DCN.
"""

from __future__ import annotations

import jax


def _mk(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests, elastic re-mesh)."""
    return _mk(shape, axes)


def devices_per_pod(mesh) -> int | None:
    if "pod" not in mesh.shape:
        return None
    return mesh.devices.size // mesh.shape["pod"]
