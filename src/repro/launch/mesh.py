"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run owns XLA_FLAGS and device counts).

Topology (TPU v5e pods): 256 chips/pod as a (16, 16) (data, model) mesh;
multi-pod adds a leading "pod" axis over DCN. The "model" axis is the
fast-ICI dimension (TP/EP collectives); "data"+"pod" carry gradient
reduction, hierarchically: reduce-scatter over ICI inside the pod, then a
cross-pod all-reduce of the scattered shards over DCN.
"""

from __future__ import annotations

import jax


def _make(shape: tuple[int, ...], axes: tuple[str, ...]):
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (elastic-remesh path and tests)."""
    return _make(shape, axes)
