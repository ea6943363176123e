"""Production mesh definitions.

Functions, not module-level constants: importing this module never touches
jax device state (required so smoke tests see 1 CPU device while the dry-run
sees 512 forced host devices).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """A mesh whose axes are all ``Auto`` (sharding propagated by XLA, as
    the logical-axis rules in ``parallel/sharding.py`` assume)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_pipeline_mesh():
    """Multi-pod with the pod axis re-purposed as a pipeline-stage axis
    (inter-pod ICI carries only microbatch activations per tick)."""
    return _mesh((2, 16, 16), ("pipe", "data", "model"))


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (forced host devices)."""
    return _mesh(shape, axes)
