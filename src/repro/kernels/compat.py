"""Pallas TPU compiler parameters shared by every kernel.

Kernels name each grid dimension :data:`PARALLEL` or :data:`ARBITRARY` and
pass the scoped-VMEM request their launch plan derived.
"""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu

PARALLEL = pltpu.GridDimensionSemantics.PARALLEL
ARBITRARY = pltpu.GridDimensionSemantics.ARBITRARY


def tpu_compiler_params(*dimension_semantics, vmem_limit_bytes=None):
    """Compiler params with per-grid-dim semantics and an optional
    scoped-VMEM limit (``None`` keeps the chip's default)."""
    return pltpu.CompilerParams(dimension_semantics=tuple(dimension_semantics),
                                vmem_limit_bytes=vmem_limit_bytes)
