"""Production mesh construction.

A FUNCTION, not a module constant — importing this module never touches jax
device state (jax locks the device count on first backend init).

Every mesh here has Auto axes: the serving and training code place arrays
with ``NamedSharding``s and in-jit constraints and let the partitioner
propagate the rest (``jax.make_mesh`` defaults to Explicit axes, which make
shardings part of array types).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (one v5e pod slice, 256 chips) or 2×16×16 (two pods, 512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_host_mesh(device=None):
    """Degenerate 1×1 mesh over one device: the first local device by
    default, or ``device`` (a replica of a fleet on its own chip)."""
    return _auto_mesh((1, 1), ("data", "model"),
                      devices=None if device is None else [device])


def make_test_mesh(shape=(2, 4), axis_names=None):
    """Small explicit-shape mesh for tests and CPU benchmarks.

    ``make_production_mesh`` hard-codes pod slices (256/512 chips) that can
    never instantiate on a test host; tests build meshes through this helper
    instead, under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    Axis names default to the production convention, rightmost-aligned:
    2 axes → ("data", "model"), 3 axes → ("pod", "data", "model").
    """
    if axis_names is None:
        axis_names = ("pod", "data", "model")[-len(shape):]
    need = math.prod(shape)
    have = jax.device_count()
    if have < need:
        raise RuntimeError(
            f"mesh {tuple(shape)} needs {need} devices but the backend has "
            f"{have}; run under XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={need} (set BEFORE jax initializes)")
    return _auto_mesh(shape, axis_names)


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link
