"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the pod axis
is pure data parallelism across pods (gradient all-reduce crosses DCI).

Defined as functions so importing this module never touches jax device
state; the dry-run sets xla_force_host_platform_device_count *before* any
jax initialization (see dryrun.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # the sharding engine (launch/sharding.py) places arrays and constrains
    # activations for GSPMD to propagate: Auto axes, not make_mesh's
    # default Explicit ones, under which e.g. the embedding gather of a
    # data-sharded table by data-sharded tokens is a type error
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over the first devices: forced host devices in tests,
    the real chips in ``chip_smoke.py --chips 4``."""
    return _auto_mesh(shape, axes)


# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
DCI_FACTOR = 10.0               # cross-pod links ~10x slower than ICI
