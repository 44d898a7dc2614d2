"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state. Single pod = (data=16, model=16) = 256 chips (one TPU v5e pod slice);
multi-pod adds a leading 'pod' axis: (pod=2, data=16, model=16) = 512 chips.

The `pod` axis is the slow (DCN/inter-pod) dimension: only data-parallel
gradient reduction crosses it; `model` stays inside a pod (ICI).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests use small ones, e.g. (2, 2))."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


# TPU v5e hardware constants (per chip) — used by the roofline analysis.
TPU_V5E = {
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bandwidth": 819e9,      # bytes/s
    "hbm_bytes": 16 * 2**30,
    "ici_link_bandwidth": 50e9,  # bytes/s per link
}
