"""Production mesh definitions (TPU v5e target).

A function, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only dryrun.py forces
512 host devices)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """jax.make_mesh with Auto axes: the models place activations with
    ``with_sharding_constraint`` (models/layers.constrain), which JAX
    accepts on Auto axes only — make_mesh defaults to Explicit."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: "data" carries the federated client cohorts / global batch,
    "model" carries megatron+expert sharding, "pod" extends the cohort axis
    across pods (see DESIGN.md §3)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Tiny mesh for CPU-host sharding tests (requires >=data*model devices)."""
    return _auto_mesh((data, model), ("data", "model"))


# v5e hardware constants used by the roofline analysis (benchmarks/roofline).
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
