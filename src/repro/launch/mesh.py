"""Production mesh definition.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: (16, 16) = 256 chips, axes
("data", "model"). Multi-pod: (2, 16, 16) = 512 chips, axes
("pod", "data", "model") — the "pod" axis is the slow inter-pod (DCN/ICI
cross-link) dimension and defaults to pure data parallelism.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_shard_mesh(n_shards: int):
    """1-D mesh over the first ``n_shards`` local devices, axis
    ``("shards",)`` — the stage-1 cache partition axis (DESIGN.md §13).
    Distinct from the model mesh: cache shards are data-parallel scan
    slices keyed by cluster ownership, not model-parallel weight
    shards. CI simulates 8 CPU devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    from jax.sharding import Mesh
    import numpy as np

    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"mesh needs {n_shards} devices, host has {len(devs)}"
        )
    return Mesh(np.array(devs[:n_shards]), ("shards",))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes)
    )


# TPU v5e-class hardware constants used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,  # per chip
    "hbm_bw": 819e9,            # bytes/s per chip
    "ici_bw": 50e9,             # bytes/s per link (~per chip, one direction)
    "hbm_bytes": 16 * 1024**3,  # 16 GiB per chip
}
