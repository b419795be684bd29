"""Production mesh builders (brief-mandated shapes).

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import; tests see
the single real CPU device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model code steers layouts with
    ``with_sharding_constraint``, which Explicit axes (the default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_shard_mesh(n_shards: int, axis: str = "shard"):
    """1-D serving mesh for the topology's ``mesh`` execution backend: one
    device per shard group along ``axis``. On a CPU host force enough
    virtual devices with XLA_FLAGS=--xla_force_host_platform_device_count=N
    (set before the first jax import); a multi-process ``jax.distributed``
    launch yields the same mesh over real per-host devices, so the serving
    code path is identical."""
    import numpy as np
    from jax.sharding import Mesh
    devs = jax.devices()
    if n_shards < 1:
        raise ValueError(f"need at least one shard, got {n_shards}")
    if len(devs) < n_shards:
        raise ValueError(
            f"mesh backend needs {n_shards} devices for {n_shards} shards "
            f"but only {len(devs)} are visible — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_shards} "
            f"(before importing jax) or launch one process per host via "
            f"jax.distributed")
    return Mesh(np.asarray(devs[:n_shards]), (axis,))


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (host) devices exist — used by tests."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return _auto_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (roofline denominators; brief-specified)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~per chip, 1 link used)
