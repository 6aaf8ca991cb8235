"""Production mesh construction (brief: MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (device count is locked at first jax init, and smoke tests
must see 1 CPU device while the dry-run sees 512 fakes).
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "shard_devices", "HW"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis explicitly ``Auto`` (the sharding
    mode every mesh in this repo uses)."""
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def shard_devices(n_shards: int):
    """Round-robin ``n_shards`` placements over the local devices.

    The sharded serving fabric calls this once at construction.  On a
    single-device host every shard lands on the same device (still correct —
    shards are then a concurrency/affinity construct, not a placement one);
    with ``--xla_force_host_platform_device_count=N`` or real multi-chip
    hosts the shards spread.  Returns a list of length ``n_shards``.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    devs = jax.local_devices()
    return [devs[i % len(devs)] for i in range(n_shards)]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod).
    Multi-pod:   (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


class HW:
    """TPU v5e hardware constants (per chip) for the roofline terms."""

    PEAK_BF16 = 197e12  # FLOP/s
    PEAK_INT8 = 394e12  # OP/s
    HBM_BW = 819e9  # B/s
    ICI_BW = 50e9  # B/s per link (~3 links usable per chip on a 2D torus)
    HBM_BYTES = 16 * 1024 ** 3
    VMEM_BYTES = 128 * 1024 ** 2
