"""Mesh construction helpers.

A 2-D ``(stripe, shard)`` mesh over however many devices exist.  The shard
axis is kept small (it shards the m*8 coding-bit columns of the GF matmul),
the stripe axis takes the rest — stripes are the abundant dimension in a
storage workload, exactly like PGs are for placement.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

STRIPE_AXIS = "stripe"
SHARD_AXIS = "shard"


def mesh_shape_for(n: int, max_shard: int = 2) -> Tuple[int, int]:
    """Factor n devices into (stripe, shard) with shard | n and small."""
    shard = 1
    for cand in range(min(max_shard, n), 0, -1):
        if n % cand == 0:
            shard = cand
            break
    return n // shard, shard


def make_mesh(n_devices: Optional[int] = None,
              devices: Optional[Sequence] = None,
              max_shard: int = 2) -> Mesh:
    """A (stripe, shard) mesh over *devices* (default: the default
    backend's).  Asking for more devices than that backend has raises:
    a mesh never moves off the chips onto virtual host devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devices)}; for a "
                "virtual mesh start the process with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n_devices}")
        devices = devices[:n_devices]
    dp, tp = mesh_shape_for(len(devices), max_shard)
    arr = np.array(devices).reshape(dp, tp)
    return Mesh(arr, (STRIPE_AXIS, SHARD_AXIS))
