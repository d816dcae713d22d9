"""Topology discovery: ONE 1-D batch-axis mesh over the addressable chips.

The dispatch mesh is deliberately one-dimensional: a flushed batch of
coalesced EC requests is (S, k, C) with stripes as the abundant axis,
so ``NamedSharding(mesh, PartitionSpec("batch"))`` over the stripe rows
spreads the whole flush across every chip with zero collectives on the
forward path (the SNIPPETS.md [2] shape).  The 2-D ``(stripe, shard)``
mesh in ``parallel/mesh.py`` stays the research surface for
column-sharded decode; the dispatch runtime wants the simplest layout
that makes "more traffic" become "more chips".

CPU smoke rides the virtual host platform
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) because it is
then the default backend; discovery never swaps one backend for another.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

BATCH_AXIS = "batch"


def addressable_devices(n: Optional[int] = None) -> List:
    """The devices a dispatch mesh may span: whatever the default
    backend exposes, full stop.

    Requesting more than exist CLAMPS (batch_mesh) — the mesh must
    never silently relocate off an accelerator onto virtual host CPUs
    because an operator over-asked.  A multi-device CPU smoke mesh is
    an environment contract, not a runtime trick:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` must be set
    at process start (tests/conftest.py and ``bench --smoke`` both do;
    a late ``jax_num_cpu_devices`` config flip cannot work here — the
    cpu backend is already initialized the moment the default platform
    is cpu, and the pinned jax does not expose the knob at all)."""
    import jax
    del n   # the request is a clamp bound, not a growth target
    return list(jax.devices())


def batch_mesh(n: Optional[int] = None):
    """A 1-D ``("batch",)`` mesh over *n* devices (``None``/-1 = all
    addressable).  Requests beyond what the process can see CLAMP to
    the available device count rather than raising: capacity is an
    operator knob (``ec_mesh_chips``) and a misconfigured count must
    degrade to a smaller mesh, never take the write path down."""
    from jax.sharding import Mesh
    want = None if n is None or n < 0 else max(int(n), 1)
    devices = addressable_devices(want)
    if want is not None:
        devices = devices[:want]
    return Mesh(np.array(devices), (BATCH_AXIS,))
