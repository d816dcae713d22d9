"""The CRUSH map compiler and the rjenkins hashes of the device mapper.

``compile_map`` turns a straw2 CrushMap (plus ``choose_args``) into the
dense tensors ops/crush_fast.py's candidate tables read: per-bucket item,
hash-id and weight tables padded to the largest fanout, indexed by
``-1 - id``.  ``hash32_2``/``hash32_3`` are crush_hash32_rjenkins1_2/3
(reference src/crush/hash.c) in uint32 lanes.  Importing this module
flips no jax flag and starts no backend.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax.numpy as jnp

from ..crush.constants import CRUSH_BUCKET_STRAW2, CRUSH_ITEM_NONE
from ..crush.types import CrushMap

_SEED = np.uint32(1315423911)
_PAD1 = np.uint32(231232)
_PAD2 = np.uint32(1232)


# ---- rjenkins in uint32 lanes (crush/hash.c) ------------------------------

def _mix(a, b, c):
    a = a - b; a = a - c; a = a ^ (c >> 13)
    b = b - c; b = b - a; b = b ^ (a << 8)
    c = c - a; c = c - b; c = c ^ (b >> 13)
    a = a - b; a = a - c; a = a ^ (c >> 12)
    b = b - c; b = b - a; b = b ^ (a << 16)
    c = c - a; c = c - b; c = c ^ (b >> 5)
    a = a - b; a = a - c; a = a ^ (c >> 3)
    b = b - c; b = b - a; b = b ^ (a << 10)
    c = c - a; c = c - b; c = c ^ (b >> 15)
    return a, b, c


def hash32_2(a, b):
    a = a.astype(jnp.uint32); b = b.astype(jnp.uint32)
    h = _SEED ^ a ^ b
    x = jnp.broadcast_to(_PAD1, a.shape)
    y = jnp.broadcast_to(_PAD2, a.shape)
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash32_3(a, b, c):
    a = a.astype(jnp.uint32); b = b.astype(jnp.uint32)
    c = c.astype(jnp.uint32)
    a, b, c = jnp.broadcast_arrays(a, b, c)
    h = _SEED ^ a ^ b ^ c
    x = jnp.broadcast_to(_PAD1, h.shape)
    y = jnp.broadcast_to(_PAD2, h.shape)
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


# ---- compiled map ---------------------------------------------------------

class CompiledCrushMap:
    """Dense-tensor form of a straw2 CrushMap (+choose_args) for the device.

    Buckets are indexed by ``-1 - id``.  ``weights`` carries the per-position
    straw2 weight sets (crush.h:273 crush_choose_arg); position 0 is the
    plain item_weights when no choose_args are attached.
    """

    def __init__(self, m: CrushMap,
                 choose_args: Optional[Sequence] = None):
        nb = len(m.buckets)
        S = max((b.size for b in m.buckets if b is not None), default=1)
        S = max(S, 1)
        items = np.full((nb, S), CRUSH_ITEM_NONE, dtype=np.int32)
        hash_ids = np.zeros((nb, S), dtype=np.int32)
        sizes = np.zeros(nb, dtype=np.int32)
        npos = 1
        if choose_args is not None:
            for arg in choose_args:
                if arg is not None and arg.weight_set:
                    npos = max(npos, len(arg.weight_set))
        weights = np.zeros((npos, nb, S), dtype=np.uint32)
        for bi, b in enumerate(m.buckets):
            if b is None:
                continue
            if b.size and b.alg != CRUSH_BUCKET_STRAW2:
                raise ValueError("device mapper supports straw2 buckets only")
            sizes[bi] = b.size
            items[bi, :b.size] = b.items
            hash_ids[bi, :b.size] = b.items
            for it in b.items:
                if it >= 0 and it >= m.max_devices:
                    raise ValueError("bucket item beyond max_devices")
                if it < 0 and m.bucket(it) is None:
                    raise ValueError("dangling bucket reference")
            w = np.asarray(b.item_weights, dtype=np.uint32)
            weights[:, bi, :b.size] = w[None, :]
            arg = None
            if choose_args is not None and bi < len(choose_args):
                arg = choose_args[bi]
            if arg is not None:
                if arg.weight_set:
                    for p in range(npos):
                        ws = arg.weight_set[min(p, len(arg.weight_set) - 1)]
                        weights[p, bi, :b.size] = np.asarray(
                            ws.weights, dtype=np.uint32)
                if arg.ids:
                    hash_ids[bi, :b.size] = arg.ids
        if m.choose_local_tries or m.choose_local_fallback_tries:
            raise ValueError("device mapper requires bobtail+ tunables "
                             "(choose_local_*_tries == 0)")
        self.map = m
        self.nbuckets = nb
        self.npos = npos
        self.items = jnp.asarray(items)
        self.hash_ids = jnp.asarray(hash_ids)
        self.sizes = jnp.asarray(sizes)
        self.weights = jnp.asarray(weights)
        # f32 reciprocals for the f32 draw; 0 marks zero-weight lanes
        with np.errstate(divide="ignore"):
            inv = np.where(weights > 0, 1.0 / weights.astype(np.float64),
                           0.0)
        self.inv_weights = jnp.asarray(inv.astype(np.float32))
        self.lane = jnp.arange(S, dtype=jnp.int32)


def compile_map(m: CrushMap, choose_args=None) -> CompiledCrushMap:
    """Host-side compilation; raises ValueError if unsupported on device."""
    return CompiledCrushMap(m, choose_args)
