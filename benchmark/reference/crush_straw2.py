"""Plain CRUSH reference for a two-level straw2 map, vectorised over PGs.

It follows upstream Ceph's ``src/crush/mapper.c`` and ``hash.c``
(rjenkins1 hash, ``crush_ln``, ``bucket_straw2_choose``,
``crush_choose_firstn`` with ``recurse_to_leaf``, ``is_out``) for the one
rule shape the placement configuration states: ``take root; chooseleaf
firstn <size> type host; emit`` over a straw2 root of straw2 hosts, with
the tunables of the configuration file.  It imports nothing of the
program: the map comes from the configuration's numbers, the reweight
vector from the benchmark's own churn.

Rows are NONE-padded like the program's ``PoolMapping.up``.  Lanes are
processed in blocks so the peak stays a few hundred MiB at 100k PGs.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from .crush_ln_table import LL_TBL, RH_LH_TBL

NONE = 0x7FFFFFFF
_SEED = np.uint32(1315423911)
_U = np.uint32


def _mix(a, b, c):
    a = a - b; a = a - c; a = a ^ (c >> _U(13))
    b = b - c; b = b - a; b = b ^ (a << _U(8))
    c = c - a; c = c - b; c = c ^ (b >> _U(13))
    a = a - b; a = a - c; a = a ^ (c >> _U(12))
    b = b - c; b = b - a; b = b ^ (a << _U(16))
    c = c - a; c = c - b; c = c ^ (b >> _U(5))
    a = a - b; a = a - c; a = a ^ (c >> _U(3))
    b = b - c; b = b - a; b = b ^ (a << _U(10))
    c = c - a; c = c - b; c = c ^ (b >> _U(15))
    return a, b, c


def hash2(a, b):
    """crush_hash32_rjenkins1_2."""
    a, b = np.asarray(a, np.uint32), np.asarray(b, np.uint32)
    h = _SEED ^ a ^ b
    x = np.full_like(h, 231232)
    y = np.full_like(h, 1232)
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash3(a, b, c):
    """crush_hash32_rjenkins1_3."""
    a, b, c = (np.asarray(v, np.uint32) for v in (a, b, c))
    a, b, c = np.broadcast_arrays(a, b, c)
    h = _SEED ^ a ^ b ^ c
    x = np.full_like(h, 231232)
    y = np.full_like(h, 1232)
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


def _crush_ln_all() -> np.ndarray:
    """crush_ln(u) for every 16-bit u, as int64 (2^44 * log2(u + 1))."""
    rh_lh = np.asarray(RH_LH_TBL, dtype=np.uint64)
    ll = np.asarray(LL_TBL, dtype=np.uint64)
    out = np.empty(0x10000, dtype=np.int64)
    for u in range(0x10000):
        x = u + 1
        iexpon = 15
        if not x & 0x18000:
            bits = 16 - x.bit_length()
            x <<= bits
            iexpon = 15 - bits
        index1 = (x >> 8) << 1
        rh = int(rh_lh[index1 - 256])
        lh = int(rh_lh[index1 + 1 - 256])
        xl64 = (x * rh) >> 48
        result = iexpon << 44
        result += (lh + int(ll[xl64 & 0xFF])) >> 4
        out[u] = result
    return out


_LN = None


def _ln_table() -> np.ndarray:
    global _LN
    if _LN is None:
        _LN = _crush_ln_all()
    return _LN


def straw2_choose(ids: np.ndarray, weights: np.ndarray, x: np.ndarray,
                  r: np.ndarray) -> np.ndarray:
    """bucket_straw2_choose for one bucket per lane.

    ids, weights: (L, n) items and 16.16 crush weights of each lane's
    bucket; x, r: (L,).  Returns the chosen index into the row."""
    u = hash3(x[:, None], ids.astype(np.int64).astype(np.uint32),
              r[:, None].astype(np.uint32)) & _U(0xFFFF)
    ln = _ln_table()[u] - np.int64(0x1000000000000)
    w = weights.astype(np.int64)
    # div64_s64 truncates toward zero; ln <= 0
    draw = np.where(w > 0, -((-ln) // np.maximum(w, 1)),
                    np.iinfo(np.int64).min)
    return np.argmax(draw, axis=1)


def is_out(weight: np.ndarray, item: np.ndarray, x: np.ndarray) -> np.ndarray:
    """mapper.c is_out for device items, vectorised."""
    w = weight[item].astype(np.int64)
    h = (hash2(x, item.astype(np.uint32)) & _U(0xFFFF)).astype(np.int64)
    return (w < 0x10000) & ((w == 0) | (h >= w))


class TwoLevelStraw2:
    """A straw2 root over straw2 hosts of devices, from plain numbers."""

    def __init__(self, spec: Dict):
        self.n_hosts = int(spec["hosts"])
        self.per_host = int(spec["osds_per_host"])
        self.host_ids = np.asarray(
            [int(spec["first_host_id"]) - h for h in range(self.n_hosts)],
            dtype=np.int64)
        self.osd_weight = int(spec["osd_crush_weight"])
        self.host_weight = self.osd_weight * self.per_host
        t = spec["tunables"]
        self.choose_tries = int(t["choose_total_tries"]) + 1
        self.vary_r = int(t["chooseleaf_vary_r"])
        self.stable = int(t["chooseleaf_stable"])
        if int(t["chooseleaf_descend_once"]) != 1 or \
                int(t["choose_local_tries"]) or \
                int(t["choose_local_fallback_tries"]) or self.stable != 1:
            raise ValueError("the reference covers the jewel tunables only")
        self.recurse_tries = 1      # chooseleaf_descend_once

    def pps(self, pool_id: int, pg_num: int) -> np.ndarray:
        """raw_pg_to_pps for a HASHPSPOOL pool with pgp_num == pg_num."""
        ps = np.arange(pg_num, dtype=np.uint32)
        return hash2(ps, np.full_like(ps, pool_id))

    def map(self, xs: np.ndarray, weight: np.ndarray, numrep: int,
            block: int = 16384) -> np.ndarray:
        out = np.full((len(xs), numrep), NONE, dtype=np.int32)
        for lo in range(0, len(xs), block):
            out[lo:lo + block] = self._map_block(
                xs[lo:lo + block].astype(np.uint32), weight, numrep)
        return out

    def _map_block(self, x: np.ndarray, weight: np.ndarray,
                   numrep: int) -> np.ndarray:
        L = len(x)
        hosts = np.full((L, numrep), NONE, dtype=np.int64)
        leaves = np.full((L, numrep), NONE, dtype=np.int64)
        outpos = np.zeros(L, dtype=np.int64)
        root_ids = self.host_ids
        root_w = np.full(self.n_hosts, self.host_weight, dtype=np.int64)
        leaf_w = np.full(self.per_host, self.osd_weight, dtype=np.int64)
        rows = np.arange(L)
        for rep in range(numrep):       # chooseleaf_stable: rep from 0
            ftotal = np.zeros(L, dtype=np.int64)
            todo = rows.copy()
            while len(todo):
                xt = x[todo]
                r = rep + ftotal[todo]
                hidx = straw2_choose(np.broadcast_to(root_ids, (len(todo),
                                                                self.n_hosts)),
                                     np.broadcast_to(root_w, (len(todo),
                                                              self.n_hosts)),
                                     xt, r)
                host = root_ids[hidx]
                pos = outpos[todo]
                prior = np.arange(numrep)[None, :] < pos[:, None]
                collide = np.any(prior & (hosts[todo] == host[:, None]),
                                 axis=1)
                # recurse to the leaf: one try (descend_once), r' = r
                sub_r = r >> (self.vary_r - 1) if self.vary_r else \
                    np.zeros_like(r)
                leaf_ids = (hidx[:, None] * self.per_host
                            + np.arange(self.per_host)[None, :])
                lidx = straw2_choose(leaf_ids,
                                     np.broadcast_to(leaf_w, leaf_ids.shape),
                                     xt, sub_r)
                leaf = leaf_ids[np.arange(len(todo)), lidx]
                leaf_collide = np.any(
                    prior & (leaves[todo] == leaf[:, None]), axis=1)
                reject = ~collide & (leaf_collide | is_out(weight, leaf, xt))
                ok = ~collide & ~reject
                won = todo[ok]
                hosts[won, outpos[won]] = host[ok]
                leaves[won, outpos[won]] = leaf[ok]
                outpos[won] += 1
                failed = todo[~ok]
                ftotal[failed] += 1
                # a rep that ran out of tries is skipped (skip_rep)
                todo = failed[ftotal[failed] < self.choose_tries]
        return leaves.astype(np.int32)
