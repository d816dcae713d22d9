"""pg-upmap optimizer — semantics-exact port of OSDMap::calc_pg_upmaps.

The reference balancer's upmap mode (src/osd/OSDMap.cc:3926, driven by
`osdmaptool --upmap` and mgr/balancer) iteratively finds the fullest
OSD whose deviation ratio exceeds the threshold and retargets ONE of
its PGs onto underfull OSDs via the constrained rule re-mapper
(crush/remap.py), restarting until nothing exceeds the threshold or
``max`` changes were made.

Decision-identical with the reference, which requires care beyond the
algorithm's shape:
  - float32 arithmetic for weights/targets/deviations (the reference
    uses C ``float``; threshold comparisons sit exactly on boundaries);
  - map/set orderings: pgs ascend (pool, seed); osds ascend;
    deviation ties break by ascending osd, and the fullest-first scan
    visits equal deviations in DESCENDING osd order (C++ multimap
    rbegin reverses insertion order within equal keys);
  - ``orig`` comes from the RAW mapping (no upmaps applied), while the
    per-iteration PG counts come from the upmap-applied ``up`` sets.

Byte-exact agreement with the reference's recorded `osdmaptool
--upmap` output is pinned by tests/test_osdmaptool_golden.py.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..crush.remap import get_rule_weight_osd_map, try_remap_rule
from ..crush.wrapper import do_rule_batch
from .osdmap import OSDMap
from .types import pg_t

NONE = 0x7FFFFFFF
F = np.float32


class PendingInc:
    """The slice of OSDMap::Incremental calc_pg_upmaps fills."""

    def __init__(self):
        self.new_pg_upmap_items: Dict[pg_t, List[Tuple[int, int]]] = {}
        self.old_pg_upmap_items: Set[pg_t] = set()


def _raw_all(m: OSDMap, pool_id: int, pool) -> List[List[int]]:
    """RAW mapping (no upmaps) for every pg of the pool, in one batch
    (the per-iteration loop only overlays upmap items on top of this,
    so it is computed once)."""
    size = pool.size
    ruleno = m.crush.find_rule(pool.crush_rule, pool.type, size)
    if ruleno < 0:
        return [[] for _ in range(pool.pg_num)]
    pps = [pool.raw_pg_to_pps(pg_t(pool_id, ps))
           for ps in range(pool.pg_num)]
    out, lens, _engine = do_rule_batch(
        m.crush.crush, ruleno, pps, size, m.osd_weight,
        m.crush.crush.choose_args.get(pool_id))
    rows = [out[i, :lens[i]].tolist() for i in range(len(pps))]
    for row in rows:
        m._remove_nonexistent_osds(pool, row)
    return rows


def try_pg_upmap(m: OSDMap, pg: pg_t, overfull: Set[int],
                 underfull: Sequence[int],
                 raw: Sequence[int]
                 ) -> Optional[Tuple[List[int], List[int]]]:
    """(OSDMap::try_pg_upmap)  ``raw`` is the pg's raw mapping
    (caller-cached _pg_to_raw_osds result).  Returns (orig, out) or
    None when no useful remap exists."""
    pool = m.get_pg_pool(pg.pool)
    if pool is None:
        return None
    rule = m.crush.find_rule(pool.crush_rule, pool.type, pool.size)
    if rule < 0:
        return None
    orig = list(raw)
    if not any(o in overfull for o in orig):
        return None
    out = try_remap_rule(m.crush, rule, pool.size, overfull, underfull,
                         orig)
    if out is None or out == orig:
        return None
    return orig, out


def calc_pg_upmaps(m: OSDMap, max_deviation_ratio: float, max: int,
                   only_pools: Optional[Set[int]] = None,
                   pending_inc: Optional[PendingInc] = None) -> int:
    """(OSDMap::calc_pg_upmaps)  Mutates ``m``'s pg_upmap_items like
    the reference mutates its tmp copy; returns changes made."""
    if pending_inc is None:
        pending_inc = PendingInc()
    if not only_pools:
        only_pools = set(m.pools.keys())
    max_dev = F(max_deviation_ratio)

    raw_cache: Dict[int, List[List[int]]] = {}
    for pool_id in sorted(only_pools):
        pool = m.pools.get(pool_id)
        if pool is not None:
            raw_cache[pool_id] = _raw_all(m, pool_id, pool)

    num_changed = 0
    while True:
        pgs_by_osd: Dict[int, List[pg_t]] = {}
        total_pgs = 0
        osd_weight_total = F(0.0)
        osd_weight: Dict[int, F] = {}
        for pool_id in sorted(m.pools.keys()):
            if pool_id not in only_pools:
                continue
            pool = m.pools[pool_id]
            raws = raw_cache[pool_id]
            for ps in range(pool.pg_num):
                pg = pg_t(pool_id, ps)
                row = raws[ps]
                if pg in m.pg_upmap or pg in m.pg_upmap_items:
                    row = m._apply_upmap(pool, pg, list(row))
                # the reference counts UP sets (pg_to_up_acting_osds):
                # down/nonexistent osds must not accumulate pgs
                for o in m._raw_to_up_osds(pool, list(row)):
                    if o != NONE:
                        pgs_by_osd.setdefault(o, []).append(pg)
            total_pgs += pool.size * pool.pg_num

            ruleno = m.crush.find_rule(pool.crush_rule, pool.type,
                                       pool.size)
            # no matching rule -> empty weight map (the reference's
            # unsigned-index ENOENT), while total_pgs still counted
            pmap = get_rule_weight_osd_map(m.crush, ruleno) \
                if ruleno >= 0 else {}
            for osd in sorted(pmap):
                # get_weightf: 16.16 in/out weight as C float
                wf = F(F(m.osd_weight[osd]) / F(0x10000)) \
                    if 0 <= osd < m.max_osd else F(0.0)
                adjusted = F(wf * F(pmap[osd]))
                osd_weight[osd] = F(osd_weight.get(osd, F(0.0))
                                    + adjusted)
                osd_weight_total = F(osd_weight_total + adjusted)
        for osd in sorted(osd_weight):
            pgs_by_osd.setdefault(osd, [])

        if osd_weight_total == 0:
            break
        pgs_per_weight = F(F(total_pgs) / osd_weight_total)

        # deviation per osd; multimap<float,int> == stable sort by
        # deviation over ascending-osd insertion order
        osd_deviation: Dict[int, F] = {}
        deviation_osd: List[Tuple[F, int]] = []
        overfull: Set[int] = set()
        for osd in sorted(pgs_by_osd):
            target = F(F(osd_weight.get(osd, F(0.0))) * pgs_per_weight)
            deviation = F(F(len(pgs_by_osd[osd])) - target)
            osd_deviation[osd] = deviation
            deviation_osd.append((deviation, osd))
            if float(deviation) >= 1.0:
                overfull.add(osd)
        deviation_osd.sort(key=lambda t: float(t[0]))  # stable

        underfull: List[int] = []
        for dev, osd in deviation_osd:
            if float(dev) >= -0.999:
                break
            underfull.append(osd)
        if not overfull or not underfull:
            break

        # fullest first; reversed(stable sort) == multimap rbegin
        # (equal deviations visited in descending osd order)
        restart = False
        for dev, osd in reversed(deviation_osd):
            target = F(F(osd_weight.get(osd, F(0.0))) * pgs_per_weight)
            assert target > 0
            if F(dev / target) < max_dev:
                break
            num_to_move = int(dev)       # trunc toward zero
            if num_to_move < 1:
                break

            pgs = pgs_by_osd[osd]        # ascending (pool, seed)

            # drop an existing remap that lands on this overfull osd
            for pg in pgs:
                items = m.pg_upmap_items.get(pg)
                if items is not None:
                    for _frm, to in items:
                        if to == osd:
                            del m.pg_upmap_items[pg]
                            pending_inc.old_pg_upmap_items.add(pg)
                            num_changed += 1
                            restart = True
                            break   # entry gone; scanning on would
                            #         re-delete (the reference erases
                            #         mid-iteration, which is UB there)
                if restart:
                    break
            if restart:
                break

            for pg in pgs:
                if pg in m.pg_upmap or pg in m.pg_upmap_items:
                    continue
                r = try_pg_upmap(m, pg, overfull, underfull,
                                 raw_cache[pg.pool][pg.ps])
                if r is None:
                    continue
                orig, out = r
                if len(orig) != len(out):
                    continue
                assert orig != out
                rmi = [(orig[i], out[i]) for i in range(len(out))
                       if orig[i] != out[i]]
                m.pg_upmap_items[pg] = rmi
                pending_inc.new_pg_upmap_items[pg] = list(rmi)
                restart = True
                num_changed += 1
                break
            if restart:
                break

        if not restart:
            break
        max -= 1
        if max == 0:
            break
    return num_changed
