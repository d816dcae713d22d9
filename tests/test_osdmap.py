"""OSDMap layer: object→PG, the mapping pipeline, incrementals, batch cache.

Mirrors the reference's test/osd/TestOSDMap.cc checks: up/acting through
upmap, pg_temp, primary affinity, down/out OSDs; plus batch-vs-scalar
equality for OSDMapMapping (the device/native/host batch backends must agree
with pg_to_up_acting_osds everywhere).
"""
import numpy as np
import pytest

from ceph_tpu.crush import CRUSH_BUCKET_STRAW2, CRUSH_ITEM_NONE
from ceph_tpu.osdmap import (
    Incremental, OSDMap, OSDMapMapping, TYPE_ERASURE, TYPE_REPLICATED,
    pg_pool_t, pg_t,
)
from ceph_tpu.utils import ceph_str_hash_rjenkins


def build_osdmap(n_hosts=5, per_host=4, pg_num=64, ec=False):
    m = OSDMap()
    m.epoch = 1
    n = n_hosts * per_host
    m.set_max_osd(n)
    cw = m.crush
    cw.set_type_name(1, "host")
    cw.set_type_name(10, "root")
    host_ids = []
    for h in range(n_hosts):
        osds = list(range(h * per_host, (h + 1) * per_host))
        hid = cw.add_bucket(CRUSH_BUCKET_STRAW2, 1, f"host{h}", osds,
                            [0x10000] * per_host, id=-(h + 2))
        host_ids.append(hid)
    cw.add_bucket(CRUSH_BUCKET_STRAW2, 10, "default", host_ids,
                  [0x10000 * per_host] * n_hosts, id=-1)
    for i in range(n):
        m.set_osd(i, up=True)
    if ec:
        rno = cw.add_simple_rule("ecrule", "default", "host", mode="indep",
                                 rule_type=TYPE_ERASURE)
        cw.set_rule_mask_max_size(rno, 10)
        pool = pg_pool_t(type=TYPE_ERASURE, size=6, min_size=5,
                         crush_rule=rno, pg_num=pg_num, pgp_num=pg_num)
    else:
        rno = cw.add_simple_rule("replicated_rule", "default", "host",
                                 mode="firstn")
        pool = pg_pool_t(type=TYPE_REPLICATED, size=3, min_size=2,
                         crush_rule=rno, pg_num=pg_num, pgp_num=pg_num)
    pid = m.add_pool("rbd", pool)
    return m, pid, n


def test_object_to_pg_stable():
    m, pid, _ = build_osdmap()
    pg = m.map_to_pg(pid, "foo")
    assert pg.pool == pid
    assert pg.ps == ceph_str_hash_rjenkins("foo")
    # namespace changes the hash
    pg2 = m.map_to_pg(pid, "foo", nspace="ns")
    assert pg2.ps != pg.ps


def test_basic_mapping_properties():
    m, pid, n = build_osdmap()
    pool = m.get_pg_pool(pid)
    seen = set()
    for ps in range(pool.pg_num):
        up, upp, acting, actp = m.pg_to_up_acting_osds(pg_t(pid, ps))
        assert len(up) == 3
        assert len(set(up)) == 3
        # one per host
        hosts = {o // 4 for o in up}
        assert len(hosts) == 3
        assert upp == up[0]
        assert acting == up
        seen.update(up)
    assert len(seen) > n // 2


def test_down_osd_drops_from_up():
    m, pid, _ = build_osdmap()
    target = None
    for ps in range(64):
        up, _, _, _ = m.pg_to_up_acting_osds(pg_t(pid, ps))
        if 0 in up:
            target = ps
            break
    assert target is not None
    m.set_osd(0, up=False)  # down but still in
    up, _, _, _ = m.pg_to_up_acting_osds(pg_t(pid, target))
    assert 0 not in up


def test_out_osd_remapped():
    m, pid, _ = build_osdmap()
    pgs_with_0 = [ps for ps in range(64)
                  if 0 in m.pg_to_up_acting_osds(pg_t(pid, ps))[0]]
    m.osd_weight[0] = 0  # marked out
    for ps in pgs_with_0:
        up, _, _, _ = m.pg_to_up_acting_osds(pg_t(pid, ps))
        assert 0 not in up
        assert len(up) == 3


def test_pg_temp_overrides_acting():
    m, pid, _ = build_osdmap()
    pg = pg_t(pid, 5)
    up, upp, _, _ = m.pg_to_up_acting_osds(pg)
    tmp = [o for o in range(12, 15)]
    m.pg_temp[pg] = tmp
    up2, upp2, acting, actp = m.pg_to_up_acting_osds(pg)
    assert up2 == up and upp2 == upp
    assert acting == tmp
    assert actp == tmp[0]
    m.primary_temp[pg] = tmp[2]
    *_, actp2 = m.pg_to_up_acting_osds(pg)
    assert actp2 == tmp[2]


def test_pg_upmap_and_items():
    m, pid, _ = build_osdmap()
    pg = pg_t(pid, 9)
    up, *_ = m.pg_to_up_acting_osds(pg)
    # full upmap
    explicit = [1, 6, 13]
    m.pg_upmap[pg] = explicit
    up2, *_ = m.pg_to_up_acting_osds(pg)
    assert up2 == explicit
    del m.pg_upmap[pg]
    # item remap: swap first to some unused osd
    src = up[0]
    dst = next(o for o in range(m.max_osd) if o not in up)
    m.pg_upmap_items[pg] = [(src, dst)]
    up3, *_ = m.pg_to_up_acting_osds(pg)
    assert dst in up3 and src not in up3
    # remap to an out osd is ignored
    m.osd_weight[dst] = 0
    up4, *_ = m.pg_to_up_acting_osds(pg)
    assert up4 == up
    m.osd_weight[dst] = 0x10000
    # a pg_upmap with an out target voids the whole override, including
    # pg_upmap_items (OSDMap.cc:1971 early return)
    m.osd_weight[1] = 0
    m.pg_upmap[pg] = [1, 6, 13]
    up5, *_ = m.pg_to_up_acting_osds(pg)
    assert up5 == up


def test_primary_affinity_shifts_lead():
    m, pid, _ = build_osdmap()
    m.set_primary_affinity(0, 0)  # never primary
    for ps in range(64):
        up, upp, _, _ = m.pg_to_up_acting_osds(pg_t(pid, ps))
        if 0 in up:
            assert upp != 0
            assert up[0] == upp  # replicated pools shift primary to front


def test_incremental_roundtrip():
    m, pid, _ = build_osdmap()
    inc = Incremental(epoch=2)
    inc.new_up[3] = False
    inc.new_weight[7] = 0
    m.apply_incremental(inc)
    assert m.epoch == 2
    assert m.is_down(3)
    assert m.is_out(7)
    inc2 = Incremental(epoch=3)
    inc2.new_pg_temp[pg_t(pid, 1)] = [2, 6, 10]
    m.apply_incremental(inc2)
    assert m.pg_temp[pg_t(pid, 1)] == [2, 6, 10]


@pytest.mark.parametrize("ec", [False, True])
def test_batch_mapping_matches_scalar(ec):
    m, pid, n = build_osdmap(pg_num=128, ec=ec)
    # sprinkle state: down, out, reweighted, affinity, overrides
    m.set_osd(2, up=False)
    m.osd_weight[5] = 0
    m.osd_weight[9] = 0x8000
    m.set_primary_affinity(1, 0x4000)
    m.pg_temp[pg_t(pid, 3)] = [15, 16, 17]
    m.primary_temp[pg_t(pid, 7)] = 11
    if not ec:
        m.pg_upmap_items[pg_t(pid, 11)] = [(0, 19)]
    mapping = OSDMapMapping()
    mapping.update(m)
    for ps in range(128):
        pg = pg_t(pid, ps)
        up, upp, acting, actp = m.pg_to_up_acting_osds(pg)
        bup, bupp, bacting, bactp = mapping.get(pg)
        assert bup == up, (ps, bup, up)
        assert bupp == upp, ps
        assert bacting == acting, (ps, bacting, acting)
        assert bactp == actp, ps


def test_batch_mapping_host_fallback_agrees(monkeypatch):
    from ceph_tpu import native
    m, pid, n = build_osdmap(pg_num=64)
    dev = OSDMapMapping(use_device=True)
    host = OSDMapMapping(use_device=False)
    dev.update(m)
    # no native library: the interpreter answers
    monkeypatch.setattr(native, "native_available", lambda: False)
    host.update(m)
    for ps in range(64):
        assert dev.get(pg_t(pid, ps)) == host.get(pg_t(pid, ps))
    assert dev.last_backend[pid] == "device"
    assert host.last_backend[pid] == "host"
