"""An LRC pool (upstream's LRCprofile, plugin=lrc k=4 m=2 l=3) through
the served path, held to the plain kml reference
(benchmark/reference/lrc_kml.py, which imports nothing of the program):
every stored shard at its physical position, and degraded reads that
repair one lost data chunk from its local group and two from the
global layer."""
import numpy as np
import pytest

from benchmark.reference import lrc_kml
from ceph_tpu.cluster import MiniCluster
from ceph_tpu.ec.lrc import (l_lrc_global_repairs, l_lrc_local_repairs,
                             lrc_perf_counters)
from ceph_tpu.osd.ec_backend import ECBackend

K, M, L, SU = 4, 2, 3, 4096
POOL = "lrc"
DATA_POS = (0, 1, 4, 5)           # the D positions of the mapping DD__DD__


def _cluster():
    c = MiniCluster(n_osds=10)
    c.create_ec_pool(POOL, k=K, m=M, pg_num=8, plugin="lrc",
                     extra_profile={"l": str(L)})
    return c


def _acting(c, oid):
    osdmap = c.mon.osdmap
    pid = osdmap.lookup_pg_pool_name(POOL)
    pool = osdmap.pools[pid]
    return list(osdmap.pg_to_up_acting_osds(
        pool.raw_pg_to_pg(osdmap.map_to_pg(pid, oid)))[2])


def _stored(c, oid):
    """physical shard -> (OSD holding it, its bytes)"""
    out = {}
    for osd_id, osd in c.osds.items():
        for cid in osd.store.list_collections():
            for ho in osd.store.list_objects(cid):
                if ho.oid == oid:
                    out[int(ho.shard)] = (osd_id,
                                          bytes(osd.store.read(cid, ho)))
    return out


@pytest.mark.parametrize("size", [3 * K * SU, 50_000],
                         ids=["aligned", "unaligned"])
@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_stored_shards_equal_the_reference(seed, size):
    c = _cluster()
    body = np.random.default_rng(seed).bytes(size)
    client = c.client("client.t")
    assert client.write_full(POOL, "obj", body) == 0
    want = lrc_kml.all_chunks(body, K, M, L, SU)
    acting = _acting(c, "obj")
    stored = _stored(c, "obj")
    assert sorted(stored) == list(range(8))
    for j in range(8):
        osd, got = stored[j]
        assert osd == acting[j], j
        assert got == want[j].tobytes(), f"shard {j}"
    assert client.read(POOL, "obj") == body


@pytest.fixture(scope="module")
def written():
    c = _cluster()
    client = c.client("client.t")
    bodies = {}
    for i in range(4):
        body = np.random.default_rng([7, i]).bytes(2 * K * SU + 1000 * i)
        assert client.write_full(POOL, f"obj{i}", body) == 0
        bodies[f"obj{i}"] = body
    return c, client, bodies


@pytest.fixture
def sub_reads(monkeypatch):
    """(oid, physical shard) of every data sub-read the OSDs answer."""
    seen = []
    orig = ECBackend.handle_sub_read

    def handle_sub_read(self, msg, store):
        if not msg.attrs_only:
            seen.append((msg.oid, msg.shard))
        return orig(self, msg, store)

    monkeypatch.setattr(ECBackend, "handle_sub_read", handle_sub_read)
    return seen


def _read_with_down(c, client, oid, positions, seen):
    acting = _acting(c, oid)
    victims = [acting[p] for p in positions]
    for v in victims:
        c.kill_osd(v)
        c.mark_osd_down(v)
    pc = lrc_perf_counters()
    before = (pc.get(l_lrc_local_repairs), pc.get(l_lrc_global_repairs))
    del seen[:]
    try:
        got = client.read(POOL, oid)
    finally:
        for v in victims:
            c.revive_osd(v)
    after = (pc.get(l_lrc_local_repairs), pc.get(l_lrc_global_repairs))
    read = sorted(s for o, s in seen if o == oid)
    return got, read, (after[0] - before[0], after[1] - before[1])


@pytest.mark.parametrize("lost", DATA_POS)
def test_one_lost_data_chunk_is_repaired_from_its_group(written, sub_reads,
                                                        lost):
    c, client, bodies = written
    oid = f"obj{DATA_POS.index(lost)}"
    got, read, (local, global_) = _read_with_down(c, client, oid, [lost],
                                                  sub_reads)
    assert got == bodies[oid]
    group = range(0, 4) if lost < 4 else range(4, 8)
    other = [p for p in DATA_POS if p not in group]
    # the group's survivors (its other data chunk and its global
    # parity), its local parity, and the other group's data chunks
    assert read == sorted([p for p in group if p != lost] + other)
    assert len(read) == 5
    assert (local, global_) == (1, 0)


@pytest.mark.parametrize("group", [0, 1])
def test_two_lost_in_one_group_fall_back_to_the_global_layer(
        written, sub_reads, group):
    c, client, bodies = written
    oid = f"obj{2 + group}"
    lost = [4 * group, 4 * group + 1]
    got, read, (local, global_) = _read_with_down(c, client, oid, lost,
                                                  sub_reads)
    assert got == bodies[oid]
    # the global layer's survivors: both global parities and the other
    # group's data
    assert read == sorted([2, 6] + [p for p in DATA_POS if p not in lost])
    assert (local, global_) == (0, 1)
