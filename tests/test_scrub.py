"""Scrub-lite: background crc consistency checking + repair by decode.

Models the reference scrub path (src/osd/PG.cc scrub, ScrubStore.cc,
ECUtil.cc:161-207 HashInfo): a background pass compares every stored
shard against its crc (EC) or cross-replica digests (replicated), turns
inconsistencies into missing entries, and lets recovery repair them —
with no client read involved.
"""
import numpy as np

from ceph_tpu.cluster import MiniCluster
from ceph_tpu.osd.pg_log import PG_META_OID


def payload(n=20000, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


def _corrupt_one_shard(c, oid):
    """Flip a byte of one stored EC shard; returns (osd_id, cid, before)."""
    for osd in c.osds.values():
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == oid and ho.shard >= 0:
                    obj = osd.store.colls[cid][ho]
                    before = bytes(obj.data)
                    obj.data = bytearray(obj.data)
                    obj.data[7] ^= 0x5A
                    return osd.osd_id, cid, ho, before
    raise AssertionError("no shard found")


def test_scrub_detects_and_repairs_bitrot_without_client_read():
    c = MiniCluster(n_osds=6)
    c.create_ec_pool("p", k=4, m=2, pg_num=4, plugin="tpu")
    cl = c.client("client.s")
    data = payload(seed=3)
    assert cl.write_full("p", "obj", data) == 0
    osd_id, cid, ho, before = _corrupt_one_shard(c, "obj")
    reads_before = sum(o.perf["op_r"] for o in c.osds.values())
    c.scrub()
    c.network.pump()
    c.run_recovery()
    # no client read happened
    assert sum(o.perf["op_r"] for o in c.osds.values()) == reads_before
    # the corrupt shard was rewritten byte-exact
    after = bytes(c.osds[osd_id].store.colls[cid][ho].data)
    assert after == before, "scrub repair must restore the shard"
    assert cl.read("p", "obj") == data


def test_scrub_detects_missing_shard():
    """An object silently deleted from one shard at rest (operator error,
    disk eating files) comes back after a scrub."""
    c = MiniCluster(n_osds=6)
    c.create_ec_pool("p", k=3, m=2, pg_num=2, plugin="tpu")
    cl = c.client("client.m")
    assert cl.write_full("p", "obj", payload(seed=4)) == 0
    # delete one shard's copy at rest
    for osd in c.osds.values():
        done = False
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in list(osd.store.list_objects(cid)):
                if ho.oid == "obj" and ho.shard >= 0:
                    del osd.store.colls[cid][ho]
                    done = True
                    break
            if done:
                break
        if done:
            break
    c.scrub()
    c.network.pump()
    c.run_recovery()
    holders = [1 for o in c.osds.values()
               for cid in o.store.list_collections()
               if "_meta" not in cid
               for ho in o.store.list_objects(cid) if ho.oid == "obj"]
    assert len(holders) == 5  # k+m shards restored
    assert cl.read("p", "obj") == payload(seed=4)


def test_scrub_replicated_digest_mismatch():
    c = MiniCluster(n_osds=5)
    c.create_replicated_pool("r", size=3, pg_num=2)
    cl = c.client("client.r")
    data = payload(5000, seed=6)
    assert cl.write_full("r", "ro", data) == 0
    # corrupt a NON-primary replica (the primary's copy is scrub-auth)
    _, primary = cl._calc_target(cl.lookup_pool("r"), "ro")
    for osd in c.osds.values():
        if osd.osd_id == primary:
            continue
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == "ro":
                    obj = osd.store.colls[cid][ho]
                    obj.data = bytearray(obj.data)
                    obj.data[3] ^= 0xFF
                    victim = osd.osd_id
                    break
    c.scrub()
    c.network.pump()
    c.run_recovery()
    for osd in c.osds.values():
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == "ro":
                    assert bytes(osd.store.read(cid, ho)) == data, \
                        f"osd.{osd.osd_id} copy still corrupt"


def test_scrub_clean_cluster_is_noop():
    c = MiniCluster(n_osds=6)
    c.create_ec_pool("p", k=3, m=2, pg_num=2, plugin="tpu")
    cl = c.client("client.n")
    for i in range(3):
        assert cl.write_full("p", f"o{i}", payload(seed=i)) == 0
    before = sum(o.perf["recovery_push"] for o in c.osds.values())
    c.scrub()
    after = sum(o.perf["recovery_push"] for o in c.osds.values())
    assert after == before
    states = [pg.state for o in c.osds.values()
              for pg in o.pgs.values() if pg.is_primary()]
    assert all(s == "active" for s in states)
