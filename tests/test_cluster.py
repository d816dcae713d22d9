"""vstart-lite integration: EC pool IO, degraded reads, recovery, thrashing.

Models the reference's standalone cluster tests
(qa/standalone/erasure-code/test-erasure-code.sh: build a cluster, create
an EC pool with crush-failure-domain=osd, write/read objects, kill OSDs)
plus the Thrasher loop behaviors (qa/tasks/ceph_manager.py).
"""
import numpy as np
import pytest

from ceph_tpu.cluster import MiniCluster


def payload(n=40000, seed=7):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


@pytest.fixture(scope="module")
def ec_cluster():
    c = MiniCluster(n_osds=7)
    c.create_ec_pool("ecpool", k=4, m=2, pg_num=16, plugin="tpu",
                     failure_domain="host")
    return c


def test_ec_write_read_roundtrip(ec_cluster):
    c = ec_cluster
    client = c.client("client.rt")
    data = payload()
    assert client.write_full("ecpool", "obj1", data) == 0
    assert client.read("ecpool", "obj1") == data
    assert client.stat("ecpool", "obj1") == len(data)


def test_object_chunks_land_on_distinct_osds(ec_cluster):
    c = ec_cluster
    client = c.client("client.place")
    client.write_full("ecpool", "obj2", payload(seed=2))
    holders = []
    for osd in c.osds.values():
        for cid in osd.store.list_collections():
            for ho in osd.store.list_objects(cid):
                if ho.oid == "obj2":
                    holders.append((osd.osd_id, ho.shard))
    assert len(holders) == 6           # k+m shards
    assert len({h[0] for h in holders}) == 6  # all on distinct osds


def test_degraded_read_after_failure_detection(ec_cluster):
    c = ec_cluster
    client = c.client("client.deg")
    data = payload(seed=3)
    client.write_full("ecpool", "obj3", data)
    # find a shard holder that is not any pg primary we need, kill it
    victim = None
    for osd in c.osds.values():
        for cid in osd.store.list_collections():
            for ho in osd.store.list_objects(cid):
                if ho.oid == "obj3":
                    victim = osd.osd_id
        if victim is not None:
            break
    c.kill_osd(victim)
    # heartbeats detect the silent osd and the mon publishes a new epoch
    for _ in range(6):
        c.tick(dt=6.0)
    assert not c.mon.osdmap.is_up(victim)
    # degraded read must reconstruct the lost shard
    assert client.read("ecpool", "obj3") == data
    c.revive_osd(victim)
    for _ in range(3):
        c.tick(dt=6.0)
    assert c.mon.osdmap.is_up(victim)


def test_recovery_restores_redundancy():
    c = MiniCluster(n_osds=8)
    c.create_ec_pool("ec2", k=3, m=2, pg_num=8)
    client = c.client("client.rec")
    data = payload(seed=4)
    client.write_full("ec2", "objr", data)
    holders = {o.osd_id for o in c.osds.values()
               if any(ho.oid == "objr"
                      for cid in o.store.list_collections()
                      for ho in o.store.list_objects(cid))}
    victim = next(iter(holders))
    c.kill_osd(victim)
    c.mark_osd_down(victim)
    c.mark_osd_out(victim)   # out -> crush remaps to a replacement shard
    # recovery should have pushed the lost chunk to the replacement
    new_holders = {o.osd_id for o in c.osds.values()
                   if o.osd_id != victim
                   and o.name not in c.network.down
                   and any(ho.oid == "objr"
                           for cid in o.store.list_collections()
                           for ho in o.store.list_objects(cid))}
    assert len(new_holders) == 5  # k+m distinct live holders again
    assert client.read("ec2", "objr") == data
    # second failure after recovery is still survivable
    victim2 = next(iter(new_holders))
    c.kill_osd(victim2)
    c.mark_osd_down(victim2)
    assert client.read("ec2", "objr") == data


def test_corrupt_shard_detected_and_reconstructed():
    c = MiniCluster(n_osds=6)
    c.create_ec_pool("ec3", k=4, m=2, pg_num=8)
    client = c.client("client.scrub")
    data = payload(seed=5)
    client.write_full("ec3", "objc", data)
    # flip bits in one stored shard; HashInfo crc must catch it on read
    for osd in c.osds.values():
        for cid in osd.store.list_collections():
            for ho in osd.store.list_objects(cid):
                if ho.oid == "objc":
                    obj = osd.store.colls[cid][ho]
                    obj.data = bytearray(obj.data)
                    obj.data[10] ^= 0xFF
                    break
            else:
                continue
            break
        else:
            continue
        break
    assert client.read("ec3", "objc") == data


def test_replicated_pool_roundtrip_and_recovery():
    c = MiniCluster(n_osds=5)
    c.create_replicated_pool("rbd", size=3, pg_num=8)
    client = c.client("client.rep")
    data = payload(seed=6, n=10000)
    assert client.write_full("rbd", "ro", data) == 0
    assert client.read("rbd", "ro") == data
    holders = {o.osd_id for o in c.osds.values()
               if any(ho.oid == "ro"
                      for cid in o.store.list_collections()
                      for ho in o.store.list_objects(cid))}
    assert len(holders) == 3
    victim = next(iter(holders))
    c.kill_osd(victim)
    c.mark_osd_down(victim)
    c.mark_osd_out(victim)
    assert client.read("rbd", "ro") == data
    new_holders = {o.osd_id for o in c.osds.values()
                   if o.name not in c.network.down
                   and any(ho.oid == "ro"
                           for cid in o.store.list_collections()
                           for ho in o.store.list_objects(cid))}
    assert len(new_holders) == 3


def test_delete_removes_all_shards(ec_cluster):
    c = ec_cluster
    client = c.client("client.del")
    client.write_full("ecpool", "objd", payload(seed=8, n=5000))
    assert client.remove("ecpool", "objd") == 0
    c.network.pump()
    leftovers = [1 for o in c.osds.values()
                 for cid in o.store.list_collections()
                 for ho in o.store.list_objects(cid) if ho.oid == "objd"]
    assert not leftovers
    with pytest.raises(IOError):
        client.read("ecpool", "objd")


def test_lrc_pool_end_to_end():
    c = MiniCluster(n_osds=9)
    c.create_ec_pool("lrcpool", pg_num=8, plugin="lrc",
                     extra_profile={"k": "4", "m": "2", "l": "3"})
    client = c.client("client.lrc")
    data = payload(seed=9)
    assert client.write_full("lrcpool", "objl", data) == 0
    assert client.read("lrcpool", "objl") == data


def test_writes_blocked_below_min_size():
    """Writes to a PG with fewer than min_size live acting members are
    refused (EAGAIN -> client retry -> -110), while reads still serve
    degraded; recovery of the acting set unblocks writes."""
    from ceph_tpu.cluster import MiniCluster
    c = MiniCluster(n_osds=3)          # exactly k+m: no spare to remap to
    c.create_ec_pool("ms", k=2, m=1, plugin="isa", pg_num=4)
    cl = c.client("client.ms")
    data = payload(seed=11)
    cl.write_full("ms", "obj", data)
    victim = None
    for osd in c.osds.values():
        for cid in osd.store.list_collections():
            for ho in osd.store.list_objects(cid):
                if ho.oid == "obj" and victim is None:
                    victim = osd.osd_id
    c.kill_osd(victim)
    for _ in range(6):
        c.tick(dt=6.0)
    c.mark_osd_out(victim)
    c.network.pump()
    # only 2 live osds remain for a min_size=3 pool: writes refuse
    assert cl.write_full("ms", "obj", b"nope") in (-11, -110)
    # degraded reads still reconstruct
    assert cl.read("ms", "obj") == data
    # revive AND mark back in: acting refills, writes flow again
    c.revive_osd(victim)
    for _ in range(4):
        c.tick(dt=6.0)
    c.mon.mark_osd_in(victim)
    c.network.pump()
    c.run_recovery()
    c.network.pump()
    assert cl.write_full("ms", "obj", b"back") == 0
    assert cl.read("ms", "obj") == b"back"
