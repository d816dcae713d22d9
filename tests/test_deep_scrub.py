"""Shallow vs deep scrub (the reference's scrub / deep-scrub split).

Shallow scrubs (src/osd/PG.cc chunky_scrub with deep=false) compare
metadata across copies — sizes, attr and omap digests — without reading
object data; deep scrubs additionally checksum every byte.  The OSD
scheduler runs cheap shallow scrubs often and upgrades to deep when
osd_deep_scrub_interval lapses (OSD.cc sched_scrub).
"""
import numpy as np

from ceph_tpu.cluster import MiniCluster
from ceph_tpu.common.config import g_conf


def payload(n=20000, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


def _body(osd, cid, ho) -> bytearray:
    """The stored body as a bytearray the test may rot in place (a
    committed body may be an immutable buffer the store adopted)."""
    o = osd.store.colls[cid][ho]
    o.data = bytearray(o.data)
    return o.data


def _find_copy(c, oid, skip_primary_of=None):
    """(osd, cid, ho) of one stored copy, preferring a non-primary."""
    hits = []
    for osd in c.osds.values():
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == oid:
                    hits.append((osd, cid, ho))
    if skip_primary_of is not None:
        nonprim = [h for h in hits if h[0].osd_id != skip_primary_of]
        if nonprim:
            return nonprim[0]
    return hits[0]


def _data_reads(c):
    return sum(o.perf["op_r"] for o in c.osds.values())


def test_shallow_scrub_reads_no_data_and_misses_bitrot():
    """Proof the shallow pass really is metadata-only: flipped bytes
    (same size, same attrs) sail through a shallow scrub and are caught
    by the next deep one."""
    c = MiniCluster(n_osds=4)
    c.create_replicated_pool("p", size=3, pg_num=4)
    cl = c.client("client.s")
    data = payload(seed=1)
    assert cl.write_full("p", "obj", data) == 0
    cl2 = c.client("client.probe")
    pid = cl2.lookup_pool("p")
    _pg, primary = cl2._calc_target(pid, "obj")
    osd, cid, ho, = _find_copy(c, "obj", skip_primary_of=primary)
    before = bytes(osd.store.colls[cid][ho].data)
    _body(osd, cid, ho)[5] ^= 0xA5
    corrupted = bytes(osd.store.colls[cid][ho].data)

    reads = []
    orig_read = type(osd.store).read

    def counting_read(self, *a, **kw):
        reads.append(1)
        return orig_read(self, *a, **kw)

    type(osd.store).read = counting_read
    try:
        c.scrub(deep=False)
        shallow_reads = len(reads)
        # same size + attrs: the shallow pass cannot (and must not
        # claim to) see the rot
        assert bytes(osd.store.colls[cid][ho].data) == corrupted
        c.scrub(deep=True)
    finally:
        type(osd.store).read = orig_read
    assert shallow_reads == 0, "shallow scrub read object data"
    assert bytes(osd.store.colls[cid][ho].data) == before
    assert cl.read("p", "obj") == data


def test_shallow_scrub_catches_size_mismatch():
    c = MiniCluster(n_osds=4)
    c.create_replicated_pool("p", size=3, pg_num=4)
    cl = c.client("client.s")
    data = payload(seed=2)
    assert cl.write_full("p", "obj", data) == 0
    cl2 = c.client("client.probe")
    _pg, primary = cl2._calc_target(cl2.lookup_pool("p"), "obj")
    osd, cid, ho = _find_copy(c, "obj", skip_primary_of=primary)
    del _body(osd, cid, ho)[-100:]        # silent truncation
    c.scrub(deep=False)
    assert bytes(osd.store.colls[cid][ho].data) == data
    assert cl.read("p", "obj") == data


def test_shallow_scrub_catches_attr_divergence():
    c = MiniCluster(n_osds=4)
    c.create_replicated_pool("p", size=3, pg_num=4)
    cl = c.client("client.s")
    assert cl.write_full("p", "obj", b"stable bytes") == 0
    assert cl.setxattr("p", "obj", "color", b"blue") == 0
    cl2 = c.client("client.probe")
    _pg, primary = cl2._calc_target(cl2.lookup_pool("p"), "obj")
    osd, cid, ho = _find_copy(c, "obj", skip_primary_of=primary)
    from ceph_tpu.osd.ec_backend import USER_ATTR_PREFIX
    osd.store.colls[cid][ho].attrs[USER_ATTR_PREFIX + "color"] = b"red"
    c.scrub(deep=False)
    assert osd.store.colls[cid][ho].attrs[
        USER_ATTR_PREFIX + "color"] == b"blue"
    assert cl.getxattr("p", "obj", "color") == b"blue"


def test_shallow_scrub_catches_ec_size_vs_hinfo():
    """EC shallow pass: a shard whose stored length disagrees with its
    HashInfo total is repaired without any data read on clean shards."""
    c = MiniCluster(n_osds=6)
    c.create_ec_pool("p", k=3, m=2, pg_num=2, plugin="isa")
    cl = c.client("client.s")
    data = payload(seed=5)
    assert cl.write_full("p", "obj", data) == 0
    for osd in c.osds.values():
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == "obj" and ho.shard >= 0:
                    del _body(osd, cid, ho)[-16:]
                    c.scrub(deep=False)
                    assert cl.read("p", "obj") == data
                    return
    raise AssertionError("no EC shard found")


def test_corrupt_primary_loses_majority_vote():
    """A corrupt PRIMARY copy must not become the scrub authority and
    'repair' healthy replicas from bad data: the authoritative value is
    the majority among self-consistent copies (be_select_auth_object),
    so the primary repairs itself from the survivors."""
    c = MiniCluster(n_osds=4)
    c.create_replicated_pool("p", size=3, pg_num=4)
    cl = c.client("client.s")
    data = payload(seed=9)
    assert cl.write_full("p", "obj", data) == 0
    cl2 = c.client("client.probe")
    _pg, primary = cl2._calc_target(cl2.lookup_pool("p"), "obj")
    posd = c.osds[primary]
    hit = None
    for cid in posd.store.list_collections():
        if "_meta" in cid:
            continue
        for ho in posd.store.list_objects(cid):
            if ho.oid == "obj" and hit is None:
                _body(posd, cid, ho)[7] ^= 0x3C
                hit = (cid, ho)
    assert hit is not None
    c.scrub(deep=True)
    c.tick()
    cid, ho = hit
    assert bytes(posd.store.colls[cid][ho].data) == data, \
        "primary must be repaired from the majority, not vice versa"
    assert cl.read("p", "obj") == data
    # and the healthy replicas were left alone / stayed correct
    for osd in c.osds.values():
        for c2 in osd.store.list_collections():
            if "_meta" in c2:
                continue
            for h2 in osd.store.list_objects(c2):
                if h2.oid == "obj":
                    assert bytes(osd.store.colls[c2][h2].data) == data


def test_identical_rot_on_majority_of_copies_still_repaired():
    """Even when the SAME corruption hits a majority of replicas,
    the write-time recorded digest (object_info data_digest role)
    identifies each rotted copy as self-inconsistent — voting alone
    would elect the corruption."""
    c = MiniCluster(n_osds=4)
    c.create_replicated_pool("p", size=3, pg_num=4)
    cl = c.client("client.s")
    data = payload(seed=11)
    assert cl.write_full("p", "obj", data) == 0
    cl2 = c.client("client.probe")
    _pg, primary = cl2._calc_target(cl2.lookup_pool("p"), "obj")
    # identical byte-flip on every NON-primary copy (2 of 3)
    n = 0
    for osd in c.osds.values():
        if osd.osd_id == primary:
            continue
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == "obj":
                    _body(osd, cid, ho)[3] ^= 0xFF
                    n += 1
    assert n == 2
    c.scrub(deep=True)
    for osd in c.osds.values():
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == "obj":
                    assert bytes(osd.store.colls[cid][ho].data) == data
    assert cl.read("p", "obj") == data


def test_identical_attr_rot_on_majority_cannot_outvote_primary():
    """Data digests validate bytes, not metadata — identical attr rot
    on two (data-validated) replicas must not outvote the healthy
    primary's metadata."""
    c = MiniCluster(n_osds=4)
    c.create_replicated_pool("p", size=3, pg_num=4)
    cl = c.client("client.s")
    assert cl.write_full("p", "obj", b"solid" * 200) == 0
    assert cl.setxattr("p", "obj", "owner", b"alice") == 0
    cl2 = c.client("client.probe")
    _pg, primary = cl2._calc_target(cl2.lookup_pool("p"), "obj")
    from ceph_tpu.osd.ec_backend import USER_ATTR_PREFIX
    n = 0
    for osd in c.osds.values():
        if osd.osd_id == primary:
            continue
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == "obj":
                    osd.store.colls[cid][ho].attrs[
                        USER_ATTR_PREFIX + "owner"] = b"mallory"
                    n += 1
    assert n == 2
    c.scrub(deep=True)
    assert cl.getxattr("p", "obj", "owner") == b"alice"
    for osd in c.osds.values():
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == "obj":
                    assert osd.store.colls[cid][ho].attrs[
                        USER_ATTR_PREFIX + "owner"] == b"alice"


def test_digestless_object_keeps_primary_authority():
    """After a partial overwrite wipes the recorded digests, identical
    rot on a majority of replicas must NOT outvote the healthy primary
    (the pre-digest semantics are the fallback, not plain majority)."""
    c = MiniCluster(n_osds=4)
    c.create_replicated_pool("p", size=3, pg_num=4)
    cl = c.client("client.s")
    data = payload(seed=13)
    assert cl.write_full("p", "obj", data) == 0
    assert cl.write("p", "obj", b"QQ", offset=100) == 0   # digest wiped
    expect = bytearray(data)
    expect[100:102] = b"QQ"
    expect = bytes(expect)
    cl2 = c.client("client.probe")
    _pg, primary = cl2._calc_target(cl2.lookup_pool("p"), "obj")
    n = 0
    for osd in c.osds.values():
        if osd.osd_id == primary:
            continue
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == "obj":
                    _body(osd, cid, ho)[3] ^= 0xFF
                    n += 1
    assert n == 2
    c.scrub(deep=True)
    for osd in c.osds.values():
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == "obj":
                    assert bytes(osd.store.colls[cid][ho].data) == expect
    assert cl.read("p", "obj") == expect


def test_repaired_copy_does_not_rescrub_forever():
    """A recovery push mints a recorded digest the other copies lack;
    that must not read as an attr inconsistency, or every scrub would
    re-'repair' a correct copy forever."""
    c = MiniCluster(n_osds=4)
    c.create_replicated_pool("p", size=3, pg_num=4)
    cl = c.client("client.s")
    data = payload(seed=17)
    assert cl.write_full("p", "obj", data) == 0
    assert cl.write("p", "obj", b"ZZ", offset=50) == 0    # digests wiped
    expect = bytearray(data)
    expect[50:52] = b"ZZ"
    expect = bytes(expect)
    cl2 = c.client("client.probe")
    _pg, primary = cl2._calc_target(cl2.lookup_pool("p"), "obj")
    hit = 0
    for osd in c.osds.values():
        if osd.osd_id == primary:
            continue
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == "obj" and hit == 0:
                    _body(osd, cid, ho)[3] ^= 0x55
                    hit += 1
    assert hit == 1
    c.scrub(deep=True)          # finds + repairs (push mints a digest)
    c.tick()
    errs_after_repair = len(c.mon.log_last(100, level="ERR"))
    for _ in range(3):          # further scrubs must stay quiet
        c.scrub(deep=True)
        c.tick()
    assert len(c.mon.log_last(100, level="ERR")) == errs_after_repair, \
        c.mon.log_last(5, level="ERR")
    assert cl.read("p", "obj") == expect


def test_scheduler_upgrades_to_deep_on_interval():
    c = MiniCluster(n_osds=4)
    c.create_replicated_pool("p", size=3, pg_num=4)
    cl = c.client("client.s")
    assert cl.write_full("p", "obj", b"x" * 1000) == 0
    shallow_iv = 100.0
    deep_iv = 1000.0
    g_conf.set_val("osd_scrub_min_interval", shallow_iv)
    g_conf.set_val("osd_deep_scrub_interval", deep_iv)
    try:
        prim = [pg for o in c.osds.values() for pg in o.pgs.values()
                if pg.is_primary() and pg.pg_log.head > 0]
        assert prim
        # past the shallow interval: scrub happens, deep does not
        c.tick(dt=shallow_iv * 1.2, rounds=1)
        assert all(p.last_scrub_stamp > 0 for p in prim)
        assert all(p.last_deep_scrub_stamp == 0 for p in prim)
        # past the deep interval: the due scrub upgrades to deep
        c.tick(dt=deep_iv, rounds=1)
        assert all(p.last_deep_scrub_stamp > 0 for p in prim)
    finally:
        g_conf.set_val("osd_scrub_min_interval", 86400.0)
        g_conf.set_val("osd_deep_scrub_interval", 604800.0)
