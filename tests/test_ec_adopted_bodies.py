"""Whole-shard EC writes adopted by the store.

The encode packs every shard of an op into one buffer; the fan-out
sends each shard OSD a memoryview of its row; the OSD's ``MemStore``
copies it once into a ``bytes`` at queue time and keeps that ``bytes``
as the shard body, with no zero-fill and no second copy.  The pack
itself is not kept.  Held to the plain ISA-L reference
(benchmark/reference/gf256_rs.py, which imports nothing of the
program)."""
import numpy as np
import pytest

from benchmark.reference import gf256_rs
from ceph_tpu.cluster import MiniCluster
from ceph_tpu.os_store import memstore

K, M, SU = 4, 2, 4096
POOL = "ec"


@pytest.fixture
def adopted(monkeypatch):
    """The ``adopted_bytes`` of every transaction, as its span's args
    leave it."""
    seen = []

    class _Tracer:
        def span(self, *, prof, **args):
            rec = dict(args)
            seen.append(rec)

            class _Scope:
                def __enter__(self):
                    return self

                def __exit__(self, *exc):
                    return False

                def set(self, **more):
                    rec.update(more)

            return _Scope()

    monkeypatch.setattr(memstore, "g_tracer", _Tracer())
    return seen


def _stored(c, oid):
    """shard -> its committed body object in the OSD's store"""
    out = {}
    for osd in c.osds.values():
        for cid in osd.store.list_collections():
            for ho in osd.store.list_objects(cid):
                if ho.oid == oid:
                    out[int(ho.shard)] = osd.store.colls[cid][ho].data
    return out


@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_write_full_adopts_one_copy_per_shard(adopted, seed):
    c = MiniCluster(n_osds=7)
    c.create_ec_pool(POOL, k=K, m=M, pg_num=8, plugin="tpu")
    rng = np.random.default_rng(seed)
    want = None
    for body in (rng.bytes(3 * K * SU), rng.bytes(2 * K * SU)):
        del adopted[:]
        assert c.client("client.t").write_full(POOL, "obj", body) == 0
        want = gf256_rs.all_shards(body, K, M, SU)
        # every shard body replaced whole, by reference, nothing staged
        assert sum(a["adopted_bytes"] for a in adopted) == \
            sum(len(w) for w in want)
        assert all(a["staged_bytes"] == 0 for a in adopted)
    stored = _stored(c, "obj")
    assert sorted(stored) == list(range(K + M))
    for shard, data in stored.items():
        assert type(data) is bytes
        np.testing.assert_array_equal(
            np.frombuffer(data, dtype=np.uint8), want[shard])
    assert c.client("client.r").read(POOL, "obj") == body
