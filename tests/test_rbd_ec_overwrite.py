"""RBD on an erasure-coded data pool with overwrites: the served
read-modify-write path against a plain reference.

Each image (order 16: 64 KiB objects, four 16 KiB stripes each) keeps
its header in a replicated pool and its data in a k=4 m=2 ``tpu``
``reed_sol_van`` pool with overwrites on; it is created through
``ceph_tpu.rbd`` and prefilled through the image.  Offset writes are
then sent as ``Image.write`` sends them, one ``CEPH_OSD_OP_WRITE`` per
object extent, 16 in flight, by an asynchronous client.  The reference
applies the acknowledged writes to the prefill, per object in the order
their replies came back, and encodes each object with ISA-L's
``reed_sol_van`` over GF(2^8) (0x11d), computed here in plain numpy.
Every stored shard on the acting OSDs, and the bytes read back through
the image, must equal it.
"""
from collections import deque

import numpy as np
import pytest

from ceph_tpu.client.rados import RadosClient
from ceph_tpu.cluster import MiniCluster
from ceph_tpu.msg.messages import CEPH_OSD_OP_WRITE, MOSDOp, MOSDOpReply
from ceph_tpu.os_store import hobject_t
from ceph_tpu.osd.ec_backend import (ExtentCache, l_pipeline_rmw_ops,
                                     pipeline_perf_counters)
from ceph_tpu.rbd import RBD, Image

K, M, SU = 4, 2, 4096
ORDER = 16
OBJ = 1 << ORDER
N_OBJ = 8
BLOCK = 4096


# ---- the plain reference ----------------------------------------------------
def _mul_table() -> np.ndarray:
    exp, log = [0] * 512, [0] * 256
    v = 1
    for i in range(255):
        exp[i], log[v] = v, i
        v <<= 1
        if v & 0x100:
            v ^= 0x11D
    exp[255:510] = exp[:255]
    mul = np.zeros((256, 256), dtype=np.uint8)
    for a in range(1, 256):
        for b in range(1, 256):
            mul[a, b] = exp[log[a] + log[b]]
    return mul


MUL = _mul_table()


def _van_rows(k: int, m: int) -> np.ndarray:
    """gf_gen_rs_matrix's coding rows: row i is [g^0 .. g^(k-1)], g = 2^i."""
    out = np.zeros((m, k), dtype=np.uint8)
    g = 1
    for i in range(m):
        p = 1
        for j in range(k):
            out[i, j] = p
            p = MUL[p, g]
        g = MUL[g, 2]
    return out


def ref_shards(body: bytes) -> np.ndarray:
    """(k + m, len / k): shard j is chunk j of every stripe."""
    data = np.frombuffer(body, np.uint8).reshape(-1, K, SU) \
        .transpose(1, 0, 2).reshape(K, -1)
    rows = _van_rows(K, M)
    coding = np.zeros((M, data.shape[1]), dtype=np.uint8)
    for i in range(M):
        for j in range(K):
            coding[i] ^= MUL[rows[i, j]][data[j]]
    return np.concatenate([data, coding])


def apply(bodies, acked) -> None:
    """Splice each acknowledged (objno, offset, data), in order."""
    for objno, off, data in acked:
        bodies[objno][off:off + len(data)] = data


# ---- the served path --------------------------------------------------------
class AsyncWriter(RadosClient):
    """Sends offset writes without waiting for their replies, keeping
    ``depth`` in flight; ``acked`` lists them in reply order."""

    def __init__(self, c, img: Image, name: str):
        super().__init__(c.network, c.mon, name)
        self.img = img
        self.pool_id = self.lookup_pool(img.data_pool)
        self.pending = {}
        self.acked = []
        self.todo = deque()

    def ms_fast_dispatch(self, msg) -> None:
        if isinstance(msg, MOSDOpReply) and msg.tid in self.pending:
            w = self.pending.pop(msg.tid)
            assert msg.result == 0, (w[:2], msg.result)
            self.acked.append(w)
            self._issue()
            return
        super().ms_fast_dispatch(msg)

    def _issue(self) -> None:
        if not self.todo:
            return
        objno, off, data = self.todo.popleft()
        oid = self.img._obj(objno)
        pgid, primary = self._calc_target(self.pool_id, oid)
        self._tid += 1
        self.pending[self._tid] = (objno, off, data)
        self.messenger.send_message(MOSDOp(
            tid=self._tid, pool=pgid[0], oid=oid, pgid=pgid,
            op=CEPH_OSD_OP_WRITE, data=data, offset=off,
            epoch=self.osdmap.epoch), f"osd.{primary}")

    def run(self, writes, depth: int = 16):
        """Image writes (offset, data), split into object extents."""
        for offset, data in writes:
            pos = 0
            for objno, off, ln in self.img._extents(offset, len(data)):
                self.todo.append((objno, off, data[pos:pos + ln]))
                pos += ln
        n = len(self.todo)
        for _ in range(min(depth, n)):
            self._issue()
        while self.pending and self.network.pump():
            pass
        assert not self.pending and not self.todo
        assert len(self.acked) == n
        return self.acked


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(n_osds=8)
    c.create_replicated_pool("rbd", size=3, pg_num=8)
    c.create_ec_pool("rbd_data", k=K, m=M, pg_num=16, plugin="tpu",
                     extra_profile={"technique": "reed_sol_van"},
                     ec_overwrites=True)
    return c


def new_image(c, name: str, seed: int):
    """An image created through ceph_tpu.rbd with its data in the EC
    pool, every object prefilled through the image; (image, bodies)."""
    cl = c.client(f"client.{name}")
    RBD(cl).create("rbd", name, N_OBJ * OBJ, ORDER, data_pool="rbd_data")
    img = Image(cl, "rbd", name)
    rng = np.random.default_rng([seed, 1])
    bodies = []
    for objno in range(N_OBJ):
        body = rng.bytes(OBJ)
        assert img.write(objno * OBJ, body) == OBJ
        bodies.append(bytearray(body))
    return img, bodies


def check(c, img: Image, bodies) -> None:
    """Every shard on its acting OSD, and every byte read back through
    the image, equals the reference."""
    osdmap = c.mon.osdmap
    pid = osdmap.lookup_pg_pool_name(img.data_pool)
    pool = osdmap.pools[pid]
    for objno, body in enumerate(bodies):
        oid = img._obj(objno)
        pg = pool.raw_pg_to_pg(osdmap.map_to_pg(pid, oid))
        acting = osdmap.pg_to_up_acting_osds(pg)[2]
        want = ref_shards(bytes(body))
        assert len(acting) == K + M
        for j, osd in enumerate(acting):
            got = c.osds[osd].store.read(f"{pid}.{pg.ps}s{j}",
                                         hobject_t(oid, j))
            assert bytes(got) == want[j].tobytes(), (objno, j)
    assert img.read(0, N_OBJ * OBJ) == b"".join(bytes(b) for b in bodies)


def rmw_ops() -> int:
    return pipeline_perf_counters().get(l_pipeline_rmw_ops)


# ---- cases ------------------------------------------------------------------
@pytest.mark.parametrize("seed", [2**31 + 11, 7])
def test_random_aligned_4k_overwrites(cluster, seed):
    """rbd bench --io-type write --io-pattern rand in small: 4 KiB
    aligned writes at uniform block offsets, 16 in flight."""
    img, bodies = new_image(cluster, f"rand{seed}", seed)
    rng = np.random.default_rng([seed, 2])
    n_blocks = N_OBJ * OBJ // BLOCK
    writes = [(int(b) * BLOCK, rng.bytes(BLOCK))
              for b in rng.integers(0, n_blocks, 96)]
    before = rmw_ops()
    acked = AsyncWriter(cluster, img, f"client.aio{seed}").run(writes)
    # every write went through the read-modify-write path
    assert rmw_ops() - before == len(writes)
    apply(bodies, acked)
    check(cluster, img, bodies)


def test_unaligned_and_stripe_spanning_writes(cluster):
    img, bodies = new_image(cluster, "unaligned", 5)
    rng = np.random.default_rng(6)
    sw = K * SU
    writes = [
        (5000, rng.bytes(3000)),                # inside one chunk
        (sw - 700, rng.bytes(1500)),            # across a stripe boundary
        (3 * OBJ + 123, rng.bytes(2 * sw + 9)),  # three stripes, odd ends
        (OBJ - 2000, rng.bytes(5000)),          # across two objects
        (5 * OBJ + SU - 1, rng.bytes(1)),       # one byte
        (7 * OBJ + sw, rng.bytes(OBJ - sw)),    # to the image's end
    ]
    acked = AsyncWriter(cluster, img, "client.aiou").run(writes)
    apply(bodies, acked)
    check(cluster, img, bodies)
    # the same shapes through Image.write, one op after the other
    later = [(off + 77, rng.bytes(len(d))) for off, d in writes[:4]]
    for off, data in later:
        assert img.write(off, data) == len(data)
        pos = 0
        for objno, o, ln in img._extents(off, len(data)):
            bodies[objno][o:o + ln] = data[pos:pos + ln]
            pos += ln
    check(cluster, img, bodies)


def test_overlapping_writes_in_flight_use_the_extent_cache(cluster,
                                                           monkeypatch):
    """16 overlapping writes to one object in flight at once: each
    queued write reads what the one before it projected."""
    img, bodies = new_image(cluster, "overlap", 9)
    hits = []
    orig = ExtentCache.read

    def read(self, oid, offset, length):
        got = orig(self, oid, offset, length)
        hits.append(got is not None)
        return got

    monkeypatch.setattr(ExtentCache, "read", read)
    rng = np.random.default_rng(10)
    base = 2 * OBJ
    writes = [(base + int(o), rng.bytes(BLOCK + 100 * i))
              for i, o in enumerate(rng.integers(0, 3 * BLOCK, 16))]
    acked = AsyncWriter(cluster, img, "client.aioo").run(writes)
    assert sum(hits) >= 8, hits
    # one object, one primary, a FIFO queue: replies in send order
    assert [a[1] for a in acked] == [w[0] - base for w in writes]
    apply(bodies, acked)
    check(cluster, img, bodies)
