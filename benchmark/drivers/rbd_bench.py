"""``rbd bench --io-type write --io-pattern rand`` on an RBD image whose
data pool is erasure-coded with overwrites on.

Set-up builds the configuration's cluster, its replicated metadata pool
and its EC data pool with the program's default options, creates the
image through ``ceph_tpu.rbd`` and writes every object of it through
``Image.write``, so that each later write overwrites data that is there.
Then it overwrites every object once with an op of the window's own
kind, which warms the window's shapes and its memory.  The window is a closed loop of ``in_flight`` ops on
the wall clock.  Each op is what ``Image.write`` sends for an extent
inside one object, issued without waiting for the reply as librbd's
``aio_write`` does: a ``CEPH_OSD_OP_WRITE`` of ``io_size`` bytes drawn
from the seed to ``rbd_data.<id>.<objno:016x>``, at the in-object offset
of a uniformly random ``io_size``-aligned image offset.

The check holds the window to the configuration's guarantees, besides
``rados_bench``'s checks of failed, stalled and resent ops, CPU
fallbacks and open breakers:

- every window write was re-encoded on the device, and went through the
  read-modify-write path (the program's ``rmw_ops`` counter);
- for a seeded sample of the objects the window wrote, each of the
  k + m shards on the OSD the map puts it on, and the object's bytes
  read back through the image, equal the plain reference
  (``reference/rbd_overwrite.py``): the prefill with every acknowledged
  write applied, per object in the order of the replies.
"""
# no ``from __future__ import annotations``: the harness loads drivers
# outside sys.modules, where a dataclass cannot resolve string annotations
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmark.drivers.rados_bench import RadosBench
from benchmark.reference import rbd_overwrite
from benchmark.traffic import ClosedLoopClient, Op, PayloadPool
from ceph_tpu.msg.messages import CEPH_OSD_OP_WRITE, MOSDOp, new_trace_id
from ceph_tpu.trace.oplat import stamp_client

KIND = "rbd_ec_pool"
OBJECT_SAMPLE = 24          # window-written objects whose shards are compared
IO_BUF = 8 << 20            # the seeded bytes payloads are drawn from


@dataclass
class ExtentOp(Op):
    """A write of ``body`` at ``offset`` into image object ``objno``."""
    objno: int = 0
    offset: int = 0


class ExtentClient(ClosedLoopClient):
    """The closed loop, sending offset writes (``CEPH_OSD_OP_WRITE``)."""

    def _send(self, op: ExtentOp) -> None:
        with self.span("submit"):
            pgid, primary = self._calc_target(self.pool_id, op.oid)
            op.attempts += 1
            self._tid += 1
            tid = self._tid
            self.pending[tid] = op
            if primary < 0:
                # no primary (peering): refresh the map; resend_stalled
                # sends it again
                self.mon.send_full_map(self.name)
                return
            msg = MOSDOp(tid=tid, pool=pgid[0], oid=op.oid, pgid=pgid,
                         op=CEPH_OSD_OP_WRITE, data=bytes(op.body),
                         offset=op.offset, epoch=self.osdmap.epoch,
                         trace_id=new_trace_id())
            stamp_client(msg, self.name)
            self.messenger.send_message(msg, f"osd.{primary}")


class RbdBench(RadosBench):
    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 span: Callable, log: Callable):
        if config.get("kind") != KIND:
            raise ValueError(f"rbd_bench drives {KIND} configurations")
        if traffic.get("io_type") != "write" or \
                traffic.get("io_pattern") != "rand" or \
                traffic.get("loop") != "closed":
            raise ValueError(f"unsupported traffic {traffic}")
        self.config, self.traffic, self.seed = config, traffic, seed
        self.span, self.log = span, log
        self.pool_cfg = config["data_pool"]
        self.k, self.m = int(self.pool_cfg["k"]), int(self.pool_cfg["m"])
        self.stripe_unit = int(self.pool_cfg["stripe_unit"])
        self.image_cfg = config["image"]
        self.object_size = 1 << int(self.image_cfg["order"])
        self.n_objects = int(self.image_cfg["size_bytes"]) // \
            self.object_size
        # client_MiBps and the per-op metrics count io_size bytes per op
        self.object_bytes = int(traffic["io_size"])
        self.in_flight = int(traffic["in_flight"])
        self.op = "write"
        self.victims: List[int] = []        # no OSD is taken down
        self.rng = np.random.default_rng([seed, 0xB0])
        self.io_buf = np.random.default_rng([seed, 0x10]).bytes(IO_BUF)
        self.prefill = PayloadPool(seed, self.object_size)
        self.clients: List[ClosedLoopClient] = []
        self._build()

    # ---- set-up -----------------------------------------------------------
    def _build(self) -> None:
        # the counter the check reads; a program without it cannot be
        # checked, so the run ends here
        from ceph_tpu.osd.ec_backend import (l_pipeline_rmw_ops,
                                             pipeline_perf_counters)
        from ceph_tpu.cluster import MiniCluster
        from ceph_tpu.common.config import g_conf
        from ceph_tpu.fault import fault_perf_counters, l_fault_cpu_fallbacks
        from ceph_tpu.rbd import RBD, Image
        self.rmw_ops = lambda: pipeline_perf_counters().get(
            l_pipeline_rmw_ops)
        for name, want in self.config["options"].items():
            got = g_conf.get_val(name)
            if got != want:
                raise RuntimeError(f"option {name} is {got!r}, the "
                                   f"configuration states {want!r}")
        self.fallbacks0 = fault_perf_counters().get(l_fault_cpu_fallbacks)
        cl = self.config["cluster"]
        t0 = time.perf_counter()
        self.c = MiniCluster(n_osds=int(cl["n_osds"]),
                             osds_per_host=int(cl["osds_per_host"]))
        meta = self.config["metadata_pool"]
        self.c.create_replicated_pool(meta["name"], size=int(meta["size"]),
                                      pg_num=int(meta["pg_num"]))
        p = self.pool_cfg
        self.pool = p["name"]
        self.pool_id = self.c.create_ec_pool(
            self.pool, k=self.k, m=self.m, pg_num=int(p["pg_num"]),
            plugin=p["plugin"], failure_domain=p["failure_domain"],
            extra_profile={"technique": p["technique"],
                           "stripe_unit": str(self.stripe_unit)},
            ec_overwrites=bool(p["allow_ec_overwrites"]))
        pool = self.c.mon.osdmap.pools[self.pool_id]
        if pool.stripe_width != self.k * self.stripe_unit or \
                pool.allows_ecoverwrites() != p["allow_ec_overwrites"]:
            raise RuntimeError("the data pool differs from the "
                               "configuration")
        self.log(f"cluster and pools built in "
                 f"{time.perf_counter() - t0:.2f} s")
        t0 = time.perf_counter()
        rbd_client = self.c.client("client.rbd")
        im = self.image_cfg
        RBD(rbd_client).create(
            meta["name"], im["name"], int(im["size_bytes"]),
            order=int(im["order"]), data_pool=self.pool,
            journaling=im["journaling"], exclusive_lock=im["exclusive_lock"],
            object_map=im["object_map"])
        self.img = Image(rbd_client, meta["name"], im["name"])
        for objno in range(self.n_objects):
            self.img.write(objno * self.object_size,
                           bytes(self.prefill.body(objno)))
        self.log(f"image created and its {self.n_objects} objects written "
                 f"in {time.perf_counter() - t0:.2f} s")
        # one op on every object, in a seeded order: it compiles the
        # window's shapes, and leaves each shard's rollback stash holding
        # a body, as in the window's steady state (the first overwrite of
        # an object grows the stash by its k + m shards)
        t0 = time.perf_counter()
        self._run_ops([self._next_window_op(i, int(objno)) for i, objno in
                       enumerate(self.rng.permutation(self.n_objects))])
        self.log(f"warmed up with {self.n_objects} ops in "
                 f"{time.perf_counter() - t0:.2f} s")

    def _client(self, name: str, next_op, issue_more) -> ClosedLoopClient:
        client = ExtentClient(self.c.network, self.c.mon, name, self.pool,
                              next_op, issue_more, self.span)
        self.clients.append(client)
        return client

    # ---- the window --------------------------------------------------------
    def _next_window_op(self, i: int, objno: Optional[int] = None
                        ) -> ExtentOp:
        """Op *i*: io_size bytes from the seed at a uniformly random
        io_size-aligned offset of the image, or of object *objno*."""
        io = self.object_bytes
        if objno is None:
            block = int(self.rng.integers(self.n_objects * self.object_size
                                          // io))
            objno, off = divmod(block * io, self.object_size)
        else:
            off = int(self.rng.integers(self.object_size // io)) * io
        at = int(self.rng.integers(IO_BUF - io + 1))
        return ExtentOp(i, "write", self.img._obj(objno),
                        memoryview(self.io_buf)[at:at + io],
                        objno=objno, offset=off)

    def window(self, seconds: float) -> Dict:
        rmw0 = self.rmw_ops()
        res = super().window(seconds)
        res["rmw_ops"] = self.rmw_ops() - rmw0
        return res

    def _codec_min_bytes(self, op: ExtentOp) -> int:
        """(k + m) chunks for each stripe the write re-encodes."""
        sw = self.k * self.stripe_unit
        first = op.offset // sw
        last = (op.offset + len(op.body) - 1) // sw
        return (last - first + 1) * (self.k + self.m) * self.stripe_unit

    # ---- the check ---------------------------------------------------------
    def check(self) -> Dict[str, Dict]:
        checks = super().check()
        r = self.result
        done = self.client.done
        checks["writes_not_encoded_on_device"] = {
            "value": max(0, len(done) - r["layer"]["device_calls"]["encode"]),
            "limit": 0}
        checks["writes_not_rmw"] = {
            "value": max(0, len(done) - r["rmw_ops"]), "limit": 0}
        shards, read_back = self._check_objects()
        checks["shards_differing"] = {"value": shards, "limit": 0}
        checks["bytes_read_back_differing"] = {"value": read_back,
                                               "limit": 0}
        return checks

    def _acked(self) -> List[rbd_overwrite.Write]:
        """Every acknowledged write since the prefill, in reply order
        (each client's ops finished before the next client started)."""
        return [(o.objno, o.offset, o.body) for cl in self.clients
                for o in cl.done if o.ok]

    def _check_objects(self):
        """(shards differing or missing, bytes read back differing) over
        a seeded sample of the objects the window wrote."""
        from ceph_tpu.os_store import hobject_t
        written = sorted({o.objno for o in self.client.done if o.ok})
        if not written:
            return 1, 1
        rng = np.random.default_rng([self.seed, 0xC4EC])
        sample = sorted(int(n) for n in rng.choice(
            written, min(OBJECT_SAMPLE, len(written)), replace=False))
        want = rbd_overwrite.expected(
            lambda n: bytes(self.prefill.body(n)), self._acked(), sample,
            self.k, self.m, self.stripe_unit)
        osdmap = self.c.mon.osdmap
        pool = osdmap.pools[self.pool_id]
        bad_shards = bad_bytes = 0
        for objno in sample:
            body, shards = want[objno]
            oid = self.img._obj(objno)
            pg = pool.raw_pg_to_pg(osdmap.map_to_pg(self.pool_id, oid))
            acting = self._acting(oid)
            for j in range(self.k + self.m):
                cid, ho = f"{self.pool_id}.{pg.ps}s{j}", hobject_t(oid, j)
                osd = self.c.osds.get(acting[j]) if j < len(acting) else None
                store = osd.store if osd is not None else None
                if store is None or not store.collection_exists(cid) or \
                        not store.exists(cid, ho) or \
                        bytes(store.read(cid, ho)) != shards[j].tobytes():
                    bad_shards += 1
            got = np.frombuffer(self.img.read(objno * self.object_size,
                                              self.object_size), np.uint8)
            ref = np.frombuffer(body, np.uint8)
            n = min(len(got), len(ref))
            bad_bytes += int(np.count_nonzero(got[:n] != ref[:n])) + \
                abs(len(got) - len(ref))
        self.log(f"compared {len(sample) * (self.k + self.m)} stored shards "
                 f"and the bytes of {len(sample)} window-written objects "
                 "with the reference")
        return bad_shards, bad_bytes


def build(config: Dict, traffic: Dict, seed: int, span: Callable,
          log: Callable) -> RbdBench:
    return RbdBench(config, traffic, seed, span, log)
