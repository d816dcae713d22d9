"""``rados bench write`` on an LRC pool of a ``MiniCluster``.

The pool is upstream's LRCprofile example, ``plugin=lrc k=4 m=2 l=3
crush-failure-domain=host``: kml makes 8 chunks at the physical
positions of the mapping ``DD__DD__`` and three layers, a global
jerasure ``reed_sol_van`` k=4 m=2 and one local k=3 m=1 per group, each
encoded by a device call of its own.  Everything else is
``rados_bench``'s: set-up with the program's default options, warm-up,
and a window that is a closed loop of ``in_flight`` ops on the wall
clock.  Set-up refuses to run unless every layer of the pool's codec
runs on the device.

The check holds the window to the configuration's guarantees, besides
``rados_bench``'s checks of failed, stalled and resent ops, CPU
fallbacks and open breakers:

- every window write was encoded on the device, one call per layer
  (``writes_not_encoded_on_device``);
- for a seeded sample of the window's writes, each of the 8 shards is
  stored at its physical position, on the OSD the map names for it, and
  equals the plain kml reference (``reference/lrc_kml.py``)
  (``shards_differing``);
- the same objects read back through a client equal their bodies
  (``bytes_read_back_differing``);
- then the OSD that holds the most data chunks of them is killed and
  marked down (not out) and they are read again
  (``degraded_read_back_differing``); every read that lost a data chunk
  must have been repaired by the local layers alone, as the program's
  ``lrc`` counters count (``degraded_reads_not_local``).  The OSD is
  revived afterwards, so a later window finds the cluster whole.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List

import numpy as np

from benchmark.drivers.rados_bench import WRITE_SAMPLE, RadosBench
from benchmark.reference import lrc_kml
from benchmark.traffic import Op, PayloadPool

KIND = "lrc_pool"


class LrcBench(RadosBench):
    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 span: Callable, log: Callable):
        if config.get("kind") != KIND:
            raise ValueError(f"lrc_bench drives {KIND} configurations")
        if traffic.get("op") != "write_full" or \
                traffic.get("loop") != "closed":
            raise ValueError(f"unsupported traffic {traffic}")
        self.config, self.traffic, self.seed = config, traffic, seed
        self.span, self.log = span, log
        self.pool_cfg = config["pool"]
        self.k, self.m = int(self.pool_cfg["k"]), int(self.pool_cfg["m"])
        self.l = int(self.pool_cfg["l"])
        self.mapping, self.layer_maps = lrc_kml.parse_kml(self.k, self.m,
                                                          self.l)
        self.n = len(self.mapping)
        self.data_pos = [p for p, ch in enumerate(self.mapping) if ch == "D"]
        self.object_bytes = int(traffic["object_bytes"])
        self.in_flight = int(traffic["in_flight"])
        self.op = traffic["op"]
        self.rng = np.random.default_rng([seed, 0xB0])
        self.payloads = PayloadPool(seed, self.object_bytes)
        self.sample: Dict[str, Op] = {}
        self._build()

    # ---- set-up -----------------------------------------------------------
    def _build(self) -> None:
        # the counters the check reads; a program without them cannot be
        # checked, so the run ends here
        from ceph_tpu.ec.lrc import (l_lrc_global_repairs,
                                     l_lrc_local_repairs, lrc_perf_counters)
        from ceph_tpu.cluster import MiniCluster
        from ceph_tpu.common.config import g_conf
        from ceph_tpu.fault import fault_perf_counters, l_fault_cpu_fallbacks
        pc = lrc_perf_counters()
        self.repairs = lambda: (pc.get(l_lrc_local_repairs),
                                pc.get(l_lrc_global_repairs))
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
        p = self.pool_cfg
        self.pool = p["name"]
        # upstream's profile: plugin, k, m, l and the failure domain only
        self.pool_id = self.c.create_ec_pool(
            self.pool, k=self.k, m=self.m, pg_num=int(p["pg_num"]),
            plugin=p["plugin"], failure_domain=p["failure_domain"],
            extra_profile={"l": str(self.l)})
        pool = self.c.mon.osdmap.pools[self.pool_id]
        self.stripe_unit = int(p["stripe_unit"])
        if pool.stripe_width != self.k * self.stripe_unit or \
                pool.size != self.n:
            raise RuntimeError(f"pool stripe width {pool.stripe_width} and "
                               f"size {pool.size}, configuration "
                               f"{self.k * self.stripe_unit} and {self.n}")
        self._require_device_layers()
        self.log(f"cluster and pool built in {time.perf_counter() - t0:.2f} s")
        self.n_pre = 0
        self.prefill_oids: List[str] = []
        t0 = time.perf_counter()
        warm = self._warmup_ops()
        self.n_warm = len(warm)
        self._run_ops(warm)
        self.log(f"warmed up in {time.perf_counter() - t0:.2f} s")

    def _require_device_layers(self) -> None:
        """Every layer of every PG's codec runs on the device, and the
        layers are kml's."""
        codecs = [pg.backend.ec_impl for pgid, pg in self.c.primary_pgs()
                  if pgid[0] == self.pool_id and pg.backend is not None]
        if not codecs:
            raise RuntimeError("no PG of the pool has an EC backend")
        for ec in codecs:
            maps = [layer.chunks_map for layer in ec.layers]
            if maps != self.layer_maps:
                raise RuntimeError(f"the pool's layers are {maps}, kml "
                                   f"makes {self.layer_maps}")
            off = [layer.chunks_map for layer in ec.layers
                   if not layer.erasure_code._use_device()]
            if off:
                raise RuntimeError(f"layers {off} do not run on the device")

    # ---- the window --------------------------------------------------------
    def _codec_min_bytes(self, op: Op) -> int:
        """The least any implementation of the code touches for a write:
        the k data chunks read and the n - k coding chunks written (a
        single generator matrix for all layers touches exactly that; the
        layered encode touches more)."""
        return self.n * (self.object_bytes // self.k)

    # ---- the check ---------------------------------------------------------
    def check(self) -> Dict[str, Dict]:
        from ceph_tpu.fault import (fault_perf_counters, g_breakers,
                                    l_fault_cpu_fallbacks)
        checks = super().check()
        done = self.client.done
        calls = self.result["layer"]["device_calls"]["encode"]
        checks["writes_not_encoded_on_device"] = {
            "value": max(0, len(done) - calls // len(self.layer_maps)),
            "limit": 0}
        checks["bytes_read_back_differing"] = {
            "value": self._read_back("client.check"), "limit": 0}
        degraded, not_local = self._degraded_read_back()
        checks["degraded_read_back_differing"] = {"value": degraded,
                                                  "limit": 0}
        checks["degraded_reads_not_local"] = {"value": not_local,
                                              "limit": 0}
        # the check's own reads ran through the codec too
        checks["cpu_fallbacks"]["value"] = \
            fault_perf_counters().get(l_fault_cpu_fallbacks) - \
            self.fallbacks0
        checks["breakers_open"]["value"] = len(g_breakers.degraded())
        return checks

    def _check_shards(self, writes: List[Op]) -> int:
        """All n stored shards of a seeded sample of *writes* against the
        reference, each at its physical position on the OSD the map
        names for it; a shard missing or elsewhere counts too."""
        if not writes:
            self.sample = {}
            return 1
        rng = np.random.default_rng([self.seed, 0xC4EC])
        pick = rng.choice(len(writes), min(WRITE_SAMPLE, len(writes)),
                          replace=False)
        self.sample = {writes[i].oid: writes[i] for i in sorted(pick)}
        stored: Dict = {}
        for osd_id, osd in self.c.osds.items():
            for cid in osd.store.list_collections():
                for ho in osd.store.list_objects(cid):
                    if getattr(ho, "oid", None) in self.sample:
                        stored[(ho.oid, int(ho.shard))] = (
                            osd_id, osd.store.read(cid, ho))
        bad = 0
        for oid, op in self.sample.items():
            want = lrc_kml.all_chunks(bytes(op.body), self.k, self.m,
                                      self.l, self.stripe_unit)
            acting = self._acting(oid)
            for j in range(self.n):
                got = stored.get((oid, j))
                if got is None or got[0] != acting[j] or \
                        bytes(got[1]) != want[j].tobytes():
                    bad += 1
        self.log(f"compared {len(self.sample) * self.n} stored shards of "
                 f"{len(self.sample)} window writes with the reference")
        return bad

    def _read_back(self, name: str) -> int:
        """Bytes of the sampled objects that a client reads back
        differently from their bodies (all of a body whose read fails)."""
        client = self.c.client(name)
        bad = 0
        for oid, op in self.sample.items():
            ref = np.frombuffer(bytes(op.body), np.uint8)
            try:
                got = np.frombuffer(client.read(self.pool, oid), np.uint8)
            except IOError:
                bad += len(ref)
                continue
            n = min(len(got), len(ref))
            bad += int(np.count_nonzero(got[:n] != ref[:n])) + \
                abs(len(got) - len(ref))
        return bad

    def _degraded_read_back(self):
        """(bytes read back differing, reads that lost a data chunk and
        were not repaired by the local layers alone) with the OSD that
        holds the most data chunks of the sample down."""
        if not self.sample:
            return 1, 1
        acting = {oid: self._acting(oid) for oid in self.sample}
        held: Dict[int, int] = {}
        for row in acting.values():
            for p in self.data_pos:
                held[row[p]] = held.get(row[p], 0) + 1
        victim = max(sorted(held), key=held.get)
        lost = sum(1 for row in acting.values()
                   if victim in [row[p] for p in self.data_pos])
        self.c.kill_osd(victim)
        self.c.mark_osd_down(victim)
        try:
            local0, global0 = self.repairs()
            bad = self._read_back("client.check_degraded")
            local1, global1 = self.repairs()
        finally:
            self.c.revive_osd(victim)
        not_local = max(lost - (local1 - local0), global1 - global0)
        self.log(f"osd.{victim} down: read {len(self.sample)} objects back, "
                 f"{lost} through a repair ({local1 - local0} local, "
                 f"{global1 - global0} global)")
        return bad, not_local


def build(config: Dict, traffic: Dict, seed: int, span: Callable,
          log: Callable) -> LrcBench:
    return LrcBench(config, traffic, seed, span, log)
