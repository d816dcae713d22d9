"""``rados bench`` on an EC pool of a ``MiniCluster``: write, rand read.

Set-up builds the configuration's cluster and pool with the program's
default options, fills the pool where the traffic reads, takes OSDs
down where it asks, and warms every shape the window will use.  The
window is a closed loop of ``in_flight`` ops on the wall clock.  The
check afterwards holds the window to the configuration's guarantees:

- every op of the window completed with result 0 on its first send (the
  window resends nothing), and every read returned the body written,
  byte for byte;
- for a sample of the window's writes, drawn from the seed, each of the
  k + m shards is stored, on the OSD the map puts it on, and equals the
  plain Reed-Solomon reference (``reference/gf256_rs.py``);
- encodes and degraded decodes ran on the device, no call fell back to
  the CPU twin, and no codec breaker is open.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List

import numpy as np

from benchmark.reference import gf256_rs
from benchmark.traffic import (ClosedLoopClient, Op, PayloadPool,
                               latency_ms, percentile)

KIND = "ec_pool"
PUMP_CHUNK = 256            # messages delivered per network.pump call
MAX_STALLS = 8
WRITE_SAMPLE = 24           # window writes whose shards are compared
MIB = float(1 << 20)


class RadosBench:
    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 span: Callable, log: Callable):
        if config.get("kind") != KIND:
            raise ValueError(f"rados_bench drives {KIND} configurations")
        self.config, self.traffic, self.seed = config, traffic, seed
        self.span, self.log = span, log
        self.pool_cfg = config["pool"]
        self.k, self.m = int(self.pool_cfg["k"]), int(self.pool_cfg["m"])
        self.object_bytes = int(traffic["object_bytes"])
        self.in_flight = int(traffic["in_flight"])
        self.op = traffic["op"]
        if self.op not in ("write_full", "read") or \
                traffic.get("loop") != "closed":
            raise ValueError(f"unsupported traffic {traffic}")
        self.rng = np.random.default_rng([seed, 0xB0])
        self.payloads = PayloadPool(seed, self.object_bytes)
        self.victims: List[int] = []
        self.erased: Dict[str, List[int]] = {}   # oid -> erased data shards
        self._build()

    # ---- set-up -----------------------------------------------------------
    def _build(self) -> None:
        from ceph_tpu.cluster import MiniCluster
        from ceph_tpu.common.config import g_conf
        from ceph_tpu.fault import fault_perf_counters, l_fault_cpu_fallbacks
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
        self.pool_id = self.c.create_ec_pool(
            self.pool, k=self.k, m=self.m, pg_num=int(p["pg_num"]),
            plugin=p["plugin"], failure_domain=p["failure_domain"],
            extra_profile={"technique": p["technique"]})
        pool = self.c.mon.osdmap.pools[self.pool_id]
        self.stripe_unit = int(p["stripe_unit"])
        if pool.stripe_width != self.k * self.stripe_unit:
            raise RuntimeError(f"pool stripe width {pool.stripe_width}, "
                               f"configuration {self.k * self.stripe_unit}")
        self.log(f"cluster and pool built in {time.perf_counter() - t0:.2f} s")
        self.n_pre = int(self.traffic.get("prefill_objects", 0))
        self.prefill_oids = [f"bench_obj_{i}" for i in range(self.n_pre)]
        if self.n_pre:
            t0 = time.perf_counter()
            self._run_ops([Op(i, "write", oid, self.payloads.body(i))
                           for i, oid in enumerate(self.prefill_oids)])
            self.log(f"prefilled {self.n_pre} objects in "
                     f"{time.perf_counter() - t0:.2f} s")
        t0 = time.perf_counter()
        for _ in range(int(self.traffic.get("down_osds", 0))):
            self._take_down_busiest()
        if self.victims:
            self.log(f"OSDs {self.victims} killed and marked down in "
                     f"{time.perf_counter() - t0:.2f} s")
        t0 = time.perf_counter()
        warm = self._warmup_ops()
        self.n_warm = len(warm)
        self._run_ops(warm)
        self.log(f"warmed up in {time.perf_counter() - t0:.2f} s")

    def _acting(self, oid: str) -> List[int]:
        osdmap = self.c.mon.osdmap
        pool = osdmap.pools[self.pool_id]
        _u, _up, acting, _ap = osdmap.pg_to_up_acting_osds(
            pool.raw_pg_to_pg(osdmap.map_to_pg(self.pool_id, oid)))
        return list(acting)

    def _take_down_busiest(self) -> None:
        """Kill the OSD that holds the most data shards of the prefill and
        mark it down (not out: no recovery runs)."""
        held: Dict[int, int] = {}
        acting = {oid: self._acting(oid) for oid in self.prefill_oids}
        for row in acting.values():
            for osd in row[:self.k]:
                if osd not in self.victims:
                    held[osd] = held.get(osd, 0) + 1
        victim = max(sorted(held), key=held.get)
        self.c.kill_osd(victim)
        self.c.mark_osd_down(victim)
        self.victims.append(victim)
        for oid, row in acting.items():
            self.erased[oid] = [j for j in range(self.k)
                                if row[j] in self.victims]
        n_dec = sum(1 for e in self.erased.values() if e)
        self.log(f"osd.{victim} down: {n_dec} of {self.n_pre} objects "
                 "read through a decode")

    def _warmup_ops(self) -> List[Op]:
        if self.op == "read":
            # every object once: every erasure pattern the window meets
            return [Op(i, "read", oid, self.payloads.body(i))
                    for i, oid in enumerate(self.prefill_oids)]
        n = int(self.traffic.get("warmup_ops", self.in_flight))
        return [Op(i, "write", f"warmup_{self.seed}_{i}",
                   self.payloads.body(self.n_pre + i)) for i in range(n)]

    def _client(self, name: str, next_op, issue_more) -> ClosedLoopClient:
        return ClosedLoopClient(self.c.network, self.c.mon, name, self.pool,
                                next_op, issue_more, self.span)

    def _pump_until_done(self, client: ClosedLoopClient,
                         resend: bool) -> int:
        """Deliver messages until *client* has no op pending; returns how
        often the fabric went quiet with ops pending.  Set-up resends
        them (peering can leave a PG without a primary for a while); the
        window fails them at once, since a sound cluster answers every
        op it was sent."""
        stalls = 0
        while client.pending:
            with self.span("network.pump"):
                n = self.c.network.pump(PUMP_CHUNK)
            if n == 0 and client.pending:
                stalls += 1
                if not resend or stalls > MAX_STALLS:
                    client.fail_pending("the fabric went quiet")
                else:
                    client.resend_stalled()
        return stalls

    def _run_ops(self, ops: List[Op]) -> None:
        """Set-up traffic: *ops* through a closed loop; any failure
        ends the run."""
        it = iter(ops)
        left = [len(ops)]

        def next_op(_i):
            left[0] -= 1
            return next(it)

        client = self._client(f"client.setup{len(self.c.network.endpoints)}",
                              next_op, lambda: left[0] > 0)
        client.start(min(self.in_flight, len(ops)))
        self._pump_until_done(client, resend=True)
        bad = [o.error for o in client.done if not o.ok]
        if bad or len(client.done) != len(ops):
            raise RuntimeError(f"set-up ops failed: {bad[:3]}")

    # ---- the window --------------------------------------------------------
    def _next_window_op(self, i: int) -> Op:
        if self.op == "read":
            j = int(self.rng.integers(self.n_pre))
            return Op(i, "read", self.prefill_oids[j], self.payloads.body(j))
        j = self.n_pre + self.n_warm + i
        return Op(i, "write", f"benchmark_data_{self.seed}_object{i}",
                  self.payloads.body(j))

    def _device_calls(self) -> Dict[str, int]:
        from ceph_tpu.trace.devprof import g_devprof
        sites = g_devprof.dump()["sites"]
        return {s: sites.get(f"gf_matmul.{s}", {}).get("d2h_count", 0)
                for s in ("encode", "decode")}

    def window(self, seconds: float) -> Dict:
        from ceph_tpu.trace.devprof import g_devprof
        from ceph_tpu.trace.oplat import g_oplat
        oplat0, dev0 = g_oplat.snapshot(), g_devprof.snapshot()
        calls0 = self._device_calls()
        t_end = [0.0]
        client = self._client("client.bench", self._next_window_op,
                              lambda: time.perf_counter() < t_end[0])
        with self.span("window"):
            t_start = time.perf_counter()
            t_end[0] = t_start + seconds
            client.start(self.in_flight)
            stalls = self._pump_until_done(client, resend=False)
        t_drained = time.perf_counter()
        self.client = client
        done = client.done
        ok = [o for o in done if o.ok]
        span_s = t_drained - t_start
        calls1 = self._device_calls()
        # the window's work is every op issued while it was open; its time
        # runs until the last of them completed (a closed loop completes
        # its in-flight ops in bursts, so counting only the ops done by
        # t_end would count the rate in steps of in_flight).  A failed op
        # makes the run incorrect; the p95 printed then is of the rest.
        self.result = {
            "attempted": client.issued,
            "failed": len(done) - len(ok),
            "e2e": {
                "client_MiBps": len(ok) * self.object_bytes / MIB / span_s,
                "op_p95_ms": percentile(latency_ms(ok), 95),
            },
            "stalls": stalls,
            "info": {"ops": len(done), "span_s": span_s,
                     "op_p50_ms": percentile(latency_ms(ok), 50),
                     "drain_s": t_drained - t_end[0]},
            "timeline": [[round(o.t_issue - t_start, 6),
                          round((o.t_done - o.t_issue) * 1e3, 3)]
                         for o in done],
            # per-layer inputs, over the same window
            "layer": {
                "n_ops": len(done),
                "span_s": span_s,
                "oplat": g_oplat.breakdown_since(oplat0, span_s, len(done)),
                "devprof": {k: g_devprof.snapshot().get(k, 0) - v
                            for k, v in dev0.items()},
                "codec_min_bytes": sum(self._codec_min_bytes(o)
                                       for o in done),
                "device_calls": {k: calls1[k] - calls0[k] for k in calls1},
            },
        }
        return self.result

    def _codec_min_bytes(self, op: Op) -> int:
        """The least the codec must read and write for *op*, whatever
        kernel does it: an encode reads k data chunks and writes m coding
        chunks; a decode reads k surviving chunks and writes the erased
        data chunks the read needs; a healthy read decodes nothing."""
        chunk = self.object_bytes // self.k
        if op.kind == "write":
            return (self.k + self.m) * chunk
        erased = len(self.erased.get(op.oid, ()))
        return (self.k + erased) * chunk if erased else 0

    # ---- the check ---------------------------------------------------------
    def check(self) -> Dict[str, Dict]:
        from ceph_tpu.fault import (fault_perf_counters, g_breakers,
                                    l_fault_cpu_fallbacks)
        r = self.result
        done = self.client.done
        checks = {
            "failed_ops": {"value": r["failed"], "limit": 0},
            # the window sends no op twice: a lost message or reply
            # shows here, not only as latency
            "window_stalls": {"value": r["stalls"], "limit": 0},
            "ops_resent": {"value": sum(o.attempts - 1 for o in done),
                           "limit": 0},
            "cpu_fallbacks": {
                "value": fault_perf_counters().get(l_fault_cpu_fallbacks)
                - self.fallbacks0, "limit": 0},
            "breakers_open": {"value": len(g_breakers.degraded()),
                              "limit": 0},
        }
        calls = r["layer"]["device_calls"]
        if self.op == "write_full":
            checks["writes_not_encoded_on_device"] = {
                "value": max(0, len(done) - calls["encode"]), "limit": 0}
            checks["shards_differing"] = {
                "value": self._check_shards(
                    [o for o in done if o.ok and o.kind == "write"]),
                "limit": 0}
        elif self.victims:
            need = sum(1 for o in done if self.erased.get(o.oid))
            checks["decodes_not_on_device"] = {
                "value": max(0, need - calls["decode"]), "limit": 0}
        return checks

    def _check_shards(self, writes: List[Op]) -> int:
        """Stored k + m shards of a seeded sample of *writes* against the
        reference; a shard missing or on the wrong OSD counts too."""
        if not writes:
            return 1
        rng = np.random.default_rng([self.seed, 0xC4EC])
        pick = rng.choice(len(writes), min(WRITE_SAMPLE, len(writes)),
                          replace=False)
        sample = {writes[i].oid: writes[i] for i in sorted(pick)}
        stored: Dict = {}
        for osd_id, osd in self.c.osds.items():
            for cid in osd.store.list_collections():
                for ho in osd.store.list_objects(cid):
                    if getattr(ho, "oid", None) in sample:
                        stored[(ho.oid, int(ho.shard))] = (
                            osd_id, osd.store.read(cid, ho))
        bad = 0
        n = self.k + self.m
        for oid, op in sample.items():
            want = gf256_rs.all_shards(op.body, self.k, self.m,
                                       self.stripe_unit)
            acting = self._acting(oid)
            for j in range(n):
                got = stored.get((oid, j))
                if got is None or got[0] != acting[j] or \
                        bytes(got[1]) != want[j].tobytes():
                    bad += 1
        self.log(f"compared {len(sample) * n} stored shards of "
                 f"{len(sample)} window writes with the reference")
        return bad


def build(config: Dict, traffic: Dict, seed: int, span: Callable,
          log: Callable) -> RadosBench:
    return RadosBench(config, traffic, seed, span, log)
