"""Whole-map placement under OSD churn, through ``OSDMapMapping.update()``.

Set-up builds the configuration's CRUSH map and replicated pool into an
``OSDMap`` (the map of ``build_remap_crush``, copied below with the
yardstick), maps every PG once, and warms the epoch path.  The window is
a closed loop of epochs: each marks one OSD out (reweight 0), or the
earliest out OSD back in once ``max_out`` are out, then calls
``update()``; the next epoch starts when it returns.  The check
afterwards maps a seeded sample of the window's epochs, and the last
one, with the plain reference (``reference/crush_straw2.py``) and
compares up, acting and both primaries of every PG.
"""
from __future__ import annotations

import collections
import time
from typing import Callable, Dict, List

import numpy as np

from benchmark.reference.crush_straw2 import NONE, TwoLevelStraw2
from benchmark.traffic import percentile

KIND = "osdmap"
EPOCH_SAMPLE = 2            # seeded epochs compared, besides the last
IN = 0x10000


def build_crush(cw, spec: Dict) -> int:
    """Build the configuration's two-level straw2 map into the program's
    CrushWrapper *cw* (``bench/workloads.py:build_remap_crush``, copied);
    returns the rule number."""
    from ceph_tpu.crush import CRUSH_BUCKET_STRAW2
    per_host = int(spec["osds_per_host"])
    n_hosts = int(spec["hosts"])
    w = int(spec["osd_crush_weight"])
    cw.set_type_name(1, "host")
    cw.set_type_name(10, "root")
    hosts = []
    for h in range(n_hosts):
        osds = list(range(h * per_host, (h + 1) * per_host))
        hosts.append(cw.add_bucket(CRUSH_BUCKET_STRAW2, 1, f"host{h}", osds,
                                   [w] * per_host,
                                   id=int(spec["first_host_id"]) - h))
    cw.set_max_devices(n_hosts * per_host)
    cw.add_bucket(CRUSH_BUCKET_STRAW2, 10, "default", hosts,
                  [w * per_host] * n_hosts, id=int(spec["root_id"]))
    return cw.add_simple_rule("data", "default", "host", mode="firstn")


class OsdmapChurn:
    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 span: Callable, log: Callable):
        if config.get("kind") != KIND:
            raise ValueError(f"osdmap_churn drives {KIND} configurations")
        if traffic.get("loop") != "closed" or \
                int(traffic["change_per_epoch"]) != 1:
            raise ValueError(f"unsupported traffic {traffic}")
        self.config, self.traffic, self.seed = config, traffic, seed
        self.span, self.log = span, log
        self.max_out = int(traffic["max_out"])
        self.rng = np.random.default_rng([seed, 0xF1A9])
        self.out: collections.deque = collections.deque()
        self._build()

    def _build(self) -> None:
        from ceph_tpu.fault import fault_perf_counters, l_fault_cpu_fallbacks
        from ceph_tpu.osdmap.mapping import OSDMapMapping
        from ceph_tpu.osdmap.osdmap import OSDMap
        from ceph_tpu.osdmap.types import TYPE_REPLICATED, pg_pool_t
        self.fallbacks0 = fault_perf_counters().get(l_fault_cpu_fallbacks)
        spec, p = self.config["crush"], self.config["pool"]
        m = OSDMap()
        m.epoch = 1
        rno = build_crush(m.crush, spec)
        cm = m.crush.crush
        for key, want in spec["tunables"].items():
            if getattr(cm, key) != want:
                raise RuntimeError(f"crush tunable {key} is "
                                   f"{getattr(cm, key)}, configuration {want}")
        self.n_osds = int(spec["hosts"]) * int(spec["osds_per_host"])
        for o in range(self.n_osds):
            m.set_osd(o, up=True)
        self.size, self.pg_num = int(p["size"]), int(p["pg_num"])
        self.pool_id = m.add_pool(p["name"], pg_pool_t(
            type=TYPE_REPLICATED, size=self.size, min_size=self.size - 1,
            crush_rule=rno, pg_num=self.pg_num, pgp_num=self.pg_num),
            pool_id=int(p["pool_id"]))
        self.m = m
        self.mapping = OSDMapMapping()
        t0 = time.perf_counter()
        with self.span("mapping.update"):
            self.mapping.update(m)
        self.log(f"first update() of {self.pg_num} PGs took "
                 f"{time.perf_counter() - t0:.2f} s")
        t0 = time.perf_counter()
        for _ in range(int(self.traffic.get("warmup_epochs", 4))):
            self._epoch()
        self.log(f"warmed up in {time.perf_counter() - t0:.2f} s")

    def _flip(self) -> None:
        """One map change: an OSD out, or the earliest out one back in."""
        w = self.m.osd_weight
        if len(self.out) < self.max_out:
            cand = [o for o in range(self.n_osds) if w[o] == IN]
            o = cand[int(self.rng.integers(len(cand)))]
            w[o] = 0
            self.out.append(o)
        else:
            w[self.out.popleft()] = IN
        self.m.epoch += 1

    def _epoch(self) -> float:
        t0 = time.perf_counter()
        self._flip()
        with self.span("mapping.update"):
            self.mapping.update(self.m)
        return time.perf_counter() - t0

    def window(self, seconds: float) -> Dict:
        pick = np.random.default_rng([self.seed, 0x5A3])
        kept: List = []          # reservoir of (epoch index, weights, pm)
        last = None
        lat: List[float] = []
        off_device = 0
        with self.span("window"):
            t_start = time.perf_counter()
            t_end = t_start + seconds
            while time.perf_counter() < t_end:
                lat.append(self._epoch())
                if self.mapping.last_backend.get(self.pool_id) != "device":
                    off_device += 1
                i = len(lat) - 1
                entry = (i, np.asarray(self.m.osd_weight, np.uint32),
                         self.mapping.pools[self.pool_id])
                last = entry
                if i < EPOCH_SAMPLE:
                    kept.append(entry)
                else:
                    j = int(pick.integers(i + 1))
                    if j < EPOCH_SAMPLE:
                        kept[j] = entry
        t_stop = time.perf_counter()
        self.sample = sorted({e[0]: e for e in kept + [last]}.values(),
                             key=lambda e: e[0]) if last else []
        self.off_device = off_device
        lat_ms = np.asarray(lat) * 1e3
        (_fp, fr), = self.mapping._rule_cache.values()
        # the window runs until the last epoch it started returned
        self.result = {
            "attempted": len(lat), "failed": 0,
            "e2e": {
                "remap_ms": (t_stop - t_start) * 1e3 / len(lat)
                if lat else None,
                "remap_p95_ms": percentile(lat_ms, 95),
            },
            "info": {"epochs": len(lat), "span_s": t_stop - t_start,
                     "remap_p50_ms": percentile(lat_ms, 50),
                     "residual_fraction": float(fr.residual_fraction)},
            "timeline": [round(x, 3) for x in lat_ms.tolist()],
            "layer": {"epochs": len(lat), "span_s": t_stop - t_start},
        }
        return self.result

    def check(self) -> Dict[str, Dict]:
        from ceph_tpu.fault import (fault_perf_counters, g_breakers,
                                    l_fault_cpu_fallbacks)
        ref = TwoLevelStraw2(self.config["crush"])
        xs = ref.pps(self.pool_id, self.pg_num)
        bad = 0
        t0 = time.perf_counter()
        for _i, weight, pm in self.sample:
            up = ref.map(xs, weight, self.size)
            primary = np.where(up[:, 0] != NONE, up[:, 0], -1)
            differ = (np.any(pm.up != up, axis=1)
                      | np.any(pm.acting != up, axis=1)
                      | (pm.up_primary != primary)
                      | (pm.acting_primary != primary))
            bad += int(differ.sum())
        self.log(f"compared {len(self.sample)} epochs "
                 f"({[e[0] for e in self.sample]}) of {self.pg_num} PGs with "
                 f"the reference in {time.perf_counter() - t0:.2f} s")
        return {
            "pgs_differing": {"value": bad if self.sample else 1,
                              "limit": 0},
            "epochs_off_device": {"value": self.off_device, "limit": 0},
            "cpu_fallbacks": {
                "value": fault_perf_counters().get(l_fault_cpu_fallbacks)
                - self.fallbacks0, "limit": 0},
            "breakers_open": {"value": len(g_breakers.degraded()),
                              "limit": 0},
        }


def build(config: Dict, traffic: Dict, seed: int, span: Callable,
          log: Callable) -> OsdmapChurn:
    return OsdmapChurn(config, traffic, seed, span, log)
