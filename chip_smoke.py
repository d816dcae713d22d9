"""Bring-up check: the main path on a TPU chip, against host references.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the multi-chip phase only

One process, one run through the entry points a user calls:

- device     JAX's default device is a TPU; there is no CPU fallback.
- codec      ``create_erasure_code`` tpu k=8 m=4: ``encode_batch`` of 64
             objects of 1 MiB (64, 8, 131072) in one device call, then
             ``decode_batch`` with one data and one coding shard erased;
             both byte-identical to the same codec with ``backend=host``.
- served     ``MiniCluster`` of 16 OSDs with a k=8 m=4 tpu pool, default
             options: ``write_full`` 64 objects of 4 MiB (256 MiB), read
             them back, kill the OSD holding the most data shards, tick
             until the mon sees it, read everything again (degraded reads).
             All byte-exact; the writes must have encoded and the degraded
             reads decoded on the device.
- placement  100,000 PGs x 3 on the 1000-OSD straw2 map of the remap
             benchmark through ``OSDMapMapping.update()``, then 1% of the
             OSDs out and one remap epoch; both equal the native C++
             mapper for every PG.

``--chips 4`` runs only what exists across chips, and what it is compared
with: the served path with ``ec_mesh_chips=4`` against the same run on one
device, and ``sharded_fast_rule`` over a 4-device mesh against the native
mapper.

After every phase the run fails if a device call was served by the CPU
twin (``cpu_fallbacks``), a codec circuit breaker is open, or a mapping
left the device.  Progress lines (wall and compile seconds, sizes) go to
stderr; the last stdout line is the result.  Any failure exits non-zero
and prints no result.  The phases are functions of their sizes so a CPU
rehearsal can call them small; ``main()`` runs only on a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from typing import Callable, Dict

import numpy as np

K, M = 8, 4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result or left the device."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class CompileMeter:
    """Programs compiled fresh vs loaded from the persistent cache.

    JAX's backend-compile event fires for both, so a cache hit is told
    apart by the cache-hit event that precedes it in the same call.
    ``cached_writes`` names the fresh compiles slow enough for the cache
    to keep (>= its minimum compile time): a run whose cache is warm
    must have none."""

    def __init__(self, min_cached_secs: float):
        import jax
        self.min_cached_secs = min_cached_secs
        self.fresh = 0
        self.compile_s = 0.0
        self.hits = 0
        self.load_s = 0.0
        self.cached_writes: list = []
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event != "/jax/core/compile/backend_compile_duration":
            return
        if self._hit:
            self.hits += 1
            self.load_s += duration
        else:
            self.fresh += 1
            self.compile_s += duration
            if duration >= self.min_cached_secs:
                self.cached_writes.append(kw.get("fun_name", "?"))
        self._hit = False

    def snapshot(self) -> Dict:
        return {"fresh_compiles": self.fresh, "compile_s": self.compile_s,
                "cache_hits": self.hits, "cache_load_s": self.load_s,
                "cached_writes": list(self.cached_writes)}


def _device_site_calls(site: str) -> int:
    """Device calls at *site* that returned their result to the host
    (the profiler counts the device->host leg only after the call)."""
    from ceph_tpu.trace.devprof import g_devprof
    return g_devprof.dump()["sites"].get(site, {}).get("d2h_count", 0)


def check_no_cpu_path(phase: str, fallbacks_at_start: int) -> None:
    """Fail when anything in *phase* was quietly served off the device.
    (Mappings are checked where they are built: only the placement
    phase builds an OSDMapMapping.)"""
    from ceph_tpu.fault import (fault_perf_counters, g_breakers,
                                l_fault_cpu_fallbacks)
    fb = fault_perf_counters().get(l_fault_cpu_fallbacks)
    if fb != fallbacks_at_start:
        raise SmokeFailure(f"{phase}: {fb - fallbacks_at_start} device "
                           "calls were served by the CPU twin")
    open_breakers = g_breakers.degraded()
    if open_breakers:
        raise SmokeFailure(f"{phase}: circuit breakers open: "
                           f"{open_breakers}")


# ---- phases -------------------------------------------------------------
def phase_device(chips: int) -> Dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"JAX's default device is {devs[0].platform}, "
                           "not a TPU")
    if len(devs) < chips:
        raise SmokeFailure(f"{chips} chips asked for, {len(devs)} found")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def phase_codec(n_objects: int = 64, object_size: int = 1 << 20,
                seed: int = 0) -> Dict:
    from ceph_tpu.ec import create_erasure_code
    dev = create_erasure_code({"plugin": "tpu", "k": str(K), "m": str(M)})
    host = create_erasure_code({"plugin": "tpu", "k": str(K),
                                "m": str(M), "backend": "host"})
    chunk = object_size // K
    data = np.random.default_rng(seed).integers(
        0, 256, (n_objects, K, chunk), dtype=np.uint8)
    enc0, dec0 = (_device_site_calls("gf_matmul.encode"),
                  _device_site_calls("gf_matmul.decode"))
    coding = dev.encode_batch(data)
    want_coding = host.encode_batch(data)
    if coding.shape != (n_objects, M, chunk) or \
            not np.array_equal(coding, want_coding):
        raise SmokeFailure("codec: device encode_batch != host")
    # erase data shard 0 and coding shard k
    survivors = {i: data[:, i] for i in range(1, K)}
    survivors.update({K + j: coding[:, j] for j in range(1, M)})
    got = dev.decode_batch(survivors, [0, K])
    ref = host.decode_batch(survivors, [0, K])
    for i, want in ((0, data[:, 0]), (K, coding[:, 0])):
        if not (np.array_equal(got[i], ref[i])
                and np.array_equal(got[i], want)):
            raise SmokeFailure(f"codec: decode_batch shard {i} != host")
    if _device_site_calls("gf_matmul.encode") == enc0 or \
            _device_site_calls("gf_matmul.decode") == dec0:
        raise SmokeFailure("codec: encode/decode never reached the device")
    return {"batch": list(data.shape), "data_mib": data.nbytes >> 20,
            "erased": [0, K]}


def _fill_cluster(n_osds: int, n_objects: int, object_size: int,
                  pg_num: int, seed: int):
    """A fresh cluster with an EC pool holding *n_objects* seeded bodies
    written through the client."""
    from ceph_tpu.cluster import MiniCluster
    c = MiniCluster(n_osds=n_osds)
    c.create_ec_pool("smoke", k=K, m=M, plugin="tpu",
                     failure_domain="osd", pg_num=pg_num)
    cl = c.client("client.smoke")
    bodies = np.random.default_rng(seed).integers(
        0, 256, (n_objects, object_size), dtype=np.uint8)
    for i in range(n_objects):
        rc = cl.write_full("smoke", f"obj{i}", bodies[i].tobytes())
        if rc != 0:
            raise SmokeFailure(f"served: write_full obj{i} returned {rc}")
    return c, cl, bodies


def _read_all(cl, bodies: np.ndarray, what: str) -> None:
    for i in range(len(bodies)):
        if cl.read("smoke", f"obj{i}") != bodies[i].tobytes():
            raise SmokeFailure(f"served: {what} read of obj{i} differs")


def _kill_busiest_data_osd(c, n_objects: int, max_ticks: int = 20) -> int:
    """Kill the OSD holding the most data shards of the objects and tick
    until the mon marks it down.  Returns the victim."""
    osdmap = c.mon.osdmap
    pid = osdmap.lookup_pg_pool_name("smoke")
    pool = osdmap.pools[pid]
    held: Dict[int, int] = {}
    for i in range(n_objects):
        _up, _upp, acting, _actp = osdmap.pg_to_up_acting_osds(
            pool.raw_pg_to_pg(osdmap.map_to_pg(pid, f"obj{i}")))
        for osd in acting[:K]:
            held[osd] = held.get(osd, 0) + 1
    victim = max(sorted(held), key=held.get)
    c.kill_osd(victim)
    for _ in range(max_ticks):
        if not c.mon.osdmap.is_up(victim):
            return victim
        c.tick(dt=6.0)
    raise SmokeFailure(f"served: osd.{victim} killed but never marked down")


def phase_served(n_osds: int = 16, n_objects: int = 64,
                 object_size: int = 4 << 20, pg_num: int = 64,
                 seed: int = 1) -> Dict:
    """The cluster places PGs with the scalar host CRUSH of its OSDMap
    (no OSDMapMapping runs here); its EC encodes and decodes must reach
    the device."""
    enc0 = _device_site_calls("gf_matmul.encode")
    t0 = time.perf_counter()
    c, cl, bodies = _fill_cluster(n_osds, n_objects, object_size, pg_num,
                                  seed)
    t_write = time.perf_counter()
    encodes = _device_site_calls("gf_matmul.encode") - enc0
    if encodes == 0:
        raise SmokeFailure("served: no write encoded on the device")
    _read_all(cl, bodies, "healthy")
    t_read = time.perf_counter()
    victim = _kill_busiest_data_osd(c, n_objects)
    dec0 = _device_site_calls("gf_matmul.decode")
    t_deg0 = time.perf_counter()
    _read_all(cl, bodies, "degraded")
    t_deg = time.perf_counter()
    decodes = _device_site_calls("gf_matmul.decode") - dec0
    if decodes == 0:
        raise SmokeFailure("served: no degraded read decoded on the device")
    return {"objects": n_objects, "object_mib": object_size / (1 << 20),
            "data_mib": bodies.nbytes >> 20, "osds": n_osds,
            "victim": victim, "device_encodes": encodes,
            "device_decodes": decodes,
            "write_s": t_write - t0, "read_s": t_read - t_write,
            "degraded_read_s": t_deg - t_deg0}


def _native_rows(cw, rno: int, pps: np.ndarray, weight, size: int):
    """The C++ mapper's rows, NONE-padded like PoolMapping.up."""
    from ceph_tpu.crush.wrapper import do_rule_batch
    res, _lens, engine = do_rule_batch(cw.crush, rno, pps, size, weight)
    if engine != "native":
        raise SmokeFailure("placement: the C++ mapper did not answer")
    return res.astype(np.int32)


def phase_placement(n_osds: int = 1000, n_pgs: int = 100_000,
                    out_frac: float = 0.01, size: int = 3) -> Dict:
    from ceph_tpu.bench.workloads import build_remap_crush
    from ceph_tpu.osdmap.mapping import OSDMapMapping, pool_pps
    from ceph_tpu.osdmap.osdmap import OSDMap
    from ceph_tpu.osdmap.types import TYPE_REPLICATED, pg_pool_t
    m = OSDMap()
    m.epoch = 1
    _cw, rno = build_remap_crush(n_osds, uniform=True, cw=m.crush)
    for o in range(n_osds):
        m.set_osd(o, up=True)
    pid = m.add_pool("rbd", pg_pool_t(
        type=TYPE_REPLICATED, size=size, min_size=size - 1,
        crush_rule=rno, pg_num=n_pgs, pgp_num=n_pgs))
    pps = pool_pps(m.pools[pid], pid,
                   np.arange(n_pgs, dtype=np.uint32))
    mapping = OSDMapMapping()
    out = {"pgs": n_pgs, "osds": n_osds, "size": size}
    n_out = max(1, int(n_osds * out_frac))
    out_osds = np.random.default_rng(3).choice(n_osds, n_out,
                                               replace=False)
    for epoch in ("initial", "remap"):
        if epoch == "remap":
            for o in out_osds:
                m.osd_weight[int(o)] = 0
            m.epoch += 1
        t0 = time.perf_counter()
        mapping.update(m)
        out[f"{epoch}_update_s"] = time.perf_counter() - t0
        if mapping.last_backend.get(pid) != "device":
            raise SmokeFailure(f"placement: {epoch} mapping ran on "
                               f"{mapping.last_backend.get(pid)!r}")
        want = _native_rows(m.crush, rno, pps, m.osd_weight, size)
        pm = mapping.pools[pid]
        for name, got in (("up", pm.up), ("acting", pm.acting)):
            bad = np.nonzero(np.any(got != want, axis=1))[0]
            if len(bad):
                raise SmokeFailure(
                    f"placement: {epoch} {name} differs from the native "
                    f"mapper on {len(bad)} PGs (first {bad[:5].tolist()})")
    (_fp, fr), = mapping._rule_cache.values()
    out.update(out_osds=n_out, residual_fraction=fr.residual_fraction)
    return out


def phase_mesh(chips: int = 4, n_osds: int = 16, n_objects: int = 64,
               object_size: int = 4 << 20, pg_num: int = 64,
               n_pgs: int = 100_000, n_crush_osds: int = 1000,
               seed: int = 1) -> Dict:
    """The meshed EC served path against the single-device one, and the
    sharded CRUSH rule against the native mapper."""
    import jax
    from ceph_tpu.common.config import g_conf
    from ceph_tpu.mesh import g_mesh
    from ceph_tpu.mesh.runtime import (l_mdec_dispatches, l_mdec_fallbacks,
                                       l_mesh_dispatches, l_mesh_fallbacks,
                                       mesh_decode_perf_counters,
                                       mesh_perf_counters)
    mesh_options = {"ec_mesh_chips": chips,
                    "ec_dispatch_batch_window_us": 200_000}
    pc, dpc = mesh_perf_counters(), mesh_decode_perf_counters()

    def run(meshed: bool):
        for name, value in mesh_options.items():
            if meshed:
                g_conf.set_val(name, value)
            else:
                g_conf.rm_val(name)
        topo = g_mesh.topology()
        if meshed:
            devs = list(topo.devices.flat)
            if len({d.id for d in devs}) != chips or \
                    any(d.platform != jax.devices()[0].platform
                        for d in devs):
                raise SmokeFailure(f"mesh: spans {devs}, not {chips} "
                                   "distinct chips of the default backend")
        c, cl, bodies = _fill_cluster(n_osds, n_objects, object_size,
                                      pg_num, seed)
        victim = _kill_busiest_data_osd(c, n_objects)
        _read_all(cl, bodies, "degraded")
        return victim, _ec_shard_bodies(c)

    counts0 = (pc.get(l_mesh_dispatches), dpc.get(l_mdec_dispatches),
               pc.get(l_mesh_fallbacks), dpc.get(l_mdec_fallbacks))
    t0 = time.perf_counter()
    try:
        victim_m, meshed = run(True)
    finally:
        for name in mesh_options:
            g_conf.rm_val(name)
    t_mesh = time.perf_counter()
    counts = (pc.get(l_mesh_dispatches), dpc.get(l_mdec_dispatches),
              pc.get(l_mesh_fallbacks), dpc.get(l_mdec_fallbacks))
    enc_d, dec_d, enc_fb, dec_fb = (b - a for a, b in zip(counts0, counts))
    if enc_d == 0 or dec_d == 0:
        raise SmokeFailure(f"mesh: {enc_d} encode and {dec_d} decode "
                           "flushes rode the mesh")
    if enc_fb or dec_fb:
        raise SmokeFailure(f"mesh: {enc_fb} encode and {dec_fb} decode "
                           "flushes fell back to one device")
    victim_s, single = run(False)
    t_single = time.perf_counter()
    if victim_m != victim_s or set(meshed) != set(single):
        raise SmokeFailure("mesh: meshed and single-device clusters "
                           "placed shards differently")
    diffs = [key for key in single if bytes(meshed[key]) != bytes(single[key])]
    if diffs:
        raise SmokeFailure(f"mesh: {len(diffs)} stored shard bodies differ "
                           f"from the single-device run: {diffs[:3]}")

    from ceph_tpu.bench.workloads import build_remap_crush
    from ceph_tpu.parallel import make_mesh, sharded_fast_rule
    cw, rno = build_remap_crush(n_crush_osds, uniform=True)
    xs = np.arange(n_pgs, dtype=np.uint32)
    w = np.full(n_crush_osds, 0x10000, dtype=np.uint32)
    w[np.random.default_rng(3).choice(n_crush_osds,
                                      max(1, n_crush_osds // 100),
                                      replace=False)] = 0
    t2 = time.perf_counter()
    sf = sharded_fast_rule(cw.crush, rno, 3, make_mesh(chips))
    res, cnt = sf.map_batch(xs, w)
    t3 = time.perf_counter()
    from ceph_tpu.crush.constants import CRUSH_ITEM_NONE
    got = np.where(np.arange(3)[None, :] < cnt[:, None], res,
                   CRUSH_ITEM_NONE)
    bad = np.nonzero(np.any(got != _native_rows(cw, rno, xs, w, 3),
                            axis=1))[0]
    if len(bad):
        raise SmokeFailure(f"mesh: sharded CRUSH differs from the native "
                           f"mapper on {len(bad)} PGs")
    return {"chips": chips, "objects": n_objects,
            "data_mib": n_objects * object_size >> 20,
            "mesh_encode_flushes": enc_d, "mesh_decode_flushes": dec_d,
            "shard_bodies_compared": len(single),
            "meshed_served_s": t_mesh - t0,
            "single_served_s": t_single - t_mesh,
            "sharded_crush_pgs": n_pgs, "sharded_crush_s": t3 - t2}


def _ec_shard_bodies(c) -> Dict:
    """Every stored EC shard body in the cluster, keyed by where it is."""
    out = {}
    for i, osd in c.osds.items():
        for cid in osd.store.list_collections():
            if "_meta" in cid or "s" not in cid.split(".")[-1]:
                continue
            for ho in osd.store.list_objects(cid):
                out[(i, cid, str(ho))] = osd.store.read(cid, ho)
    return out


# ---- driver -------------------------------------------------------------
def run_phase(name: str, fn: Callable[[], Dict], meter: CompileMeter
              ) -> Dict:
    from ceph_tpu.fault import fault_perf_counters, l_fault_cpu_fallbacks
    fallbacks = fault_perf_counters().get(l_fault_cpu_fallbacks)
    c0 = meter.snapshot()
    t0 = time.perf_counter()
    info = fn()
    wall = time.perf_counter() - t0
    check_no_cpu_path(name, fallbacks)
    c1 = meter.snapshot()
    info.update({"wall_s": wall}, **{k: c1[k] - c0[k] for k in c1
                                      if k != "cached_writes"})
    info["cached_writes"] = c1["cached_writes"][len(c0["cached_writes"]):]
    log(f"phase={name} ok " + json.dumps(info, sort_keys=True))
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phase")
    args = ap.parse_args(argv)

    from ceph_tpu.arch import configure_compile_cache
    cache_dir = configure_compile_cache()
    import jax
    meter = CompileMeter(
        jax.config.jax_persistent_cache_min_compile_time_secs)
    log(f"compile cache: {cache_dir}")
    dev = run_phase("device", lambda: phase_device(args.chips), meter)
    if args.chips == 1:
        run_phase("codec", phase_codec, meter)
        run_phase("served", phase_served, meter)
        run_phase("placement", phase_placement, meter)
    else:
        run_phase("mesh", lambda: phase_mesh(args.chips), meter)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception as e:  # every failure: say why, print no result
        traceback.print_exc()
        log(f"FAILED: {type(e).__name__}: {e}")
        code = 1
    sys.exit(code)
