"""Counter-coverage lint: no registered metric may silently skip the
Prometheus exporter.

Satellite of the devprof PR.  Twice now a counter family was added to
`perf dump` and only later discovered missing from the mgr exposition
(the PR 3 dimensionless-axis fix, the PR 6 qos wiring).  This lint
closes the loop structurally: it walks every ``PerfCounters`` logger
registered in the cluster's collection AND every ``PerfHistogram`` in
the process registry, and asserts each family appears in the rendered
exposition — so a new counter that skips the exporter fails tier-1,
not a dashboard review.
"""
import re

import pytest


@pytest.fixture(scope="module")
def cluster_and_text():
    from ceph_tpu.common.config import g_conf
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.mesh import g_mesh
    c = MiniCluster(n_osds=6)
    c.create_ec_pool("lint", k=3, m=2, pg_num=8)
    cl = c.client("client.lint")
    assert cl.write_full("lint", "o", b"c" * 16000) == 0
    assert cl.read("lint", "o")[:1] == b"c"
    # one partial overwrite so the read-modify-write counter moves
    assert cl.write("lint", "o", b"w" * 100, 4000) == 0
    # one write through the MESH path so the per-chip occupancy
    # histogram registers and the mesh counters move — the lint below
    # then covers the mesh families like any other; skew probes run on
    # every flush so the mesh_chip scoreboard families register too
    g_conf.set_val("ec_mesh_chips", 8)
    g_conf.set_val("ec_dispatch_batch_window_us", 200_000)
    g_conf.set_val("ec_mesh_skew_sample_every", 1)
    try:
        assert cl.write_full("lint", "om", b"m" * 60000) == 0
        # and one through the RATELESS coded path so the
        # mesh_rateless_* family registers, moves, and is lint-covered
        g_conf.set_val("ec_mesh_rateless", True)
        assert cl.write_full("lint", "or", b"n" * 60000) == 0
    finally:
        g_conf.rm_val("ec_mesh_chips")
        g_conf.rm_val("ec_dispatch_batch_window_us")
        g_conf.rm_val("ec_mesh_skew_sample_every")
        g_conf.rm_val("ec_mesh_rateless")
        g_mesh.topology()
    from ceph_tpu.mesh import g_chipstat, rateless_perf_counters
    from ceph_tpu.mesh.rateless import l_rl_flushes
    assert g_chipstat.summary()["probes"] > 0, \
        "mesh write produced no skew probe — scoreboard families " \
        "would be lint-invisible"
    assert rateless_perf_counters().get(l_rl_flushes) > 0, \
        "mesh write never rode the rateless path — its counter " \
        "family would be lint-invisible"
    # one DEGRADED read through the MESH path (kill a data-shard
    # holder, reconstruct with the mesh up) so the mesh_decode_*
    # counter family and the decode occupancy histogram register and
    # move — the lint below then covers the meshed READ path too
    lint_pid = c.mon.osdmap.lookup_pg_pool_name("lint")
    victim = next(
        o.osd_id for o in c.osds.values()
        for cid in o.store.list_collections()
        if cid.startswith(f"{lint_pid}.") and "s" in cid
        and cid.rsplit("s", 1)[1] in ("1", "2")   # non-primary DATA shard
        and any(ho.oid == "om" for ho in o.store.list_objects(cid)))
    c.kill_osd(victim)
    c.mark_osd_down(victim)
    from ceph_tpu.mesh import mesh_decode_perf_counters
    from ceph_tpu.mesh.runtime import l_mdec_dispatches, l_mdec_fallbacks
    # the counters are process-wide: an earlier test in this process
    # (a device fault in test_mesh_decode.py) may have moved them
    fallbacks0 = mesh_decode_perf_counters().get(l_mdec_fallbacks)
    g_conf.set_val("ec_mesh_chips", 8)
    try:
        assert cl.read("lint", "om")[:1] == b"m"
    finally:
        g_conf.rm_val("ec_mesh_chips")
        g_mesh.topology()
    assert mesh_decode_perf_counters().get(l_mdec_dispatches) > 0, \
        "degraded read never rode the meshed decode path — its " \
        "counter family would be lint-invisible"
    assert mesh_decode_perf_counters().get(l_mdec_fallbacks) == \
        fallbacks0, "the degraded read fell back off the mesh"
    c.revive_osd(victim)
    for _ in range(3):
        c.tick(dt=6.0)
    # one repair round through a regenerating pool so the `recovery`
    # counter families and the bytes-per-shard histogram register and
    # move — the lint below then covers them like any other family
    c.create_ec_pool("lintregen", k=3, m=2, pg_num=2,
                     plugin="regenerating", extra_profile={"d": "4"})
    assert cl.write_full("lintregen", "r", b"r" * 3000) == 0
    regen_pid = c.mon.osdmap.lookup_pg_pool_name("lintregen")
    victim = next(pg.acting[-1] for _pgid, pg in c.primary_pgs()
                  if pg.backend is not None and _pgid[0] == regen_pid)
    c.kill_osd(victim)
    c.mark_osd_down(victim)
    c.mark_osd_out(victim)
    for _ in range(6):
        c.tick(dt=1.0)
    from ceph_tpu.recovery import (l_recovery_repair_rounds,
                                   recovery_perf_counters)
    assert recovery_perf_counters().get(l_recovery_repair_rounds) > 0
    assert cl.read("lintregen", "r") == b"r" * 3000
    # one write through the DEVICE-RESIDENT path (fused encode+crc,
    # shard bodies kept in HBM) and one materializing read-back so the
    # memstore_device_* family registers AND moves — the lint below
    # then covers the zero-copy write path like any other family
    g_conf.set_val("os_memstore_device_bytes_max", 1 << 30)
    try:
        assert cl.write_full("lint", "od", b"z" * 16000) == 0
        assert cl.read("lint", "od") == b"z" * 16000
    finally:
        g_conf.rm_val("os_memstore_device_bytes_max")
    from ceph_tpu.os_store import memstore_device_perf_counters
    msd = memstore_device_perf_counters().dump()
    assert msd["crc_device"] > 0 and msd["materializations"] > 0, \
        "write never rode the device-resident path — its counter " \
        "family would be lint-invisible"
    # one mgr tick so the telemetry ring holds a post-IO sample and
    # the ceph_cluster_* rollup families render with real content
    c.tick(dt=1.0)
    return c, c.admin_socket.execute("prometheus metrics")


def _prom_name(raw: str) -> str:
    return re.sub(r"[^a-zA-Z0-9_:]", "_", raw)


def test_every_perf_counter_is_exported(cluster_and_text):
    """Every numeric counter of every registered logger renders as a
    ``ceph_daemon_<logger>_<counter>`` sample."""
    c, text = cluster_and_text
    sample_names = {line.split("{")[0].split(" ")[0]
                    for line in text.splitlines()
                    if line and not line.startswith("#")}
    missing = []
    dump = c.perf_collection.dump()
    assert dump, "empty perf collection"
    for logger, counters in sorted(dump.items()):
        if not isinstance(counters, dict):
            continue
        for cname, val in sorted(counters.items()):
            if not isinstance(val, (int, float)):
                # time-avg counters dump as {sum, avgcount}: the
                # renderer skips them by design (no scalar sample)
                continue
            want = f"ceph_daemon_{_prom_name(f'{logger}_{cname}')}"
            if want not in sample_names:
                missing.append(want)
    assert not missing, \
        f"{len(missing)} registered counters missing from the " \
        f"exposition: {missing[:10]}"


def test_every_histogram_family_is_exported(cluster_and_text):
    """Every registered PerfHistogram NAME renders as a ``# TYPE ...
    histogram`` family with _bucket/_sum/_count series."""
    from ceph_tpu.trace import g_perf_histograms
    _c, text = cluster_and_text
    types = {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _h, _t, name, typ = line.split(None, 3)
            types[name] = typ
    sample_names = {line.split("{")[0].split(" ")[0]
                    for line in text.splitlines()
                    if line and not line.startswith("#")}
    names = {hname for (_logger, hname), _h in g_perf_histograms.items()}
    assert names, "no histograms registered"
    missing = []
    for hname in sorted(names):
        fam = f"ceph_{_prom_name(hname)}"
        if types.get(fam) != "histogram":
            missing.append(f"{fam} (no TYPE histogram)")
            continue
        for sfx in ("_bucket", "_sum", "_count"):
            if f"{fam}{sfx}" not in sample_names:
                missing.append(f"{fam}{sfx}")
    assert not missing, \
        f"histogram families missing from the exposition: {missing[:10]}"


def test_known_new_families_covered_by_the_lint(cluster_and_text):
    """Canary: the lint actually sees the newest counter families
    (devprof, oplat) — if someone unregisters a logger the lint must
    not silently pass on an empty set."""
    c, _text = cluster_and_text
    assert "devprof" in c.perf_collection.dump()
    assert "oplat" in c.perf_collection.dump()
    # mesh-PR canary: the mesh logger is registered AND the fixture's
    # mesh write registered the per-chip occupancy family, so the
    # generic lints above really cover the mesh surfaces
    assert "mesh" in c.perf_collection.dump()
    assert c.perf_collection.dump()["mesh"]["dispatches"] > 0
    # control-plane canary (ceph_tpu/control): the controller's logger
    # is registered on every cluster, so ceph_daemon_control_* rides
    # the generic exposition/coverage lints above
    assert "control" in c.perf_collection.dump()
    assert "skipped_cooldown" in c.perf_collection.dump()["control"]
    # chaos-PR canaries: the scenario engine's logger and the elastic
    # mesh-membership family are registered on every cluster, so
    # ceph_daemon_chaos_* / ceph_daemon_mesh_membership_* ride the
    # generic exposition/coverage lints above
    assert "chaos" in c.perf_collection.dump()
    assert "accept_pass" in c.perf_collection.dump()["chaos"]
    assert "mesh_membership" in c.perf_collection.dump()
    assert "drained_reqs" in c.perf_collection.dump()["mesh_membership"]
    # zero-copy-PR canary: the memstore_device logger is registered on
    # every cluster and the fixture's residency write + read moved it,
    # so ceph_daemon_memstore_device_* rides the generic
    # exposition/coverage lints above
    assert "memstore_device" in c.perf_collection.dump()
    assert c.perf_collection.dump()["memstore_device"]["crc_device"] > 0
    assert c.perf_collection.dump()[
        "memstore_device"]["materializations"] > 0
    # meshed-READ-path canary: the mesh_decode logger is registered
    # and the fixture's degraded read moved it AND registered the
    # decode occupancy family, so the generic lints above really
    # cover the straggler-proof read path's surfaces
    assert "mesh_decode" in c.perf_collection.dump()
    assert c.perf_collection.dump()["mesh_decode"]["dispatches"] > 0
    assert "fallbacks" in c.perf_collection.dump()["mesh_decode"]
    # LRC canary: the layered code's repair counters are registered on
    # every cluster, so ceph_daemon_lrc_* rides the generic lints above
    assert {"local_repairs", "global_repairs"} <= \
        set(c.perf_collection.dump()["lrc"])
    assert "ceph_daemon_lrc_local_repairs" in _text
    assert "ceph_daemon_lrc_global_repairs" in _text
    from ceph_tpu.trace import g_perf_histograms
    from ceph_tpu.trace.oplat import stage_of_hist_name
    assert any(lg == "devprof" for (lg, _n), _h
               in g_perf_histograms.items())
    # the fixture's write/read registered per-stage oplat families on
    # the OSD daemons — so the generic histogram lint above is really
    # covering the stage-latency ledger's exposition
    oplat_stages = {stage_of_hist_name(n)
                    for (_lg, n), _h in g_perf_histograms.items()
                    if stage_of_hist_name(n)}
    assert {"admission", "class_queue", "device_call", "reply"} <= \
        oplat_stages, oplat_stages
    assert any(n == "dispatch_chip_occupancy_histogram"
               for (_lg, n), _h in g_perf_histograms.items())
    assert any(n == "mesh_decode_chip_occupancy_histogram"
               for (_lg, n), _h in g_perf_histograms.items())


def test_cluster_rollup_families_exported(cluster_and_text):
    """Telemetry-PR lint: every stage and rate in the mgr rollup
    snapshot renders as a ``ceph_cluster_*`` gauge — a new rollup
    series that skips the exporter fails tier-1, like a counter."""
    c, text = cluster_and_text
    roll = c.mgr.telemetry.rollup()
    assert roll["oplat_p99_usec"], "rollup carries no oplat stages"
    assert {"device_call", "class_queue", "reply"} <= \
        set(roll["oplat_p99_usec"]), roll["oplat_p99_usec"]
    missing = []
    for q in ("p50", "p99", "p999"):
        for stage in roll["oplat_p99_usec"]:
            want = f'ceph_cluster_oplat_{q}_usec{{stage="{stage}"}}'
            if want not in text:
                missing.append(want)
    assert set(roll["rates"]) == {"ops", "h2d_bytes", "d2h_bytes",
                                  "admission_rejections"}
    for key in roll["rates"]:
        if f"ceph_cluster_rate_{key} " not in text:
            missing.append(f"ceph_cluster_rate_{key}")
    assert not missing, \
        f"cluster rollup series missing from the exposition: {missing}"


def test_slo_and_telemetry_options_documented():
    """Options-coverage lint: every ``mgr_slo_*`` / ``mgr_telemetry_*``
    option must be documented in docs/OBSERVABILITY.md's SLO option
    table — an objective an operator cannot discover is an objective
    nobody sets."""
    import os
    from ceph_tpu.common.config import g_conf
    doc_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "OBSERVABILITY.md")
    with open(doc_path) as f:
        doc = f.read()
    opts = sorted(n for n in g_conf.schema
                  if n.startswith(("mgr_slo_", "mgr_telemetry_")))
    assert opts, "no SLO/telemetry options registered"
    missing = [n for n in opts if n not in doc]
    assert not missing, \
        f"undocumented mgr_slo_/mgr_telemetry_ options: {missing}"


def test_rmw_counter_is_exported(cluster_and_text):
    """The read-modify-write op counter (the benchmark's
    ``writes_not_rmw`` check reads it) renders with the ops it counted."""
    from ceph_tpu.osd.ec_backend import (l_pipeline_rmw_ops,
                                         pipeline_perf_counters)
    _c, text = cluster_and_text
    n = pipeline_perf_counters().get(l_pipeline_rmw_ops)
    assert n >= 1
    samples = [line for line in text.splitlines()
               if line.startswith("ceph_daemon_pipeline_rmw_ops")]
    assert samples and float(samples[0].rsplit(" ", 1)[1]) >= 1, samples
