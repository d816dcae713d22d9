"""LogMonitor + ConfigKeyService: paxos-replicated mon services.

The reference runs several PaxosServices over one Paxos instance
(src/mon/PaxosService.h): LogMonitor commits daemons' clog entries into
a replicated history (src/mon/LogMonitor.cc) and ConfigKeyService keeps
a replicated key-value store (src/mon/ConfigKeyService.cc).  Here both
ride the same consensus as the OSDMap: their payloads travel inside
committed Incrementals, so they are exactly as failover-proof as the
map itself.
"""
import json

from ceph_tpu.cluster import MiniCluster


def test_cluster_log_records_events():
    c = MiniCluster(n_osds=4)
    c.create_replicated_pool("lp", size=3, pg_num=4)
    c.network.pump()
    msgs = [e[3] for e in c.mon.cluster_log]
    assert any("pool 'lp' created" in m for m in msgs)
    c.mark_osd_down(2)
    msgs = [e[3] for e in c.mon.cluster_log]
    assert any("osd.2 marked down" in m for m in msgs)
    # level filter
    wrn = c.mon.log_last(50, level="WRN")
    assert wrn and all(e[2] == "WRN" for e in wrn)


def test_config_key_roundtrip_and_persistence(tmp_path):
    c = MiniCluster(n_osds=3)
    c.mon.config_key_set("mgr/balancer/mode", "upmap")
    c.mon.config_key_set("rgw/zone", "us-east")
    c.network.pump()
    assert c.mon.config_key_get("mgr/balancer/mode") == "upmap"
    c.mon.config_key_rm("rgw/zone")
    assert c.mon.config_key_get("rgw/zone") is None
    assert c.mon.config_key_dump() == {"mgr/balancer/mode": "upmap"}
    # state is rebuilt from the committed epoch history on restore
    c.checkpoint(str(tmp_path / "ck"))
    c2 = MiniCluster.restore(str(tmp_path / "ck"))
    assert c2.mon.config_key_get("mgr/balancer/mode") == "upmap"
    assert c2.mon.config_key_get("rgw/zone") is None
    # and the cluster log history came back too
    assert c2.mon.cluster_log == c.mon.cluster_log


def test_services_replicate_to_peons_and_survive_failover():
    c = MiniCluster(n_osds=4, n_mons=3)
    c.create_replicated_pool("p", size=3, pg_num=4)
    c.mon.config_key_set("flag/one", "1")
    c.mon.log_entry("admin", "INF", "hello quorum")
    c.mon.flush_log()
    c.network.pump()
    for m in c.mons:
        assert m.config_key_get("flag/one") == "1"
        assert any(e[3] == "hello quorum" for e in m.cluster_log)
    # leader dies: the successor still has both services' state
    c.kill_mon(0)
    for _ in range(6):
        c.tick(dt=6.0)
    leader = c.mon
    assert leader.name != "mon.0"
    assert leader.config_key_get("flag/one") == "1"
    assert any(e[3] == "hello quorum" for e in leader.cluster_log)
    # and keeps committing new service state
    leader.config_key_set("flag/two", "2")
    c.network.pump()
    for m in c.mons:
        if m.name == "mon.0":
            continue
        assert m.config_key_get("flag/two") == "2"


def test_scrub_inconsistency_reaches_cluster_log():
    c = MiniCluster(n_osds=4)
    c.create_replicated_pool("p", size=3, pg_num=4)
    cl = c.client("client.s")
    assert cl.write_full("p", "obj", b"clean bytes" * 50) == 0
    # corrupt one NON-primary replica at rest, then deep-scrub
    _pg, primary = cl._calc_target(cl.lookup_pool("p"), "obj")
    hit = 0
    for osd in c.osds.values():
        if osd.osd_id == primary:
            continue
        for cid in osd.store.list_collections():
            if "_meta" in cid:
                continue
            for ho in osd.store.list_objects(cid):
                if ho.oid == "obj" and hit == 0:
                    obj = osd.store.colls[cid][ho]
                    obj.data = bytearray(obj.data)
                    obj.data[3] ^= 0xFF
                    hit += 1
    assert hit == 1
    c.scrub(deep=True)
    c.tick()        # the tick flushes pending clog entries
    errs = c.mon.log_last(20, level="ERR")
    assert any("scrub" in e[3] and "inconsistent" in e[3] for e in errs)
    assert cl.read("p", "obj") == b"clean bytes" * 50


def test_osd_clog_survives_mon_death_without_duplicates():
    """Daemons broadcast clog to every mon (a single-target send dies
    with that mon); the leader dedups the fan-in so the entry commits
    exactly once.  With mon.0 dead, the entry must still land."""
    c = MiniCluster(n_osds=4, n_mons=3)
    c.create_replicated_pool("p", size=3, pg_num=4)
    cl = c.client("client.s")
    assert cl.write_full("p", "obj", b"payload" * 40) == 0
    c.kill_mon(0)
    for _ in range(6):
        c.tick(dt=6.0)
    assert c.mon.name != "mon.0"
    _pg, primary = cl._calc_target(cl.lookup_pool("p"), "obj")
    c.osds[primary].clog("ERR", "synthetic inconsistency report")
    c.network.pump()
    c.tick()
    hits = [e for e in c.mon.cluster_log
            if e[3] == "synthetic inconsistency report"]
    assert len(hits) == 1, hits


def test_ceph_cli_log_and_config_key(tmp_path, capsys):
    from ceph_tpu.tools import ceph_cli
    c = MiniCluster(n_osds=3)
    c.create_replicated_pool("p", size=2, pg_num=4)
    c.mon.config_key_set("a/b", "c")
    c.network.pump()
    ckpt = str(tmp_path / "ck")
    c.checkpoint(ckpt)

    def run(*argv):
        rc = ceph_cli.main(["--cluster", ckpt, *argv])
        return rc, capsys.readouterr().out

    rc, out = run("log", "last", "50")
    assert rc == 0 and "pool 'p' created" in out
    rc, out = run("config-key", "dump")
    assert rc == 0 and json.loads(out) == {"a/b": "c"}
    rc, out = run("config-key", "get", "a/b")
    assert rc == 0 and out.strip() == "c"
    rc, _ = run("config-key", "exists", "a/b")
    assert rc == 0
    rc, _ = run("config-key", "get", "missing")
    assert rc == 1
    rc, _ = run("log", "tail")
    assert rc == 1
    rc, _ = run("log", "last", "abc")
    assert rc == 1
