"""Rateless coded mesh encode (ceph_tpu/mesh/rateless.py) — the
straggler-proof flush's acceptance gates.

- ``ec_mesh_rateless`` off (the default) is the block-sharded SPMD
  path by construction; on, every flushed encode group over-decomposes
  into coded row-blocks and completes from the first sufficient
  subset;
- byte identity: rateless-coded groups vs the single-device oracle
  across randomized (k, m, technique, chunk, stripes) mixes including
  non-multiple-of-mesh totals, with skew sampling on EVERY flush;
- the chaos-style ISSUE acceptance: a hard ``mesh.chip_fail``
  mid-flush completes every op from the surviving subset — host
  re-solves, zero single-device fallbacks — and only when the
  survivors cannot span does the flush degrade down the ladder
  (single-device, then host twin), still byte-identical;
- scoreboard feedback: a SUSPECT chip is deweighted to parity-only
  (zero real stripes on the occupancy table) and the flush stops
  waiting for it; once healed it clears through its parity probes;
- a rateless cluster twin stores shard BODIES byte-identical to the
  unprotected twin;
- observability: the ``mesh_rateless_*`` counter family on perf dump
  / ``dispatch dump`` / Prometheus, the rateless pane's geometry;
- fence-count gate extended: the rateless path adds ZERO
  ``block_until_ready`` beyond the existing drain policy (readiness
  polling + np.asarray fetches only), sampling on or off.
"""
import numpy as np
import pytest

from ceph_tpu.common.config import g_conf
from ceph_tpu.dispatch import g_dispatcher
from ceph_tpu.ec.isa import ErasureCodeIsa
from ceph_tpu.ec.tpu_plugin import ErasureCodeTpu
from ceph_tpu.fault import g_faults
from ceph_tpu.mesh import (g_chipstat, g_mesh, mesh_perf_counters,
                           rateless_perf_counters)
from ceph_tpu.mesh.rateless import (l_rl_chip_failures,
                                    l_rl_coded_tasks, l_rl_flushes,
                                    l_rl_host_resolves,
                                    l_rl_insufficient,
                                    l_rl_subset_completions,
                                    l_rl_suspect_deweights,
                                    l_rl_wasted_blocks)
from ceph_tpu.mesh.runtime import l_mesh_dispatches, l_mesh_fallbacks
from ceph_tpu.osd.ecutil import encode as eu_encode, stripe_info_t


def _reset_rateless_state():
    from ceph_tpu.fault import g_breakers
    g_faults.clear()
    g_dispatcher.flush()
    for name in ("ec_mesh_chips", "ec_mesh_rateless",
                 "ec_mesh_rateless_tasks", "ec_mesh_skew_sample_every",
                 "ec_mesh_skew_threshold", "ec_dispatch_batch_max",
                 "ec_dispatch_batch_window_us"):
        g_conf.rm_val(name)
    g_mesh.topology()
    g_chipstat.reset()
    g_breakers.reset()


@pytest.fixture
def rateless_conf():
    """Every test starts and leaves with the dispatcher drained, the
    options at their defaults, no fault armed, the scoreboard zeroed
    and the mesh torn down.  Resetting on entry too matters: a SUSPECT
    chip or a slowdown fault left by an earlier test in the same worker
    puts a parity block first in the drain and moves host_resolves."""
    _reset_rateless_state()
    yield
    _reset_rateless_state()


def _rateless_on(chips=8, sample_every=0, tasks=0):
    g_conf.set_val("ec_mesh_chips", chips)
    g_conf.set_val("ec_dispatch_batch_window_us", 10_000_000)
    g_conf.set_val("ec_dispatch_batch_max", 64)
    g_conf.set_val("ec_mesh_rateless", True)
    if tasks:
        g_conf.set_val("ec_mesh_rateless_tasks", tasks)
    g_conf.set_val("ec_mesh_skew_sample_every", sample_every)


def _mk_impl(plugin, k, m, technique):
    impl = plugin()
    impl.init({"k": str(k), "m": str(m), "technique": technique})
    return impl


def _same_shards(a, b):
    assert sorted(a) == sorted(b)
    for i in a:
        assert np.asarray(a[i]).tobytes() == np.asarray(b[i]).tobytes(), \
            f"shard {i} differs"


def test_rateless_off_by_default(rateless_conf):
    """The default is the SPMD path: a mesh flush with rateless off
    moves no rateless counters."""
    assert bool(g_conf.get_val("ec_mesh_rateless")) is False
    g_conf.set_val("ec_mesh_chips", 8)
    g_conf.set_val("ec_dispatch_batch_window_us", 10_000_000)
    g_conf.set_val("ec_dispatch_batch_max", 64)
    pc = rateless_perf_counters()
    before = pc.get(l_rl_flushes)
    impl = _mk_impl(ErasureCodeTpu, 4, 2, "reed_sol_van")
    sinfo = stripe_info_t(4, 4 * 1024)
    d = (np.arange(2 * 4 * 1024) % 251).astype(np.uint8)
    f = g_dispatcher.submit_encode(sinfo, impl, d, set(range(6)))
    g_dispatcher.flush()
    _same_shards(f.result(), eu_encode(sinfo, impl, d, set(range(6))))
    assert pc.get(l_rl_flushes) == before


MIX = [
    (ErasureCodeTpu, 4, 2, "reed_sol_van"),
    (ErasureCodeTpu, 8, 4, "reed_sol_van"),
    (ErasureCodeIsa, 3, 2, "cauchy"),
    (ErasureCodeIsa, 6, 3, "reed_sol_van"),
]


@pytest.mark.parametrize("seed", [7, 31, 61])
def test_rateless_byte_identity_property(rateless_conf, seed):
    """Rateless-coded groups vs the single-device oracle across
    randomized (k, m, technique, chunk size, stripe count) mixes —
    stripe totals deliberately NOT multiples of the mesh size, mixed
    chunk sizes sharing a bucket, and skew sampling probing EVERY
    flush (the drain-fed scoreboard must never touch the data
    path)."""
    _rateless_on(chips=8, sample_every=1)
    rng = np.random.default_rng(seed)
    impls = [_mk_impl(p, k, m, t) for p, k, m, t in MIX]
    specs = []
    for _ in range(18):
        impl = impls[rng.integers(0, len(impls))]
        k, m = impl.k, impl.m
        chunk = int(rng.choice([512, 768, 1024, 1536]))
        stripes = int(rng.integers(1, 7))     # totals rarely % 8 == 0
        sinfo = stripe_info_t(k, k * chunk)
        data = rng.integers(0, 256, size=stripes * k * chunk,
                            dtype=np.uint8)
        specs.append((sinfo, impl, data, set(range(k + m))))
    oracles = [eu_encode(s, i, d, w) for s, i, d, w in specs]
    pc = rateless_perf_counters()
    before = pc.get(l_rl_flushes)
    futs = [g_dispatcher.submit_encode(s, i, d, w)
            for s, i, d, w in specs]
    g_dispatcher.flush()
    for f, oracle in zip(futs, oracles):
        _same_shards(f.result(), oracle)
    # the rateless path actually ran (not a silent SPMD/single pass)
    assert pc.get(l_rl_flushes) > before
    assert g_chipstat.summary()["probes"] > 0


def test_chip_fail_completes_from_surviving_subset(rateless_conf):
    """THE chaos-style ISSUE acceptance: one chip hard-dead mid-flush
    (mesh.chip_fail) is just an erasure — every op completes from the
    surviving subset, byte-identical, with host re-solves and ZERO
    single-device fallbacks."""
    _rateless_on(chips=8)
    impl = _mk_impl(ErasureCodeTpu, 4, 2, "reed_sol_van")
    sinfo = stripe_info_t(4, 4 * 1024)
    want = set(range(6))
    rng = np.random.default_rng(3)

    def flush_checked(n=3):
        payloads = [rng.integers(0, 256, size=3 * 4 * 1024,
                                 dtype=np.uint8) for _ in range(n)]
        oracles = [eu_encode(sinfo, impl, p, want) for p in payloads]
        futs = [g_dispatcher.submit_encode(sinfo, impl, p, want)
                for p in payloads]
        g_dispatcher.flush()
        for f, o in zip(futs, oracles):
            _same_shards(f.result(), o)

    flush_checked()                  # warmup, healthy
    pc = rateless_perf_counters()
    mpc = mesh_perf_counters()
    fb0 = mpc.get(l_mesh_fallbacks)
    hr0 = pc.get(l_rl_host_resolves)
    cf0 = pc.get(l_rl_chip_failures)
    sc0 = pc.get(l_rl_subset_completions)
    g_faults.inject("mesh.chip_fail", mode="always", match="chip=3/")
    try:
        flush_checked()
        flush_checked()
    finally:
        g_faults.clear("mesh.chip_fail")
    assert pc.get(l_rl_host_resolves) > hr0, \
        "the dead chip's systematic block was never re-solved"
    assert pc.get(l_rl_chip_failures) >= cf0 + 2
    assert pc.get(l_rl_subset_completions) > sc0
    assert mpc.get(l_mesh_fallbacks) == fb0, \
        "a sufficient subset answered — the single-device fallback " \
        "must not be reached"


def test_insufficient_survivors_degrade_down_the_ladder(rateless_conf):
    """When fewer than a sufficient subset of chips answer (every chip
    failed), the flush degrades to the single-device path — the next
    ladder rung, not an op failure — and outputs stay byte-identical."""
    from ceph_tpu.fault import g_breakers
    _rateless_on(chips=8)
    impl = _mk_impl(ErasureCodeTpu, 4, 2, "reed_sol_van")
    sinfo = stripe_info_t(4, 4 * 1024)
    want = set(range(6))
    rng = np.random.default_rng(5)
    payloads = [rng.integers(0, 256, size=2 * 4 * 1024, dtype=np.uint8)
                for _ in range(3)]
    oracles = [eu_encode(sinfo, impl, p, want) for p in payloads]
    pc = rateless_perf_counters()
    mpc = mesh_perf_counters()
    fb0 = mpc.get(l_mesh_fallbacks)
    ins0 = pc.get(l_rl_insufficient)
    g_faults.inject("mesh.chip_fail", mode="always")   # every chip
    try:
        futs = [g_dispatcher.submit_encode(sinfo, impl, p, want)
                for p in payloads]
        g_dispatcher.flush()
        for f, o in zip(futs, oracles):
            _same_shards(f.result(), o)
    finally:
        g_faults.clear()
        g_breakers.reset()
    assert pc.get(l_rl_insufficient) > ins0
    assert mpc.get(l_mesh_fallbacks) > fb0


def test_suspect_chip_deweighted_to_parity_only(rateless_conf):
    """The scoreboard feedback loop (the telemetry finally actuates):
    once a chip is SUSPECT its placement carries zero real stripes —
    parity only — and the flush completes without waiting for it even
    though it is still slow."""
    import time
    _rateless_on(chips=8, sample_every=1)
    g_conf.set_val("ec_mesh_skew_threshold", 3.0)
    impl = _mk_impl(ErasureCodeTpu, 4, 2, "reed_sol_van")
    sinfo = stripe_info_t(4, 4 * 1024)
    want = set(range(6))
    rng = np.random.default_rng(11)

    def flush_once():
        payloads = [rng.integers(0, 256, size=2 * 4 * 1024,
                                 dtype=np.uint8) for _ in range(3)]
        oracles = [eu_encode(sinfo, impl, p, want) for p in payloads]
        t0 = time.perf_counter()
        futs = [g_dispatcher.submit_encode(sinfo, impl, p, want)
                for p in payloads]
        g_dispatcher.flush()
        for f, o in zip(futs, oracles):
            _same_shards(f.result(), o)
        return time.perf_counter() - t0

    flush_once()                     # warmup
    g_chipstat.reset()
    pc = rateless_perf_counters()
    g_faults.inject("mesh.chip_slowdown", mode="always",
                    match="chip=5/", delay_us=100_000)
    try:
        for _ in range(8):
            flush_once()
            if g_chipstat.suspects():
                break
        assert [s["chip"] for s in g_chipstat.suspects()] == [5]
        dw0 = pc.get(l_rl_suspect_deweights)
        before = {i: v["stripes"] for i, v in g_mesh.per_chip().items()}
        wall = flush_once()
        after = {i: v["stripes"] for i, v in g_mesh.per_chip().items()}
        assert after[5] == before.get(5, 0), \
            "a SUSPECT chip received real stripes"
        assert sum(after.values()) > sum(before.values())
        assert pc.get(l_rl_suspect_deweights) > dw0
        # the still-slow suspect (100 ms) never gated the flush
        assert wall < 0.09, f"flush waited for the suspect: {wall}"
    finally:
        g_faults.clear("mesh.chip_slowdown")


def test_cluster_twin_stored_shards_byte_identical(rateless_conf):
    """A rateless cluster stores shard BODIES byte-identical to the
    unprotected twin across a write/overwrite/append mix — the ISSUE's
    stored-bytes receipt, one level below the dispatch outputs."""
    from ceph_tpu.cluster import MiniCluster

    def shard_bodies(c):
        out = {}
        for i, osd in c.osds.items():
            for cid in osd.store.list_collections():
                if "_meta" in cid or "s" not in cid.split(".")[-1]:
                    continue
                for ho in osd.store.list_objects(cid):
                    out[(i, cid, str(ho))] = osd.store.read(cid, ho)
        return out

    def run(rateless: bool):
        if rateless:
            _rateless_on(chips=8)
            g_conf.set_val("ec_dispatch_batch_window_us", 200_000)
        else:
            for name in ("ec_mesh_chips", "ec_mesh_rateless",
                         "ec_dispatch_batch_max",
                         "ec_dispatch_batch_window_us"):
                g_conf.rm_val(name)
        g_mesh.topology()
        c = MiniCluster(n_osds=6)
        c.create_ec_pool("rltwin", k=3, m=2, pg_num=4)
        cl = c.client("client.rl")
        rng = np.random.default_rng(42)
        expected = {}
        for i in range(4):
            body = bytes(rng.integers(0, 256, 9000 + 4111 * i,
                                      dtype=np.uint8))
            assert cl.write_full("rltwin", f"o{i}", body) == 0
            expected[f"o{i}"] = body
        tail = bytes(rng.integers(0, 256, 5000, dtype=np.uint8))
        assert cl.append("rltwin", "o1", tail) == 0
        expected["o1"] = expected["o1"] + tail
        for oid, body in expected.items():
            assert cl.read("rltwin", oid) == body, (rateless, oid)
        return shard_bodies(c)

    pc = rateless_perf_counters()
    before = pc.get(l_rl_flushes)
    coded = run(rateless=True)
    assert pc.get(l_rl_flushes) > before
    plain = run(rateless=False)
    assert set(coded) == set(plain)
    diffs = [key for key in plain
             if bytes(coded[key]) != bytes(plain[key])]
    assert not diffs, f"{len(diffs)} shard bodies differ: {diffs[:5]}"


def test_rateless_task_knob_and_dump_pane(rateless_conf):
    """``ec_mesh_rateless_tasks`` reads live (geometry rebuilt on the
    next flush), clamps to mesh size + 1, and the rateless pane rides
    ``dispatch dump``'s mesh block with options, geometry and the
    counter family."""
    _rateless_on(chips=8, tasks=12)
    impl = _mk_impl(ErasureCodeTpu, 4, 2, "reed_sol_van")
    sinfo = stripe_info_t(4, 4 * 1024)
    d = (np.arange(2 * 4 * 1024) % 251).astype(np.uint8)
    f = g_dispatcher.submit_encode(sinfo, impl, d, set(range(6)))
    g_dispatcher.flush()
    _same_shards(f.result(), eu_encode(sinfo, impl, d, set(range(6))))
    pane = g_dispatcher.dump()["mesh"]["rateless"]
    assert pane["options"]["ec_mesh_rateless"] is True
    assert pane["options"]["ec_mesh_rateless_tasks"] == 12
    assert pane["n_sys"] == 8 and pane["n_parity"] == 4
    assert pane["counters"]["flushes"] > 0
    assert pane["counters"]["coded_tasks"] > 0
    # under-asking clamps to one parity block (redundancy never zero)
    g_conf.set_val("ec_mesh_rateless_tasks", 3)
    f = g_dispatcher.submit_encode(sinfo, impl, d, set(range(6)))
    g_dispatcher.flush()
    f.result()
    pane = g_dispatcher.dump()["mesh"]["rateless"]
    assert pane["n_parity"] == 1


def test_wasted_blocks_account_the_bandwidth_price(rateless_conf):
    """Healthy flushes complete before consuming the parity blocks:
    wasted_blocks counts exactly the protection's bandwidth price and
    host_resolves stays zero (no erasures to solve around)."""
    _rateless_on(chips=8)
    impl = _mk_impl(ErasureCodeTpu, 4, 2, "reed_sol_van")
    sinfo = stripe_info_t(4, 4 * 1024)
    want = set(range(6))
    d = (np.arange(2 * 4 * 1024) % 251).astype(np.uint8)
    f = g_dispatcher.submit_encode(sinfo, impl, d, want)
    g_dispatcher.flush()
    f.result()                       # warmup builds plans
    pc = rateless_perf_counters()
    w0, c0, h0 = (pc.get(l_rl_wasted_blocks), pc.get(l_rl_coded_tasks),
                  pc.get(l_rl_host_resolves))
    f = g_dispatcher.submit_encode(sinfo, impl, d, want)
    g_dispatcher.flush()
    f.result()
    coded = pc.get(l_rl_coded_tasks) - c0
    wasted = pc.get(l_rl_wasted_blocks) - w0
    assert coded == 10               # 8 systematic + 2 parity (auto)
    assert 0 < wasted <= 2, wasted   # at most the parity overhead
    assert pc.get(l_rl_host_resolves) == h0


def test_zero_syncs_on_rateless_path(rateless_conf, monkeypatch):
    """Fence-count gate extended (ISSUE satellite): the rateless path
    adds ZERO block_until_ready beyond the existing drain policy —
    readiness polling plus np.asarray fetches only — with sampling
    off AND with probes on every flush."""
    import jax
    _rateless_on(chips=8, sample_every=0)
    impl = _mk_impl(ErasureCodeTpu, 4, 2, "reed_sol_van")
    sinfo = stripe_info_t(4, 4 * 1024)
    want = set(range(6))
    d = (np.arange(3 * 4 * 1024) % 251).astype(np.uint8)
    f = g_dispatcher.submit_encode(sinfo, impl, d, want)
    g_dispatcher.flush()
    f.result()                       # compile warmup
    calls = {"n": 0}
    real = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    for sample_every in (0, 1):
        g_conf.set_val("ec_mesh_skew_sample_every", sample_every)
        f = g_dispatcher.submit_encode(sinfo, impl, d, want)
        g_dispatcher.flush()
        f.result()
        assert calls["n"] == 0, \
            f"rateless path synced (sample_every={sample_every})"


def test_rateless_counters_on_prometheus(rateless_conf):
    """The mesh_rateless_* family renders on the mgr's Prometheus
    surface (golden-test satellite) and on perf dump."""
    from ceph_tpu.cluster import MiniCluster
    _rateless_on(chips=8)
    g_conf.set_val("ec_dispatch_batch_window_us", 200_000)
    c = MiniCluster(n_osds=6)
    c.create_ec_pool("rlprom", k=3, m=2, pg_num=4)
    cl = c.client("client.rlprom")
    assert cl.write_full("rlprom", "o", b"r" * 60000) == 0
    prom = c.admin_socket.execute("prometheus metrics")
    for cname in ("flushes", "coded_tasks", "parity_tasks",
                  "wasted_blocks", "subset_completions",
                  "host_resolves", "suspect_deweights"):
        line = next((ln for ln in prom.splitlines()
                     if ln.startswith(f"ceph_daemon_mesh_rateless_"
                                      f"{cname} ")), None)
        assert line is not None, f"mesh_rateless_{cname} not exported"
    flushes = next(float(ln.split()[-1]) for ln in prom.splitlines()
                   if ln.startswith("ceph_daemon_mesh_rateless_"
                                    "flushes "))
    assert flushes > 0
