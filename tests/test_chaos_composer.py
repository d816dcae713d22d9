"""Composed-chaos scenario engine (ceph_tpu/chaos) + elastic mesh
membership (injectargs-live ``ec_mesh_chips``).

The tentpole's acceptance gates live here:

- same seed => IDENTICAL storyline (the composer consumes exactly one
  seeded stream and nothing else — no wall clock, no ambient state);
- the two nastiest found seeds are pinned as tier-1 smokes and must
  pass the engine's UNIVERSAL acceptance end to end: every op
  byte-exact, every expected health check raises AND clears, every
  raise leaves a finalized incident bundle whose gseq timeline tells
  the injected storyline back, zero wedges, zero operator action;
- the ISSUE-mandated storm+straggler+abusive combination completes the
  same way with the legs forced;
- ``ec_mesh_chips`` is injectargs-live: a mid-traffic retire drains
  in-flight dispatch on the OLD mesh (zero lost flushes, zero
  single-device fallbacks), a re-add takes real stripes within ONE
  flush, both byte-exact, both journaled as first-class
  mesh_chip_retire / mesh_chip_add events;
- the fault-site catalog is machine-readable (``sites()`` /
  ``fault list format=json``) and every site is documented in
  docs/ROBUSTNESS.md (the docs lint).

The N-seed soak scales with ``CEPH_TPU_SOAK_SEEDS`` (slow tier).
"""
import os

import numpy as np
import pytest

from ceph_tpu.chaos import (LEG_BUILDERS, ScenarioSpec, compose_scenario,
                            leg_names, run_scenario, run_seed)
from ceph_tpu.common.config import g_conf
from ceph_tpu.fault import g_breakers, g_faults
from ceph_tpu.trace.journal import g_journal

# the two nastiest storylines the seed scan surfaced, pinned forever
# (recomposed when the leg catalog grew to 11): 24 composes a hard
# chip-failure burst, probabilistic device errors AND a recovery storm;
# 103 loses incident captures under a 30ms straggler while the mesh
# retires and re-adds chips mid-flight
PINNED_SEEDS = (24, 103)

TOUCHED = (
    "ec_mesh_chips", "ec_mesh_rateless", "ec_mesh_rateless_tasks",
    "ec_mesh_skew_sample_every", "ec_mesh_skew_threshold",
    "ec_dispatch_batch_max", "ec_dispatch_batch_window_us",
    "mgr_control_enable", "mgr_control_cooldown_ticks",
    "chaos_storyline_legs_max", "chaos_settle_ticks_max",
)


@pytest.fixture(autouse=True)
def _clean():
    from ceph_tpu.dispatch import g_dispatcher
    from ceph_tpu.mesh import g_chipstat, g_mesh
    g_journal.reset()
    saved = {n: g_conf.values.get(n) for n in TOUCHED}
    yield
    for n, v in saved.items():
        if v is None:
            g_conf.rm_val(n)
        else:
            g_conf.set_val(n, v)
    g_faults.clear()
    g_breakers.reset()
    g_dispatcher.flush()
    g_mesh.topology()
    g_chipstat.reset()
    g_journal.reset()


# ---- the composer ----------------------------------------------------------
def test_same_seed_identical_schedule():
    """Determinism is the contract: one seed, one storyline — value
    equality across independent compositions, stable dump, and the
    legs-forced variant is just as reproducible."""
    for seed in (0, 7, 24, 103, 20260807):
        a, b = compose_scenario(seed), compose_scenario(seed)
        assert a == b, f"seed {seed} composed two different storylines"
        assert a.dump() == b.dump()
        assert isinstance(a, ScenarioSpec) and a.seed == seed
        assert a.events == tuple(sorted(
            a.events, key=lambda e: (e.round, e.action, e.detail)))
    f1 = compose_scenario(5, legs=("chip_straggler", "recovery_storm"))
    f2 = compose_scenario(5, legs=("chip_straggler", "recovery_storm"))
    assert f1 == f2
    assert f1.legs == ("chip_straggler", "recovery_storm")
    # different seeds must be able to differ (not a constant composer)
    assert any(compose_scenario(s) != compose_scenario(s + 1)
               for s in range(5))


def test_composer_samples_only_known_primitives():
    """Every sampled storyline stays inside the primitive inventory:
    leg names from the catalog, fault sites from the registry — and an
    unknown leg is a loud error, not a silent skip."""
    sites = set(g_faults.sites())
    for seed in range(40):
        spec = compose_scenario(seed)
        assert set(spec.legs) <= set(leg_names())
        assert 1 <= len(spec.legs) <= \
            int(g_conf.get_val("chaos_storyline_legs_max"))
        for ev in spec.events:
            d = dict(ev.detail)
            if ev.action in ("fault_arm", "fault_clear"):
                assert d["site"] in sites, \
                    f"seed {seed} schedules unknown site {d['site']}"
    with pytest.raises(ValueError):
        compose_scenario(1, legs=("not_a_leg",))


def test_legs_max_option_is_live():
    """chaos_storyline_legs_max caps the sampled leg count (the
    composer reads it at compose time, injectargs-live)."""
    g_conf.set_val("chaos_storyline_legs_max", 1)
    assert all(len(compose_scenario(s).legs) == 1 for s in range(20))


# ---- fault-site enumeration (the composer's primitive inventory) -----------
def test_fault_sites_api_and_json_listing():
    """sites() is a machine-readable name->description catalog,
    list_sites() the sorted `fault list format=json` shape, and both
    agree with the human pane."""
    sites = g_faults.sites()
    assert len(sites) >= 10
    assert all(isinstance(k, str) and isinstance(v, str) and v
               for k, v in sites.items())
    sites["bogus"] = "x"                     # a copy, not the catalog
    assert "bogus" not in g_faults.sites()
    rows = g_faults.list_sites()
    assert [r["name"] for r in rows] == sorted(g_faults.sites())
    g_faults.inject("msg.drop", mode="once", match="MOSDOp ")
    armed = {r["name"]: r["armed"] for r in g_faults.list_sites()}
    assert armed["msg.drop"] is not None
    assert armed["msg.drop"]["mode"] == "once"
    assert all(v is None for s, v in armed.items() if s != "msg.drop")
    g_faults.clear()
    assert set(g_faults.dump()["sites"]) == set(g_faults.sites())


def test_every_fault_site_documented_in_robustness():
    """The docs lint: a fault site that isn't in docs/ROBUSTNESS.md is
    an undocumented operator surface — adding a site requires adding
    its row to the catalog table."""
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "docs", "ROBUSTNESS.md")
    with open(path) as f:
        docs = f.read()
    missing = sorted(s for s in g_faults.sites() if s not in docs)
    assert not missing, \
        f"fault sites missing from docs/ROBUSTNESS.md: {missing}"


# ---- the pinned tier-1 storyline smokes ------------------------------------
@pytest.mark.parametrize("seed", PINNED_SEEDS)
def test_pinned_seed_passes_universal_acceptance(seed):
    """The nastiest found seeds, end to end on a real cluster: the
    engine's whole acceptance conjunction must hold with zero operator
    action."""
    r = run_seed(seed)
    assert r["byte_exact"], r
    assert not r["wedged"], r
    assert r["storyline_told"], r
    assert r["all_raises_resolved"], r
    for chk, row in r["checks"].items():
        assert all(row.values()), (chk, row)
    assert r["mesh_fallbacks"] == 0, r
    assert r["accepted"], r


def test_issue_storyline_storm_straggler_abusive():
    """The mandated composition: recovery storm + straggling chip +
    abusive client, forced legs, one seed — completes byte-exact with
    zero operator action, the finalized bundle timeline contains the
    injected events in causal order, and the same seed reproduces the
    exact schedule."""
    legs = ("abusive_client", "chip_straggler", "recovery_storm")
    spec = compose_scenario(20260807, legs=legs)
    assert spec == compose_scenario(20260807, legs=legs)
    assert spec.legs == legs
    assert "TPU_MESH_SKEW" in spec.expected_checks
    assert spec.rate_multipliers            # the abusive dial engaged
    r = run_scenario(spec)
    assert r["accepted"], r
    row = r["checks"]["TPU_MESH_SKEW"]
    # raise, clear, and a finalized bundle whose gseq-ordered timeline
    # tells the storyline back (fault fire -> suspect mark -> raise ->
    # clear, strictly increasing gseq) — _bundle_ok's chain contract
    assert row == {"raised": True, "cleared": True, "bundle_ok": True}
    assert any(b["state"] == "resolved" and b["trigger"] == "TPU_MESH_SKEW"
               for b in r["incidents"]["bundles"]), r["incidents"]


def test_issue_storyline_degraded_read_under_straggler():
    """The degraded-read storyline: a dead OSD forces every read of its
    objects through EC decode while one chip straggles 30ms, a second
    chip fails hard and shard reads return EIO — the nastiest seed the
    forced-leg scan surfaced (28: kill at round 1, four chip failures,
    seven EIOs, straggler and failing chip distinct and overlapping).
    Decode groups must ride the mesh throughout (no single-device
    fallbacks), stay byte-exact, and the skew check must raise, clear
    and finalize its bundle with zero operator action."""
    from ceph_tpu.mesh import mesh_decode_perf_counters
    from ceph_tpu.mesh.runtime import l_mdec_dispatches, l_mdec_fallbacks
    legs = ("chip_fail", "degraded_read_straggler", "shard_eio")
    spec = compose_scenario(28, legs=legs)
    assert spec == compose_scenario(28, legs=legs)
    assert spec.legs == legs
    assert "TPU_MESH_SKEW" in spec.expected_checks
    before = mesh_decode_perf_counters().get(l_mdec_dispatches)
    fb_before = mesh_decode_perf_counters().get(l_mdec_fallbacks)
    r = run_scenario(spec)
    assert r["accepted"], r
    assert r["byte_exact"], r
    assert r["mesh_fallbacks"] == 0, r
    row = r["checks"]["TPU_MESH_SKEW"]
    assert row == {"raised": True, "cleared": True, "bundle_ok": True}
    mdec = mesh_decode_perf_counters()
    assert mdec.get(l_mdec_dispatches) > before, \
        "degraded reads never reached the meshed decode path"
    assert mdec.get(l_mdec_fallbacks) == fb_before, \
        "meshed decode fell back to single-device under the storyline"


@pytest.mark.slow
def test_seed_soak():
    """The N-seed soak (CEPH_TPU_SOAK_SEEDS, default 12): every
    sampled storyline in the range must pass universal acceptance —
    the composer has no unlucky seeds, only engine bugs."""
    n = int(os.environ.get("CEPH_TPU_SOAK_SEEDS", "12"))
    failed = []
    for seed in range(n):
        r = run_seed(seed)
        if not r["accepted"]:
            failed.append((seed, r["legs"], {
                k: r[k] for k in ("byte_exact", "wedged",
                                  "storyline_told",
                                  "all_raises_resolved", "checks")}))
    assert not failed, failed


# ---- elastic mesh membership ----------------------------------------------
def test_elastic_membership_retire_and_add_under_traffic():
    """ec_mesh_chips is injectargs-live: a retire mid-flight drains
    the dispatcher on the OLD mesh first (zero lost flushes, zero
    single-device fallbacks, every op byte-exact), a re-add takes real
    stripes within ONE flush (visible in the per-chip occupancy
    table), and both transitions are journaled first-class."""
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.dispatch import g_dispatcher
    from ceph_tpu.ec.tpu_plugin import ErasureCodeTpu
    from ceph_tpu.mesh import g_chipstat, g_mesh
    from ceph_tpu.mesh.runtime import (l_member_chip_adds,
                                       l_member_chip_retires,
                                       l_member_drained_reqs,
                                       l_mesh_fallbacks,
                                       membership_perf_counters,
                                       mesh_perf_counters)
    from ceph_tpu.osd.ecutil import encode as eu_encode, stripe_info_t

    g_conf.set_val("ec_mesh_chips", 8)
    g_conf.set_val("ec_mesh_rateless", True)
    g_conf.rm_val("ec_mesh_rateless_tasks")
    g_conf.set_val("ec_mesh_skew_sample_every", 1)
    g_conf.set_val("ec_dispatch_batch_window_us", 10_000_000)
    g_conf.set_val("ec_dispatch_batch_max", 64)
    g_dispatcher.flush()
    MiniCluster(n_osds=3)
    mesh = g_mesh.topology()
    if mesh is None or mesh.size < 8:
        pytest.skip("needs an 8-device mesh "
                    "(xla_force_host_platform_device_count)")
    impl = ErasureCodeTpu()
    impl.init({"k": "4", "m": "2", "technique": "reed_sol_van"})
    sinfo = stripe_info_t(4, 4 * 1024)
    want = set(range(6))
    rng = np.random.default_rng(24)

    def submit(n=3):
        payloads = [rng.integers(0, 256, size=2 * 4 * 1024,
                                 dtype=np.uint8) for _ in range(n)]
        oracles = [eu_encode(sinfo, impl, p, want) for p in payloads]
        futs = [g_dispatcher.submit_encode(sinfo, impl, p, want)
                for p in payloads]
        return futs, oracles

    def settle(futs, oracles):
        for f, oracle in zip(futs, oracles):
            res = f.result()
            assert sorted(res) == sorted(oracle)
            for i in oracle:
                assert np.asarray(res[i]).tobytes() == \
                    np.asarray(oracle[i]).tobytes()

    settle(*submit())                           # compile warmup
    g_dispatcher.flush()
    g_chipstat.reset()
    g_journal.reset()
    mpc = membership_perf_counters()
    fb0 = mesh_perf_counters().get(l_mesh_fallbacks)
    ret0 = mpc.get(l_member_chip_retires)
    add0 = mpc.get(l_member_chip_adds)
    dr0 = mpc.get(l_member_drained_reqs)

    # ---- RETIRE, with requests in flight --------------------------------
    futs, oracles = submit()                    # queued, NOT flushed
    g_conf.set_checked("ec_mesh_chips", 6)      # injectargs-live
    assert g_mesh.topology().size == 6
    settle(futs, oracles)                       # zero lost flushes
    assert mpc.get(l_member_drained_reqs) - dr0 >= 3, \
        "the retire did not drain the in-flight requests"
    assert mpc.get(l_member_chip_retires) - ret0 == 2
    retire_evs = [e for e in g_journal.merged()
                  if e["type"] == "mesh_chip_retire"]
    assert len(retire_evs) == 1
    assert retire_evs[0]["chips_from"] == 8
    assert retire_evs[0]["chips_to"] == 6
    assert retire_evs[0]["retired"] == [6, 7]
    settle(*submit())                           # traffic on the 6-mesh
    g_dispatcher.flush()

    # ---- ADD back to 8 ---------------------------------------------------
    occ_before = {i: v["stripes"]
                  for i, v in g_mesh.per_chip().items()}
    g_conf.set_checked("ec_mesh_chips", 8)
    assert g_mesh.topology().size == 8
    # ONE flush after the add, with a real stripe in every one of the
    # 8 systematic blocks: placement rotates with the process-global
    # flush count, so a partly padded flush could land its pad blocks on
    # chips 6 and 7 whatever tests this worker ran before
    settle(*submit(4))
    g_dispatcher.flush()
    occ_after = {i: v["stripes"] for i, v in g_mesh.per_chip().items()}
    gained = [i for i in (6, 7)
              if occ_after.get(i, 0) > occ_before.get(i, 0)]
    assert gained, \
        "re-added chips took no real stripes within one flush: " \
        f"{occ_before} -> {occ_after}"
    assert mpc.get(l_member_chip_adds) - add0 == 2
    add_evs = [e for e in g_journal.merged()
               if e["type"] == "mesh_chip_add"]
    assert len(add_evs) == 1
    assert add_evs[0]["chips_from"] == 6
    assert add_evs[0]["chips_to"] == 8
    # the whole cycle stayed on the coded path
    assert mesh_perf_counters().get(l_mesh_fallbacks) == fb0, \
        "a membership transition degraded a flush to single-device"
    assert g_mesh.dump()["membership"]["transitions"] >= 2


def test_membership_noop_and_lifecycle_edges_not_journaled():
    """Setting ec_mesh_chips to its current value is a no-op (no
    drain, no transition), and mesh up/down (0<->N at fixture
    boundaries) is lifecycle, never a membership event."""
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.mesh import g_mesh
    from ceph_tpu.mesh.runtime import membership_perf_counters
    g_conf.set_val("ec_mesh_chips", 8)
    MiniCluster(n_osds=3)
    mesh = g_mesh.topology()
    if mesh is None or mesh.size < 8:
        pytest.skip("needs an 8-device mesh")
    g_journal.reset()
    t0 = g_mesh.dump()["membership"]["transitions"]
    g_conf.set_checked("ec_mesh_chips", 8)      # same value: no-op
    assert g_mesh.dump()["membership"]["transitions"] == t0
    assert not [e for e in g_journal.merged()
                if e["type"] in ("mesh_chip_add", "mesh_chip_retire")]
    # target_chips gauge tracks the knob even when it is a no-op
    from ceph_tpu.mesh.runtime import l_member_target_chips
    assert membership_perf_counters().get(l_member_target_chips) == 8
