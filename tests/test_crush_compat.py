"""crush-compat balancer mode: per-position weight_set optimization.

The reference balancer's second mode (pybind/mgr/balancer/module.py
do_crush_compat) flattens PG distribution by optimizing the crush map's
choose_args weight_set (crush.h:273) instead of emitting pg_upmap
entries — for clients too old to decode upmaps.  These tests require:
stddev improves on a skewed map with ZERO upmap entries, and the device
mappers evaluate the optimized weight_set bit-exactly.
"""
import numpy as np
import pytest

from ceph_tpu.crush import CrushWrapper, CRUSH_BUCKET_STRAW2
from ceph_tpu.crush.mapper import crush_do_rule
from ceph_tpu.osdmap import OSDMap, pg_t
from ceph_tpu.osdmap.balancer import calc_weight_set
from ceph_tpu.osdmap.types import pg_pool_t, TYPE_REPLICATED


def skewed_map(n_hosts=6, per_host=4, pg_num=256):
    m = OSDMap()
    cw = m.crush
    cw.set_type_name(1, "host")
    cw.set_type_name(10, "root")
    rng = np.random.default_rng(17)
    hosts, osd = [], 0
    for h in range(n_hosts):
        osds = list(range(osd, osd + per_host))
        osd += per_host
        # skew: identical CLAIMED weights but real clusters never land
        # perfectly — compat mode corrects the hash noise
        ws = [0x10000] * per_host
        hosts.append(cw.add_bucket(CRUSH_BUCKET_STRAW2, 1, f"h{h}",
                                   osds, ws, id=-(h + 2)))
    m.set_max_osd(osd)
    cw.add_bucket(CRUSH_BUCKET_STRAW2, 10, "default", hosts,
                  [0x10000 * per_host] * n_hosts, id=-1)
    for i in range(osd):
        m.set_osd(i, up=True)
    rno = cw.add_simple_rule("data", "default", "host", mode="firstn")
    pool = pg_pool_t(type=TYPE_REPLICATED, size=3, min_size=2,
                     crush_rule=rno, pg_num=pg_num, pgp_num=pg_num)
    pid = m.add_pool("p", pool)
    m.epoch = 1
    return m, pid, rno


def per_osd_stddev(m, pid):
    pool = m.pools[pid]
    counts = {}
    for ps in range(pool.pg_num):
        up, _ = m.pg_to_raw_up(pg_t(pid, ps))
        for o in up:
            if o != 0x7FFFFFFF:
                counts[o] = counts.get(o, 0) + 1
    vals = [counts.get(o, 0) for o in range(m.max_osd)]
    return float(np.std(vals))


def test_weight_set_flattens_distribution_without_upmaps():
    m, pid, _ = skewed_map()
    before = per_osd_stddev(m, pid)
    b2, after = calc_weight_set(m, pid)
    assert b2 == pytest.approx(before)
    assert after < before, (before, after)
    assert per_osd_stddev(m, pid) == pytest.approx(after)
    # the whole point of compat mode: zero upmap entries
    assert not m.pg_upmap and not m.pg_upmap_items
    # the optimized args are per-position (one weight list per replica
    # slot, crush_choose_arg's weight_set shape)
    args = m.crush.crush.choose_args[pid]
    ws = next(a.weight_set for a in args if a.weight_set)
    assert len(ws) == m.pools[pid].size


@pytest.mark.slow   # ~17 s weight-set device sweep heavyweight
def test_batch_mapping_uses_weight_set():
    """OSDMapMapping's whole-map batch path must agree with the scalar
    pipeline once choose_args are installed."""
    from ceph_tpu.osdmap.mapping import OSDMapMapping
    m, pid, _ = skewed_map(n_hosts=4, per_host=3, pg_num=64)
    calc_weight_set(m, pid, max_iterations=8)
    mapping = OSDMapMapping()
    mapping.update(m)
    for ps in range(64):
        up, upp, acting, actp = m.pg_to_up_acting_osds(pg_t(pid, ps))
        bup, bprim = mapping.get(pg_t(pid, ps))[:2], None
        got_up, got_upp, got_acting, got_actp = mapping.get(pg_t(pid, ps))
        assert got_up == up and got_acting == acting
        assert got_upp == upp and got_actp == actp


def test_mgr_crush_compat_mode_publishes():
    """End-to-end through the mgr: the optimized weight_set rides a
    topology epoch to every subscriber; no upmaps appear."""
    from ceph_tpu.cluster import MiniCluster
    c = MiniCluster(n_osds=9, osds_per_host=3)
    c.create_replicated_pool("p", size=3, pg_num=128)
    pid = c.mon.osdmap.lookup_pg_pool_name("p")
    before, after = c.mgr.balancer_optimize_crush_compat(pid)
    assert after <= before
    assert not c.mon.osdmap.pg_upmap_items
    if after < before:
        # published: OSDs' maps carry the same choose_args
        osd = next(iter(c.osds.values()))
        assert pid in osd.osdmap.crush.crush.choose_args
    cl = c.client("client.b")
    assert cl.write_full("p", "o", b"balanced") == 0
    assert cl.read("p", "o") == b"balanced"


@pytest.mark.slow   # ~25-40 s of XLA compile+replay on 1 core: the
# indep/exact64 heavyweights run in the slow tier so tier-1 fits its
# wall budget (they were enable_x64-broken in the seed; fixed in PR 1)
@pytest.mark.parametrize("weights", ["all_in", "two_out", "reweighted"])
def test_fast_path_firstn_weight_set_bit_exact(weights):
    """The candidate-table fast path evaluates firstn rules under
    per-position weight sets bit-exactly: positions index by the
    DYNAMIC outpos (mapper.c:513), materialized as a candidate axis
    and gathered by each lane's success count during resolution.
    ``all_in`` is the optimized choose_args as calc_weight_set leaves
    them, every OSD in."""
    from ceph_tpu.ops.crush_fast import compile_fast_rule
    m, pid, rno = skewed_map(n_hosts=5, per_host=3, pg_num=128)
    calc_weight_set(m, pid, max_iterations=10)
    args = m.crush.crush.choose_args[pid]
    assert max(len(a.weight_set) for a in args if a.weight_set) > 1
    cw = m.crush
    fr = compile_fast_rule(cw.crush, rno, 3, choose_args=args)
    assert fr.posP > 1 and fr.firstn
    xs = np.arange(400, dtype=np.uint32)
    w = {"all_in": [0x10000] * m.max_osd,
         "two_out": [0x10000] * (m.max_osd - 2) + [0, 0x8000],
         "reweighted": list(np.random.default_rng(3).integers(
             0, 5, m.max_osd) * 0x4000)}[weights]
    res, cnt = fr.map_batch(xs, np.asarray(w, np.uint32))
    for x in range(len(xs)):
        expect = crush_do_rule(cw.crush, rno, int(x), 3, list(w), args)
        assert list(res[x, :cnt[x]]) == expect, (x, w[:4])


@pytest.mark.slow   # ~25-40 s of XLA compile+replay on 1 core: the
# indep/exact64 heavyweights run in the slow tier so tier-1 fits its
# wall budget (they were enable_x64-broken in the seed; fixed in PR 1)
def test_reweighted_nonuniform_map_stays_device_zero_residual():
    """VERDICT r4 #9 done-criterion: a REWEIGHTED (non-uniform bucket
    weights) firstn map runs on the device mapper with ZERO host
    replays — the exact64 draw handles arbitrary weights bit-exactly,
    so crush_nonuniform_residual_fraction is 0.0, not ~0.08%."""
    from ceph_tpu.ops.crush_fast import compile_fast_rule
    m = OSDMap()
    cw = m.crush
    cw.set_type_name(1, "host")
    cw.set_type_name(10, "root")
    rng = np.random.default_rng(5)
    hosts, osd = [], 0
    for h in range(16):
        osds = list(range(osd, osd + 4))
        osd += 4
        # ceph osd crush reweight aftermath: every device different
        ws = [int(w) for w in rng.integers(0x8000, 0x30000, 4)]
        hosts.append(cw.add_bucket(CRUSH_BUCKET_STRAW2, 1, f"h{h}",
                                   osds, ws, id=-(h + 2)))
    m.set_max_osd(osd)
    # root stays uniform (the bench's shape): residuals here can only
    # come from draw inexactness, which exact64 eliminates — not from
    # the materialized-rounds collision tail a heavily skewed root
    # would add
    cw.add_bucket(CRUSH_BUCKET_STRAW2, 10, "default", hosts,
                  [0x40000] * 16, id=-1)
    for i in range(osd):
        m.set_osd(i, up=True)
    rno = cw.add_simple_rule("data", "default", "host", mode="firstn")
    # tries_cap=7: enough materialized retry rounds that the
    # collision tail (orthogonal to draw exactness) can't flag a
    # lane; the residual then isolates draw inexactness alone
    fr = compile_fast_rule(cw.crush, rno, 3, tries_cap=7)
    # uniform root rides the quotient tables; the reweighted leaf
    # level is the exact64 path under test
    assert fr.integer_exact_levels == [True, False]
    xs = np.arange(2000, dtype=np.uint32)
    for w in ([0x10000] * osd,
              [0x10000] * (osd - 3) + [0, 0x8000, 0xc000]):
        res, cnt = fr.map_batch(xs, np.asarray(w, np.uint32))
        assert fr.residual_fraction == 0.0
        for x in range(0, 2000, 37):
            expect = cw.do_rule(rno, int(x), 3, list(w))
            assert list(res[x, :cnt[x]]) == expect, (x, w[-3:])
    # and the pool-level mapping keeps the device backend
    pool = pg_pool_t(type=TYPE_REPLICATED, size=3, min_size=2,
                     crush_rule=rno, pg_num=128, pgp_num=128)
    pid = m.add_pool("p", pool)
    m.epoch = 1
    from ceph_tpu.osdmap.mapping import OSDMapMapping
    mapping = OSDMapMapping()
    mapping.update(m)
    assert mapping.last_backend[pid] == "device"
    for ps in range(0, 128, 11):
        up, upp, acting, actp = m.pg_to_up_acting_osds(pg_t(pid, ps))
        got = mapping.get(pg_t(pid, ps))
        assert got[0] == up and got[2] == acting


def test_native_mapper_choose_args_bit_exact():
    """The C++ batch evaluator consumes choose_args from the blob
    (ids overrides + per-position weight_set) and matches the host
    interpreter exactly — so the residual-replay and middle fallback
    tiers never degrade to the scalar Python loop."""
    from ceph_tpu.native import NativeCrushMapper, native_available
    if not native_available():
        pytest.skip("native lib unavailable")
    m, pid, rno = skewed_map(n_hosts=5, per_host=3, pg_num=64)
    calc_weight_set(m, pid, max_iterations=8)
    args = m.crush.crush.choose_args[pid]
    cw = m.crush
    nm = NativeCrushMapper(cw.crush, args)
    w = [0x10000] * (m.max_osd - 1) + [0]
    out, lens = nm.do_rule_batch(rno, list(range(300)), 3, w)
    for x in range(300):
        expect = crush_do_rule(cw.crush, rno, x, 3, list(w), args)
        assert list(out[x][:lens[x]]) == expect, x


def test_batch_mapping_stays_on_device_with_weight_set():
    """The VERDICT done-criterion: a compat-balanced firstn pool keeps
    the DEVICE batch mapper (no silent per-PG Python fallback)."""
    from ceph_tpu.osdmap.mapping import OSDMapMapping
    m, pid, _ = skewed_map(n_hosts=4, per_host=3, pg_num=64)
    calc_weight_set(m, pid, max_iterations=8)
    assert pid in m.crush.crush.choose_args
    mapping = OSDMapMapping()
    mapping.update(m)
    assert mapping.last_backend[pid] == "device"
    for ps in range(0, 64, 7):
        up, upp, acting, actp = m.pg_to_up_acting_osds(pg_t(pid, ps))
        got_up, got_upp, got_acting, got_actp = mapping.get(pg_t(pid, ps))
        assert got_up == up and got_acting == acting
