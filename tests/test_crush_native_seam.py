"""The choice between the C++ engine and the Python interpreter
(``crush/wrapper.py``), against the interpreter
``crush.mapper.crush_do_rule``, its oracle.

``CrushWrapper.do_rule`` (per x) and ``do_rule_batch`` (the batch
callers: ``OSDMapMapping``, upmap, ``crush_fast``'s residual replay,
``crushtool --test``) evaluate on the C++ engine
(``native.NativeCrushMapper``) where the library loads, and on the
interpreter where it does not, where the engine refuses the map (a
malformed ``choose_args``), or where a choose-tries histogram is armed.
Whichever runs, the placement is the interpreter's, for every bucket
alg, rule shape, tunables profile, weight vector and ``choose_args``;
each ``do_rule`` evaluation leaves one ``crush.scalar`` profiler span
whose ``impl`` names the engine, and ``do_rule_batch`` returns its name.
"""
import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.crush import (
    CrushWrapper, CRUSH_BUCKET_LIST, CRUSH_ITEM_NONE, CRUSH_BUCKET_STRAW,
    CRUSH_BUCKET_STRAW2, CRUSH_BUCKET_TREE, CRUSH_BUCKET_UNIFORM,
    PG_POOL_TYPE_ERASURE,
)
from ceph_tpu.crush.constants import (
    CRUSH_RULE_CHOOSE_INDEP, CRUSH_RULE_EMIT, CRUSH_RULE_TAKE,
)
from ceph_tpu.crush.mapper import crush_do_rule
from ceph_tpu.crush.types import (
    ChooseArg, CrushMap, Rule, RuleStep, WeightSet,
)
from ceph_tpu.trace import g_tracer

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native toolchain unavailable")

ALGS = {"uniform": CRUSH_BUCKET_UNIFORM, "list": CRUSH_BUCKET_LIST,
        "tree": CRUSH_BUCKET_TREE, "straw": CRUSH_BUCKET_STRAW,
        "straw2": CRUSH_BUCKET_STRAW2}
PROFILES = ("argonaut", "bobtail", "firefly", "hammer", "jewel")


def _map(rng, host_alg, n_hosts=6, per_host=4, root_alg=CRUSH_BUCKET_STRAW2,
         cw=None):
    """Hosts of *host_alg* under a root of *root_alg* (into *cw* where
    given); uneven weights except in uniform buckets, which hold one
    weight."""
    cw = CrushWrapper() if cw is None else cw
    n = n_hosts * per_host
    cw.set_max_devices(n)
    cw.set_type_name(1, "host")
    cw.set_type_name(10, "root")
    hosts, host_w = [], []
    for h in range(n_hosts):
        osds = list(range(h * per_host, (h + 1) * per_host))
        w = [0x10000] * per_host if host_alg == CRUSH_BUCKET_UNIFORM else \
            [int(rng.integers(1, 5)) * 0x8000 for _ in osds]
        hosts.append(cw.add_bucket(host_alg, 1, f"host{h}", osds, w,
                                   id=-(h + 2)))
        host_w.append(sum(w))
    cw.add_bucket(root_alg, 10, "default", hosts, host_w, id=-1)
    for i in range(n):
        cw.set_item_name(i, f"osd.{i}")
    return cw, n


def _weights(rng, n, kind):
    if kind == "full":
        return [0x10000] * n
    if kind == "zero":
        return [0] * n
    if kind == "partial":
        return [int(v) for v in rng.choice([0, 0x4000, 0x8000, 0x10000],
                                           size=n)]
    return [0x10000] * (n // 2)             # short: the rest read as out


def _lrc_rule(cw, ruleno):
    """take; choose indep 3 host; choose indep 2 osd; emit: two steps
    of choose, no chooseleaf."""
    return cw.add_rule(Rule(steps=[
        RuleStep(CRUSH_RULE_TAKE, -1, 0),
        RuleStep(CRUSH_RULE_CHOOSE_INDEP, 3, 1),
        RuleStep(CRUSH_RULE_CHOOSE_INDEP, 2, 0),
        RuleStep(CRUSH_RULE_EMIT, 0, 0)],
        ruleset=ruleno, type=PG_POOL_TYPE_ERASURE, min_size=1, max_size=20),
        "lrc", ruleno)


def _rules(cw):
    """(name, ruleno, numrep) of firstn and indep, with and without
    chooseleaf, and a two-step choose rule."""
    out = [
        ("firstn_leaf", cw.add_simple_rule("fl", "default", "host",
                                           mode="firstn"), 3),
        ("indep_leaf", cw.add_simple_rule("il", "default", "host",
                                          mode="indep",
                                          rule_type=PG_POOL_TYPE_ERASURE), 4),
        ("firstn_osd", cw.add_simple_rule("fo", "default", "",
                                          mode="firstn"), 3),
        ("indep_osd", cw.add_simple_rule("io", "default", "", mode="indep",
                                         rule_type=PG_POOL_TYPE_ERASURE), 5),
    ]
    out.append(("lrc", _lrc_rule(cw, 4), 6))
    return out


def _assert_same(cw, ruleno, numrep, weight, xs, ca_index=None):
    ca = cw.crush.choose_args.get(ca_index) if ca_index is not None \
        else None
    native.NativeCrushMapper(cw.crush, ca)  # the engine takes the map
    for x in xs:
        got = cw.do_rule(ruleno, x, numrep, weight,
                         choose_args_index=ca_index)
        want = crush_do_rule(cw.crush, ruleno, x, numrep, weight, ca)
        assert got == want, (ruleno, x, got, want)


@pytest.mark.parametrize("alg", sorted(ALGS))
def test_native_seam_matches_interpreter_per_bucket_alg(alg):
    rng = np.random.default_rng(sorted(ALGS).index(alg) + 11)
    cw, n = _map(rng, ALGS[alg], root_alg=ALGS[alg])
    weight = _weights(rng, n, "partial")
    for _name, rno, numrep in _rules(cw):
        _assert_same(cw, rno, numrep, weight, range(150))


@pytest.mark.parametrize("profile", PROFILES)
def test_native_seam_matches_interpreter_per_tunables_profile(profile):
    rng = np.random.default_rng(PROFILES.index(profile) + 21)
    cw, n = _map(rng, CRUSH_BUCKET_STRAW)
    cw.set_tunables_profile(profile)
    weight = _weights(rng, n, "partial")
    for _name, rno, numrep in _rules(cw):
        _assert_same(cw, rno, numrep, weight, range(120))


@pytest.mark.parametrize("kind", ["full", "zero", "partial", "short"])
def test_native_seam_matches_interpreter_per_weight_vector(kind):
    rng = np.random.default_rng(31)
    cw, n = _map(rng, CRUSH_BUCKET_STRAW2)
    weight = _weights(rng, n, kind)
    # every OSD out runs each rep to its last try: few inputs suffice
    xs = range(15 if kind == "zero" else 150)
    for _name, rno, numrep in _rules(cw):
        _assert_same(cw, rno, numrep, weight, xs)


def _choose_args(rng, cw, positions=2):
    args = []
    for b in cw.crush.buckets:
        if b is None or b.alg != CRUSH_BUCKET_STRAW2:
            args.append(ChooseArg())
            continue
        args.append(ChooseArg(
            ids=[int(v) for v in rng.integers(-1000, 1000, size=b.size)],
            weight_set=[WeightSet([int(v) * 0x4000 for v in
                                   rng.integers(0, 5, size=b.size)])
                        for _ in range(positions)]))
    return args


def test_native_seam_matches_interpreter_with_choose_args():
    rng = np.random.default_rng(41)
    cw, n = _map(rng, CRUSH_BUCKET_STRAW2)
    cw.crush.choose_args[7] = _choose_args(rng, cw)
    weight = _weights(rng, n, "partial")
    for _name, rno, numrep in _rules(cw):
        _assert_same(cw, rno, numrep, weight, range(150), ca_index=7)
    # an index with no set evaluates without overrides
    _assert_same(cw, 0, 3, weight, range(20), ca_index=8)


@pytest.mark.parametrize("numrep", [1, 2])
def test_native_seam_result_max_below_rule_size(numrep):
    rng = np.random.default_rng(51)
    cw, n = _map(rng, CRUSH_BUCKET_STRAW2)
    weight = _weights(rng, n, "partial")
    for _name, rno, _size in _rules(cw):
        _assert_same(cw, rno, numrep, weight, range(100))


def test_native_seam_rule_out_of_range_or_absent():
    rng = np.random.default_rng(61)
    cw, n = _map(rng, CRUSH_BUCKET_STRAW2)
    rno = cw.add_simple_rule("r", "default", "host")
    _lrc_rule(cw, rno + 3)                  # leaves rules rno+1, rno+2 empty
    weight = [0x10000] * n
    for bad in (-1, rno + 1, rno + 2, cw.crush.max_rules, 1000):
        assert cw.do_rule(bad, 5, 3, weight) == []
        assert crush_do_rule(cw.crush, bad, 5, 3, weight) == []


def _ec_cluster_map(k, m, pg_num):
    """The benchmark's EC deployment (16 OSDs, one per host, failure
    domain host), as its driver builds it."""
    from ceph_tpu.cluster import MiniCluster
    c = MiniCluster(n_osds=16, osds_per_host=1)
    pid = c.create_ec_pool("bench", k=k, m=m, pg_num=pg_num,
                           failure_domain="host",
                           extra_profile={"technique": "reed_sol_van"})
    return c.mon.osdmap, pid


@pytest.mark.parametrize("k,m,pg_num", [(8, 4, 128), (4, 2, 256)])
def test_native_seam_every_pg_of_the_benchmark_ec_maps(k, m, pg_num):
    from ceph_tpu.osdmap.types import pg_t
    osdmap, pid = _ec_cluster_map(k, m, pg_num)
    pool = osdmap.pools[pid]
    cw = osdmap.crush
    rno = cw.find_rule(pool.crush_rule, pool.type, pool.size)
    assert rno >= 0 and pool.size == k + m
    out_one = list(osdmap.osd_weight)
    out_one[3] = 0                          # one OSD out, as recovery sees
    for weight in (list(osdmap.osd_weight), out_one):
        for ps in range(pg_num):
            pps = pool.raw_pg_to_pps(pg_t(pid, ps))
            got = cw.do_rule(rno, pps, pool.size, weight)
            assert got == crush_do_rule(cw.crush, rno, pps, pool.size,
                                        weight), ps
            assert len(got) == k + m


# ---- which engine ran, and the span that says so --------------------------

@pytest.fixture
def spans(monkeypatch):
    """The ``crush.scalar`` profiler spans opened, each as the dict of
    its args once closed: a stand-in annotation with a session on."""
    seen = []

    class Annotation:
        def __init__(self, name, **args):
            self.name, self.args = name, dict(args)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            if self.name == "crush.scalar":
                seen.append(self.args)
            return False

        def set_metadata(self, **args):
            self.args.update(args)

    monkeypatch.setattr(g_tracer, "_annotation", Annotation)
    monkeypatch.setattr(g_tracer, "_profiling", lambda: True)
    return seen


def _small(rng=None):
    cw, n = _map(rng or np.random.default_rng(71), CRUSH_BUCKET_STRAW2)
    rno = cw.add_simple_rule("r", "default", "host", mode="indep",
                             rule_type=PG_POOL_TYPE_ERASURE)
    return cw, rno, [0x10000] * n


def test_native_seam_span_names_native_engine(spans):
    cw, rno, weight = _small()
    cw.do_rule(rno, 3, 4, weight)
    assert spans == [{"impl": "native"}]
    crush_do_rule(cw.crush, rno, 3, 4, weight)
    assert spans[1:] == [{"impl": "python"}]
    cw.do_rule(rno + 1, 3, 4, weight)       # no rule: nothing evaluated
    assert len(spans) == 2


def test_native_seam_falls_back_without_library(spans, monkeypatch):
    cw, rno, weight = _small()
    want = [crush_do_rule(cw.crush, rno, x, 4, weight) for x in range(40)]
    spans.clear()
    monkeypatch.setattr(native, "native_available", lambda: False)
    assert [cw.do_rule(rno, x, 4, weight) for x in range(40)] == want
    assert spans == [{"impl": "python"}] * 40


def test_native_seam_falls_back_on_malformed_choose_args(spans):
    rng = np.random.default_rng(81)
    cw, rno, weight = _small(rng)
    args = _choose_args(rng, cw, positions=1)
    # a weight_set row one longer than its bucket: the interpreter reads
    # the first size entries, serialize_map refuses the map
    root = args[0]
    root.weight_set[0].weights.append(0x10000)
    cw.crush.choose_args[3] = args
    with pytest.raises(ValueError):
        native.serialize_map(cw.crush, args)
    want = [crush_do_rule(cw.crush, rno, x, 4, weight, args)
            for x in range(40)]
    spans.clear()
    got = [cw.do_rule(rno, x, 4, weight, choose_args_index=3)
           for x in range(40)]
    assert got == want
    assert spans == [{"impl": "python"}] * 40


def test_native_seam_fills_armed_choose_tries_histogram(spans):
    cw, rno, weight = _small()
    weight[2] = weight[9] = 0               # retries to count
    m = cw.crush
    m.choose_tries = [0] * (m.choose_total_tries + 1)
    want = [crush_do_rule(m, rno, x, 4, weight) for x in range(60)]
    want_hist, m.choose_tries = m.choose_tries, [0] * len(m.choose_tries)
    spans.clear()
    assert [cw.do_rule(rno, x, 4, weight) for x in range(60)] == want
    assert m.choose_tries == want_hist and sum(want_hist) > 0
    assert spans == [{"impl": "python"}] * 60
    m.choose_tries = None                   # disarmed: native again
    cw.do_rule(rno, 0, 4, weight)
    assert spans[-1] == {"impl": "native"}


# ---- the one native-or-interpreter guard, at every call site --------------

def _guarded_map():
    """A hammer-tunables straw2 map: the device mapper refuses it (its
    firstn chooseleaf is not stable), the C++ mapper takes it."""
    from ceph_tpu.osdmap.osdmap import OSDMap
    from ceph_tpu.osdmap.types import TYPE_REPLICATED, pg_pool_t
    rng = np.random.default_rng(91)
    osdmap = OSDMap()
    _cw, n = _map(rng, CRUSH_BUCKET_STRAW2, cw=osdmap.crush)
    osdmap.crush.set_tunables_profile("hammer")
    for o in range(n):
        osdmap.set_osd(o, up=True)
    osdmap.osd_weight[5] = 0
    osdmap.osd_weight[9] = 0x8000
    rno = osdmap.crush.add_simple_rule("r", "default", "host")
    pid = osdmap.add_pool("p", pg_pool_t(
        type=TYPE_REPLICATED, size=3, min_size=2, crush_rule=rno,
        pg_num=48, pgp_num=48))
    return osdmap, pid, rno


def _oracle(m, rno, xs, numrep, weight):
    return [crush_do_rule(m, rno, int(x), numrep, list(weight))
            for x in xs]


def _site_do_rule(spans, batch_engines):
    osdmap, _pid, rno = _guarded_map()
    cw, w = osdmap.crush, osdmap.osd_weight
    want = _oracle(cw.crush, rno, range(40), 3, w)
    spans.clear()                       # the oracle's own spans
    assert [cw.do_rule(rno, x, 3, w) for x in range(40)] == want
    assert not batch_engines
    return {s["impl"] for s in spans}


def _site_raw_batch(spans, batch_engines):
    from ceph_tpu.osdmap.mapping import OSDMapMapping, pool_pps
    osdmap, pid, rno = _guarded_map()
    pool = osdmap.pools[pid]
    pps = pool_pps(pool, pid, np.arange(pool.pg_num, dtype=np.uint32))
    mapping = OSDMapMapping(use_device=False)
    raw = mapping._raw_batch(osdmap, pid, pool, pps)
    got = [[int(v) for v in row if v != CRUSH_ITEM_NONE] for row in raw]
    assert got == _oracle(osdmap.crush.crush, rno, pps, 3,
                          osdmap.osd_weight)
    backend = mapping.last_backend[pid]
    assert backend == {"native": "native", "python": "host"}[
        batch_engines[-1]]
    return set(batch_engines)


def _site_upmap_raw_all(spans, batch_engines):
    from ceph_tpu.osdmap.types import pg_t
    from ceph_tpu.osdmap.upmap import _raw_all
    osdmap, pid, rno = _guarded_map()
    pool = osdmap.pools[pid]
    pps = [pool.raw_pg_to_pps(pg_t(pid, ps)) for ps in range(pool.pg_num)]
    want = _oracle(osdmap.crush.crush, rno, pps, 3, osdmap.osd_weight)
    spans.clear()                       # the oracle's own spans
    assert _raw_all(osdmap, pid, pool) == want
    # one interpreter span per PG where the interpreter answered, none
    # from the C++ batch: no per-PG retry through do_rule
    interp = pool.pg_num if batch_engines == ["python"] else 0
    assert spans == [{"impl": "python"}] * interp
    return set(batch_engines)


def _site_replay_exact(spans, batch_engines):
    from ceph_tpu.ops.crush_fast import compile_fast_rule
    cw, rno, weight = _small()
    fr = compile_fast_rule(cw.crush, rno, 4)
    xs = np.arange(64, dtype=np.uint32)
    out = np.full((64, 4), -5, dtype=np.int32)
    counts = np.zeros(64, dtype=np.int32)
    lanes = np.arange(0, 64, 3)         # the lanes forced to replay
    fr._replay_exact(lanes, xs, np.asarray(weight, np.uint32), out, counts)
    want = _oracle(cw.crush, rno, xs[lanes], 4, weight)
    assert [out[i, :counts[i]].tolist() for i in lanes] == want
    assert (out[np.setdiff1d(xs, lanes)] == -5).all()
    return set(batch_engines)


def _site_tester(spans, batch_engines):
    import io
    from ceph_tpu.crush.tester import CrushTester
    osdmap, _pid, rno = _guarded_map()
    w = osdmap.osd_weight
    out, cnt = CrushTester(osdmap.crush, out=io.StringIO())._map_batch(
        rno, list(range(40)), 3, w)
    got = [out[i, :cnt[i]].tolist() for i in range(40)]
    assert got == _oracle(osdmap.crush.crush, rno, range(40), 3, w)
    return set(batch_engines)


SITES = {"do_rule": _site_do_rule, "raw_batch": _site_raw_batch,
         "upmap_raw_all": _site_upmap_raw_all,
         "replay_exact": _site_replay_exact, "tester": _site_tester}


@pytest.fixture
def batch_engines(monkeypatch):
    """The engine named by every ``do_rule_batch`` call a site makes."""
    from ceph_tpu.crush import tester, wrapper
    from ceph_tpu.ops import crush_fast
    from ceph_tpu.osdmap import mapping, upmap
    seen = []
    real = wrapper.do_rule_batch

    def recording(*args, **kw):
        rows, counts, engine = real(*args, **kw)
        seen.append(engine)
        return rows, counts, engine

    for mod in (tester, crush_fast, mapping, upmap):
        monkeypatch.setattr(mod, "do_rule_batch", recording)
    return seen


def _refuse(monkeypatch, how):
    """The binding refuses every map: ``serialize_map`` with the
    ValueError a malformed ``choose_args`` raises, or the C++ parser
    with its RuntimeError."""
    def parse_failed(*_a, **_k):
        raise RuntimeError("native map parse failed")

    def malformed(*_a, **_k):
        raise ValueError("choose_args ids len != bucket size")

    if how == "serialize":
        monkeypatch.setattr(native, "serialize_map", malformed)
    else:
        monkeypatch.setattr(native.NativeCrushMapper, "do_rule",
                            parse_failed)
        monkeypatch.setattr(native.NativeCrushMapper, "do_rule_batch",
                            parse_failed)


@pytest.mark.parametrize("condition", ["library", "no_library",
                                       "choose_tries_armed",
                                       "refused_serialize",
                                       "refused_parse"])
@pytest.mark.parametrize("site", sorted(SITES))
def test_engine_guard_at_every_call_site(site, condition, spans,
                                         batch_engines, monkeypatch):
    """Each CRUSH call site maps exactly as the interpreter does and
    names the engine the one guard picked: the C++ mapper where the
    library loads, no choose-tries histogram is armed and the binding
    takes the map; else the interpreter."""
    if condition == "no_library":
        monkeypatch.setattr(native, "native_available", lambda: False)
    elif condition == "choose_tries_armed":
        # every map armed, as crushtool --show-choose-tries arms one;
        # room for the 100 tries of an indep rule
        monkeypatch.setattr(CrushMap, "choose_tries", [0] * 128,
                            raising=False)
    elif condition.startswith("refused"):
        _refuse(monkeypatch, condition.split("_")[1])
    want = "native" if condition == "library" else "python"
    assert SITES[site](spans, batch_engines) == {want}


def test_replay_loads_the_native_mapper_once(monkeypatch):
    """Residual lanes on every epoch replay on one C++ mapper, loaded
    (and its map serialized) once per compiled rule."""
    from ceph_tpu.ops.crush_fast import compile_fast_rule
    built = []

    class Counting(native.NativeCrushMapper):
        def __init__(self, *a, **k):
            built.append(1)
            super().__init__(*a, **k)

    monkeypatch.setattr(native, "NativeCrushMapper", Counting)
    cw, n = _map(np.random.default_rng(95), CRUSH_BUCKET_STRAW2)
    rno = cw.add_simple_rule("r", "default", "host")
    fr = compile_fast_rule(cw.crush, rno, 3, tries_cap=1)
    xs = np.arange(300, dtype=np.uint32)
    rng = np.random.default_rng(96)
    residual = 0.0
    for _epoch in range(3):
        weight = [int(v) for v in rng.choice([0, 0x4000, 0x10000], size=n)]
        res, cnt = fr.map_batch(xs, weight)
        residual += fr.residual_fraction
        assert [res[x, :cnt[x]].tolist() for x in range(300)] == \
            _oracle(cw.crush, rno, xs, 3, weight)
    assert residual > 0
    assert len(built) == 1
