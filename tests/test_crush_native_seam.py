"""The per-PG CRUSH lookup, ``CrushWrapper.do_rule``, against the Python
interpreter ``crush.mapper.crush_do_rule``, its oracle.

``do_rule`` evaluates on the C++ engine (``native.NativeCrushMapper``)
where the library loads, and on the interpreter where it does not,
where the engine refuses the map (a malformed ``choose_args``), or
where a choose-tries histogram is armed.  Whichever runs, the placement
is the interpreter's, for every bucket alg, rule shape, tunables
profile, weight vector and ``choose_args``; and each evaluation leaves
one ``crush.scalar`` profiler span whose ``impl`` names the engine.
"""
import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.crush import (
    CrushWrapper, CRUSH_BUCKET_LIST, CRUSH_BUCKET_STRAW,
    CRUSH_BUCKET_STRAW2, CRUSH_BUCKET_TREE, CRUSH_BUCKET_UNIFORM,
    PG_POOL_TYPE_ERASURE,
)
from ceph_tpu.crush.constants import (
    CRUSH_RULE_CHOOSE_INDEP, CRUSH_RULE_EMIT, CRUSH_RULE_TAKE,
)
from ceph_tpu.crush.mapper import crush_do_rule
from ceph_tpu.crush.types import ChooseArg, Rule, RuleStep, WeightSet
from ceph_tpu.trace import g_tracer

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native toolchain unavailable")

ALGS = {"uniform": CRUSH_BUCKET_UNIFORM, "list": CRUSH_BUCKET_LIST,
        "tree": CRUSH_BUCKET_TREE, "straw": CRUSH_BUCKET_STRAW,
        "straw2": CRUSH_BUCKET_STRAW2}
PROFILES = ("argonaut", "bobtail", "firefly", "hammer", "jewel")


def _map(rng, host_alg, n_hosts=6, per_host=4, root_alg=CRUSH_BUCKET_STRAW2):
    """Hosts of *host_alg* under a root of *root_alg*; uneven weights
    except in uniform buckets, which hold one weight."""
    cw = CrushWrapper()
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
