"""Device CRUSH mapper parity: the served chain vs the host interpreter.

Every mapping the candidate-table mapper (``ops/crush_fast.py``) produces
must equal crush_do_rule's output exactly — same winners, same retry
outcomes, same NONE holes — across rule styles (firstn/indep, chooseleaf
and direct), tunable profiles, weight-based rejection, choose_args, and
uneven hierarchies.  A map it refuses (``UnsupportedRule``) is served by
``crush.wrapper.do_rule_batch``, which must answer on the C++ mapper
with the same mappings.
"""
import numpy as np
import pytest

from ceph_tpu.crush import CrushWrapper, CRUSH_BUCKET_STRAW2, CRUSH_ITEM_NONE
from ceph_tpu.crush.types import Rule, RuleStep
from ceph_tpu.crush.constants import (
    CRUSH_RULE_CHOOSELEAF_FIRSTN, CRUSH_RULE_CHOOSELEAF_INDEP,
    CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSE_INDEP, CRUSH_RULE_EMIT,
    CRUSH_RULE_TAKE, PG_POOL_TYPE_ERASURE,
)

from ceph_tpu.crush.mapper import crush_do_rule
from ceph_tpu.crush.wrapper import do_rule_batch
from ceph_tpu.native import native_available
from ceph_tpu.ops.crush_fast import UnsupportedRule, compile_fast_rule
from ceph_tpu.ops.crush_kernels import compile_map

N_X = 400


def build_map(n_hosts=5, osds_per_host=4, uneven=False, seed=7):
    rng = np.random.default_rng(seed)
    cw = CrushWrapper()
    cw.set_type_name(1, "host")
    cw.set_type_name(10, "root")
    host_ids, host_ws = [], []
    osd = 0
    for h in range(n_hosts):
        k = osds_per_host + (int(rng.integers(-2, 3)) if uneven else 0)
        k = max(1, k)
        osds = list(range(osd, osd + k))
        osd += k
        if uneven:
            ws = [int(rng.integers(1, 4)) * 0x10000 for _ in osds]
        else:
            ws = [0x10000] * k
        hid = cw.add_bucket(CRUSH_BUCKET_STRAW2, 1, f"host{h}", osds, ws,
                            id=-(h + 2))
        host_ids.append(hid)
        host_ws.append(sum(ws))
    cw.set_max_devices(osd)
    cw.add_bucket(CRUSH_BUCKET_STRAW2, 10, "default", host_ids, host_ws,
                  id=-1)
    return cw, osd


def assert_parity(cw, ruleno, result_max, weight, n_x=N_X,
                  choose_args=None, refused=False):
    """The device mapper's rows equal the interpreter's
    (``crush_do_rule``, independent of both served engines) for every x.  With
    *refused*, the device mapper must refuse the map and the batch seam
    must answer on the C++ mapper (where the library loads) instead."""
    xs = np.arange(n_x, dtype=np.uint32)
    if refused:
        with pytest.raises(UnsupportedRule):
            compile_fast_rule(cw.crush, ruleno, result_max, choose_args)
        res, cnt, engine = do_rule_batch(cw.crush, ruleno, xs, result_max,
                                         weight, choose_args)
        assert engine == ("native" if native_available() else "python")
    else:
        fr = compile_fast_rule(cw.crush, ruleno, result_max, choose_args)
        res, cnt = fr.map_batch(xs, weight)
    res, cnt = np.asarray(res), np.asarray(cnt)
    for x in range(n_x):
        expect = crush_do_rule(cw.crush, ruleno, x, result_max, weight,
                               choose_args)
        got = list(res[x, :cnt[x]])
        assert got == expect, (x, got, expect)


def test_chooseleaf_firstn_parity():
    cw, n = build_map()
    rno = cw.add_simple_rule("data", "default", "host", mode="firstn")
    assert_parity(cw, rno, 3, [0x10000] * n)


def test_chooseleaf_firstn_uneven_weights():
    cw, n = build_map(n_hosts=7, osds_per_host=3, uneven=True)
    rno = cw.add_simple_rule("data", "default", "host", mode="firstn")
    assert_parity(cw, rno, 3, [0x10000] * n)


@pytest.mark.slow   # ~19 s XLA compile+replay heavyweight on 1 core
def test_firstn_with_out_devices():
    cw, n = build_map(n_hosts=6, osds_per_host=4)
    rno = cw.add_simple_rule("data", "default", "host", mode="firstn")
    rng = np.random.default_rng(3)
    weight = [0x10000] * n
    # a mix of fully-out, reweighted, and in devices
    for i in rng.choice(n, size=n // 3, replace=False):
        weight[i] = int(rng.choice([0, 0x4000, 0x8000, 0xC000]))
    assert_parity(cw, rno, 3, weight)


def test_choose_firstn_direct_osds():
    cw, n = build_map(n_hosts=4, osds_per_host=5)
    steps = [RuleStep(CRUSH_RULE_TAKE, -1, 0),
             RuleStep(CRUSH_RULE_CHOOSE_FIRSTN, 0, 0),
             RuleStep(CRUSH_RULE_EMIT, 0, 0)]
    rno = cw.add_rule(Rule(steps=steps, ruleset=1, type=1,
                           min_size=1, max_size=10), "flat")
    weight = [0x10000] * n
    weight[3] = 0
    weight[11] = 0x7000
    assert_parity(cw, rno, 3, weight)


@pytest.mark.slow   # ~25-40 s of XLA compile+replay on 1 core: the
# indep/exact64 heavyweights run in the slow tier so tier-1 fits its
# wall budget (they were enable_x64-broken in the seed; fixed in PR 1)
def test_chooseleaf_indep_parity():
    cw, n = build_map(n_hosts=8, osds_per_host=3, uneven=True)
    rno = cw.add_simple_rule("ecrule", "default", "host", mode="indep",
                             rule_type=PG_POOL_TYPE_ERASURE)
    cw.set_rule_mask_max_size(rno, 8)
    assert_parity(cw, rno, 6, [0x10000] * n)


@pytest.mark.slow   # ~25-40 s of XLA compile+replay on 1 core: the
# indep/exact64 heavyweights run in the slow tier so tier-1 fits its
# wall budget (they were enable_x64-broken in the seed; fixed in PR 1)
def test_chooseleaf_indep_with_out_devices_emits_holes():
    cw, n = build_map(n_hosts=5, osds_per_host=2)
    rno = cw.add_simple_rule("ecrule", "default", "host", mode="indep",
                             rule_type=PG_POOL_TYPE_ERASURE)
    weight = [0x10000] * n
    weight[0] = 0
    weight[5] = 0
    assert_parity(cw, rno, 4, weight)
    # indep pads failures with CRUSH_ITEM_NONE: force an impossible layout
    cw2, n2 = build_map(n_hosts=3, osds_per_host=1)
    r2 = cw2.add_simple_rule("ec2", "default", "host", mode="indep",
                             rule_type=PG_POOL_TYPE_ERASURE)
    assert_parity(cw2, r2, 5, [0x10000] * n2)


def test_choose_indep_direct_osds():
    cw, n = build_map(n_hosts=4, osds_per_host=4)
    steps = [RuleStep(CRUSH_RULE_TAKE, -1, 0),
             RuleStep(CRUSH_RULE_CHOOSE_INDEP, 0, 0),
             RuleStep(CRUSH_RULE_EMIT, 0, 0)]
    rno = cw.add_rule(Rule(steps=steps, ruleset=1, type=3,
                           min_size=1, max_size=20), "flatec")
    weight = [0x10000] * n
    weight[7] = 0
    assert_parity(cw, rno, 4, weight)


def test_chained_choose_steps():
    # take root -> choose firstn 2 type host -> chooseleaf/choose 2 osds
    cw, n = build_map(n_hosts=6, osds_per_host=4, uneven=True)
    steps = [RuleStep(CRUSH_RULE_TAKE, -1, 0),
             RuleStep(CRUSH_RULE_CHOOSE_FIRSTN, 2, 1),
             RuleStep(CRUSH_RULE_CHOOSE_FIRSTN, 2, 0),
             RuleStep(CRUSH_RULE_EMIT, 0, 0)]
    rno = cw.add_rule(Rule(steps=steps, ruleset=1, type=1,
                           min_size=1, max_size=10), "two-level")
    assert_parity(cw, rno, 4, [0x10000] * n)


@pytest.mark.parametrize("profile", ["bobtail", "firefly", "hammer", "jewel"])
def test_tunable_profiles(profile):
    cw, n = build_map(n_hosts=5, osds_per_host=3, uneven=True)
    cw.set_tunables_profile(profile)
    rno = cw.add_simple_rule("data", "default", "host", mode="firstn")
    weight = [0x10000] * n
    weight[2] = 0
    # pre-jewel chooseleaf is not stable: the device mapper refuses it
    assert_parity(cw, rno, 3, weight, n_x=200, refused=profile != "jewel")


@pytest.mark.slow   # ~13 s XLA compile+replay heavyweight on 1 core
def test_choose_args_weight_override():
    cw, n = build_map(n_hosts=4, osds_per_host=3)
    rno = cw.add_simple_rule("data", "default", "host", mode="firstn")
    args = cw.choose_args_create(0)
    # give host1's middle osd a different per-position weight
    from ceph_tpu.crush.types import WeightSet
    b = cw.get_bucket(-3)
    args[2].weight_set = [
        WeightSet(weights=[0x8000 if i == 1 else w
                           for i, w in enumerate(b.item_weights)]),
        WeightSet(weights=list(b.item_weights)),
    ]
    assert_parity(cw, rno, 3, [0x10000] * n,
                  choose_args=cw.choose_args_get(0))


def test_rejects_non_straw2_map():
    from ceph_tpu.crush import CRUSH_BUCKET_STRAW
    cw = CrushWrapper()
    cw.set_max_devices(4)
    cw.set_type_name(10, "root")
    cw.add_bucket(CRUSH_BUCKET_STRAW, 10, "default", [0, 1, 2, 3],
                  [0x10000] * 4, id=-1)
    with pytest.raises(ValueError):
        compile_map(cw.crush)


def test_rejects_legacy_tunables():
    cw, _ = build_map()
    cw.set_tunables_profile("argonaut")
    with pytest.raises(ValueError):
        compile_map(cw.crush)


def test_choose_take_buckets_own_type():
    """A choose step targeting the take bucket's own type must still draw
    from the bucket (do-while semantics, mapper.c:487-498), not return the
    take bucket itself."""
    cw, n = build_map(n_hosts=4, osds_per_host=3)
    steps = [RuleStep(CRUSH_RULE_TAKE, -1, 0),
             RuleStep(CRUSH_RULE_CHOOSE_FIRSTN, 2, 10),  # type 10 == root
             RuleStep(CRUSH_RULE_EMIT, 0, 0)]
    rno = cw.add_rule(Rule(steps=steps, ruleset=1, type=1,
                           min_size=1, max_size=10), "degenerate")
    assert_parity(cw, rno, 2, [0x10000] * n, n_x=64, refused=True)
