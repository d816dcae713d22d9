"""Cross-validation against the REFERENCE's own generated mappings.

ADVICE r1 #4: the self-generated corpus pins stability but not upstream
bit-compatibility.  These fixtures close that gap: the reference tree
ships cram tests whose expected outputs were produced by the reference
crushtool itself (src/test/cli/crushtool/*.t) — text crushmaps compiled
and evaluated by the C implementation.  We parse the SAME text maps with
our compiler, evaluate with our mapper, and require every mapping to
match the reference's recorded output byte-for-byte:

- set-choose.t: 36864 mappings — 6 rules (chained choose / chooseleaf /
  set-choose variants) x 2 numreps x 1024 x values x 3 osd-weight
  vectors, over straw(v1) buckets.
- bad-mappings.t / test-map-firstn-indep.t: firstn + indep short-result
  expectations incl. CRUSH_ITEM_NONE padding.

Provenance: expected outputs are read directly from the reference tree
at test time (REF_CLI below), not copied into this repo.
"""
import os
import re

import pytest

from ceph_tpu.crush.compiler import CrushCompiler
from ceph_tpu.crush.mapper import crush_do_rule

REF_CLI = "/root/reference/src/test/cli/crushtool"

pytestmark = pytest.mark.skipif(
    not os.path.isdir(REF_CLI), reason="reference tree not mounted")

_RULE_HDR = re.compile(r"rule (\d+) \(\S+\), x = (\d+)\.\.(\d+), "
                       r"numrep = (\d+)\.\.(\d+)")
_MAPPING = re.compile(r"CRUSH rule (\d+) x (\d+) \[([\d,]*)\]")
_BAD = re.compile(r"bad mapping rule (\d+) x (\d+) num_rep (\d+) "
                  r"result \[([\d,]*)\]")
_WEIGHT = re.compile(r"--weight (\d+) ([.\d]+)")


def _compile_text(path):
    with open(path) as f:
        return CrushCompiler().compile(f.read())


def _parse_runs(t_path):
    """Split a .t into crushtool --test runs: [(weights, expectations)]
    where expectations = list of (rule, numrep, x, result-list)."""
    runs = []
    current = None
    pending = None  # (rule, x_min, x_max, nr_min, nr_max, seen-count)
    with open(t_path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("$ crushtool") and "--test" in line:
                current = {"weights": _WEIGHT.findall(line), "maps": []}
                runs.append(current)
                pending = None
                continue
            if current is None:
                continue
            m = _RULE_HDR.match(line)
            if m:
                pending = tuple(int(g) for g in m.groups())
                nr_min = pending[3]
                current["maps"].append((nr_min, []))
                continue
            m = _MAPPING.match(line)
            if m and pending is not None:
                rule, x = int(m.group(1)), int(m.group(2))
                result = [int(v) for v in m.group(3).split(",")] \
                    if m.group(3) else []
                current["maps"][-1][1].append((rule, x, result))
    return runs


def _weights_vector(weight_args, n_devices):
    w = [0x10000] * n_devices
    for dev, val in weight_args:
        w[int(dev)] = int(float(val) * 0x10000)
    return w


def test_set_choose_mappings_match_reference():
    """Every mapping the reference crushtool recorded for the straw(v1)
    chained-choose map must come out of our compiler+mapper identically."""
    cw = _compile_text(os.path.join(REF_CLI, "set-choose.crushmap.txt"))
    m = cw.crush
    runs = _parse_runs(os.path.join(REF_CLI, "set-choose.t"))
    assert len(runs) == 3
    total = 0
    for run in runs:
        w = _weights_vector(run["weights"], m.max_devices)
        for nr_min, block in run["maps"]:
            # each block covers numrep = nr_min..nr_max in x-order batches
            per_x = {}
            for rule, x, result in block:
                per_x.setdefault((rule, x), []).append(result)
            for (rule, x), results in per_x.items():
                for i, expect in enumerate(results):
                    numrep = nr_min + i
                    got = crush_do_rule(m, rule, x, numrep, w)
                    assert got == expect, (
                        f"rule {rule} x {x} numrep {numrep} w={run['weights']}: "
                        f"{got} != {expect}")
                    total += 1
    assert total == 36864, total


@pytest.mark.parametrize("t_name,map_name", [
    ("bad-mappings.t", "bad-mappings.crushmap.txt"),
    ("test-map-firstn-indep.t", "test-map-firstn-indep.txt"),
])
def test_bad_mappings_match_reference(t_name, map_name):
    """Short-result expectations (firstn truncation, indep NONE holes)
    recorded by the reference crushtool."""
    cw = _compile_text(os.path.join(REF_CLI, map_name))
    m = cw.crush
    w = [0x10000] * m.max_devices
    checked = 0
    with open(os.path.join(REF_CLI, t_name)) as f:
        for line in f:
            mm = _BAD.match(line.strip())
            if not mm:
                continue
            rule, x, numrep = (int(mm.group(i)) for i in range(1, 4))
            expect = [int(v) for v in mm.group(4).split(",")] \
                if mm.group(4) else []
            got = crush_do_rule(m, rule, x, numrep, w)
            assert got == expect, (rule, x, numrep, got, expect)
            checked += 1
    assert checked >= 2, checked


_SET_FLAG = re.compile(r"--set-([a-z-]+) (\d+)")
_FLAG_ATTR = {
    "choose-local-tries": "choose_local_tries",
    "choose-local-fallback-tries": "choose_local_fallback_tries",
    "choose-total-tries": "choose_total_tries",
    "chooseleaf-descend-once": "chooseleaf_descend_once",
    "chooseleaf-vary-r": "chooseleaf_vary_r",
    "chooseleaf-stable": "chooseleaf_stable",
    "straw-calc-version": "straw_calc_version",
}


def _run_binary_fixture(t_name: str, map_name: str, stride: int = 1):
    """Replay a cram fixture that evaluates a BINARY reference crushmap:
    decode it with our codec, apply the command's --set-* tunables and
    --weight vector, and compare every recorded mapping."""
    from ceph_tpu.crush.binfmt import decode_crushmap
    t_path = os.path.join(REF_CLI, t_name)
    total = 0
    m = w = None
    nr_min = 1
    seen: dict = {}
    with open(t_path) as f:
        for raw in f:
            line = raw.strip()
            if line.startswith("$ crushtool") and "--test" in line:
                mm = re.search(r'-i "\$TESTDIR/([^"]+)"', line)
                assert mm and mm.group(1) == map_name, line
                with open(os.path.join(REF_CLI, map_name), "rb") as bf:
                    m = decode_crushmap(bf.read()).crush
                for flag, val in _SET_FLAG.findall(line):
                    setattr(m, _FLAG_ATTR[flag], int(val))
                w = _weights_vector(_WEIGHT.findall(line), m.max_devices)
                continue
            hdr = _RULE_HDR.match(line)
            if hdr:
                nr_min = int(hdr.group(4))
                seen = {}
                continue
            mm = _MAPPING.match(line)
            if mm and m is not None:
                rule, x = int(mm.group(1)), int(mm.group(2))
                # numrep = header minimum + how many sweeps of this x we
                # have already passed (results can be SHORTER than
                # numrep, so len(result) is not a substitute)
                numrep = nr_min + seen.get((rule, x), 0)
                seen[(rule, x)] = seen.get((rule, x), 0) + 1
                if x % stride:
                    continue
                expect = [int(v) for v in mm.group(3).split(",")] \
                    if mm.group(3) else []
                got = crush_do_rule(m, rule, x, numrep, w)
                assert got == expect, (t_name, rule, x, numrep, got,
                                       expect)
                total += 1
    return total


# stride subsamples the recorded x values to bound suite runtime (the
# heavy maps cost ~10-45 ms per exact host evaluation); every file still
# contributes hundreds of cross-checked mappings per run
@pytest.mark.parametrize("t_name,map_name,stride", [
    ("test-map-legacy-tunables.t", "test-map-a.crushmap", 16),
    ("test-map-bobtail-tunables.t", "test-map-a.crushmap", 16),
    ("test-map-firefly-tunables.t", "test-map-vary-r.crushmap", 16),
    ("test-map-hammer-tunables.t",
     "test-map-hammer-tunables.crushmap", 16),
    ("test-map-jewel-tunables.t", "test-map-jewel-tunables.crushmap", 16),
    ("test-map-indep.t", "test-map-indep.crushmap", 16),
    ("test-map-tries-vs-retries.t",
     "test-map-tries-vs-retries.crushmap", 16),
    ("test-map-vary-r-0.t", "test-map-vary-r.crushmap", 16),
    ("test-map-vary-r-1.t", "test-map-vary-r.crushmap", 16),
    ("test-map-vary-r-2.t", "test-map-vary-r.crushmap", 16),
    ("test-map-vary-r-3.t", "test-map-vary-r.crushmap", 16),
    ("test-map-vary-r-4.t", "test-map-vary-r.crushmap", 16),
])
def test_binary_fixture_mappings_match_reference(t_name, map_name, stride):
    """Binary maps produced by the reference crushtool, decoded by our
    codec, must map identically across every tunables profile the
    reference recorded (legacy/bobtail/firefly/hammer/jewel, indep,
    tries-vs-retries, vary-r 0..4)."""
    total = _run_binary_fixture(t_name, map_name, stride)
    assert total > 100, total


def _set_choose_served(m, rule, xs, numrep, w):
    """*rule* for every x on the chain that serves this straw(v1) map:
    the device mapper refuses it, the batch seam answers."""
    import numpy as np
    from ceph_tpu.crush.wrapper import do_rule_batch
    from ceph_tpu.native import native_available
    from ceph_tpu.ops.crush_fast import compile_fast_rule
    with pytest.raises(ValueError):
        compile_fast_rule(m, rule, numrep)
    out, cnt, engine = do_rule_batch(m, rule, np.asarray(xs), numrep, w)
    assert engine == ("native" if native_available() else "python")
    return out, cnt


def test_set_choose_mappings_on_served_batch_path():
    """The SAME 36864 recorded reference mappings, evaluated by the
    batch chain that serves this map (straw v1 draws, local tries, perm
    fallback): ``crush.wrapper.do_rule_batch`` on the C++ mapper where
    the library loads, instead of the per-x host interpreter."""
    import numpy as np

    cw = _compile_text(os.path.join(REF_CLI, "set-choose.crushmap.txt"))
    m = cw.crush
    runs = _parse_runs(os.path.join(REF_CLI, "set-choose.t"))
    assert len(runs) == 3
    # group expectations by (rule, numrep) -> {x: result}
    grouped = {}
    for ri, run in enumerate(runs):
        for nr_min, block in run["maps"]:
            per_x = {}
            for rule, x, result in block:
                per_x.setdefault((rule, x), []).append(result)
            for (rule, x), results in per_x.items():
                for i, expect in enumerate(results):
                    grouped.setdefault((ri, rule, nr_min + i),
                                       {})[x] = expect
    total = 0
    for (ri, rule, numrep), per_x in sorted(grouped.items()):
        w = _weights_vector(runs[ri]["weights"], m.max_devices)
        xs = np.asarray(sorted(per_x), dtype=np.uint32)
        out, cnt = _set_choose_served(m, rule, xs, numrep, w)
        for i, x in enumerate(xs):
            got = [int(v) for v in out[i, :cnt[i]]]
            assert got == per_x[int(x)], (
                f"run {ri} rule {rule} numrep {numrep} x {x}: "
                f"{got} != {per_x[int(x)]}")
            total += 1
    assert total == 36864, total


def test_served_batch_path_with_dead_slots():
    """Heavy-out weight vectors kill whole slots, driving the
    chooseleaf recursion's outpos behind the attempt index — the served
    batch chain must track the reference exactly."""
    import numpy as np

    cw = _compile_text(os.path.join(REF_CLI, "set-choose.crushmap.txt"))
    m = cw.crush
    xs = np.arange(160, dtype=np.uint32)
    rng = np.random.default_rng(13)
    bad = 0
    for rule in (2, 5):              # the chooseleaf rules
        for trial in range(4):
            w = [0x10000] * m.max_devices
            for d in rng.choice(m.max_devices, size=7, replace=False):
                w[int(d)] = 0 if trial % 2 else 0x2000
            out, cnt = _set_choose_served(m, rule, xs, 3, w)
            for x in range(len(xs)):
                exp = crush_do_rule(m, rule, int(x), 3, w)
                if [int(v) for v in out[x, :cnt[x]]] != exp:
                    bad += 1
    assert bad == 0, bad
