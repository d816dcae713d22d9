"""The plain references agree with the program on inputs where the
program is trusted (its host codec, its native C++ mapper).  The
references themselves import nothing of the program; these tests do."""
import numpy as np

from benchmark.harness import Cell
from benchmark.reference import gf256_rs
from benchmark.reference.crush_straw2 import NONE, TwoLevelStraw2, hash2


def test_rs_reference_matches_the_isa_host_codec():
    from ceph_tpu.ec import create_erasure_code
    k, m, su = 8, 4, 4096
    codec = create_erasure_code({"plugin": "isa", "k": str(k),
                                 "m": str(m), "technique": "reed_sol_van"})
    body = np.random.default_rng(1).integers(0, 256, 3 * k * su,
                                             dtype=np.uint8).tobytes()
    want = gf256_rs.all_shards(body, k, m, su)
    stripes = np.frombuffer(body, np.uint8).reshape(-1, k, su)
    for s in range(stripes.shape[0]):
        enc = codec.encode(set(range(k + m)), stripes[s].tobytes())
        for i in range(k + m):
            got = np.frombuffer(bytes(enc[i]), np.uint8)
            assert np.array_equal(got, want[i, s * su:(s + 1) * su]), i


def test_rs_reference_differs_from_cauchy():
    k, m = 8, 4
    assert not np.array_equal(gf256_rs.rs_van_matrix(k, m)[1:],
                              np.ones((m - 1, k), np.uint8))


def test_crush_reference_matches_the_native_mapper():
    from ceph_tpu.crush import CrushWrapper
    from ceph_tpu.crush.hash import crush_hash32_2_np
    from ceph_tpu.native import NativeCrushMapper
    from benchmark.drivers.osdmap_churn import build_crush
    spec = Cell("crush_32k.osd_flap").config["crush"]
    cw = CrushWrapper()
    rno = build_crush(cw, spec)
    ref = TwoLevelStraw2(spec)
    xs = ref.pps(1, 6000)
    ps = np.arange(6000, dtype=np.uint32)
    assert np.array_equal(xs, crush_hash32_2_np(ps, np.uint32(1)))
    w = np.full(1000, 0x10000, np.uint32)
    w[np.random.default_rng(5).choice(1000, 25, replace=False)] = 0
    w[7] = 0x9000
    got = ref.map(xs, w, 3)
    res, lens = NativeCrushMapper(cw.crush).do_rule_batch(
        rno, xs.tolist(), 3, list(w))
    res, lens = np.asarray(res, np.int32), np.asarray(lens)
    want = np.where(np.arange(3)[None, :] < lens[:, None], res[:, :3], NONE)
    assert np.array_equal(got, want)


def test_hash2_known_values():
    # crush_hash32_rjenkins1_2, as the upstream C computes it
    from ceph_tpu.crush.hash import crush_hash32_2_np
    a = np.arange(0, 5000, 7, dtype=np.uint32)
    assert np.array_equal(hash2(a, a ^ 0x5bd1e995),
                          crush_hash32_2_np(a, a ^ np.uint32(0x5bd1e995)))
