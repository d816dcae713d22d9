"""Cross-validation: Python host implementations vs the independent C++ ones.

Two independently written implementations of the same published semantics
agreeing on random maps is the strongest mapping-exactness signal available
in this environment (the reference's native libs are empty submodules).
"""
import ctypes
import os
import platform
import subprocess

import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.crush import (
    CrushWrapper, CRUSH_BUCKET_LIST, CRUSH_BUCKET_STRAW,
    CRUSH_BUCKET_STRAW2, CRUSH_BUCKET_TREE, CRUSH_BUCKET_UNIFORM,
    PG_POOL_TYPE_ERASURE,
)
from ceph_tpu.crush.mapper import crush_do_rule
from ceph_tpu.ec.rs_codec import MatrixRSCodec
from ceph_tpu.gf.matrices import gf_gen_rs_matrix
from ceph_tpu.gf.tables import gf_mul
from ceph_tpu.utils.crc32c import crc32c, crc32c_sw

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native toolchain unavailable")


def test_gf_mul_parity():
    rng = np.random.default_rng(0)
    for _ in range(500):
        a, b = (int(v) for v in rng.integers(0, 256, 2))
        assert native.get_lib().gf_mul_c(a, b) == gf_mul(a, b)


def test_rs_encode_parity():
    k, m = 8, 4
    matrix = gf_gen_rs_matrix(k + m, k)
    codec = MatrixRSCodec(matrix)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=(k, 4096), dtype=np.uint8)
    got = native.native_rs_encode(matrix[k:], data)
    np.testing.assert_array_equal(got, codec.encode(data))


def test_crc32c_reference_vectors():
    # golden vectors from the reference's test/common/test_crc32c.cc
    # (ceph convention: raw castagnoli update, no pre/post inversion),
    # through the binding and through the wrapper every caller uses
    for crc in (native.crc32c, crc32c):
        assert crc(b"foo bar baz", 0) == 4119623852
        assert crc(b"foo bar baz", 1234) == 881700046
        assert crc(b"whiz bang boom", 0) == 2360230088
        assert crc(b"whiz bang boom", 5678) == 3743019208
        assert crc(b"\x01" * 5, 0) == 2715569182
        assert crc(b"\x01" * 35, 0) == 440531800
        assert crc(b"\x01" * 4096000, 0) == 31583199
        assert crc(b"\x01" * 4096000, 1234) == 1400919119


# every tail length, both sides of a page, both sides of where the
# three streams start (3 x 2 KiB) and of 3 x 8 KiB, and an EC shard of a
# 4 MiB object at k=8
CRC_LENGTHS = [*range(71), 4095, 4096, 4097, 3 * 2048 - 1, 3 * 2048,
               3 * 2048 + 1, 3 * 8192 - 1, 3 * 8192, 3 * 8192 + 1,
               512 * 1024]


@pytest.fixture(scope="module")
def crc_data():
    return np.random.default_rng(24).integers(
        0, 256, 512 * 1024 + 8, dtype=np.uint8)


@pytest.mark.parametrize("n", CRC_LENGTHS)
def test_crc32c_matches_python_table(crc_data, n):
    """The native crc against the per-byte Python table, from start
    addresses 0-7 bytes past an aligned one, as each bytes-like type the
    callers pass, and chained as ``HashInfo.append`` chains a shard."""
    for off in range(8):
        view = crc_data[off:off + n]
        want = crc32c_sw(view)
        for data in (view.tobytes(), bytearray(view.tobytes()),
                     memoryview(view), view):
            assert native.crc32c(data) == want
            assert crc32c(data) == want
        cut = n // 3
        assert crc32c(view[cut:], crc32c(view[:cut])) == want
        assert native.crc32c(view, 1234) == crc32c_sw(view, 1234)


def test_crc32c_strided_and_wide_arrays(crc_data):
    """An array that is not one contiguous row is hashed by its bytes in
    C order, as the Python table hashes it."""
    a = crc_data[:4096]
    assert crc32c(a[::2]) == crc32c_sw(a[::2])
    assert crc32c(a.reshape(64, 64)) == crc32c_sw(a)


def _cpu_flags():
    with open("/proc/cpuinfo") as f:
        for line in f:
            if line.startswith("flags"):
                return set(line.split(":", 1)[1].split())
    return set()


def test_crc32c_uses_sse42_where_the_cpu_has_it():
    if platform.machine() != "x86_64" or "sse4_2" not in _cpu_flags():
        pytest.skip("not an x86-64 host with SSE4.2")
    assert native.crc32c_impl() == "sse42"


@pytest.fixture(scope="module")
def table8_lib(tmp_path_factory):
    """native/crc32c.cpp built without the CPU's crc instruction."""
    off = {"x86_64": ["-mno-sse4.2"],
           "aarch64": ["-march=armv8-a+nocrc"]}.get(platform.machine(), [])
    so = tmp_path_factory.mktemp("table8") / "libcrc32c_table8.so"
    src = os.path.join(os.path.dirname(native.__file__), os.pardir,
                       "native", "crc32c.cpp")
    subprocess.run(["g++", "-O2", "-fPIC", "-shared", "-std=c++17", *off,
                    "-o", str(so), src], check=True)
    lib = ctypes.CDLL(str(so))
    lib.ceph_crc32c.restype = ctypes.c_uint32
    lib.ceph_crc32c.argtypes = [
        ctypes.c_uint32, ctypes.c_void_p, ctypes.c_int64]
    lib.ceph_crc32c_impl.restype = ctypes.c_char_p
    return lib


def test_crc32c_table8_fallback_parity(table8_lib, crc_data):
    assert table8_lib.ceph_crc32c_impl() == b"table8"

    def table8(view, crc=0xFFFFFFFF):
        return table8_lib.ceph_crc32c(crc, view.ctypes.data, view.nbytes)

    raw = np.frombuffer(b"foo bar baz", dtype=np.uint8)
    assert table8(raw, 0) == 4119623852
    assert table8(raw, 1234) == 881700046
    for n in CRC_LENGTHS:
        for off in (0, 3):
            view = crc_data[off:off + n]
            assert table8(view) == native.crc32c(view), (n, off)


def _random_map(rng, n_hosts, osds_per_host, algs):
    cw = CrushWrapper()
    n = n_hosts * osds_per_host
    cw.set_max_devices(n)
    cw.set_type_name(1, "host")
    cw.set_type_name(10, "root")
    host_ids = []
    host_weights = []
    for h in range(n_hosts):
        osds = list(range(h * osds_per_host, (h + 1) * osds_per_host))
        weights = [int(rng.integers(1, 4)) * 0x10000 for _ in osds]
        alg = algs[int(rng.integers(len(algs)))]
        hid = cw.add_bucket(alg, 1, f"host{h}", osds, weights, id=-(h + 2))
        host_ids.append(hid)
        host_weights.append(sum(weights))
    cw.add_bucket(CRUSH_BUCKET_STRAW2, 10, "default", host_ids,
                  host_weights, id=-1)
    for i in range(n):
        cw.set_item_name(i, f"osd.{i}")
    return cw


@pytest.mark.parametrize("mode,rule_type", [("firstn", 1), ("indep", 3)])
@pytest.mark.parametrize("algs", [
    (CRUSH_BUCKET_STRAW2,),
    (CRUSH_BUCKET_UNIFORM, CRUSH_BUCKET_LIST, CRUSH_BUCKET_STRAW,
     CRUSH_BUCKET_STRAW2, CRUSH_BUCKET_TREE),
])
def test_mapper_parity_random_maps(mode, rule_type, algs):
    rng = np.random.default_rng(len(algs) * 10 + (1 if mode == "firstn" else 2))
    for trial in range(5):
        n_hosts = int(rng.integers(3, 8))
        oph = int(rng.integers(2, 5))
        cw = _random_map(rng, n_hosts, oph, algs)
        rno = cw.add_simple_rule("r", "default", "host", mode=mode,
                                 rule_type=rule_type)
        assert rno >= 0
        nm = native.NativeCrushMapper(cw.crush)
        n = n_hosts * oph
        weight = [0x10000] * n
        # randomly degrade some osds
        for i in rng.integers(0, n, size=max(1, n // 4)):
            weight[int(i)] = int(rng.integers(0, 2)) * 0x8000
        nrep = 3
        for x in range(500):
            py = crush_do_rule(cw.crush, rno, x, nrep, weight)
            cc = nm.do_rule(rno, x, nrep, weight)
            assert py == cc, (trial, x, py, cc)


def test_mapper_parity_batch():
    rng = np.random.default_rng(7)
    cw = _random_map(rng, 6, 4, (CRUSH_BUCKET_STRAW2,))
    rno = cw.add_simple_rule("r", "default", "host", mode="indep",
                             rule_type=PG_POOL_TYPE_ERASURE)
    nm = native.NativeCrushMapper(cw.crush)
    weight = [0x10000] * 24
    out, lens = nm.do_rule_batch(rno, list(range(1000)), 4, weight)
    for x in (0, 17, 500, 999):
        assert crush_do_rule(cw.crush, rno, x, 4, weight) == \
            out[x, :lens[x]].tolist()
