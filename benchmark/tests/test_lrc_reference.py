"""The kml reference worked by hand and against the pinned corpus, the
LRC readers (``benchmark/lrc_spans.py``) on a synthetic trace and a
recorded one, and the lrc_bench check catching planted faults (CPU,
small deployment)."""
import contextlib
import copy
import hashlib
import json
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark import harness, program_spans
from benchmark.reference import lrc_kml
from benchmark.reference.gf256_rs import MUL

K, M, L, SU = 4, 2, 3, 4096
CELL = "lrc_k4m2l3.write_4m"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRICS = os.path.join(ROOT, "benchmark", "metrics")


def test_kml_layout_by_hand():
    mapping, layers = lrc_kml.parse_kml(K, M, L)
    assert mapping == "DD__DD__"
    assert layers == ["DDc_DDc_", "DDDc____", "____DDDc"]


def test_local_parity_is_the_xor_of_its_group():
    """jerasure's m=1 coding row is all ones, so a group's local parity
    (position 3 or 7) is the XOR of its 3 chunks: 2 data chunks and the
    group's global parity (position 2 or 6)."""
    assert np.array_equal(lrc_kml.jerasure_rs_van(3, 1),
                          np.ones((1, 3), np.uint8))
    body = np.random.default_rng(5).bytes(2 * K * SU)
    ch = lrc_kml.all_chunks(body, K, M, L, SU)
    assert np.array_equal(ch[3], ch[0] ^ ch[1] ^ ch[2])
    assert np.array_equal(ch[7], ch[4] ^ ch[5] ^ ch[6])
    # data chunk i of every stripe at the i-th D of the mapping
    stripes = np.frombuffer(body, np.uint8).reshape(-1, K, SU)
    for i, p in enumerate((0, 1, 4, 5)):
        assert np.array_equal(ch[p].reshape(-1, SU), stripes[:, i])
    # the global parities: jerasure's first coding row is all ones too,
    # its second weighs data chunk j by the matrix's entry
    g = lrc_kml.jerasure_rs_van(K, M)
    assert np.array_equal(g[0], np.ones(K, np.uint8)) and g[1, 0] == 1
    data = [ch[p] for p in (0, 1, 4, 5)]
    assert np.array_equal(ch[2], data[0] ^ data[1] ^ data[2] ^ data[3])
    q = np.zeros_like(ch[6])
    for j in range(K):
        q ^= MUL[g[1, j]][data[j]]
    assert np.array_equal(ch[6], q)


def test_reference_matches_the_pinned_corpus():
    with open(os.path.join(ROOT, "tests", "corpus", "ec_chunks.json")) as f:
        entry = json.load(f)["profiles"]["lrc_k4m2l3"]
    assert entry["profile"] == {"plugin": "lrc", "k": "4", "m": "2",
                                "l": "3"}
    payload = hashlib.shake_128(b"ceph-tpu-corpus-v1").digest(65536)
    # the corpus encodes the payload as one stripe of 16 KiB chunks
    ch = lrc_kml.all_chunks(payload, K, M, L, entry["chunk_size"])
    assert ch.shape == (8, entry["chunk_size"])
    for j in range(8):
        assert hashlib.sha256(ch[j].tobytes()).hexdigest() == \
            entry["chunk_sha256"][str(j)], j


@pytest.mark.parametrize("kml", [(8, 4, 4), (4, 2, 4), (4, 2, 2)])
def test_kml_refuses_invalid_profiles(kml):
    """BASELINE's k=8 m=4 l=4 makes 3 groups, and 8 is no multiple of
    3; k=4 m=2 l=4 fails (k + m) % l, l=2 makes 3 groups again.  The
    program refuses them as well."""
    from ceph_tpu.ec import create_erasure_code
    with pytest.raises(ValueError):
        lrc_kml.parse_kml(*kml)
    k, m, l = kml
    with pytest.raises(ValueError):
        create_erasure_code({"plugin": "lrc", "k": str(k), "m": str(m),
                             "l": str(l)})


# ---- the readers ------------------------------------------------------------

def _reader(name):
    return harness.load_module(os.path.join(METRICS, f"{name}.py"),
                               "test_lrc_metric_" + name)


THREADS = [{
    "ec.lrc.encode": [
        (1000, 1600, {"stripes": 256, "calls": 3, "host_bytes": 100}),
        (2000, 2400, {"stripes": 256, "calls": 3, "host_bytes": 50})],
}, {
    "ec.lrc.encode": [(500, 600, {"stripes": 1, "calls": 2,
                                  "host_bytes": 10})],
}]


@pytest.mark.parametrize("name,want", [
    ("lrc_encode_us_per_op", 1100e-3 / 2),
    ("lrc_codec_calls_per_op", 8 / 2),
    ("lrc_host_bytes_per_op", 160 / 2),
])
def test_lrc_readers(name, want):
    mod = _reader(name)

    def run(threads, n_ops):
        return NS(cell="x", result={"layer": {"n_ops": n_ops}},
                  lrc_spans=program_spans.ProgramSpans(threads, 0, 10**9))

    assert mod.read(run(THREADS, 2)) == pytest.approx(want)
    assert mod.read(run(THREADS, 0)) is None
    assert mod.read(run([], 2)) is None       # no such span


def test_lrc_spans_from_a_recorded_trace(tmp_path, monkeypatch):
    """One layered encode and one local repair in a profiler session on
    the CPU backend: three layer calls, 18 stripe chunks copied."""
    import jax
    from ceph_tpu.ec import create_erasure_code
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    ec = create_erasure_code({"plugin": "lrc", "k": "4", "m": "2",
                              "l": "3", "backend": "tpu"})
    stripes = np.random.default_rng(1).integers(0, 256, (2, K, 64),
                                                dtype=np.uint8)
    with harness.profiler(os.path.join(str(tmp_path), "trace", "cell")):
        with jax.profiler.TraceAnnotation("window"):
            full = ec.encode_batch_full(stripes)
            got = ec.decode_batch({i: full[:, i] for i in range(1, 8)},
                                  [0, 1, 4, 5])
    assert np.array_equal(got[0], stripes[:, 0])
    run = NS(cell="cell", result={"layer": {"n_ops": 1}})
    assert _reader("lrc_codec_calls_per_op").read(run) == 3
    assert _reader("lrc_host_bytes_per_op").read(run) == \
        (4 + 4 + 2 + 3 + 1 + 3 + 1) * 2 * 64
    assert _reader("lrc_encode_us_per_op").read(run) > 0
    assert run.lrc_spans.count("ec.lrc.decode") == 1
    assert run.lrc_spans.arg_sum("ec.lrc.decode", "local") == 1
    assert run.lrc_spans.arg_sum("ec.lrc.decode", "layers") == 1


# ---- the check catches planted faults ---------------------------------------

@contextlib.contextmanager
def _encode_patched(alter):
    """Every write's shards (physical id -> bytes) passed through
    *alter* as the primary's encode returns them."""
    from ceph_tpu.osd.ec_backend import ECBackend
    orig = ECBackend._encode

    def _encode(self, data):
        shards = {i: np.array(v, dtype=np.uint8, copy=True)
                  for i, v in orig(self, data).items()}
        alter(shards)
        return shards

    ECBackend._encode = _encode
    try:
        yield
    finally:
        ECBackend._encode = orig


def _flip_local_parity(shards):
    shards[3][0] ^= 0x01


def _logical_position(shards):
    """Logical data chunk 2 stored at position 2, not at its physical
    position 4 (and what belongs at 2 at 4)."""
    shards[2], shards[4] = shards[4], shards[2]


@pytest.fixture(scope="module")
def bench():
    # on the CPU the layers' backend (auto) resolves to the host; the
    # check needs them on a device, as on the chip
    import ceph_tpu.ops.gf_matmul as gm
    mp = pytest.MonkeyPatch()
    mp.setattr(gm, "device_available", lambda: True)
    cell = harness.Cell(CELL)
    cfg, tr = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    cfg["cluster"]["n_osds"] = 10
    cfg["pool"]["pg_num"] = 16
    tr["object_bytes"] = 65536
    yield cell.driver.build(cfg, tr, 2**31 + 29,
                            harness.span_factory(False), harness.log)
    mp.undo()


def _window(bench):
    res = bench.window(1.0)
    return res, bench.check()


def test_sound_window_passes(bench):
    res, checks = _window(bench)
    assert res["attempted"] > 16
    assert all(c["value"] == 0 for c in checks.values()), checks
    # n = 8 chunks of object_bytes / k each per write
    assert res["layer"]["codec_min_bytes"] == \
        len(bench.client.done) * 8 * (65536 // K)
    assert res["layer"]["device_calls"]["encode"] == \
        3 * (len(bench.client.done))


@pytest.mark.parametrize("alter,caught", [
    (_flip_local_parity, ("shards_differing",)),
    (_logical_position, ("shards_differing", "bytes_read_back_differing",
                         "degraded_read_back_differing")),
])
def test_a_planted_fault_fails(bench, alter, caught):
    with _encode_patched(alter):
        res, checks = _window(bench)
    assert res["failed"] == 0
    for name in caught:
        assert checks[name]["value"] > 0, (name, checks)
