"""Device GF(2^8) bit-matmul kernels vs the host oracle — byte parity.

Runs on the virtual CPU mesh in tests; the same code path runs on TPU.
"""
import itertools

import numpy as np
import pytest

from ceph_tpu.ec import plugin_registry
from ceph_tpu.ec.rs_codec import MatrixRSCodec
from ceph_tpu.gf.matrices import gf_gen_rs_matrix, gf_gen_cauchy1_matrix
from ceph_tpu.ops.gf_matmul import DeviceRSBackend


@pytest.mark.parametrize("k,m,gen", [
    (4, 2, gf_gen_rs_matrix),
    (8, 4, gf_gen_rs_matrix),
    (6, 3, gf_gen_cauchy1_matrix),
])
def test_device_encode_matches_host(k, m, gen):
    matrix = gen(k + m, k)
    host = MatrixRSCodec(matrix)
    dev = DeviceRSBackend(matrix)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(5, k, 256), dtype=np.uint8)
    got = dev.encode(data)
    assert got.shape == (5, m, 256)
    for s in range(5):
        want = host.encode(data[s])
        np.testing.assert_array_equal(got[s], want)


def test_device_decode_matches_host():
    k, m = 8, 4
    matrix = gf_gen_rs_matrix(k + m, k)
    host = MatrixRSCodec(matrix)
    dev = DeviceRSBackend(matrix)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=(3, k, 128), dtype=np.uint8)
    coding = dev.encode(data)
    full = np.concatenate([data, coding], axis=1)  # (S, k+m, C)
    for gone in itertools.combinations(range(k + m), 2):
        srcs = sorted(set(range(k + m)) - set(gone))[:k]
        survivors = full[:, srcs, :]
        want_rows = [i for i in gone if i < k]
        if not want_rows:
            continue
        rec = dev.decode_data(survivors, srcs, want_rows)
        for s in range(3):
            chunks = {i: full[s, i] for i in srcs}
            out = host.decode(chunks, want_rows)
            for idx, i in enumerate(want_rows):
                np.testing.assert_array_equal(rec[s, idx], out[i])


def test_tpu_plugin_single_stripe_parity():
    """ErasureCodeTpu chunks == isa host chunks, byte-identical."""
    prof = {"k": "4", "m": "2"}
    host = plugin_registry.factory("isa", {**prof, "backend": "host"})
    tpu = plugin_registry.factory("tpu", prof)
    rng = np.random.default_rng(2)
    payload = rng.integers(0, 256, size=4096, dtype=np.uint8).tobytes()
    want = set(range(6))
    enc_h = host.encode(want, payload)
    enc_t = tpu.encode(want, payload)
    for i in want:
        np.testing.assert_array_equal(enc_h[i], enc_t[i])


def test_tpu_plugin_batch_roundtrip():
    tpu = plugin_registry.factory("tpu", {"k": "8", "m": "4"})
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, size=(16, 8, 512), dtype=np.uint8)
    coding = tpu.encode_batch(data)
    assert coding.shape == (16, 4, 512)
    # erase shards 1 and 9 (one data, one coding) across the whole batch
    chunks = {i: (data[:, i] if i < 8 else coding[:, i - 8])
              for i in range(12) if i not in (1, 9)}
    out = tpu.decode_batch(chunks, [1, 9])
    np.testing.assert_array_equal(out[1], data[:, 1])
    np.testing.assert_array_equal(out[9], coding[:, 1])


def test_tpu_plugin_batch_coding_only_recovery():
    # all data chunks survive; only a coding shard is lost (the most common
    # repair) — regression for the skipped-reencode bug
    tpu = plugin_registry.factory("tpu", {"k": "3", "m": "2"})
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(4, 3, 64), dtype=np.uint8)
    coding = tpu.encode_batch(data)
    chunks = {i: data[:, i] for i in range(3)}
    chunks[4] = coding[:, 1]
    out = tpu.decode_batch(chunks, [3])
    np.testing.assert_array_equal(out[3], coding[:, 0])


def test_pallas_kernel_parity_with_xla_path():
    """ops/gf_pallas.py (fused unpack->MXU->pack in VMEM) must be
    byte-identical to the XLA dot_general path (here in interpret
    mode; tests/test_tpu_compile.py compiles it for the chip)."""
    import numpy as np
    import jax.numpy as jnp
    from ceph_tpu.ops.gf_matmul import gf_bit_matmul
    from ceph_tpu.ops.gf_pallas import gf_bit_matmul_pallas, \
        pallas_supported
    from ceph_tpu.gf.matrices import gf_gen_rs_matrix
    from ceph_tpu.gf.tables import expand_to_bitmatrix

    rng = np.random.default_rng(9)
    for (s, k, m, c) in [(4, 8, 4, 512), (1, 4, 2, 128), (3, 6, 3, 1152)]:
        assert pallas_supported(c)
        data = jnp.asarray(rng.integers(0, 256, (s, k, c), dtype=np.uint8))
        mat = gf_gen_rs_matrix(k + m, k)
        bits = jnp.asarray(expand_to_bitmatrix(mat[k:]).astype(np.int8))
        a = np.asarray(gf_bit_matmul(data, bits))
        b = np.asarray(gf_bit_matmul_pallas(data, bits, interpret=True))
        np.testing.assert_array_equal(a, b, err_msg=str((s, k, m, c)))
    assert not pallas_supported(96)  # below the minimum tile
