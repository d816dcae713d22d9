"""Mesh-sharded EC path: multi-device parity with the host oracle.

conftest.py forces an 8-device virtual CPU platform, so these genuinely
exercise the (stripe, shard) shardings and the digest collective.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ceph_tpu.gf.matrices import gf_gen_rs_matrix
from ceph_tpu.ec.rs_codec import MatrixRSCodec
from ceph_tpu.parallel import (
    make_mesh, mesh_shape_for, ShardedRS, pipeline_step,
    example_pipeline_args)


def test_mesh_shape_factoring():
    assert mesh_shape_for(8) == (4, 2)
    assert mesh_shape_for(1) == (1, 1)
    assert mesh_shape_for(7) == (7, 1)
    assert mesh_shape_for(4, max_shard=4) == (1, 4)


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def __init__(self, i):
        self.id = i


def test_make_mesh_raises_when_tpu_backend_is_short(monkeypatch):
    """One chip, four asked for: make_mesh raises instead of swapping in
    the virtual host devices (a mesh that silently left the chip would
    make a four-chip run a CPU run)."""
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_FakeTpu(0)])
    with pytest.raises(ValueError, match="need 4 devices, have 1"):
        make_mesh(4)


@pytest.mark.parametrize("n", [1, 2, 8])
def test_sharded_encode_matches_host(n):
    k, m, s, c = 8, 4, 16, 512
    mat = gf_gen_rs_matrix(k + m, k)
    host = MatrixRSCodec(mat)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(s, k, c), dtype=np.uint8)
    sharded = ShardedRS(mat, make_mesh(n))
    got = sharded.encode(data)
    expect = np.stack([host.encode(d) for d in data])
    assert np.array_equal(got, expect)


def test_sharded_decode_recovers_data():
    k, m, s, c = 4, 2, 8, 256
    mat = gf_gen_rs_matrix(k + m, k)
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, size=(s, k, c), dtype=np.uint8)
    sharded = ShardedRS(mat, make_mesh(8))
    coding = sharded.encode(data)
    # lose chunks 0 and 2; survivors 1,3,4,5
    srcs = [1, 3, 4, 5]
    all_chunks = np.concatenate([data, coding], axis=1)
    survivors = all_chunks[:, srcs, :]
    rec = sharded.decode_data(survivors, srcs, [0, 2])
    assert np.array_equal(rec[:, 0], data[:, 0])
    assert np.array_equal(rec[:, 1], data[:, 2])


def test_survivor_sharded_decode_xor_allreduce():
    """Contraction-sharded decode: each device holds a SLICE of the k
    survivors (no chip sees them all); the GF(2) reduction crosses the
    mesh as one psum-then-parity collective.  Byte-identical to the
    replicated-survivor decode and the host oracle."""
    k, m, s, c = 8, 4, 16, 256
    mat = gf_gen_rs_matrix(k + m, k)
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, size=(s, k, c), dtype=np.uint8)
    sharded = ShardedRS(mat, make_mesh(8))     # (4, 2) mesh
    coding = sharded.encode(data)
    allc = np.concatenate([data, coding], axis=1)
    srcs = [1, 2, 3, 5, 6, 7, 8, 10]           # lose 0, 4, 9, 11
    survivors = allc[:, srcs, :]
    want = [0, 4]
    via_collective = sharded.decode_data_survivor_sharded(
        survivors, srcs, want)
    via_replicated = sharded.decode_data(survivors, srcs, want)
    assert np.array_equal(via_collective, via_replicated)
    assert np.array_equal(via_collective[:, 0], data[:, 0])
    assert np.array_equal(via_collective[:, 1], data[:, 4])
    # a k not divisible by the shard axis is refused, not mis-sharded
    bad = ShardedRS(gf_gen_rs_matrix(5 + 2, 5), make_mesh(8))
    sv5 = np.zeros((8, 5, 64), np.uint8)
    with pytest.raises(ValueError):
        bad.decode_data_survivor_sharded(sv5, [0, 1, 2, 3, 4], [5])


def test_reshard_stripes_to_chunks_all_to_all():
    """The encode->distribution layout switch rides one all_to_all
    over the stripe axis (sequence<->head resharding analog): values
    are IDENTICAL, only the sharding moves."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ceph_tpu.parallel.mesh import STRIPE_AXIS

    k, m, s, c = 8, 4, 16, 256
    mat = gf_gen_rs_matrix(k + m, k)
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, size=(s, k, c), dtype=np.uint8)
    sharded = ShardedRS(mat, make_mesh(8))     # stripe axis size 4
    coding = sharded.encode(data)
    allc = np.concatenate([data, coding], axis=1)
    out = sharded.reshard_stripes_to_chunks(jnp.asarray(allc))
    assert np.array_equal(np.asarray(out), allc)
    # the output really is chunk-sharded over the stripe axis
    want = NamedSharding(sharded.mesh, P(None, STRIPE_AXIS, None))
    assert out.sharding.is_equivalent_to(want, ndim=3)
    with pytest.raises(ValueError):
        sharded.reshard_stripes_to_chunks(
            jnp.zeros((8, 5, 64), jnp.uint8))   # 5 % 4 != 0


def test_pipeline_step_8dev():
    mesh = make_mesh(8)
    args = example_pipeline_args(mesh, s=8, k=8, m=4, c=256)
    with mesh:
        chunks, digests = jax.jit(pipeline_step)(*args)
    chunks = np.asarray(chunks)
    data = np.asarray(args[0])
    assert np.array_equal(chunks[:, :8, :], data)
    mat = gf_gen_rs_matrix(12, 8)
    host = MatrixRSCodec(mat)
    expect = np.stack([host.encode(d) for d in data])
    assert np.array_equal(chunks[:, 8:, :], expect)
    # the digest collective must match the same fold done in numpy
    c = chunks.shape[2]
    w = (np.arange(c, dtype=np.uint64) * 0x01000193 + 0x811C9DC5) \
        .astype(np.uint32)
    expect_digests = (chunks.astype(np.uint64) * w[None, None, :]) \
        .sum(axis=(0, 2)).astype(np.uint32)
    assert np.array_equal(np.asarray(digests), expect_digests)


def test_graft_entry_contract():
    import sys, os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import __graft_entry__ as ge
    fn, example_args = ge.entry()
    out = jax.jit(fn)(*example_args)
    assert out.shape == (16, 4, 4096)
    ge.dryrun_multichip(8)


def test_sharded_crush_resolve_matches_host_oracle():
    """PGs sharded over the full 8-device mesh resolve identically to
    the exact host mapper; the packed output is genuinely distributed."""
    import numpy as np
    from ceph_tpu.crush import CrushWrapper, CRUSH_BUCKET_STRAW2
    from ceph_tpu.parallel import make_mesh
    from ceph_tpu.parallel.crush import sharded_fast_rule

    cw = CrushWrapper()
    cw.set_type_name(1, "host")
    cw.set_type_name(10, "root")
    hosts = []
    n_osds, per = 40, 4
    for h in range(n_osds // per):
        osds = list(range(h * per, (h + 1) * per))
        hosts.append(cw.add_bucket(CRUSH_BUCKET_STRAW2, 1, f"h{h}", osds,
                                   [0x10000] * per, id=-(h + 2)))
    cw.set_max_devices(n_osds)
    cw.add_bucket(CRUSH_BUCKET_STRAW2, 10, "default", hosts,
                  [0x10000 * per] * len(hosts), id=-1)
    rno = cw.add_simple_rule("data", "default", "host", mode="firstn")
    mesh = make_mesh(8)
    sf = sharded_fast_rule(cw.crush, rno, 3, mesh)
    xs = np.arange(1000, dtype=np.uint32)
    w = np.full(n_osds, 0x10000, dtype=np.uint32)
    w[7] = 0
    res, cnt = sf.map_batch(xs, w)
    wl = [int(v) for v in w]
    for x in range(0, 1000, 13):
        expect = cw.do_rule(rno, int(x), 3, wl)
        got = [int(v) for v in res[x, :cnt[x]]]
        assert got == expect, (x, got, expect)
    # the resolve output is actually sharded across devices
    packed = sf.resolve_device(w)
    assert len(packed.sharding.device_set) == 8


def test_sharded_crush_nonuniform_exact64_parity():
    """Regression: the sharded candidate build must go through
    FastRule._run_candidates so the exact64 draw traces under x64 —
    a direct _cand_jit call silently truncates the u64 tables to 32
    bits and produces wrong placements with risky=False."""
    import numpy as np
    from ceph_tpu.crush import CrushWrapper, CRUSH_BUCKET_STRAW2
    from ceph_tpu.parallel import make_mesh
    from ceph_tpu.parallel.crush import ShardedFastRule

    rng = np.random.default_rng(3)
    cw = CrushWrapper()
    cw.set_type_name(1, "host")
    cw.set_type_name(10, "root")
    hosts, osd = [], 0
    for h in range(8):
        osds = list(range(osd, osd + 4))
        osd += 4
        ws = [int(w) for w in rng.integers(0x9000, 0x22000, 4)]
        hosts.append(cw.add_bucket(CRUSH_BUCKET_STRAW2, 1, f"h{h}",
                                   osds, ws, id=-(h + 2)))
    cw.set_max_devices(osd)
    cw.add_bucket(CRUSH_BUCKET_STRAW2, 10, "default", hosts,
                  [0x30000] * 8, id=-1)
    rno = cw.add_simple_rule("data", "default", "host", mode="firstn")
    sf = ShardedFastRule(cw.crush, rno, 3, make_mesh(8))
    assert sf.fr._exact64        # non-uniform weights: exact64 is on
    xs = np.arange(640, dtype=np.uint32)
    w = [0x10000] * osd
    res, cnt = sf.map_batch(xs, np.asarray(w, np.uint32))
    for x in range(640):
        expect = cw.do_rule(rno, int(x), 3, list(w))
        assert [int(v) for v in res[x, :cnt[x]]] == expect, x


# ---- multichip completion fence (ROADMAP follow-up) -------------------------
def test_drain_sharded_touches_every_shard():
    """The mesh fence fetches one element from EVERY addressable shard
    of the last output — per-device completion proof, not just a
    block_until_ready acknowledgement."""
    from ceph_tpu.parallel import drain_sharded
    k, m, s, c = 8, 4, 16, 256
    mat = gf_gen_rs_matrix(k + m, k)
    sharded = ShardedRS(mat, make_mesh(8))
    data = np.random.default_rng(0).integers(
        0, 256, size=(s, k, c), dtype=np.uint8)
    out = sharded.encode_device(jnp.asarray(data))
    n = sharded.drain(out)
    assert n == len(out.addressable_shards) == 8
    # byte parity survives the fence (drain must not mutate)
    assert np.asarray(out).tobytes() == \
        MatrixRSCodec(mat).encode(
            np.ascontiguousarray(data.transpose(1, 0, 2)).reshape(
                k, s * c)).reshape(m, s, c).transpose(1, 0, 2).tobytes()
    # host values fall back to the single-device drain
    assert drain_sharded(np.arange(4)) == 1


def test_mesh_roofline_scales_with_devices():
    """A mesh-wide reading is judged against the MESH's physics: chip
    peaks scale by device count, so a throughput that is impossible for
    one chip but fine for eight is not flagged."""
    from ceph_tpu.bench.roofline import EC_ENCODE_K8M4, validate_reading
    from ceph_tpu.parallel import mesh_roofline
    mesh = make_mesh(8)
    single = validate_reading(10.0, EC_ENCODE_K8M4, "cpu", "", 1)
    meshwide = mesh_roofline(10.0, EC_ENCODE_K8M4, mesh, platform="cpu")
    assert meshwide["peak_tops"] == 8 * single["peak_tops"]
    assert meshwide["peak_hbm_gibs"] == 8 * single["peak_hbm_gibs"]
    # 30 GiB/s implies ~16.4 int8 TOPS: impossible on one generous-cpu
    # chip (2 TOPS), within an 8-chip mesh's 16... just over: use 25
    hot = validate_reading(25.0, EC_ENCODE_K8M4, "cpu", "", 1)
    assert hot["suspect"]
    cool = mesh_roofline(25.0, EC_ENCODE_K8M4, mesh, platform="cpu")
    assert not cool["suspect"]
    assert ShardedRS(gf_gen_rs_matrix(12, 8), mesh).roofline(
        25.0, EC_ENCODE_K8M4)["verdict"] == "ok"
