"""Program spans in the JAX profiler's trace (``trace/span.py``).

Inside one profiler session written to a temporary directory, the
codec, crc32c, scalar CRUSH, the batched CRUSH fetch, ``update()``, and
an EC write, read and partial overwrite through a mini-cluster each
leave their catalog
span, with its args as event stats, in the ``.xplane.pb`` that
``ProfileData`` reads back.  With no session and the tracer disabled a
span is one shared no-op: no ``Span``, no clock read, nothing recorded.
"""
import glob
import os
import types

import numpy as np
import pytest

from ceph_tpu.trace import g_tracer
from ceph_tpu.trace import span as span_mod

CATALOG = {
    "codec.h2d": {"bytes"},
    "codec.fetch": {"bytes"},
    "crc32c": {"bytes", "impl"},
    "crush.scalar": {"impl"},
    "osd.sub_write": {"shard"},
    "osd.sub_read": {"shard"},
    "osdmap.update": {"pgs"},
    "crush.fetch": {"rows", "full"},
    "ec.rmw": {"stripes", "preread_bytes", "cache_hit"},
    "osd.rollback_stash": {"bytes"},
    "osd.sub_write.splice": {"bytes"},
}


@pytest.fixture(autouse=True)
def clean_tracing():
    g_tracer.enable(False)
    g_tracer.collector.clear()
    yield
    g_tracer.enable(False)
    g_tracer.collector.clear()


def _session(log_dir):
    import jax
    from jax.profiler import ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def _events(log_dir):
    """name -> list of stats dicts, over every host plane and line."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in CATALOG:
                    out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


def _small_osdmap():
    from test_crush_device import build_map
    from ceph_tpu.osdmap.osdmap import OSDMap
    from ceph_tpu.osdmap.types import TYPE_REPLICATED, pg_pool_t
    m = OSDMap()
    m.epoch = 1
    cw, n = build_map(n_hosts=6, osds_per_host=4)
    m.crush = cw
    rno = cw.add_simple_rule("data", "default", "host", mode="firstn")
    for o in range(n):
        m.set_osd(o, up=True)
    m.add_pool("p", pg_pool_t(type=TYPE_REPLICATED, size=3, min_size=2,
                              crush_rule=rno, pg_num=64, pgp_num=64),
               pool_id=1)
    return m, cw, rno, n


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Run one of each path inside a profiler session; the events."""
    import jax
    from ceph_tpu.cluster import MiniCluster
    from ceph_tpu.crush.mapper import crush_do_rule
    from ceph_tpu.gf.matrices import gf_gen_rs_matrix
    from ceph_tpu.ops.crush_fast import compile_fast_rule
    from ceph_tpu.ops.gf_matmul import DeviceRSBackend
    from ceph_tpu.osdmap.mapping import OSDMapMapping
    from ceph_tpu.utils.crc32c import crc32c

    k, m, S, C = 4, 2, 2, 64
    dev = DeviceRSBackend(gf_gen_rs_matrix(k + m, k))
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (S, k, C), dtype=np.uint8)
    osdmap, cw, rno, n = _small_osdmap()
    fr = compile_fast_rule(cw.crush, rno, 3)
    xs = np.arange(256, dtype=np.uint32)
    weight = [0x10000] * n
    out_w = list(weight)
    out_w[5] = 0
    mapping = OSDMapMapping()
    c = MiniCluster(n_osds=6)
    c.create_ec_pool("spans", k=3, m=2, pg_num=8)
    cl = c.client()
    # warm every program outside the session
    coding = dev.encode(data)
    dev.decode_data(np.concatenate([data[:, 1:], coding[:, :1]], axis=1),
                    (1, 2, 3, 4), (0,))
    fr.map_batch(xs, weight)
    mapping.update(osdmap)
    assert cl.write_full("spans", "warm", b"w" * 12288) == 0

    log_dir = tmp_path_factory.mktemp("profile")
    _session(log_dir)
    try:
        coding = dev.encode(data)
        survivors = np.concatenate([data[:, 1:], coding[:, :1]], axis=1)
        decoded = dev.decode_data(survivors, (1, 2, 3, 4), (0,))
        crc32c(b"\x01" * 100)
        crush_do_rule(cw.crush, rno, 7, 3, weight)
        cw.do_rule(rno, 7, 3, weight)
        fresh = compile_fast_rule(cw.crush, rno, 3)
        fresh.map_batch(xs, weight)             # full fetch
        fresh.map_batch(xs, out_w)              # delta fetch
        osdmap.osd_weight[5] = 0
        osdmap.epoch += 1
        mapping.update(osdmap)
        assert cl.write_full("spans", "obj", b"z" * 12288) == 0
        assert cl.read("spans", "obj") == b"z" * 12288
        # a partial overwrite: the read-modify-write path
        assert cl.write("spans", "obj", b"q" * 100, 5000) == 0
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(decoded[:, 0], data[:, 0])
    return types.SimpleNamespace(events=_events(log_dir), S=S, k=k, m=m,
                                 C=C, X=len(xs), delta=fresh.delta_cap)


@pytest.mark.parametrize("name", sorted(CATALOG))
def test_catalog_span_in_profiler_trace(traced, name):
    evs = traced.events.get(name)
    assert evs, f"no {name} span in the trace"
    for stats in evs:
        assert set(stats) == CATALOG[name], (name, stats)


def test_codec_span_bytes(traced):
    S, k, m, C = traced.S, traced.k, traced.m, traced.C
    h2d = [e["bytes"] for e in traced.events["codec.h2d"]]
    fetch = [e["bytes"] for e in traced.events["codec.fetch"]]
    # the direct encode and decode come first, in that order
    assert h2d[:2] == [S * k * C, S * k * C]
    assert fetch[:2] == [S * m * C, S * 1 * C]


def test_crush_fetch_rows(traced):
    evs = traced.events["crush.fetch"]
    # the fresh rule's first fetch is whole; its second is a delta of
    # the rows that moved with one OSD out
    assert evs[0] == {"rows": traced.X, "full": 1}
    assert evs[1]["full"] == 0 and 0 < evs[1]["rows"] <= traced.delta
    assert traced.events["osdmap.update"][0]["pgs"] == 64


def test_crc32c_span_names_the_native_path(traced):
    from ceph_tpu import native
    impls = {e["impl"] for e in traced.events["crc32c"]}
    assert impls == {native.crc32c_impl()}
    assert impls <= {"sse42", "armv8", "table8"}
    assert 100 in [e["bytes"] for e in traced.events["crc32c"]]


def test_crush_scalar_span_names_the_engine(traced):
    """The interpreter called directly says ``python``; the per-PG
    lookup (``CrushWrapper.do_rule``, and the client's placement of
    each op through it) runs on the C++ engine and says ``native``."""
    impls = [e["impl"] for e in traced.events["crush.scalar"]]
    assert impls.count("python") == 1
    assert impls.count("native") >= 3       # do_rule; write, read, overwrite
    assert set(impls) == {"python", "native"}


def test_rmw_span_args(traced):
    """The overwrite re-encodes one stripe of 3 x 4096 bytes after
    reading it from the shards; each shard stashes and splices its
    4096-byte body (the full write before it stashed nothing)."""
    assert traced.events["ec.rmw"] == [
        {"stripes": 1, "preread_bytes": 12288, "cache_hit": 0}]
    splice = [e["bytes"] for e in traced.events["osd.sub_write.splice"]]
    assert splice == [4096] * 5
    stash = sorted(e["bytes"] for e in traced.events["osd.rollback_stash"])
    assert stash == [0] * 5 + [4096] * 5


def test_off_span_is_free(monkeypatch):
    """No session, tracer disabled: no Span, no clock, nothing kept."""
    def boom(*_a, **_k):
        raise AssertionError("an off span built a Span or read the clock")
    monkeypatch.setattr(span_mod, "Span", boom)
    monkeypatch.setattr(span_mod, "time",
                        types.SimpleNamespace(monotonic=boom))
    a = g_tracer.span("sub_write:s1", prof="osd.sub_write", shard=1)
    b = g_tracer.span(prof="crc32c", bytes=4)
    assert a is b                       # one shared no-op object
    with a as sp:
        a.set(rows=3)
        assert sp is None
    # the crc32c every caller goes through, its impl arg included
    from ceph_tpu.utils.crc32c import crc32c
    crc32c(b"\x01" * 4)
    assert g_tracer.span(prof="crc32c", bytes=4, impl="sse42") is a
    assert g_tracer.collector.dump() == {}


def test_ring_span_keeps_name_and_takes_args_as_tags():
    g_tracer.enable()
    with g_tracer.span("sub_write:s3", daemon="osd.1", trace_id=9,
                       prof="osd.sub_write", shard=3) as sp:
        assert g_tracer.current() is sp
    assert g_tracer.current() is None
    (got,) = g_tracer.collector.dump("osd.1")["osd.1"]
    assert got["name"] == "sub_write:s3" and got["tags"] == {"shard": 3}
    assert got["end"] is not None
    # a profiler-only span never enters the ring
    with g_tracer.span(prof="crc32c", bytes=4) as sp:
        assert sp is None
    assert list(g_tracer.collector.dump()) == ["osd.1"]
