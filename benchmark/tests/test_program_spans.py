"""program_spans on synthetic planes with known answers, each reader on
a small run, and the path from a recorded trace through ``of_run``."""
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, program_spans

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def planes():
    """Window [100, 1100) on the main thread; a second thread too."""
    main = NS(name="main", events=[
        ev("window", 100, 1000),
        ev("window", 2000, 10),                 # shorter: not the window
        ev("osd.sub_write", 50, 150, shard=1),  # clipped to [100, 200)
        ev("crc32c", 60, 60, bytes=8),          # outside: [60, 120) -> 20
        ev("osd.sub_write", 300, 200, shard=2),
        ev("crc32c", 320, 30, bytes=16),
        ev("crush.scalar", 400, 50),
        ev("crc32c", 1050, 100, bytes=32),      # clipped to [1050, 1100)
        ev("crc32c", 1500, 10, bytes=64),       # after the window
    ])
    worker = NS(name="worker", events=[
        ev("osd.sub_read", 600, 100, shard=3),
        ev("osd.sub_read", 620, 20, shard=4),   # nested, same name
        ev("crc32c", 650, 10, bytes=128),
        ev("crush.fetch", 700, 40, rows=5, full=0),
        ev("crush.fetch", 900, 60, rows=7, full=1),
        ev("other", 0, 5000),
    ])
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        ev("crc32c", 100, 500)])])              # not a host plane
    return [device, NS(name="/host:CPU", lines=[main, worker])]


@pytest.fixture
def spans():
    return program_spans.from_planes(planes(), "window")


def test_totals_clip_to_the_window_and_sum_threads(spans):
    # crc32c: 20 + 30 + 50 + 10 (worker)
    assert spans.total_s("crc32c") == pytest.approx(110e-9)
    assert spans.count("crc32c") == 4
    # nested sub_read counted once
    assert spans.total_s("osd.sub_read") == pytest.approx(100e-9)
    assert spans.total_s("osd.sub_write") == pytest.approx(300e-9)
    assert spans.total_s("codec.h2d") is None


def test_self_time_less_named_children(spans):
    got = spans.self_s(("osd.sub_write", "osd.sub_read"),
                       ("crc32c", "crush.scalar"))
    # sub_write [100,200)+[300,500) = 300 less crc 20 + 30 + scalar 50;
    # sub_read [600,700) = 100 less crc 10
    assert got == pytest.approx((300 - 100 + 100 - 10) * 1e-9)
    assert spans.self_s(("codec.fetch",), ("crc32c",)) is None


def test_arg_sums(spans):
    assert spans.arg_sum("crc32c", "bytes") == 8 + 16 + 32 + 128
    assert spans.arg_sum("crush.fetch", "rows") == 12
    assert spans.arg_sum("crush.fetch", "full") == 1
    assert spans.arg_sum("osdmap.update", "pgs") is None


def test_no_window_no_spans():
    assert program_spans.from_planes(planes()[:1], "window") is None


def _reader(name):
    return harness.load_module(os.path.join(METRICS, f"{name}.py"),
                               "test_metric_" + name)


def _run(threads, **layer):
    return NS(cell="x", result={"layer": layer},
              program_spans=program_spans.ProgramSpans(threads, 0, 10**9))


EC_THREADS = [{
    "osd.sub_write": [(0, 4000, {"shard": 0})],
    "crc32c": [(100, 1100, {"bytes": 4})],
    "crush.scalar": [(2000, 2500, {})],
    "codec.h2d": [(5000, 5300, {"bytes": 9})],
    "codec.fetch": [(5300, 6000, {"bytes": 3})],
}]
PLACE_THREADS = [{
    "osdmap.update": [(0, 10_000_000, {"pgs": 8}),
                      (20_000_000, 30_000_000, {"pgs": 8})],
    "crush.fetch": [(1_000_000, 3_000_000, {"rows": 10, "full": 0}),
                    (21_000_000, 25_000_000, {"rows": 30, "full": 0})],
}]


@pytest.mark.parametrize("name,want", [
    ("codec_h2d_us_per_op", 300e-3 / 2),
    ("codec_fetch_us_per_op", 700e-3 / 2),
    ("crc_us_per_op", 1000e-3 / 2),
    ("scalar_crush_us_per_op", 500e-3 / 2),
    ("osd_subop_us_per_op", 2500e-3 / 2),
])
def test_ec_readers(name, want):
    mod = _reader(name)
    assert mod.read(_run(EC_THREADS, n_ops=2)) == pytest.approx(want)
    assert mod.read(_run(EC_THREADS, n_ops=0)) is None
    assert mod.read(_run([], n_ops=2)) is None   # a program without spans


@pytest.mark.parametrize("name,want", [
    ("mapping_host_ms", (20 - 6) / 2),
    ("crush_fetch_ms", 6 / 2),
    ("crush_rows_fetched_per_epoch", 40 / 2),
])
def test_placement_readers(name, want):
    mod = _reader(name)
    assert mod.read(_run(PLACE_THREADS, epochs=2)) == pytest.approx(want)
    assert mod.read(_run([], epochs=2)) is None


def test_no_trace_reads_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    run = NS(cell="absent", result={"layer": {"n_ops": 3}})
    assert _reader("crc_us_per_op").read(run) is None


def test_recorded_trace_through_of_run(tmp_path, monkeypatch):
    """The program's spans from a profiler session on the CPU backend,
    read back from where the harness leaves the trace."""
    import jax
    from ceph_tpu.trace import g_tracer
    from ceph_tpu.utils.crc32c import crc32c
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    with harness.profiler(os.path.join(str(tmp_path), "trace", "cell")):
        with jax.profiler.TraceAnnotation("window"):
            for shard in range(3):
                with g_tracer.span(prof="osd.sub_write", shard=shard):
                    crc32c(b"\0" * 4096)
    run = NS(cell="cell", result={"layer": {"n_ops": 3}})
    spans = program_spans.of_run(run)
    assert spans.count("osd.sub_write") == 3
    assert spans.arg_sum("osd.sub_write", "shard") == 3
    assert spans.arg_sum("crc32c", "bytes") == 3 * 4096
    sub = _reader("osd_subop_us_per_op").read(run)
    crc = _reader("crc_us_per_op").read(run)
    total = spans.total_s("osd.sub_write") * 1e6 / 3
    assert sub > 0 and crc > 0
    assert sub + crc == pytest.approx(total)
