"""``store_adopted_bytes_per_op`` on synthetic traces with known
answers, and from a recorded trace of a ``MemStore``."""
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, program_spans

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _reader():
    return harness.load_module(
        os.path.join(METRICS, "store_adopted_bytes_per_op.py"),
        "test_store_metric_store_adopted_bytes_per_op")


def _run(threads, **layer):
    return NS(cell="x", result={"layer": layer},
              store_spans=program_spans.ProgramSpans(threads, 0, 10**9))


def test_reads_the_sum_per_op():
    threads = [{"os.queue_transaction": [
        (1000, 1600, {"staged_bytes": 0, "adopted_bytes": 524288}),
        (2000, 2400, {"staged_bytes": 4096, "adopted_bytes": 0})]},
        {"os.queue_transaction": [(500, 600, {"adopted_bytes": 8})]}]
    read = _reader().read
    assert read(_run(threads, n_ops=2)) == pytest.approx((524288 + 8) / 2)
    assert read(_run(threads, n_ops=0)) is None
    assert read(_run([], n_ops=2)) is None                # no such span


def test_a_program_without_the_arg_reads_nothing():
    """The parent's spans carry ``staged_bytes`` only."""
    threads = [{"os.queue_transaction": [
        (1000, 1600, {"staged_bytes": 0, "staged_objs": 1})]}]
    assert _reader().read(_run(threads, n_ops=2)) is None
    assert _reader().read(NS(cell="x", result={"layer": {"n_ops": 2}},
                             store_spans=None)) is None


def test_from_a_recorded_trace(tmp_path, monkeypatch):
    """A whole-body write of ``bytes`` is adopted; an offset write into
    it copies and adopts nothing."""
    import jax
    from ceph_tpu.os_store import MemStore, Transaction, hobject_t
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    store, ho = MemStore(), hobject_t("o", 0)
    with harness.profiler(os.path.join(str(tmp_path), "trace", "cell")):
        with jax.profiler.TraceAnnotation("window"):
            t = Transaction()
            t.create_collection("c")
            t.write("c", ho, 0, b"\1" * 4096)
            store.queue_transaction(t)
            t = Transaction()
            t.write("c", ho, 8, b"\2" * 8)
            store.queue_transaction(t)
    run = NS(cell="cell", result={"layer": {"n_ops": 2}})
    assert _reader().read(run) == 4096 / 2
