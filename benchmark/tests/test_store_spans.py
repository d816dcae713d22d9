"""The store readers (``benchmark/store_spans.py``) on a synthetic trace
with known answers, and from a recorded trace of a ``MemStore``."""
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, program_spans

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _reader(name):
    return harness.load_module(os.path.join(METRICS, f"{name}.py"),
                               "test_store_metric_" + name)


def _store_run(threads, **layer):
    return NS(cell="x", result={"layer": layer},
              store_spans=program_spans.ProgramSpans(threads, 0, 10**9))


STORE_THREADS = [{
    "os.queue_transaction": [
        (1000, 1600, {"staged_bytes": 0, "staged_objs": 1}),
        (2000, 2400, {"staged_bytes": 4096, "staged_objs": 2})],
}, {
    "os.queue_transaction": [(500, 600, {"staged_bytes": 8, "staged_objs": 1})],
}]


@pytest.mark.parametrize("name,want", [
    ("store_txn_us_per_op", 1100e-3 / 2),
    ("store_staged_bytes_per_op", (4096 + 8) / 2),
])
def test_store_readers(name, want):
    mod = _reader(name)
    assert mod.read(_store_run(STORE_THREADS, n_ops=2)) == \
        pytest.approx(want)
    assert mod.read(_store_run(STORE_THREADS, n_ops=0)) is None
    assert mod.read(_store_run([], n_ops=2)) is None  # no such span


def test_store_spans_from_a_recorded_trace(tmp_path, monkeypatch):
    """``os.queue_transaction`` from a profiler session on the CPU
    backend: one fresh write and one offset write into it."""
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
    assert _reader("store_staged_bytes_per_op").read(run) == 4096 / 2
    assert _reader("store_txn_us_per_op").read(run) > 0
    assert run.store_spans.arg_sum("os.queue_transaction",
                                   "staged_objs") == 1
