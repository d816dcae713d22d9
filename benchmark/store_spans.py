"""The object store's program spans in a traced run.

``os.queue_transaction`` (``ceph_tpu/os_store/memstore.py``
``MemStore.queue_transaction``: staging one transaction copy-on-write
and applying it, under the store's write lock; its ``staged_bytes`` arg
is the body bytes copied to stage, ``staged_objs`` the objects given a
private copy) is read here from the trace ``program_spans`` reads
(whose ``CATALOG`` it is not in).  The trace is parsed once per run; a
program without the span gives a trace without it, and every reader
then reads nothing.
"""
from __future__ import annotations

import os

from benchmark.program_spans import ProgramSpans, from_planes
from benchmark.trace_reduce import find_trace

NAMES = ("os.queue_transaction",)


def of_run(run) -> ProgramSpans | None:
    if not hasattr(run, "store_spans"):
        from benchmark.harness import OUT_DIR, WINDOW_SPAN
        try:
            path = find_trace(os.path.join(OUT_DIR, "trace", run.cell))
        except FileNotFoundError:
            run.store_spans = None
        else:
            from jax.profiler import ProfileData
            run.store_spans = from_planes(ProfileData.from_file(path).planes,
                                          WINDOW_SPAN, names=NAMES)
    return run.store_spans
