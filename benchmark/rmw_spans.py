"""The read-modify-write path's program spans in a traced run.

``ec.rmw`` (a primary splicing, re-encoding and fanning out one partial
write), ``osd.rollback_stash`` (a shard stashing the object's state
before a write) and ``osd.sub_write.splice`` (a shard splicing a
partial write into its body) are read here, with the ``crc32c`` spans
that nest in the splice, from the trace ``program_spans`` reads (whose
``CATALOG`` they are not in).  The trace is parsed once per run; a
program without these spans gives a trace without them, and every
reader then reads nothing.
"""
from __future__ import annotations

import os

from benchmark.program_spans import ProgramSpans, from_planes
from benchmark.trace_reduce import find_trace

NAMES = ("ec.rmw", "osd.rollback_stash", "osd.sub_write.splice", "crc32c")


def of_run(run) -> ProgramSpans | None:
    if not hasattr(run, "rmw_spans"):
        from benchmark.harness import OUT_DIR, WINDOW_SPAN
        try:
            path = find_trace(os.path.join(OUT_DIR, "trace", run.cell))
        except FileNotFoundError:
            run.rmw_spans = None
        else:
            from jax.profiler import ProfileData
            run.rmw_spans = from_planes(ProfileData.from_file(path).planes,
                                        WINDOW_SPAN, names=NAMES)
    return run.rmw_spans
