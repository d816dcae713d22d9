"""The layered code's program spans in a traced run.

``ec.lrc.encode`` (``ceph_tpu/ec/lrc.py``
``ErasureCodeLrc.encode_batch_full``: one write's layered encode, the
``codec.h2d`` and ``codec.fetch`` spans of its layer calls nested in it;
its ``calls`` arg is the layer calls made on the device, ``host_bytes``
the bytes its buffer fill and each layer's gather and scatter copied on
the host) and ``ec.lrc.decode`` (``ErasureCodeLrc.decode_batch``) are
read here from the trace ``program_spans`` reads (whose ``CATALOG``
they are not in).  The trace is parsed once per run; a program without
these spans gives a trace without them, and every reader then reads
nothing.
"""
from __future__ import annotations

import os

from benchmark.program_spans import ProgramSpans, from_planes
from benchmark.trace_reduce import find_trace

NAMES = ("ec.lrc.encode", "ec.lrc.decode")


def of_run(run) -> ProgramSpans | None:
    if not hasattr(run, "lrc_spans"):
        from benchmark.harness import OUT_DIR, WINDOW_SPAN
        try:
            path = find_trace(os.path.join(OUT_DIR, "trace", run.cell))
        except FileNotFoundError:
            run.lrc_spans = None
        else:
            from jax.profiler import ProfileData
            run.lrc_spans = from_planes(ProfileData.from_file(path).planes,
                                        WINDOW_SPAN, names=NAMES)
    return run.lrc_spans
