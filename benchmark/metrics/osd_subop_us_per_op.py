"""Shard sub-op handling per client op, less what other metrics read.

The time inside the ``osd.sub_write`` and ``osd.sub_read`` spans
(``osd/osd.py``: an OSD applying or reading its shard) outside the
``crc32c``, ``crush.scalar``, ``codec.h2d`` and ``codec.fetch`` spans
in them, which ``crc_us_per_op``, ``scalar_crush_us_per_op`` and the
codec metrics read; summed over the traced window, per client op
issued in it.
"""
from benchmark.program_spans import of_run, per_unit

LAYER = "client and OSD op path (client/, msg/, osd/)"
SOURCE = "program_span"
UNIT = "us"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    t = spans and spans.self_s(("osd.sub_write", "osd.sub_read"),
                               ("crc32c", "crush.scalar", "codec.h2d",
                                "codec.fetch"))
    return per_unit(run, t, "n_ops", 1e6)
