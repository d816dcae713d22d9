"""Shard splice time per client op, less its crc32c (program span).

The ``osd.sub_write.splice`` spans (``osd/ec_backend.py``
``handle_sub_write``, partial writes: read the shard's body, splice
the new chunk range in, rebuild the body and its ``HashInfo``) outside
the ``crc32c`` spans in them, which ``crc_us_per_op`` reads; summed
over the traced window, per client op issued in it.
"""
from benchmark.program_spans import per_unit
from benchmark.rmw_spans import of_run

LAYER = "client and OSD op path (client/, msg/, osd/)"
SOURCE = "program_span"
UNIT = "us"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.self_s(("osd.sub_write.splice",),
                                                ("crc32c",)),
                    "n_ops", 1e6)
