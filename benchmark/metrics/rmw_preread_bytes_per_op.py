"""Bytes the primary read from the shards per client op, for its
read-modify-writes (a count).

The sum of the ``preread_bytes`` arg of the ``ec.rmw`` spans
(``osd/ec_backend.py`` ``_rmw_have_old``: the logical bytes of the
stripes read back before the splice; 0 where the extent cache held
them), over the traced window, per client op issued in it.
"""
from benchmark.program_spans import per_unit
from benchmark.rmw_spans import of_run

LAYER = "client and OSD op path (client/, msg/, osd/)"
SOURCE = "program_counter"
UNIT = "bytes"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.arg_sum("ec.rmw", "preread_bytes"),
                    "n_ops", 1.0)
