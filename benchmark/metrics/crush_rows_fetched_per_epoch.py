"""Rows copied into the mapper's host mirror per map epoch (a count).

The sum of the ``rows`` arg of the ``crush.fetch`` spans
(``ops/crush_fast.py:map_batch``: the rows that changed on a delta
fetch, every row on a full one), over the traced window, per epoch
completed in it.
"""
from benchmark.program_spans import of_run, per_unit

LAYER = "placement host (osdmap/mapping.py, ops/crush_fast.py host side)"
SOURCE = "program_counter"
UNIT = "rows"
MOVES = "remap_ms"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.arg_sum("crush.fetch", "rows"),
                    "epochs", 1.0)
