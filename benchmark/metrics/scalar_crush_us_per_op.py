"""Scalar CRUSH time per client op (program span).

The ``crush.scalar`` spans (``crush/mapper.py:crush_do_rule``, one per
call: the client's ``_calc_target``, placement on the OSDs), summed over
the traced window, per client op issued in it.
"""
from benchmark.program_spans import of_run, per_unit

LAYER = "client and OSD op path (client/, msg/, osd/)"
SOURCE = "program_span"
UNIT = "us"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.total_s("crush.scalar"), "n_ops",
                    1e6)
