"""Bytes the shards stashed for rollback per client op (a count).

The sum of the ``bytes`` arg of the ``osd.rollback_stash`` spans (the
shard body each stash copied; 0 where the object was new or a replay
kept the first stash), over the traced window, per client op issued
in it.
"""
from benchmark.program_spans import per_unit
from benchmark.rmw_spans import of_run

LAYER = "client and OSD op path (client/, msg/, osd/)"
SOURCE = "program_counter"
UNIT = "bytes"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.arg_sum("osd.rollback_stash",
                                                 "bytes"),
                    "n_ops", 1.0)
