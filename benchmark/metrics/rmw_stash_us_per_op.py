"""Shard rollback-stash time per client op (program span).

The ``osd.rollback_stash`` spans (``osd/ec_backend.py``
``stash_pre_write_state``: a shard reading the object's body and
attrs and staging them in the PG's meta object, so that peering can
roll the write back), summed over the traced window, per client op
issued in it.
"""
from benchmark.program_spans import per_unit
from benchmark.rmw_spans import of_run

LAYER = "client and OSD op path (client/, msg/, osd/)"
SOURCE = "program_span"
UNIT = "us"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.total_s("osd.rollback_stash"),
                    "n_ops", 1e6)
