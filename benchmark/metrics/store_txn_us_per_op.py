"""Store transaction time per client op (program span).

The ``os.queue_transaction`` spans (``os_store/memstore.py``
``MemStore.queue_transaction``: one transaction staged copy-on-write
and applied, inside the ``osd.sub_write`` span of the shard that
queued it, which ``osd_subop_us_per_op`` still reads whole), summed
over the traced window, per client op issued in it.
"""
from benchmark.program_spans import per_unit
from benchmark.store_spans import of_run

LAYER = "client and OSD op path (client/, msg/, osd/)"
SOURCE = "program_span"
UNIT = "us"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.total_s("os.queue_transaction"),
                    "n_ops", 1e6)
