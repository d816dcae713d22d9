"""Body bytes the store copied to stage transactions, per client op (a
count).

The sum of the ``staged_bytes`` arg of the ``os.queue_transaction``
spans (a body shared with the committed object and edited in place is
copied, the bytes the edit keeps only; a body rewritten from offset 0
or a new object copies nothing), over the traced window, per client op
issued in it.
"""
from benchmark.program_spans import per_unit
from benchmark.store_spans import of_run

LAYER = "client and OSD op path (client/, msg/, osd/)"
SOURCE = "program_counter"
UNIT = "bytes"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.arg_sum("os.queue_transaction",
                                                 "staged_bytes"),
                    "n_ops", 1.0)
