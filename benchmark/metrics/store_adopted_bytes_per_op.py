"""Body bytes the store kept by reference, per client op (a count).

The sum of the ``adopted_bytes`` arg of the ``os.queue_transaction``
spans (a write of a read-only buffer that replaces a whole body makes
that buffer the body, with no zero-fill and no copy), over the traced
window, per client op issued in it.  A program whose spans lack the arg
reads nothing.
"""
from benchmark.program_spans import per_unit
from benchmark.store_spans import of_run

LAYER = "client and OSD op path (client/, msg/, osd/)"
SOURCE = "program_counter"
UNIT = "bytes"
MOVES = "client_MiBps"

SPAN, ARG = "os.queue_transaction", "adopted_bytes"


def read(run):
    spans = of_run(run)
    if spans is None or not any(ARG in args for t in spans.threads
                                for _s, _e, args in t.get(SPAN, ())):
        return None
    return per_unit(run, spans.arg_sum(SPAN, ARG), "n_ops", 1.0)
