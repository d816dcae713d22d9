"""Layered encode time per client op (program span).

The ``ec.lrc.encode`` spans (``ec/lrc.py``
``ErasureCodeLrc.encode_batch_full``: the buffer fill, each layer's
gather, device call and scatter, inside the codec call that
``device_call_us_per_op`` reads), summed over the traced window, per
client op issued in it.
"""
from benchmark.lrc_spans import of_run
from benchmark.program_spans import per_unit

LAYER = "codec dispatch (ec/, dispatch/)"
SOURCE = "program_span"
UNIT = "us"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.total_s("ec.lrc.encode"),
                    "n_ops", 1e6)
