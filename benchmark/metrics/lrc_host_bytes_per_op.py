"""Bytes the layered encode copies on the host per client op (a count).

The sum of the ``host_bytes`` arg of the ``ec.lrc.encode`` spans (the
data stripes filled into the all-chunks buffer, and each layer's input
gathered from it and output scattered into it), over the traced window,
per client op issued in it.
"""
from benchmark.lrc_spans import of_run
from benchmark.program_spans import per_unit

LAYER = "codec dispatch (ec/, dispatch/)"
SOURCE = "program_counter"
UNIT = "bytes"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.arg_sum("ec.lrc.encode",
                                                 "host_bytes"),
                    "n_ops", 1.0)
