"""Device codec calls of the layered encode per client op (a count).

The sum of the ``calls`` arg of the ``ec.lrc.encode`` spans (one per
layer whose delegate ran on the device: 3 for kml k=4 m=2 l=3, 1 for a
code folded into one generator matrix), over the traced window, per
client op issued in it.
"""
from benchmark.lrc_spans import of_run
from benchmark.program_spans import per_unit

LAYER = "codec dispatch (ec/, dispatch/)"
SOURCE = "program_counter"
UNIT = "calls"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.arg_sum("ec.lrc.encode", "calls"),
                    "n_ops", 1.0)
