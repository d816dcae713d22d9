"""Upload of the codec call's input per client op (program span).

The ``codec.h2d`` spans (``ops/gf_matmul.py``: the ``jnp.asarray`` of
the input batch in ``DeviceRSBackend.encode`` and ``.decode_data``),
summed over the traced window, per client op issued in it.
"""
from benchmark.program_spans import of_run, per_unit

LAYER = "codec dispatch (ec/, dispatch/)"
SOURCE = "program_span"
UNIT = "us"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.total_s("codec.h2d"), "n_ops", 1e6)
