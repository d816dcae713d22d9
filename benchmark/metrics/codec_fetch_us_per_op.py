"""Fetch of the codec call's output per client op (program span).

The ``codec.fetch`` spans (``ops/gf_matmul.py``: the ``np.asarray`` of
the output in ``DeviceRSBackend.encode`` and ``.decode_data``, where the
host waits for the kernel and copies its output back), summed over the
traced window, per client op issued in it.
"""
from benchmark.program_spans import of_run, per_unit

LAYER = "codec dispatch (ec/, dispatch/)"
SOURCE = "program_span"
UNIT = "us"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.total_s("codec.fetch"), "n_ops",
                    1e6)
