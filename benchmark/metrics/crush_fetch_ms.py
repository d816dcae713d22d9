"""Fetch of the CRUSH mapper's result per map epoch (program span).

The ``crush.fetch`` spans (``ops/crush_fast.py:map_batch``: the
``np.asarray`` of the delta, or of the whole packed result, where the
host waits for the device programs and copies their output back),
summed over the traced window, per epoch completed in it.
"""
from benchmark.program_spans import of_run, per_unit

LAYER = "placement host (osdmap/mapping.py, ops/crush_fast.py host side)"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "remap_ms"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.total_s("crush.fetch"), "epochs",
                    1e3)
