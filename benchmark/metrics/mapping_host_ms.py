"""Host time of ``update()`` per map epoch, outside the fetch.

The time inside the ``osdmap.update`` spans (``osdmap/mapping.py``:
``OSDMapMapping.update()``) outside the ``crush.fetch`` spans in them
(``crush_fetch_ms``): fingerprint, weight upload, launch, patching the
host mirror, replay and post-passes; summed over the traced window, per
epoch completed in it.
"""
from benchmark.program_spans import of_run, per_unit

LAYER = "placement host (osdmap/mapping.py, ops/crush_fast.py host side)"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "remap_ms"


def read(run):
    spans = of_run(run)
    t = spans and spans.self_s(("osdmap.update",), ("crush.fetch",))
    return per_unit(run, t, "epochs", 1e3)
