"""Host crc32c time per client op (program span).

The ``crc32c`` spans (``utils/crc32c.py:crc32c``, one per call: the
sub-write's ``HashInfo``, ``_shard_crc`` on sub-reads), summed over the
traced window, per client op issued in it.
"""
from benchmark.program_spans import of_run, per_unit

LAYER = "client and OSD op path (client/, msg/, osd/)"
SOURCE = "program_span"
UNIT = "us"
MOVES = "client_MiBps"


def read(run):
    spans = of_run(run)
    return per_unit(run, spans and spans.total_s("crc32c"), "n_ops", 1e6)
