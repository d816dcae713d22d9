"""Share of the traced window in which no operation ran on the chip, in
the EC cells (``trace_reduce.idle_share_pct``)."""
from benchmark.trace_reduce import idle_share_pct

LAYER = "device"
SOURCE = "device_trace"
UNIT = "%"
MOVES = "client_MiBps"


def read(run):
    return idle_share_pct(run.trace)
