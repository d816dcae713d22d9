"""Bytes moved between host and device per client op (a count).

From the device-flow profiler (``ceph_tpu/trace/devprof.py``, always
on): host-to-device plus device-to-host bytes over the traced window,
per client op issued in it.  Nothing when no byte crossed.
"""

LAYER = "codec dispatch (ec/, dispatch/)"
SOURCE = "program_counter"
UNIT = "bytes"
MOVES = "client_MiBps"


def read(run):
    lay = run.result["layer"]
    moved = lay["devprof"].get("h2d_bytes", 0) + \
        lay["devprof"].get("d2h_bytes", 0)
    if not lay["n_ops"] or not moved:
        return None
    return moved / lay["n_ops"]
