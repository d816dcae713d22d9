"""Codec call time per client op, host clock, as the stage ledger sees it.

The ``device_call`` (codec submit to the device call's return) and
``d2h`` (return to outputs on the host) stages of the stage ledger
(``ceph_tpu/trace/oplat.py``), summed over the traced window, per client
op issued in it.  Nothing when no op called the codec.
"""

LAYER = "codec dispatch (ec/, dispatch/)"
SOURCE = "program_span"
UNIT = "us"
MOVES = "client_MiBps"


def read(run):
    lay = run.result["layer"]
    stages = lay["oplat"]["stages"]
    t = sum(stages[s]["total_usec"] for s in ("device_call", "d2h")
            if s in stages)
    if not lay["n_ops"] or not t:
        return None
    return t / lay["n_ops"]
