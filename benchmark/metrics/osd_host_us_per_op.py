"""Host time per client op in the client and OSD op path, outside the
codec call.

The fabric is single-threaded, the closed loop never sleeps, and with
the default options (no batching window, pipeline depth 1) a codec call
blocks that thread until its outputs are on the host.  So the window
splits into the codec calls (the stage ledger's ``device_call`` and
``d2h``, ``ceph_tpu/trace/oplat.py``: what ``device_call_us_per_op``
reads) and everything else: client submit, messages, sub-ops, stores,
acks and replies.  This is the rest, per op completed in the window.
The ledger's per-op stage sums are not used for it: with 16 ops in
flight its ``client_flight`` and ``ack_gather`` stages are mostly
waits on the other ops.
"""

LAYER = "client and OSD op path (client/, msg/, osd/)"
SOURCE = "program_span"
UNIT = "us"
MOVES = "client_MiBps"


def read(run):
    lay = run.result["layer"]
    stages = lay["oplat"]["stages"]
    codec = sum(stages[s]["total_usec"] for s in ("device_call", "d2h")
                if s in stages)
    rest = lay["span_s"] * 1e6 - codec
    if not lay["n_ops"] or rest <= 0:
        return None
    return rest / lay["n_ops"]
