"""The GF(2^8) codec kernels' share of their HBM roofline.

The work counted is what the codec must do, whatever kernel does it:
an encode reads k data chunks and writes m coding chunks, a decode
reads k surviving chunks and writes the erased chunks the read needs
(the driver's ``codec_min_bytes``, summed over the ops issued in the
traced window).  Those bytes at the device's published HBM bandwidth
(``peaks.json``) give the least time; the share is that over the device
time of the GF programs in the trace.  Nothing when no GF program ran.
"""

LAYER = "kernels (ops/)"
SOURCE = "device_trace"
UNIT = "%"
MOVES = "client_MiBps"


# the programs of ops/gf_matmul.py as XLA names them
GF_PROGRAMS = ("jit_gf_bit_matmul", "jit_gfw_bit_matmul")


def read(run):
    t = sum(s for name, s in run.trace["programs"].items()
            if name in GF_PROGRAMS)
    nbytes = run.result["layer"]["codec_min_bytes"]
    if t <= 0 or not nbytes:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / t
