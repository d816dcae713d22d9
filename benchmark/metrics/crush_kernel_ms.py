"""Device time of the CRUSH mapper's programs per map epoch.

The programs of ``ops/crush_fast.py`` (candidates, resolve, packed
resolve, delta), summed from the profiler trace over the traced window,
per epoch completed in it.  Nothing when none ran.
"""

LAYER = "kernels (ops/)"
SOURCE = "device_trace"
UNIT = "ms"
MOVES = "remap_ms"


# the programs of ops/crush_fast.py as XLA names them
CRUSH_PROGRAMS = ("jit__candidates", "jit__resolve", "jit__resolve_packed",
                  "jit__delta")


def read(run):
    t = sum(s for name, s in run.trace["programs"].items()
            if name in CRUSH_PROGRAMS)
    epochs = run.result["layer"]["epochs"]
    if t <= 0 or not epochs:
        return None
    return 1e3 * t / epochs
