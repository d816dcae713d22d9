"""The benchmark's command: one run of one cell, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up (TPU start, deployment built from the seed, every shape warmed)
counts into ``setup_s``, from the start of this process to the first
timed op.  Then the window runs for ``--seconds``; then the check holds
what the window produced to the plain reference.  The last line on
stdout is the result; the lines before it on stderr end with each
number the check compared, beside its limit.  With no TPU, or fewer
chips than the cell asks for, the run prints no result and exits 2; any
other failure exits 1, also with no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# the TPU runtime's logs stay inside the checkout, not in a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "benchmark", "out",
                                                  "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmark import harness
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_PROCESS)
    except harness.NoChip as e:
        harness.log(f"NO CHIP: {e}")
        return 2
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception as e:  # any failure: say why, print no result
        traceback.print_exc()
        print(f"[benchmark] FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        code = 1
    sys.exit(code)
