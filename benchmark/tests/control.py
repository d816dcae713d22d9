"""Readings of the check: sound windows, and windows with a fault planted.

    python3 benchmark/tests/control.py --cell <cell> --seeds 11 12 13 \\
        --seconds 5 [--faults encode_altered ...] [--small]

For each seed: one set-up of the cell, then one sound window and one
window under each fault (``faults.py``; by default every fault the
cell's traffic can have), each followed by the cell's own check.  On the
chip it runs at the cell's own size (this is how ``PERF.md``'s
readings were taken); ``--small`` shrinks the deployment for a CPU
rehearsal.  Prints one JSON line per window and exits 1 when a sound
window reads incorrect or a faulted one reads correct.  This is not the
benchmark's command and the benchmark's runs never plant a fault.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import harness  # noqa: E402
from benchmark.tests.faults import BY_TRAFFIC, FAULTS  # noqa: E402


def small(cell: harness.Cell):
    """A deployment a CPU test run can hold: same shapes, less scale."""
    cfg, tr = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    if cfg["kind"] == "ec_pool":
        cfg["pool"]["pg_num"] = 16
        tr["object_bytes"] = 65536
        if "prefill_objects" in tr:
            tr["prefill_objects"] = 12
    else:
        cfg["pool"]["pg_num"] = 1500
    return cfg, tr


def readings(cell_name: str, seed: int, seconds: float, faults,
             shrink: bool = False):
    """[(fault or None, correct, checks)] for one set-up of the cell."""
    cell = harness.Cell(cell_name)
    cfg, tr = small(cell) if shrink else (cell.config, cell.traffic)
    bench = cell.driver.build(cfg, tr, seed, harness.span_factory(False),
                              harness.log)
    out = []
    for fault in [None] + list(faults):
        ctx = FAULTS[fault]() if fault else contextlib.nullcontext()
        with ctx:
            res = bench.window(seconds)
        checks = bench.check()
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        out.append((fault, correct, checks, res["attempted"]))
        if fault:
            # a fault may leave a codec breaker open: the next window
            # starts from a closed one
            from ceph_tpu.fault import g_breakers
            g_breakers.reset()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--faults", nargs="*")
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args(argv)
    harness.configure_compile_cache()
    if not args.small:
        harness.require_chips(harness.Cell(args.cell).chips)
    faults = args.faults if args.faults is not None else \
        BY_TRAFFIC[harness.Cell(args.cell).entry["traffic"]]
    bad = 0
    for seed in args.seeds:
        for fault, correct, checks, attempted in readings(
                args.cell, seed, args.seconds, faults, args.small):
            print(json.dumps({"cell": args.cell, "seed": seed,
                              "fault": fault, "correct": correct,
                              "attempted": attempted, "checks": checks}),
                  flush=True)
            bad += int(correct == (fault is not None))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
