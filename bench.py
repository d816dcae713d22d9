"""Headline benchmark driver: EC encode/decode + CRUSH remap, k=8 m=4.

Thin survivability shell over the ``ceph_tpu.bench`` subsystem, which
owns ALL measurement mechanics: completion-fenced timers (the clock
stops only after a device→host drain of the last output), warmup/repeat
statistics (median/IQR/min), a roofline validator that stamps
``suspect: true`` on any reading implying more than the chip's physical
peak, and the versioned metric schema.  See docs/BENCHMARKING.md for
the methodology.

It measures a TPU or nothing: with no TPU as JAX's default device it
exits non-zero before printing any result line.

What stays HERE is the survivability contract (the caller may kill this
process with an external timeout):
  - ONE overall wall-clock budget (CEPH_TPU_BENCH_BUDGET, default
    480 s); sections are skipped when the budget is nearly exhausted
    instead of overrunning.
  - The JSON result line is (re-)printed after EVERY completed section
    with ``"partial": true``; only the final complete emit flips it to
    false — a kill at any moment leaves a parseable last line on stdout
    that is distinguishable from a finished run.  A failed section
    still prints its line, and the run then exits non-zero.
  - A dedicated sigwait() watcher thread dumps the partial line on
    SIGTERM/SIGINT even while the main thread is blocked inside a
    compile; a deadline watchdog covers budget overrun.

Legacy flat keys (value, ec_decode_e2_gibs, crush_remap_*) are kept so
the BENCH_r*.json trajectory stays field-compatible; the new
schema-versioned records ride alongside under ``"metrics"``.
"""
from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time

import numpy as np

K, M = 8, 4
OBJECT_SIZE = 1 << 20           # 1 MiB per object
CHUNK = OBJECT_SIZE // K        # 128 KiB
BATCH = 64                      # objects per device call
TARGET_SECONDS = 3.0

# One budget for the whole run: everything below is paced against this
# deadline so the run exits cleanly before an external timeout.
BUDGET = float(os.environ.get("CEPH_TPU_BENCH_BUDGET", "480"))
_T0 = time.monotonic()


def _remaining() -> float:
    return BUDGET - (time.monotonic() - _T0)


RESULT: dict = {
    "metric": "ec_encode_k8m4_1MiB_throughput",
    "value": 0.0,
    "unit": "GiB/s",
    "vs_baseline": None,
    "partial": True,
    "metrics": [],
}
_ERRORS: list[str] = []
_SKIPPED: list[str] = []


def _emit(final: bool = False) -> None:
    """(Re-)print the result line with everything measured so far.

    ``partial`` stays true on every milestone re-print and on watcher/
    watchdog dumps; only the one complete end-of-run emit flips it to
    false, so a kill mid-run is distinguishable from a finished line
    even though both re-print identical measurement keys.

    Serializes a snapshot: this runs from the watcher/watchdog threads
    while the main thread may be inserting keys, and json.dumps over a
    mutating container raises mid-dump."""
    if final:
        RESULT["partial"] = False
    if _ERRORS:
        RESULT["error"] = "; ".join(list(_ERRORS))
    if _SKIPPED:
        RESULT["skipped_sections"] = ",".join(list(_SKIPPED))
    RESULT["elapsed_s"] = round(time.monotonic() - _T0, 1)
    snap = dict(RESULT)
    snap["metrics"] = list(RESULT["metrics"])
    sys.stdout.write(json.dumps(snap) + "\n")
    sys.stdout.flush()


def _dump_and_exit(reason: str, code: int) -> None:
    # async-safe-ish: plain dict -> json -> one write.  Used from signal
    # handlers and the watchdog thread, where the main thread may be
    # blocked inside a compile.
    _ERRORS.append(reason)
    try:
        _emit()
    finally:
        os._exit(code)


def _sig_watcher() -> None:  # pragma: no cover - signal path
    """Block in sigwait() on a non-main thread: fires immediately on
    SIGTERM/SIGINT even while the main thread is stuck in a native PJRT
    call (where a Python-level signal handler would be deferred
    indefinitely).  Requires the signals to be masked process-wide
    before any thread starts."""
    sig = signal.sigwait({signal.SIGTERM, signal.SIGINT})
    _dump_and_exit(f"killed by signal {sig}; partial results", 128 + sig)


def _watchdog() -> None:  # pragma: no cover - timing path
    """If the main thread overruns the budget by >30 s (stuck compile),
    dump whatever we have.  Daemon thread: a clean exit just drops it."""
    while True:
        left = _remaining()
        if left <= -30.0:
            _dump_and_exit("watchdog: budget exceeded; partial results", 3)
        time.sleep(min(max(left + 30.0, 1.0), 30.0))


def main() -> None:
    signal.pthread_sigmask(signal.SIG_BLOCK,
                           {signal.SIGTERM, signal.SIGINT})
    threading.Thread(target=_sig_watcher, daemon=True).start()
    threading.Thread(target=_watchdog, daemon=True).start()

    import jax
    from ceph_tpu.arch import configure_compile_cache
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[bench] no TPU: JAX's default device is {dev.platform}; "
              "nothing to measure", file=sys.stderr)
        raise SystemExit(1)
    RESULT["platform"] = dev.platform
    RESULT["device_kind"] = dev.device_kind
    RESULT["device_count"] = len(jax.devices())
    configure_compile_cache()
    _emit()     # first parseable line exists before any measurement

    from ceph_tpu.bench import workloads
    from ceph_tpu.gf.matrices import gf_gen_rs_matrix
    rng = np.random.default_rng(1234)
    matrix = gf_gen_rs_matrix(K + M, K)
    batch = rng.integers(0, 256, size=(BATCH, K, CHUNK), dtype=np.uint8)

    host_gibs = 0.0
    try:
        hm = workloads.measure_host_native(
            matrix, batch[0], target_seconds=TARGET_SECONDS / 2)
        if hm is not None:
            host_gibs = hm["value"]
            RESULT["host_native_gibs"] = round(host_gibs, 3)
            RESULT["metrics"].append(hm)
    except Exception as e:
        _ERRORS.append(f"host bench failed: {e!r}")
    _emit()

    def run_section(label: str, fn, min_needed: float) -> None:
        """Run one section inside the budget; re-emit the line after."""
        if _remaining() < min_needed:
            _SKIPPED.append(label)
            _emit()
            return
        try:
            fn()
        except Exception as e:
            _ERRORS.append(f"{label} failed: {e!r}")
        _emit()

    def encode_section() -> None:
        m = workloads.measure_encode(
            matrix, batch, target_seconds=TARGET_SECONDS,
            repeats=3)
        RESULT["metrics"].append(m)
        # headline value = the FENCED median; the roofline verdict and
        # implied TOPS ride inside the metric record
        RESULT["value"] = m["value"]
        RESULT["encode_suspect"] = m["suspect"]
        if host_gibs:
            RESULT["vs_baseline"] = round(m["value"] / host_gibs, 2)

    def decode_section() -> None:
        m = workloads.measure_decode(
            matrix, batch, target_seconds=TARGET_SECONDS,
            repeats=3)
        RESULT["metrics"].append(m)
        RESULT["ec_decode_e2_gibs"] = m["value"]

    def _partial(kv: dict) -> None:
        # milestone flush: remap numbers hit the JSON line the moment
        # they exist, so a watchdog kill later in the section cannot
        # erase the north star
        RESULT.update(kv)
        host = RESULT.get("crush_remap_native_host_ms")
        us = RESULT.get("crush_remap_us")
        if host and us:
            RESULT["crush_remap_vs_native_host"] = round(
                host / (us / 1000.0), 2)
        _emit()

    def crush_section(uniform: bool = True, infix: str = "") -> None:
        # STABLE metric keys across rounds: the workload size lives in
        # crush_remap_pgs, never in the key name.
        *_ignored, ms = workloads.measure_crush_remap(
            n_pgs=100_000, epochs=10,
            uniform=uniform, partial=_partial, infix=infix,
            debug=bool(os.environ.get("CEPH_TPU_BENCH_DEBUG")))
        RESULT["metrics"].extend(ms)

    def pipeline_section() -> None:
        # depth-8 async write pipeline vs depth-1 synchronous submit
        # from ONE thread — the dispatch-amortization headline; host-
        # materialized completions, so safe before the fetch-heavy
        # parity receipt but after the pure one-element-drain sections
        mp, mp1 = workloads.measure_ec_pipeline(
            n_requests=32,
            target_seconds=TARGET_SECONDS / 2,
            repeats=3)
        RESULT["metrics"].extend([mp, mp1])
        RESULT["ec_pipeline_gibs"] = mp["value"]
        RESULT["ec_pipeline_speedup"] = mp["speedup"]
        RESULT["ec_pipeline_occupancy"] = mp["mean_batch_occupancy"]

    def parity_section() -> None:
        RESULT["decode_parity"] = workloads.parity_check(matrix)

    # Ordered so a budget kill costs the least: encode, decode (both
    # drain via one-element fetches only), then the remap north star,
    # then extras, then the fetch-heavy parity receipt last.  min_needed
    # gates reflect that a cold-cache section pays its compiles.
    run_section("device bench", encode_section, 45.0)
    run_section("decode bench", decode_section, 45.0)
    run_section("crush bench", lambda: crush_section(True), 110.0)
    run_section("crush nonuniform bench",
                lambda: crush_section(False, "_nonuniform"), 80.0)
    run_section("ec pipeline bench", pipeline_section, 45.0)
    run_section("decode parity", parity_section, 45.0)
    _emit(final=True)
    if _ERRORS:
        raise SystemExit(1)


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # last-ditch: the JSON line must still appear,
        _ERRORS.append(f"bench crashed: {e!r}")  # but rc stays truthful
        try:
            _emit()
        except Exception:
            print(json.dumps({
                "metric": "ec_encode_k8m4_1MiB_throughput",
                "value": 0.0, "unit": "GiB/s", "vs_baseline": None,
                "partial": True,
                "error": f"bench crashed: {e!r}",
            }))
        raise SystemExit(1)
