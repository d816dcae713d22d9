"""Completion-fenced timing: the clock stops only after outputs exist
on the host.

The fence is ``block_until_ready`` followed by a device→host readback
of one element of the LAST output: PJRT executes in submission order,
so that fetch completing means every dispatch before it completed on
the device.  The readback costs one round trip and proves completion
even for an array double (or a backend) whose ready acknowledgement
arrives early — the harness tests pin that.

Accounting contract: the fenced elapsed time INCLUDES one transport
round trip (the drain fetch).  That RTT is measured separately and
reported alongside — never silently subtracted — so a reader can bound
the pure-compute time as ``elapsed - rtt <= compute <= elapsed``.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import numpy as np


class FencedTiming:
    """One fenced measurement: N steps dispatched back-to-back, drained,
    timed as a unit."""

    __slots__ = ("elapsed_s", "n_steps", "rtt_s", "fenced")

    def __init__(self, elapsed_s: float, n_steps: int, rtt_s: float):
        self.elapsed_s = elapsed_s
        self.n_steps = n_steps
        self.rtt_s = rtt_s
        self.fenced = True

    @property
    def per_step_s(self) -> float:
        return self.elapsed_s / max(self.n_steps, 1)

    def throughput(self, bytes_per_step: int) -> float:
        """GiB/s of payload through the timed region (fence included)."""
        return self.n_steps * bytes_per_step / self.elapsed_s / (1 << 30)

    def to_dict(self) -> Dict[str, Any]:
        return {"elapsed_s": self.elapsed_s, "n_steps": self.n_steps,
                "rtt_s": self.rtt_s, "fenced": True}


def drain(out: Any) -> None:
    """Materialize *out* on the host — the completion fence.

    Order matters: ``block_until_ready`` first (cheap), then a
    one-element host fetch, which no early acknowledgement can fake.
    Works on any
    object exposing the jax Array protocol or plain ``__array__`` —
    including test doubles that delay materialization.
    """
    bur = getattr(out, "block_until_ready", None)
    if bur is not None:
        bur()
    # One-ELEMENT readback, not the full array, so the fence itself
    # moves almost no bytes inside the timed region.  The slice
    # dispatch is submitted after the timed work, so its completion
    # implies everything before it completed.
    try:
        one = out.ravel()[:1]
    except Exception:
        one = out
    arr = np.asarray(one)
    if arr.size:
        arr.ravel()[:1].copy()
    # the fence IS a device->host readback: account it like any other
    # transfer so the devflow ledger never hides the drain's own copy
    from ..trace.devprof import g_devprof
    g_devprof.account_d2h("bench.drain", arr.nbytes)


def measure_rtt(make_tiny: Optional[Callable[[], Any]] = None,
                repeats: int = 3) -> float:
    """Median device→host round trip (seconds) for a tiny transfer.

    This is the fence's own cost, reported next to every fenced elapsed
    time so the reading can be bounded.
    """
    if make_tiny is None:
        import jax
        import jax.numpy as jnp

        def make_tiny():
            t = jnp.zeros((8,), jnp.int32) + jnp.int32(1)
            jax.block_until_ready(t)
            return t

    samples = []
    for _ in range(max(repeats, 1)):
        tiny = make_tiny()
        t0 = time.perf_counter()
        np.asarray(tiny)
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2]


def fenced_time(step: Callable[[int], Any], n_steps: int,
                rtt_s: Optional[float] = None,
                kernel_name: Optional[str] = None,
                drain_fn: Optional[Callable[[Any], Any]] = None
                ) -> FencedTiming:
    """Dispatch ``step(i)`` for i in [0, n_steps) back-to-back, fence on
    the LAST output, and time the whole region.

    ``drain_fn`` overrides the fence for outputs whose completion
    contract needs more than the single-element drain — a mesh-sharded
    output is only proven complete by a readback from EVERY shard's
    device (``parallel.ec.drain_sharded``); the default ``drain`` is
    the single-device contract.

    ``step`` must return the dispatch's output (device array or pytree
    leaf).  Only the LAST output is retained: a submitted PJRT dispatch
    executes whether or not its output handle is kept (dropping the
    handle frees the buffer after execution, it does not cancel it), so
    retention would buy nothing — and holding all N outputs at the
    calibrated step count can pin gigabytes of HBM and OOM a real-chip
    run.  The caller salts the step input by ``i`` so no transport/XLA
    layer can serve a repeat from cache.
    """
    if n_steps < 1:
        raise ValueError("n_steps must be >= 1")
    if rtt_s is None:
        rtt_s = measure_rtt()
    from ..trace import g_perf_histograms, g_tracer, latency_axes
    span = g_tracer.begin(
        f"bench_fence:{kernel_name or 'fenced'}") if g_tracer.enabled \
        else None
    last: Any = None
    t0 = time.perf_counter()
    with g_tracer.activate(span):
        for i in range(n_steps):
            last = step(i)
        t_issued = time.perf_counter()
        drain_span = g_tracer.begin("drain") if span is not None else None
        (drain_fn or drain)(last)
        g_tracer.finish(drain_span)
    elapsed = time.perf_counter() - t0
    g_tracer.finish(span)
    # stage-latency ledger (trace/oplat.py): a fenced region decomposes
    # into the back-to-back dispatch loop (device_call) and the drain
    # fetch that completes it (d2h) — the two stamps sum to the fenced
    # elapsed exactly, so every fenced workload's stage_breakdown
    # reconciles with its wall by construction
    from ..trace.oplat import g_oplat
    g_oplat.record("bench", "device_call", (t_issued - t0) * 1e6)
    g_oplat.record("bench", "d2h", (t0 + elapsed - t_issued) * 1e6)
    timing = FencedTiming(elapsed, n_steps, rtt_s)
    # per-step latency lands in the always-on bench histogram so
    # `python -m ceph_tpu.bench` metric lines carry the distribution
    g_perf_histograms.get("bench", "fenced_step_latency_histogram",
                          latency_axes).inc(
        elapsed / n_steps * 1e6)
    if kernel_name:
        from ..common.kernel_trace import g_kernel_timer
        if g_kernel_timer.enabled:
            g_kernel_timer._record(kernel_name, elapsed)
    return timing
