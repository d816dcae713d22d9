"""Per-kernel device timing + profiler hooks — the tracing subsystem.

The reference instruments its hot paths with LTTng tracepoints
(src/tracing/*.tp, emitted from e.g. OSD.cc:6606) and threads one
ZTracer trace id through every op (msg/Message.h:254).  The TPU-native
equivalents here:

- ``KernelTimer``: named cumulative timing of device dispatches.  Off
  by default (timing forces a ``block_until_ready`` sync per call,
  which kills dispatch pipelining); flip on via config
  ``tracing_kernels`` or ``KernelTimer.enable()`` when diagnosing.
  Dumped over the admin socket ("kernel timings") next to perf
  counters — the "perf dump" of the device side.
- profiler spans: ``trace.span.Tracer.span(..., prof=<name>)`` puts
  named host spans into a running jax profiler trace (the tracepoint
  provider analog; docs/OBSERVABILITY.md has the catalog).
- trace ids: already carried end-to-end by every message
  (msg/messages.py new_trace_id), surfaced in OpTracker events.
"""
from __future__ import annotations

import contextlib
import threading

from .lockdep import DebugLock
import time
from typing import Any, Dict, Optional

# NOTE: ..trace imports back into common (span/histogram take their
# DebugLocks from common.lockdep), so g_tracer must resolve lazily
# or the two package __init__s deadlock on import order
_g_tracer = None


def _tracer():
    global _g_tracer
    if _g_tracer is None:
        from ..trace import g_tracer
        _g_tracer = g_tracer
    return _g_tracer


class KernelTimer:
    """Cumulative wall timing per named kernel.

    Thread-safe: concurrent OSD dispatch threads (osd_op_num_threads)
    record into the same stats dict, so the read-modify-write in
    ``_record`` runs under a lock — a lost sample would silently skew
    the very numbers this exists to make trustworthy.
    """

    def __init__(self):
        self.enabled = False
        self.stats: Dict[str, Dict[str, float]] = {}
        self._lock = DebugLock("KernelTrace::lock")

    def enable(self, on: bool = True) -> None:
        self.enabled = on

    @contextlib.contextmanager
    def time(self, name: str):
        """Time a host-side block (callers drain device values inside)."""
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self._record(name, time.perf_counter() - t0)

    def timed(self, name: str, fn, *args, **kw):
        """Call fn and drain its output: the one-shot instrumented
        dispatch used by the device backends when tracing is on.

        With the span tracer active this also emits a ``kernel:<name>``
        child span (and, when timing is on and a sync therefore exists,
        a ``device_drain`` child inside it) so device work shows up in
        the op's span tree.  The sync itself is still gated on
        ``self.enabled`` alone — spans never add one.
        """
        g_tracer = _tracer()
        if not g_tracer.enabled:
            if not self.enabled:
                return fn(*args, **kw)
            return self._timed_sync(name, fn, args, kw, None)
        with g_tracer.span(f"kernel:{name}") as sp:
            if not self.enabled:
                if sp is not None:
                    sp.tags["dispatch_only"] = True
                return fn(*args, **kw)
            return self._timed_sync(name, fn, args, kw, g_tracer)

    def _timed_sync(self, name: str, fn, args, kw, tracer):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        if tracer is not None:
            drain_span = tracer.begin("device_drain")
        else:
            drain_span = None
        try:
            import jax
            jax.block_until_ready(out)
        except Exception:
            pass
        if tracer is not None:
            tracer.finish(drain_span)
        self._record(name, time.perf_counter() - t0)
        return out

    def _record(self, name: str, dt: float) -> None:
        with self._lock:
            s = self.stats.setdefault(
                name, {"calls": 0, "total_s": 0.0, "max_s": 0.0})
            s["calls"] += 1
            s["total_s"] += dt
            s["max_s"] = max(s["max_s"], dt)

    def dump(self) -> Dict[str, Dict[str, float]]:
        out = {}
        with self._lock:
            snap = {name: dict(s) for name, s in self.stats.items()}
        for name, d in sorted(snap.items()):
            if d["calls"]:
                d["avg_ms"] = round(d["total_s"] / d["calls"] * 1e3, 3)
            out[name] = d
        return out

    def reset(self) -> None:
        with self._lock:
            self.stats.clear()


g_kernel_timer = KernelTimer()
