"""The program's own spans in a traced run's profiler trace.

The program puts named host spans into the JAX profiler's trace while a
session runs (``ceph_tpu/trace/span.py``, ``Tracer.span(prof=...)``;
the catalog is in ``docs/OBSERVABILITY.md``).  This reads them from the
trace of the run's window, ``OUT_DIR/trace/<cell>``, which the harness
removes only after the readers ran.  The trace is parsed once per run.

All times are in seconds, clipped to the longest ``window`` span and
summed over the host threads (one line of a host plane each).  On a
thread, a span that nests in another of the same name is not counted
twice.  A program without such spans gives a trace without them, and
every reader then reads nothing.
"""
from __future__ import annotations

import os
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.trace_reduce import _clip, _union, find_trace

# the profiler names of the program's spans (ceph_tpu/trace/span.py)
CATALOG = ("codec.h2d", "codec.fetch", "crc32c", "crush.scalar",
           "osd.sub_write", "osd.sub_read", "osdmap.update", "crush.fetch")

Interval = Tuple[float, float]


class ProgramSpans:
    """The catalog's spans of one window, thread by thread.  Each
    reading is None where no span of the names it reads is in the
    window."""

    def __init__(self, threads: List[Dict[str, List[Tuple[float, float,
                                                         Dict]]]],
                 lo: float, hi: float):
        self.threads = threads
        self.lo, self.hi = lo, hi

    def _merged(self, names: Iterable[str]) -> List[List[Interval]]:
        """Per thread: the union of the named spans' intervals, clipped."""
        names = tuple(names)
        return [_clip(_union([(s, e) for n in names
                              for s, e, _a in t.get(n, ())]),
                      self.lo, self.hi) for t in self.threads]

    def _inside(self, name: str):
        for t in self.threads:
            for s, e, args in t.get(name, ()):
                if e > self.lo and s < self.hi:
                    yield args

    def total_s(self, name: str) -> Optional[float]:
        if not self.count(name):
            return None
        return sum(e - s for iv in self._merged((name,)) for s, e in iv) \
            * 1e-9

    def count(self, name: str) -> int:
        return sum(1 for _a in self._inside(name))

    def self_s(self, parents: Iterable[str],
               children: Iterable[str]) -> Optional[float]:
        """Time inside any of *parents* and outside every one of
        *children* (spans of those names that ran in them)."""
        parents = tuple(parents)
        if not any(self.count(p) for p in parents):
            return None
        out = 0.0
        for p_iv, c_iv in zip(self._merged(parents), self._merged(children)):
            out += sum(e - s for s, e in p_iv)
            out -= sum(e - s for s, e in _intersect(p_iv, c_iv))
        return out * 1e-9

    def arg_sum(self, name: str, arg: str) -> Optional[float]:
        if not self.count(name):
            return None
        return sum(float(a.get(arg, 0)) for a in self._inside(name))


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def from_planes(planes, window_span: str,
                names: Iterable[str] = CATALOG) -> Optional[ProgramSpans]:
    """*planes* as ``jax.profiler.ProfileData`` gives them (events with
    ``name``, ``start_ns``, ``duration_ns`` and ``stats``, pairs of an
    arg's name and value); None without a *window_span* span."""
    names = set(names)
    threads, windows = [], []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans: Dict[str, List[Tuple[float, float, Dict]]] = {}
            for ev in line.events:
                if ev.name == window_span:
                    windows.append((ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
                elif ev.name in names:
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
            if spans:
                threads.append(spans)
    if not windows:
        return None
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    return ProgramSpans(threads, lo, hi)


def of_run(run) -> Optional[ProgramSpans]:
    """The run's program spans, parsed from its trace on first use and
    kept on *run*; None where the run left no trace."""
    if not hasattr(run, "program_spans"):
        from benchmark.harness import OUT_DIR, WINDOW_SPAN
        try:
            path = find_trace(os.path.join(OUT_DIR, "trace", run.cell))
        except FileNotFoundError:
            run.program_spans = None
        else:
            from jax.profiler import ProfileData
            run.program_spans = from_planes(
                ProfileData.from_file(path).planes, WINDOW_SPAN)
    return run.program_spans


def per_unit(run, value: Optional[float], units_key: str,
             scale: float) -> Optional[float]:
    """*value* x *scale* per ``run.result["layer"][units_key]``; None
    where there is no value (no such span in the window) or the window
    had none of those units."""
    units = run.result["layer"].get(units_key)
    if value is None or not units:
        return None
    return scale * value / units
