"""Reduce a profiler trace (``.xplane.pb``) to device time and idle gaps.

Input: the trace of one window, and the name of the benchmark's host
span that marks it (``window``).  Output, all in seconds:

- ``window_s``: the span's length;
- ``busy_s``: the union of the intervals in which an operation ran on
  each device, clipped to the window and averaged over the devices;
- ``programs``: device time per XLA program (module), name without its
  ``(id)`` suffix;
- ``ops``: device time per XLA operation;
- ``layout``: event count per line of each plane, for a reader who has
  not seen such a trace;
- ``idle_gaps``: every gap between device operations inside the window,
  longest first, each named by the innermost benchmark span on the host
  that covered the middle of the gap (``-`` where none did).

Device planes are those named ``/device:<platform>:<n>``; operations
come from their ``XLA Ops`` line, programs from ``XLA Modules``.  Host
spans are the events of the host planes whose names the caller lists.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

_ID_SUFFIX = re.compile(r"\(\d+\)$")


def find_trace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: List[Tuple[float, float]], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith(
        "/device:CUSTOM")


def reduce_planes(planes, window_span: str,
                  host_spans: Iterable[str]) -> Dict:
    """*planes*: objects with ``name`` and ``lines``; a line has ``name``
    and ``events`` with ``name``, ``start_ns`` and ``duration_ns`` (the
    shape of ``jax.profiler.ProfileData``)."""
    host_spans = set(host_spans) | {window_span}
    spans: List[Tuple[float, float, str]] = []
    devices: Dict[str, Dict[str, list]] = {}
    layout: Dict[str, Dict[str, int]] = {}
    for plane in planes:
        layout[plane.name] = {line.name: sum(1 for _ in line.events)
                              for line in plane.lines}
        if _is_device(plane.name):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    d[key].append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                   ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host_spans:
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    windows = [(s, e) for s, e, n in spans if n == window_span]
    if not windows:
        raise ValueError(f"the trace has no '{window_span}' span")
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    inner = sorted((s, e, n) for s, e, n in spans
                   if n != window_span and e > lo and s < hi)
    starts = [s for s, _e, _n in inner]
    busy, programs, ops, gaps = [], {}, {}, []
    for dev in devices.values():
        evs = dev["ops"] or dev["modules"]
        iv = _clip(_union([(s, e) for s, e, _n in evs]), lo, hi)
        busy.append(sum(e - s for s, e in iv))
        for key, table in (("modules", programs), ("ops", ops)):
            for s, e, name in dev[key]:
                c = min(e, hi) - max(s, lo)
                if c > 0:
                    name = _ID_SUFFIX.sub("", name)
                    table[name] = table.get(name, 0.0) + c
        edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _cover(inner, starts, (a + b) / 2)))
    gaps.sort(key=lambda g: -g[0])
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": (sum(busy) / len(busy) * ns) if busy else 0.0,
        "devices": len(devices),
        "programs": {k: v * ns for k, v in programs.items()},
        "ops": {k: v * ns for k, v in ops.items()},
        "idle_gaps": [(name, d * ns) for d, name in gaps],
        "layout": layout,
    }


def _cover(spans: List[Tuple[float, float, str]], starts: List[float],
           t: float, look_back: int = 64) -> str:
    """The innermost (shortest) span that holds time *t*, among the
    *look_back* spans that started last before it (spans nest, and the
    benchmark's are short and sequential)."""
    best: Optional[Tuple[float, str]] = None
    i = bisect.bisect_right(starts, t)
    for s, e, n in spans[max(0, i - look_back):i]:
        if e >= t and (best is None or e - s < best[0]):
            best = (e - s, n)
    return best[1] if best else "-"


def reduce_trace(path: str, window_span: str,
                 host_spans: Iterable[str]) -> Dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, window_span,
                         host_spans)


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The result line's ``breakdown``: the device operations that took
    most time and the longest idle gaps, by the host span around them."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[n, d] for n, d in red["idle_gaps"][:top]]}


def idle_share_pct(red: Dict) -> Optional[float]:
    """1 - busy / window, in percent; None for an empty window."""
    if red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
