"""crc32c (Castagnoli) with Ceph's conventions.

Ceph computes raw crc32c updates with no pre/post inversion and seeds with
-1 (reference include/crc32c.h, common/crc32c*.cc SSE4/table paths).  The
native C++ path (ceph_tpu.native, on the CPU's crc instruction where it has
one) is preferred; this table-driven fallback is bit-identical and keeps the
dependency optional.
"""
from __future__ import annotations

import numpy as np

from ..trace.span import g_tracer

_POLY = 0x82F63B78  # reflected CRC-32C polynomial


def _build_table() -> np.ndarray:
    t = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ _POLY if c & 1 else c >> 1
        t[i] = c
    return t


_TABLE = _build_table()


def crc32c_sw(data, crc: int = 0xFFFFFFFF) -> int:
    """The per-byte table loop in Python: the reference the native paths
    are tested against, and the fallback where none is built."""
    buf = data.astype(np.uint8).tobytes() if isinstance(data, np.ndarray) \
        else bytes(data)
    table = _TABLE.tolist()
    c = crc & 0xFFFFFFFF
    for b in buf:
        c = table[(c ^ b) & 0xFF] ^ (c >> 8)
    return c


# (name, function) of the implementation this process uses, once chosen
_impl = None


def _choose():
    global _impl
    from .. import native
    if native.native_available():
        _impl = (native.crc32c_impl(), native.crc32c)
    else:
        _impl = ("python", crc32c_sw)
    return _impl


def crc32c(data, crc: int = 0xFFFFFFFF) -> int:
    """Native when built, software otherwise; same bits either way.  One
    ``crc32c`` profiler span per call; its ``impl`` arg names the path
    (``"sse42"``, ``"armv8"``, ``"table8"`` or ``"python"``)."""
    impl, fn = _impl or _choose()
    with g_tracer.span(prof="crc32c", bytes=len(data), impl=impl):
        return fn(data, crc)
