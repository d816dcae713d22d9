"""crc32c (Castagnoli) with Ceph's conventions.

Ceph computes raw crc32c updates with no pre/post inversion and seeds with
-1 (reference include/crc32c.h, common/crc32c*.cc SSE4/table paths).  The
native C++ path (ceph_tpu.native) is preferred; this table-driven fallback
is bit-identical and keeps the dependency optional.
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
    buf = np.frombuffer(bytes(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else data.astype(np.uint8)
    c = np.uint32(crc)
    for b in buf.tobytes():
        c = _TABLE[(int(c) ^ b) & 0xFF] ^ (int(c) >> 8)
        c = np.uint32(c)
    return int(c)


def crc32c(data, crc: int = 0xFFFFFFFF) -> int:
    """Native when built, software otherwise; same bits either way.  One
    ``crc32c`` profiler span per call."""
    with g_tracer.span(prof="crc32c", bytes=len(data)):
        return _crc32c(data, crc)


def _crc32c(data, crc: int) -> int:
    try:
        from ..native import crc32c as native_crc32c, native_available
        if native_available():
            return native_crc32c(
                data if isinstance(data, (bytes, np.ndarray))
                else bytes(data), crc)
    except Exception:
        pass
    return crc32c_sw(data, crc)
