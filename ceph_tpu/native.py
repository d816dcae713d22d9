"""ctypes bindings to the native C++ runtime (native/libceph_tpu_native.so).

The native library supplies:
- an independent CRUSH map evaluator (cross-validates the Python mapper and
  serves as the threaded CPU batch baseline, the ParallelPGMapper analog);
- GF(2^8) region encode (the isa-l ec_encode_data-class CPU path used as
  the benchmark baseline);
- crc32c for chunk HashInfo, on the CPU's crc instruction where it has
  one (native/crc32c.cpp).

Builds on demand with the repo's Makefile (g++ -O3 -march=native), into
a directory keyed by the CPU it runs on: a copy of the checkout moved
to a machine with another CPU builds its own library instead of loading
one made for a different CPU (SIGILL).
"""
from __future__ import annotations

import ctypes
import glob
import os
import subprocess
import threading

from .common.lockdep import DebugLock
from typing import List, Optional, Sequence

import numpy as np

from .crush.constants import (
    CRUSH_BUCKET_LIST, CRUSH_BUCKET_STRAW, CRUSH_BUCKET_STRAW2,
    CRUSH_BUCKET_TREE, CRUSH_BUCKET_UNIFORM,
)
from .crush.types import CrushMap

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(_ROOT, "native")

_lock = DebugLock("native::load")
_lib: Optional[ctypes.CDLL] = None
# set once a load has failed: a host with no toolchain is asked once,
# not on every per-PG lookup
_load_failed = False


def _host_key() -> str:
    """What a -march=native build depends on: the CPU (arch, model,
    feature flags), not the machine's name."""
    import hashlib
    import platform
    h = hashlib.sha1(platform.machine().encode())
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("model name", "flags")):
                    h.update(line.encode())
                elif not line.strip():
                    break               # the first CPU describes them all
    except OSError:
        pass
    return h.hexdigest()[:16]


def _lib_path() -> str:
    return os.path.join(_NATIVE_DIR, "build", _host_key(),
                        "libceph_tpu_native.so")


def build_native() -> str:
    so = _lib_path()
    subprocess.run(["make", "-s", "-C", _NATIVE_DIR, f"OUT={so}"],
                   check=True)
    return so


def get_lib() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
        so = _lib_path()
        if not os.path.exists(so) or (
                os.path.getmtime(so) < max(
                    os.path.getmtime(f) for f in glob.glob(
                        os.path.join(_NATIVE_DIR, "*.cpp")))):
            build_native()
        lib = ctypes.CDLL(so)
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        # argtypes are mandatory: passing python ints for int64_t params
        # without them leaves the upper register bits undefined (SysV ABI)
        lib.crush_set_ln_tables.argtypes = [i64p, i64p]
        lib.crush_do_rule_c.restype = ctypes.c_int
        lib.crush_do_rule_c.argtypes = [
            i64p, ctypes.c_int64, ctypes.c_int, ctypes.c_int64, i64p,
            ctypes.c_int, u32p, ctypes.c_int64]
        lib.crush_do_rule_batch.restype = ctypes.c_int
        lib.crush_do_rule_batch.argtypes = [
            i64p, ctypes.c_int64, ctypes.c_int, i64p, ctypes.c_int64, i64p,
            ctypes.c_int, i32p, u32p, ctypes.c_int64]
        lib.gf_rs_encode.argtypes = [
            u8p, ctypes.c_int, ctypes.c_int, u8p, u8p, ctypes.c_int64]
        lib.gf_region_xor.argtypes = [u8p, u8p, u8p, ctypes.c_int64]
        lib.ceph_crc32c.restype = ctypes.c_uint32
        lib.ceph_crc32c.argtypes = [
            ctypes.c_uint32, ctypes.c_void_p, ctypes.c_int64]
        lib.ceph_crc32c_impl.restype = ctypes.c_char_p
        lib.ceph_crc32c_impl.argtypes = []
        lib.gf_mul_c.restype = ctypes.c_uint8
        lib.gf_mul_c.argtypes = [ctypes.c_uint8, ctypes.c_uint8]
        # inject the ln tables once
        from .crush.ln import RH_LH_NP, LL_NP
        rh = RH_LH_NP.astype(np.int64)
        llt = LL_NP.astype(np.int64)
        lib.crush_set_ln_tables(
            rh.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            llt.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        _lib = lib
        return lib


def native_available() -> bool:
    global _load_failed
    if _lib is not None:
        return True
    if _load_failed:
        return False
    try:
        get_lib()
        return True
    except Exception:
        _load_failed = True
        return False


# ---- crush ----------------------------------------------------------------

def serialize_map(m: CrushMap, choose_args=None) -> np.ndarray:
    """Flatten a CrushMap into the int64 blob the native parser reads.

    ``choose_args`` (crush.h crush_choose_arg: per-bucket id overrides
    for hashing plus per-position weight_set replacements) serialize as
    a trailing section; absent section == no overrides."""
    out: List[int] = [
        m.max_devices, m.choose_local_tries, m.choose_local_fallback_tries,
        m.choose_total_tries, m.chooseleaf_descend_once,
        m.chooseleaf_vary_r, m.chooseleaf_stable,
        m.max_buckets, m.max_rules,
    ]
    for b in m.buckets:
        if b is None:
            out.append(0)
            continue
        out += [1, b.id, b.alg, b.type, b.size]
        out += list(b.items)
        if b.alg == CRUSH_BUCKET_UNIFORM:
            out.append(b.item_weight)
        elif b.alg == CRUSH_BUCKET_LIST:
            out += list(b.item_weights) + list(b.sum_weights)
        elif b.alg == CRUSH_BUCKET_TREE:
            out.append(b.num_nodes)
            out += list(b.node_weights)
        elif b.alg == CRUSH_BUCKET_STRAW:
            out += list(b.item_weights) + list(b.straws)
        elif b.alg == CRUSH_BUCKET_STRAW2:
            out += list(b.item_weights)
        else:
            raise ValueError(f"bucket alg {b.alg}")
    for r in m.rules:
        if r is None:
            out.append(0)
            continue
        out += [1, r.ruleset, r.type, r.min_size, r.max_size, len(r.steps)]
        for s in r.steps:
            out += [s.op, s.arg1, s.arg2]
    entries = []
    if choose_args is not None:
        for bno, arg in enumerate(choose_args):
            if arg is None or (not arg.ids and not arg.weight_set):
                continue
            b = m.buckets[bno] if bno < len(m.buckets) else None
            if b is None:
                continue
            # the C++ parser advances by b.size per row — a mismatched
            # arg (e.g. from an externally decoded binary map) must
            # fail LOUDLY here, not parse misaligned and silently
            # return wrong placements
            if arg.ids and len(arg.ids) != b.size:
                raise ValueError(
                    f"choose_args ids len {len(arg.ids)} != bucket "
                    f"size {b.size} (bucket index {bno})")
            for ws in arg.weight_set or []:
                if len(ws.weights) != b.size:
                    raise ValueError(
                        f"choose_args weight_set row len "
                        f"{len(ws.weights)} != bucket size {b.size} "
                        f"(bucket index {bno})")
            ent = [bno, 1 if arg.ids else 0, b.size]
            if arg.ids:
                ent += list(arg.ids)
            npos = len(arg.weight_set) if arg.weight_set else 0
            ent.append(npos)
            for ws in arg.weight_set or []:
                ent += list(ws.weights)
            entries.append(ent)
    out.append(len(entries))
    for ent in entries:
        out += ent
    return np.array(out, dtype=np.int64)


class NativeCrushMapper:
    """Batch CRUSH evaluation through the C++ engine."""

    def __init__(self, m: CrushMap, choose_args=None):
        self.lib = get_lib()
        self.blob = serialize_map(m, choose_args)

    def do_rule(self, ruleno: int, x: int, result_max: int,
                weight: Sequence[int]) -> List[int]:
        res = np.zeros(result_max, dtype=np.int64)
        w = np.asarray(weight, dtype=np.uint32)
        n = self.lib.crush_do_rule_c(
            self.blob.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(self.blob), ruleno, x,
            res.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), result_max,
            w.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), len(w))
        if n < 0:
            raise RuntimeError("native map parse failed")
        return res[:n].tolist()

    def do_rule_batch(self, ruleno: int, xs: Sequence[int], result_max: int,
                      weight: Sequence[int]):
        """Returns (out (nx, result_max) int64 NONE-padded, lens (nx,))."""
        xs = np.asarray(xs, dtype=np.int64)
        out = np.zeros((len(xs), result_max), dtype=np.int64)
        lens = np.zeros(len(xs), dtype=np.int32)
        w = np.asarray(weight, dtype=np.uint32)
        rc = self.lib.crush_do_rule_batch(
            self.blob.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(self.blob), ruleno,
            xs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(xs),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), result_max,
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            w.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), len(w))
        if rc < 0:
            raise RuntimeError("native map parse failed")
        return out, lens


# ---- gf -------------------------------------------------------------------

def native_rs_encode(matrix_rows: np.ndarray, data: np.ndarray) -> np.ndarray:
    """rows (r, k) x data (k, n) -> (r, n) over GF(2^8), C++ path."""
    lib = get_lib()
    r, k = matrix_rows.shape
    kk, n = data.shape
    assert k == kk
    mat = np.ascontiguousarray(matrix_rows, dtype=np.uint8)
    dat = np.ascontiguousarray(data, dtype=np.uint8)
    out = np.zeros((r, n), dtype=np.uint8)
    lib.gf_rs_encode(
        mat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), r, k,
        dat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_int64(n))
    return out


def _as_u8(data) -> np.ndarray:
    """The bytes of *data* as a contiguous uint8 array, without a copy
    where *data* already is one (bytes, bytearray, a contiguous
    memoryview or uint8 array); an array of another dtype is cast, as
    ``utils.crc32c.crc32c_sw`` casts it."""
    if isinstance(data, np.ndarray):
        return np.ascontiguousarray(data, dtype=np.uint8).reshape(-1)
    try:
        return np.frombuffer(data, dtype=np.uint8)
    except (TypeError, ValueError):     # no contiguous buffer to share
        return np.frombuffer(bytes(data), dtype=np.uint8)


def crc32c(data, crc: int = 0xFFFFFFFF) -> int:
    """Ceph-convention crc32c: raw castagnoli update, no pre/post inversion
    (reference include/crc32c.h); Ceph callers seed with -1.  *data* is
    any bytes-like object or array; it is read in place."""
    buf = _as_u8(data)
    lib = _lib if _lib is not None else get_lib()
    return lib.ceph_crc32c(crc & 0xFFFFFFFF, buf.ctypes.data, buf.nbytes)


def crc32c_impl() -> str:
    """The crc32c the library was built with: ``"sse42"``, ``"armv8"``
    (the CPU's crc instruction) or ``"table8"`` (slicing-by-8 tables)."""
    return get_lib().ceph_crc32c_impl().decode()
