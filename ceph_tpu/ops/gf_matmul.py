"""GF(2^8) Reed-Solomon coding as MXU matmuls.

TPU-first formulation: GF(2^8) multiplication by a constant is linear over
GF(2), so the whole (m x k) GF(2^8) coding matrix expands to a (k*8 x m*8)
0/1 matrix B (ceph_tpu.gf.tables.expand_to_bitmatrix).  Encoding a batch of
stripes is then:

    bits(S, C, k*8) = unpack(data)            # shifts + masks, fuses in XLA
    acc(S, C, m*8)  = bits @ B                # int8 matmul on the MXU
    coding          = pack(acc & 1)           # parity of the popcount

No per-byte table gathers (which do not vectorize on the VPU), no scalar
loops, static shapes throughout — this is the design that lets XLA tile the
work onto the systolic array.  The same machinery executes decode: the
host inverts the k x k survivor matrix (tiny), expands it to bits, and the
device runs the identical matmul.  Replaces the reference's SIMD paths
(isa-l ec_encode_data, src/erasure-code/isa/ErasureCodeIsa.cc:128;
jerasure_matrix_encode, jerasure/ErasureCodeJerasure.cc:155).

The batched stripe dimension S is the data-parallel axis: under a
``jax.sharding.Mesh`` the same jitted function runs SPMD with S sharded
across devices (see ceph_tpu.parallel).
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..common.lockdep import DebugLock
from ..gf.tables import expand_to_bitmatrix
from ..gf.matrices import gf_invert_matrix
from ..trace.devprof import g_devprof
from ..trace.span import g_tracer


@functools.lru_cache(maxsize=1)
def device_available() -> bool:
    """True when the default JAX backend is an accelerator."""
    try:
        return jax.devices()[0].platform not in ("cpu",)
    except Exception:
        return False


def _unpack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """uint8 (..., n) -> (..., n*8) bits, LSB-first."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[..., None] >> shifts) & jnp.uint8(1)
    return bits.reshape(*x.shape[:-1], x.shape[-1] * 8)


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., n*8) bits -> uint8 (..., n), LSB-first."""
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    b = bits.reshape(*bits.shape[:-1], bits.shape[-1] // 8, 8)
    return (b * weights).sum(axis=-1).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=())
def gf_bit_matmul(data: jnp.ndarray, bitmat: jnp.ndarray) -> jnp.ndarray:
    """data (S, k, C) uint8, bitmat (k*8, r*8) int8 -> (S, r, C) uint8.

    The contraction runs as an int8 matmul with int32 accumulation; the low
    bit of each accumulator is the GF(2) (XOR) sum.
    """
    s, k, c = data.shape
    r8 = bitmat.shape[1]
    d = jnp.transpose(data, (0, 2, 1))          # (S, C, k)
    bits = _unpack_bits(d).astype(jnp.int8)     # (S, C, k*8)
    acc = jax.lax.dot_general(
        bits, bitmat,
        dimension_numbers=(((2,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)        # (S, C, r*8)
    parity = (acc & 1).astype(jnp.uint8)
    out = _pack_bits(parity)                     # (S, C, r)
    return jnp.transpose(out, (0, 2, 1))         # (S, r, C)


@functools.partial(jax.jit, static_argnames=("w",))
def gfw_bit_matmul(data: jnp.ndarray, bitmat: jnp.ndarray,
                   w: int) -> jnp.ndarray:
    """GF(2^w) word-layout coding as the same MXU 0/1 matmul.

    data (S, k, C) uint8 viewed as little-endian w-bit words, bitmat
    (k*w, r*w) int8 companion expansion -> (S, r, C) uint8.  Each word
    unpacks to its w bits (LE byte order makes word bit b*8+i = bit i of
    byte b), the contraction runs over k*w bit lanes, and the parity low
    bit packs back into words.  w=8 degenerates to gf_bit_matmul.
    """
    s, k, c = data.shape
    ws = w // 8
    W = c // ws
    d = jnp.transpose(data.reshape(s, k, W, ws), (0, 2, 1, 3))  # (S,W,k,ws)
    bits = _unpack_bits(d.reshape(s, W, k * ws)).reshape(
        s, W, k, w).reshape(s, W, k * w).astype(jnp.int8)
    acc = jax.lax.dot_general(
        bits, bitmat,
        dimension_numbers=(((2,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)            # (S, W, r*w)
    parity = (acc & 1).astype(jnp.uint8)
    out = _pack_bits(parity)                         # (S, W, r*ws)
    r = bitmat.shape[1] // w
    out = jnp.transpose(out.reshape(s, W, r, ws), (0, 2, 1, 3))
    return out.reshape(s, r, c)


def expand_to_bitmatrix_w(coding: np.ndarray, w: int) -> np.ndarray:
    """(m, k) GF(2^w) coefficients -> (k*w, m*w) 0/1 matrix in the
    d @ B convention gfw_bit_matmul consumes (gf/tables.py
    expand_to_bitmatrix generalized via the companion representation)."""
    from ..gf.bitmatrix import element_bitmatrix
    mm, kk = coding.shape
    out = np.zeros((kk * w, mm * w), dtype=np.uint8)
    for r in range(mm):
        for c in range(kk):
            bm = element_bitmatrix(int(coding[r, c]), w)
            out[c * w:(c + 1) * w, r * w:(r + 1) * w] = bm.T
    return out


class DeviceWordRSBackend:
    """Device executor for a (k+m, k) GF(2^w) word-layout code."""

    def __init__(self, encode_matrix: np.ndarray, w: int):
        rows, k = encode_matrix.shape
        self.k = k
        self.m = rows - k
        self.w = w
        self.matrix = encode_matrix.astype(np.int64)
        bits = expand_to_bitmatrix_w(self.matrix[k:], w)
        self._enc_bits = jnp.asarray(bits.astype(np.int8))

    def encode(self, data: np.ndarray) -> np.ndarray:
        """(S, k, C) uint8 -> (S, m, C) coding chunks."""
        g_devprof.install_compile_listener()
        g_devprof.account_h2d("gf_matmul.encode_w", data.nbytes)
        with g_devprof.stage("gf_matmul.encode_w"):
            out = np.asarray(gfw_bit_matmul(jnp.asarray(data),
                                            self._enc_bits, self.w))
        g_devprof.account_d2h("gf_matmul.encode_w", out.nbytes)
        return out


def _upload(data: np.ndarray) -> jnp.ndarray:
    """The codec call's input batch onto the device."""
    with g_tracer.span(prof="codec.h2d", bytes=data.nbytes):
        return jnp.asarray(data)


def _fetch(out: jnp.ndarray, nbytes: int) -> np.ndarray:
    """The codec call's *nbytes* of output onto the host: the wait for
    the kernel, then the copy."""
    with g_tracer.span(prof="codec.fetch", bytes=nbytes):
        return np.asarray(out)


class DeviceRSBackend:
    """Device-side executor for one (k+m, k) systematic code."""

    def __init__(self, encode_matrix: np.ndarray):
        rows, k = encode_matrix.shape
        self.k = k
        self.m = rows - k
        self.matrix = encode_matrix.astype(np.uint8)
        enc_bits = expand_to_bitmatrix(self.matrix[k:])
        self._enc_bits = jnp.asarray(enc_bits.astype(np.int8))
        # bounded like the host codec's signature cache (mirrors
        # ErasureCodeIsaTableCache's 2516-entry LRU)
        self._decode_bits_cache: "OrderedDict[tuple, jnp.ndarray]" = OrderedDict()
        self._cache_lock = DebugLock("gf_matmul::decode_bits_cache")

    # -- encode -------------------------------------------------------------
    def encode(self, data: np.ndarray) -> np.ndarray:
        """(S, k, C) uint8 -> (S, m, C) coding chunks (numpy round-trip).

        THE host↔device boundary of the EC write path: the whole
        batch crosses up, the coding chunks cross back.  Both legs are
        accounted per call-site by the device-flow profiler (counter
        bumps only — no sync is added; the ``jnp.asarray`` /
        ``np.asarray`` pair was always the copy), and each leg is a
        profiler span (``codec.h2d``, ``codec.fetch``)."""
        from ..common.kernel_trace import g_kernel_timer
        g_devprof.install_compile_listener()
        g_devprof.account_h2d("gf_matmul.encode", data.nbytes)
        with g_devprof.stage("gf_matmul.encode"):
            out = g_kernel_timer.timed(
                "gf_encode", lambda: _fetch(
                    self.encode_device(_upload(data)),
                    data.shape[0] * self.m * data.shape[2]))
        g_devprof.account_d2h("gf_matmul.encode", out.nbytes)
        return out

    def encode_device(self, data: jnp.ndarray) -> jnp.ndarray:
        """Device-resident variant; composes under jit/shard_map."""
        return gf_bit_matmul(data, self._enc_bits)

    @property
    def enc_bits(self) -> jnp.ndarray:
        """The expanded 0/1 coding matrix on device — the operand the
        fused encode+crc kernel (ops/resident) composes with."""
        return self._enc_bits

    # -- decode -------------------------------------------------------------
    def _decode_bits_for(self, srcs: Tuple[int, ...],
                         want_rows: Tuple[int, ...]) -> jnp.ndarray:
        key = (srcs, want_rows)
        with self._cache_lock:
            hit = self._decode_bits_cache.get(key)
            if hit is not None:
                self._decode_bits_cache.move_to_end(key)
                return hit
        sub = self.matrix[list(srcs), :]
        inv = gf_invert_matrix(sub)              # data = inv @ survivors
        rows = inv[list(want_rows), :]
        bits_np = expand_to_bitmatrix(rows).astype(np.int8)
        g_devprof.account_h2d("gf_matmul.decode_bits", bits_np.nbytes)
        bits = jnp.asarray(bits_np)
        with self._cache_lock:
            self._decode_bits_cache[key] = bits
            from ..ec.rs_codec import DECODE_CACHE_ENTRIES
            if len(self._decode_bits_cache) > DECODE_CACHE_ENTRIES:
                self._decode_bits_cache.popitem(last=False)
        return bits

    def decode_data(self, survivors: np.ndarray, srcs: Sequence[int],
                    want_rows: Sequence[int]) -> np.ndarray:
        """survivors (S, k, C) stacked in ``srcs`` order -> the requested
        data rows (S, len(want_rows), C)."""
        bits = self._decode_bits_for(tuple(srcs), tuple(want_rows))
        g_devprof.install_compile_listener()
        g_devprof.account_h2d("gf_matmul.decode", survivors.nbytes)
        with g_devprof.stage("gf_matmul.decode"):
            S, _k, C = survivors.shape
            out = _fetch(gf_bit_matmul(_upload(survivors), bits),
                         S * len(want_rows) * C)
        g_devprof.account_d2h("gf_matmul.decode", out.nbytes)
        return out
