"""Plain Reed-Solomon reference: ISA-L's ``reed_sol_van`` over GF(2^8).

The field is GF(2^8) with the polynomial 0x11d.  The coding matrix is
ISA-L's ``gf_gen_rs_matrix``: coding row i (0-based, of m) is
``[g^0, g^1, ..., g^(k-1)]`` with ``g = 2^i``.  An EC pool stores an
object as stripes of ``k * stripe_unit`` bytes; shard j holds chunk j of
every stripe, back to back, and coding shard i is the GF sum over the
data shards.  This module imports nothing of the program.
"""
from __future__ import annotations

import numpy as np


def _tables():
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    v = 1
    for i in range(255):
        exp[i] = v
        log[v] = i
        v <<= 1
        if v & 0x100:
            v ^= 0x11D
    exp[255:510] = exp[:255]
    a = np.arange(256)
    mul = np.zeros((256, 256), dtype=np.uint8)
    nz = a[1:]
    mul[1:, 1:] = exp[log[nz][:, None] + log[nz][None, :]]
    return mul


MUL = _tables()


def gf_mul(a: int, b: int) -> int:
    return int(MUL[a, b])


def rs_van_matrix(k: int, m: int) -> np.ndarray:
    """The m x k coding rows of ISA-L's gf_gen_rs_matrix."""
    out = np.zeros((m, k), dtype=np.uint8)
    gen = 1
    for i in range(m):
        p = 1
        for j in range(k):
            out[i, j] = p
            p = gf_mul(p, gen)
        gen = gf_mul(gen, 2)
    return out


def data_shards(body: bytes, k: int, stripe_unit: int) -> np.ndarray:
    """(k, len/k) shard bodies of a body that is a whole number of
    stripes."""
    buf = np.frombuffer(body, dtype=np.uint8)
    width = k * stripe_unit
    if len(buf) % width:
        raise ValueError("body is not a whole number of stripes")
    return buf.reshape(-1, k, stripe_unit).transpose(1, 0, 2).reshape(k, -1)


def coding_shards(data: np.ndarray, m: int) -> np.ndarray:
    """(m, L) coding shards of (k, L) data shards."""
    k = data.shape[0]
    mat = rs_van_matrix(k, m)
    out = np.zeros((m, data.shape[1]), dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            out[i] ^= MUL[mat[i, j]][data[j]]
    return out


def all_shards(body: bytes, k: int, m: int, stripe_unit: int) -> np.ndarray:
    """(k + m, L): what the k + m OSDs of a PG must hold for *body*."""
    d = data_shards(body, k, stripe_unit)
    return np.concatenate([d, coding_shards(d, m)])
