"""Plain LRC reference: upstream's ``plugin=lrc k= m= l=`` layered code.

The layout is what upstream's ``ErasureCodeLrc::parse_kml`` generates
(src/erasure-code/lrc/ErasureCodeLrc.cc).  With ``g = (k + m) / l``
local groups of ``kg = k / g`` data and ``mg = m / g`` global coding
chunks each, the mapping is ``("D" * kg + "_" * mg + "_") * g`` (for
k=4 m=2 l=3: ``DD__DD__``, 8 chunks), and the layers are

- one global layer, ``("D" * kg + "c" * mg + "_") * g`` (``DDc_DDc_``);
- one local layer per group, ``"D" * l + "c"`` over the group's l + 1
  positions and ``_`` elsewhere (``DDDc____``, ``____DDDc``).

Each layer's chunks are encoded by its delegate, which kml leaves at
upstream's default: jerasure ``reed_sol_van`` with w=8, k = the layer's
``D`` count and m = its ``c`` count.  Its coding matrix is jerasure's
``reed_sol_vandermonde_coding_matrix`` (src/jerasure/src/reed_sol.c),
built here from the published construction: the extended Vandermonde
matrix of k + m rows (row 0 = e_0, the last row = e_(k-1), row i in
between = [i^0, i^1, ..., i^(k-1)]), turned systematic by column
operations, then its first coding row and each coding row's first
column scaled to ones.  The field is GF(2^8) with the polynomial 0x11d
(jerasure's w=8 default).  The layers are encoded in order, each reading
what the layers before it wrote; a local layer's ``D`` includes the
group's global coding chunk.

An EC pool stores an object as stripes of ``k * stripe_unit`` bytes:
logical data chunk i of every stripe goes to the physical position of
the i-th ``D`` of the mapping, and physical shard j holds position j of
every stripe, back to back.

Departures from upstream, none of which the benchmark's configuration
reaches: only the k/m/l form (no ``layers``, ``mapping`` or
``crush-steps`` given by hand, no ``crush-locality``, no delegate other
than jerasure ``reed_sol_van`` w=8); a body that is not a whole number
of stripes is padded with zeros to the next one, as the OSD pads a full
write.  This module imports nothing of the program.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from benchmark.reference.gf256_rs import MUL

INV = np.zeros(256, dtype=np.uint8)
INV[1:] = [int(np.nonzero(MUL[a] == 1)[0][0]) for a in range(1, 256)]


def parse_kml(k: int, m: int, l: int) -> Tuple[str, List[str]]:
    """(mapping, layers' chunk maps) as upstream's parse_kml makes them;
    ValueError where upstream refuses the profile."""
    if (k + m) % l:
        raise ValueError("k + m must be a multiple of l")
    groups = (k + m) // l
    if k % groups:
        raise ValueError("k must be a multiple of (k + m) / l")
    if m % groups:
        raise ValueError("m must be a multiple of (k + m) / l")
    kg, mg = k // groups, m // groups
    mapping = ("D" * kg + "_" * mg + "_") * groups
    layers = [("D" * kg + "c" * mg + "_") * groups]
    for i in range(groups):
        layers.append("".join(("D" * l + "c") if i == j else "_" * (l + 1)
                              for j in range(groups)))
    return mapping, layers


def _pow(a: int, e: int) -> int:
    out = 1
    for _ in range(e):
        out = int(MUL[out, a])
    return out


def jerasure_rs_van(k: int, m: int) -> np.ndarray:
    """The m x k coding rows of jerasure's
    ``reed_sol_vandermonde_coding_matrix(k, m, 8)``."""
    rows = k + m
    d = np.zeros((rows, k), dtype=np.uint8)
    d[0, 0] = 1
    d[rows - 1, k - 1] = 1
    for i in range(1, rows - 1):
        d[i] = [_pow(i, j) for j in range(k)]
    # column operations until the top k rows are the identity
    for i in range(1, k):
        j = next(r for r in range(i, rows) if d[r, i])
        if j != i:
            d[[i, j]] = d[[j, i]]
        if d[i, i] != 1:
            d[:, i] = MUL[INV[d[i, i]]][d[:, i]]
        for j in range(k):
            e = int(d[i, j])
            if j != i and e:
                d[:, j] ^= MUL[e][d[:, i]]
    # the first coding row all ones: scale each column's coding part
    for j in range(k):
        if d[k, j] != 1:
            d[k:, j] = MUL[INV[d[k, j]]][d[k:, j]]
    # the first column of every further coding row one: scale the row
    for i in range(k + 1, rows):
        if d[i, 0] != 1:
            d[i] = MUL[INV[d[i, 0]]][d[i]]
    return d[k:].copy()


def all_chunks(body: bytes, k: int, m: int, l: int,
               stripe_unit: int) -> np.ndarray:
    """(n, L): physical shard j of *body* at row j, as the n OSDs of a
    PG of the pool must hold them."""
    mapping, layers = parse_kml(k, m, l)
    n = len(mapping)
    width = k * stripe_unit
    buf = np.frombuffer(body, dtype=np.uint8)
    pad = -len(buf) % width
    if pad:
        buf = np.concatenate([buf, np.zeros(pad, np.uint8)])
    stripes = buf.reshape(-1, k, stripe_unit)
    out = np.zeros((n, stripes.shape[0], stripe_unit), dtype=np.uint8)
    data_pos = [p for p, ch in enumerate(mapping) if ch == "D"]
    for i, p in enumerate(data_pos):
        out[p] = stripes[:, i, :]
    for chunks_map in layers:
        data = [p for p, ch in enumerate(chunks_map) if ch == "D"]
        coding = [p for p, ch in enumerate(chunks_map) if ch == "c"]
        mat = jerasure_rs_van(len(data), len(coding))
        for r, p in enumerate(coding):
            acc = np.zeros_like(out[p])
            for j, q in enumerate(data):
                acc ^= MUL[mat[r, j]][out[q]]
            out[p] = acc
    return out.reshape(n, -1)
