"""Fused GF(2^8) bit-matmul as a Pallas TPU kernel.

The XLA path (ops/gf_matmul.gf_bit_matmul) materializes the 8x bit
expansion — (S, C, k*8) int8 — between the unpack and the dot, which XLA
may round-trip through HBM.  This kernel keeps the whole
unpack -> MXU matmul -> parity -> pack chain in VMEM: the grid tiles
(stripe, chunk-column) space, each program unpacks a (k, TILE_C) uint8
block to (k*8, TILE_C) bits, hits the MXU against the (m*8, k*8)
transposed bit-matrix, and packs the parity bits straight back to
(m, TILE_C) bytes.  HBM traffic is exactly input-bytes + output-bytes.

Role: the ec_encode_data hot loop (src/erasure-code/isa/
ErasureCodeIsa.cc:128) re-done as a hand-written TPU kernel.
ops/gf_matmul.gf_bit_matmul remains the default executor; this kernel is
the byte-identical alternative (tests/test_gf_matmul_device.py pins
parity).  Its speed against the XLA path is not measured on a chip yet.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# lane-dim tile; chunk sizes are 64B-aligned (SIMD_ALIGN) and usually
# large (128 KiB in the headline bench) — pick the biggest tile that
# divides C
_TILES = (4096, 2048, 1024, 512, 256, 128)


def _kernel(data_ref, bmt_ref, out_ref, *, k: int, m: int):
    """One (stripe, C-tile) program: data (1, k, T) u8 -> out (1, m, T) u8."""
    d = data_ref[0]                                    # (k, T) uint8
    shifts = jax.lax.broadcasted_iota(jnp.int32, (k, 8, d.shape[1]), 1)
    bits = ((d[:, None, :].astype(jnp.int32) >> shifts) & 1)
    bits = bits.astype(jnp.int8).reshape(k * 8, d.shape[1])
    acc = jax.lax.dot_general(
        bmt_ref[:], bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)              # (m*8, T)
    par = (acc & 1).reshape(m, 8, d.shape[1])
    weights = jax.lax.broadcasted_iota(jnp.int32, (m, 8, d.shape[1]), 1)
    out = (par << weights).sum(axis=1)
    out_ref[0] = out.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _run(data, bmt, *, interpret=False):
    s, k, c = data.shape
    m8 = bmt.shape[0]
    m = m8 // 8
    tile = next((t for t in _TILES if c % t == 0), None)
    assert tile is not None, c
    grid = (s, c // tile)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, m=m),
        out_shape=jax.ShapeDtypeStruct((s, m, c), jnp.uint8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k, tile), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((m8, bmt.shape[1]), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, m, tile), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(data, bmt)


def pallas_supported(c: int) -> bool:
    return any(c % t == 0 for t in _TILES)


def gf_bit_matmul_pallas(data: jnp.ndarray, bitmat: jnp.ndarray, *,
                         interpret: bool = False) -> jnp.ndarray:
    """data (S, k, C) uint8, bitmat (k*8, m*8) int8 -> (S, m, C) uint8.

    Same contract as ops/gf_matmul.gf_bit_matmul.  ``interpret=True``
    runs the Pallas interpreter; only the CPU tests pass it.
    """
    bmt = jnp.transpose(bitmat, (1, 0))                # (m*8, k*8)
    return _run(data, bmt, interpret=interpret)
