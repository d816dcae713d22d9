"""Sharded GF(2^8) erasure coding over a device mesh.

The encode matmul ``bits(S, C, k*8) @ B(k*8, m*8)`` shards S over the
``stripe`` axis and the m*8 output columns over the ``shard`` axis — a pure
SPMD layout needing zero collectives on the forward path (the contraction
dimension stays replicated), so throughput scales linearly with chips the
way Ceph scales EC across OSDs.  Decode reuses the identical matmul with the
host-inverted survivor matrix.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.gf_matmul import gf_bit_matmul, DeviceRSBackend
from ..trace.devprof import g_devprof
from .mesh import STRIPE_AXIS, SHARD_AXIS

from jax import shard_map


def drain_sharded(out) -> int:
    """Completion fence for a MESH-SHARDED output: fetch one element
    from EVERY addressable shard of *out*; returns the number of shards
    drained.

    The single-device drain (bench/fence.py) fetches one element of the
    last output — enough there because PJRT executes per device in
    submission order.  A sharded output extends that contract per
    device: device d's dispatches are only proven complete by a
    readback from a buffer ON d, so the mesh fence touches each shard
    once (one element each, never a full fetch, so the fence itself
    moves almost no bytes).  Unsharded / host values fall back to the
    single drain.
    """
    bur = getattr(out, "block_until_ready", None)
    if bur is not None:
        bur()
    shards = getattr(out, "addressable_shards", None)
    if not shards:
        from ..bench.fence import drain
        drain(out)
        return 1
    n = 0
    for sh in shards:
        piece = sh.data
        try:
            one = piece.ravel()[:1]
        except Exception:
            one = piece
        np.asarray(one)   # THE fence: the device->host readback
        n += 1
    # deliberately NOT accounted on the devflow ledger: n one-element
    # fetches are sub-byte calibration noise, and per-shard accounting
    # would put copies_per_op = n/n_steps over the copy-budget gate's
    # noise floor at a value that moves with step calibration — a
    # flaky gate, not a copy chain.  The dispatch-path mesh flush
    # accounts its REAL boundary crossings at mesh.assemble /
    # mesh.encode (ceph_tpu/mesh/runtime.py).
    return n


def mesh_roofline(gibs: float, workload, mesh: Mesh,
                  platform: str = "", device_kind: str = ""):
    """Roofline verdict for a mesh-wide throughput reading: the chip
    peaks scale by mesh size (N devices = N chips of headroom), so a
    sharded reading is flagged suspect only above the MESH's physics,
    not a single chip's."""
    from ..bench.roofline import validate_reading
    dev = np.asarray(mesh.devices).ravel()[0]
    return validate_reading(
        gibs, workload,
        platform or getattr(dev, "platform", "unknown"),
        device_kind or getattr(dev, "device_kind", ""),
        n_devices=mesh.size)


class ShardedRS:
    """Mesh-wide executor for one (k+m, k) systematic code.

    Wraps :class:`~ceph_tpu.ops.gf_matmul.DeviceRSBackend` with explicit
    shardings; falls back to single-device semantics when the mesh has one
    device, so callers never branch.
    """

    def __init__(self, encode_matrix: np.ndarray, mesh: Mesh):
        self.mesh = mesh
        self.backend = DeviceRSBackend(encode_matrix)
        self.k = self.backend.k
        self.m = self.backend.m
        # data (S, k, C): shard stripes; chunk + byte dims replicated
        self.data_sharding = NamedSharding(mesh, P(STRIPE_AXIS, None, None))
        # bit matrix (k*8, m*8): shard output columns over the shard axis
        self.mat_sharding = NamedSharding(mesh, P(None, SHARD_AXIS))
        # output (S, m, C): keep the chunk dim on the shard axis when it
        # divides evenly — the matmul's column sharding then lands in place
        # with zero collectives; otherwise replicate (forces a gather)
        shard_size = mesh.shape[SHARD_AXIS]
        out_chunk_axis = SHARD_AXIS if self.m % shard_size == 0 else None
        self.out_sharding = NamedSharding(
            mesh, P(STRIPE_AXIS, out_chunk_axis, None))
        self._enc_bits = jax.device_put(
            self.backend._enc_bits, self.mat_sharding)
        self._matmul_jit = jax.jit(
            gf_bit_matmul, out_shardings=self.out_sharding)
        # decode output width is len(want_rows), not m: replicate it
        self._decode_jit = jax.jit(
            gf_bit_matmul,
            out_shardings=NamedSharding(mesh, P(STRIPE_AXIS, None, None)))
        # sharded decode bit-matrices: bounded LRU mirroring the backend's
        # host-side cache so device memory cannot grow without bound
        self._dev_decode_bits: OrderedDict = OrderedDict()
        self._dev_decode_cap = 2516

    # -- completion fence (the multichip ROADMAP item) -----------------------
    def drain(self, out) -> int:
        """Prove *out* complete on EVERY device of the mesh (one-element
        fetch per shard); returns the shard count drained.  Fenced
        mesh measurements must stop the clock here, not at
        block_until_ready (see drain_sharded)."""
        return drain_sharded(out)

    def roofline(self, gibs: float, workload):
        """Physics verdict for a mesh-wide reading, peaks scaled by
        this mesh's device count."""
        return mesh_roofline(gibs, workload, self.mesh)

    # -- encode -------------------------------------------------------------
    def encode_device(self, data: jnp.ndarray) -> jnp.ndarray:
        """(S, k, C) uint8 -> (S, m, C); the stripe-axis size must divide
        S (each device takes S/stripe_axis stripes)."""
        data = jax.device_put(data, self.data_sharding)
        return self._matmul_jit(data, self._enc_bits)

    def encode(self, data: np.ndarray) -> np.ndarray:
        g_devprof.install_compile_listener()
        g_devprof.account_h2d("parallel.encode", data.nbytes)
        with g_devprof.stage("parallel.encode"):
            out = np.asarray(self.encode_device(jnp.asarray(data)))
        g_devprof.account_d2h("parallel.encode", out.nbytes)
        return out

    # -- decode -------------------------------------------------------------
    def decode_bits(self, srcs: Tuple[int, ...],
                    want_rows: Tuple[int, ...]) -> jnp.ndarray:
        key = (tuple(srcs), tuple(want_rows))
        hit = self._dev_decode_bits.get(key)
        if hit is not None:
            self._dev_decode_bits.move_to_end(key)
            return hit
        # no devprof h2d here: _decode_bits_for already accounted the
        # real host->device crossing; this device_put is a device-to-
        # device reshard onto the mesh, not a boundary copy
        bits = self.backend._decode_bits_for(*key)
        out = jax.device_put(bits, NamedSharding(self.mesh, P(None, None)))
        self._dev_decode_bits[key] = out
        if len(self._dev_decode_bits) > self._dev_decode_cap:
            self._dev_decode_bits.popitem(last=False)
        return out

    def decode_data(self, survivors: np.ndarray, srcs: Sequence[int],
                    want_rows: Sequence[int]) -> np.ndarray:
        bits = self.decode_bits(tuple(srcs), tuple(want_rows))
        g_devprof.install_compile_listener()
        g_devprof.account_h2d("parallel.decode", survivors.nbytes)
        with g_devprof.stage("parallel.decode"):
            sv = jax.device_put(jnp.asarray(survivors),
                                self.data_sharding)
            out = np.asarray(self._decode_jit(sv, bits))
        g_devprof.account_d2h("parallel.decode", out.nbytes)
        return out

    # -- contraction-sharded decode -----------------------------------------
    def decode_data_survivor_sharded(self, survivors: np.ndarray,
                                     srcs: Sequence[int],
                                     want_rows: Sequence[int]
                                     ) -> np.ndarray:
        """Decode with the SURVIVORS sharded across the ``shard`` axis.

        The degraded-read case where no single chip holds all k
        survivor shards (each device fetched its own subset from its
        OSDs — the sequence/context-parallel layout of this
        framework).  GF(2) makes the contraction reduction a psum:
        every device computes the int32 bit-accumulator over its local
        k-slice, one ``lax.psum`` rides the ICI mesh, and only THEN is
        accumulator parity taken — XOR-allreduce expressed as the
        compiler-native collective (the NCCL-allreduce role in the
        reference's recovery fan-in, osd/ECBackend.cc:1141-1281, where
        shard reads converge on the primary).
        """
        nshard = self.mesh.shape[SHARD_AXIS]
        k = survivors.shape[1]
        if k % nshard:
            raise ValueError(f"k={k} not divisible by shard axis "
                             f"size {nshard}")
        bits = self.decode_bits(tuple(srcs), tuple(want_rows))
        g_devprof.install_compile_listener()
        g_devprof.account_h2d("parallel.decode_sharded",
                              survivors.nbytes)
        with g_devprof.stage("parallel.decode_sharded"):
            sv = jax.device_put(
                jnp.asarray(survivors),
                NamedSharding(self.mesh,
                              P(STRIPE_AXIS, SHARD_AXIS, None)))
            bd = jax.device_put(
                bits, NamedSharding(self.mesh, P(SHARD_AXIS, None)))
            out = np.asarray(self._collective_decode_jit()(sv, bd))
        g_devprof.account_d2h("parallel.decode_sharded", out.nbytes)
        return out

    # -- layout conversion (all-to-all) -------------------------------------
    def reshard_stripes_to_chunks(self, chunks: jnp.ndarray
                                  ) -> jnp.ndarray:
        """(S, k+m, C) stripe-sharded -> chunk-sharded, on-mesh.

        Encode produces stripe-parallel output (each device holds ALL
        chunks of ITS stripes); distribution to OSD shards wants
        chunk-parallel layout (each device holds ONE chunk slice of
        ALL stripes — the k+m fan-out, ECBackend.cc:1942+).  The
        switch is a single ``lax.all_to_all`` over the stripe axis —
        the storage analog of the sequence<->head resharding in
        all-to-all context parallelism, riding ICI instead of a
        device->host->device bounce."""
        nstripe = self.mesh.shape[STRIPE_AXIS]
        s, r, _c = chunks.shape
        if r % nstripe or s % nstripe:
            raise ValueError(f"shape ({s}, {r}, ...) not divisible "
                             f"by stripe axis size {nstripe}")
        fn = getattr(self, "_reshard_fn", None)
        if fn is None:
            def swap(local):
                # local (S/n, r, C) -> all_to_all splits r, concats S
                return jax.lax.all_to_all(local, STRIPE_AXIS,
                                          split_axis=1, concat_axis=0,
                                          tiled=True)

            fn = self._reshard_fn = jax.jit(shard_map(
                swap, mesh=self.mesh,
                in_specs=P(STRIPE_AXIS, None, None),
                out_specs=P(None, STRIPE_AXIS, None)))
        src = jax.device_put(chunks, NamedSharding(
            self.mesh, P(STRIPE_AXIS, None, None)))
        return fn(src)

    def _collective_decode_jit(self):
        """The shard_map-wrapped kernel, built once per instance so
        repeat degraded reads hit jit's cache instead of retracing."""
        fn = getattr(self, "_collective_fn", None)
        if fn is not None:
            return fn
        from ..ops.gf_matmul import _pack_bits, _unpack_bits

        def local_partial(sv_local, bits_local):
            # sv_local (S/dp, k/tp, C); bits_local (k*8/tp, r*8)
            d = jnp.transpose(sv_local, (0, 2, 1))
            planes = _unpack_bits(d).astype(jnp.int8)
            acc = jax.lax.dot_general(
                planes, bits_local,
                dimension_numbers=(((2,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc = jax.lax.psum(acc, SHARD_AXIS)
            parity = (acc & 1).astype(jnp.uint8)
            return jnp.transpose(_pack_bits(parity), (0, 2, 1))

        fn = jax.jit(shard_map(
            local_partial, mesh=self.mesh,
            in_specs=(P(STRIPE_AXIS, SHARD_AXIS, None),
                      P(SHARD_AXIS, None)),
            out_specs=P(STRIPE_AXIS, None, None)))
        self._collective_fn = fn
        return fn
