"""Faults planted under the timed path, to show the check catches them.

Each fault is a context manager that patches the program where the
answer is produced; ``control.py`` runs them on the chip at the cells'
own sizes, ``test_faults.py`` on the CPU at a small size.  The cells can
have these faults (one chip: no exchange between chips to leave out):

- ``encode_altered``: one byte of a coding chunk flipped as the codec
  returns it (an answer altered where it is produced);
- ``encode_half``: the coding chunks of the second half of the stripes
  left as zeros (half of the batch left out);
- ``encode_stale``: every encode after the first returns the chunks of
  the one before (a step that returns its state unchanged);
- ``read_altered``: one byte of every shard flipped as the store hands
  it to a read;
- ``decode_altered``: one byte of every decode's output flipped;
- ``update_stale``: ``OSDMapMapping.update()`` keeps the mapping it
  had (a step that returns its state unchanged);
- ``update_altered``: one PG's up set rotated after each update;
- ``update_half``: the second half of the PGs keep the mapping of the
  epoch before (half of the batch left out);
- ``reply_dropped``: the fabric loses the first reply to the
  benchmark's window client (a lost answer: the window must not paper
  over it with a resend);
- ``device_fallback``: every codec call fails on the device and is
  served by the CPU twin (the guard checks must catch it);
- ``mapping_off_device``: ``update()`` maps with the native C++ mapper.
"""
from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patch(cls, name: str, make):
    orig = getattr(cls, name)
    setattr(cls, name, make(orig))
    try:
        yield
    finally:
        setattr(cls, name, orig)


def _dispatcher_cls():
    from ceph_tpu.dispatch import g_dispatcher
    return type(g_dispatcher)


def _flip(arr) -> np.ndarray:
    out = np.array(arr, dtype=np.uint8, copy=True)
    if out.size:
        out.reshape(-1)[0] ^= 0x01
    return out


def encode_altered():
    def make(orig):
        def encode(self, sinfo, ec_impl, data, want):
            shards = dict(orig(self, sinfo, ec_impl, data, want))
            last = max(shards)
            shards[last] = _flip(shards[last])
            return shards
        return encode
    return _patch(_dispatcher_cls(), "encode", make)


def encode_half():
    def make(orig):
        def encode(self, sinfo, ec_impl, data, want):
            shards = dict(orig(self, sinfo, ec_impl, data, want))
            k = ec_impl.get_data_chunk_count()
            for i in [i for i in shards if i >= k]:
                s = np.array(shards[i], dtype=np.uint8, copy=True)
                s[len(s) // 2:] = 0
                shards[i] = s
            return shards
        return encode
    return _patch(_dispatcher_cls(), "encode", make)


def encode_stale():
    prev = {}

    def make(orig):
        def encode(self, sinfo, ec_impl, data, want):
            shards = orig(self, sinfo, ec_impl, data, want)
            out = prev.get("shards", shards)
            prev["shards"] = {i: np.array(v, copy=True)
                              for i, v in shards.items()}
            return out
        return encode
    return _patch(_dispatcher_cls(), "encode", make)


def read_altered():
    from ceph_tpu.os_store.memstore import MemStore

    def make(orig):
        def read_shard(self, cid, oid):
            d = orig(self, cid, oid)
            if isinstance(d, (bytes, bytearray, memoryview)):
                return _flip(np.frombuffer(bytes(d), np.uint8)).tobytes()
            return d
        return read_shard
    return _patch(MemStore, "read_shard", make)


@contextlib.contextmanager
def decode_altered():
    cls = _dispatcher_cls()

    def make_concat(orig):
        def decode_concat(self, sinfo, ec_impl, chunks):
            return _flip(orig(self, sinfo, ec_impl, chunks))
        return decode_concat

    def make_decode(orig):
        def decode(self, sinfo, ec_impl, chunks, need):
            return {i: _flip(v) for i, v in
                    orig(self, sinfo, ec_impl, chunks, need).items()}
        return decode

    with _patch(cls, "decode_concat", make_concat), \
            _patch(cls, "decode", make_decode):
        yield


def _mapping_cls():
    from ceph_tpu.osdmap.mapping import OSDMapMapping
    return OSDMapMapping


def update_stale():
    def make(orig):
        def update(self, osdmap):
            if not self.pools:
                orig(self, osdmap)
            self.epoch = osdmap.epoch
        return update
    return _patch(_mapping_cls(), "update", make)


def update_altered():
    def make(orig):
        def update(self, osdmap):
            orig(self, osdmap)
            for pm in self.pools.values():
                pm.up[0] = np.roll(pm.up[0], 1)
        return update
    return _patch(_mapping_cls(), "update", make)


def update_half():
    def make(orig):
        def update(self, osdmap):
            before = {pid: (pm.up.copy(), pm.acting.copy())
                      for pid, pm in self.pools.items()}
            orig(self, osdmap)
            for pid, (up, acting) in before.items():
                pm = self.pools[pid]
                h = len(up) // 2
                pm.up[h:], pm.acting[h:] = up[h:], acting[h:]
        return update
    return _patch(_mapping_cls(), "update", make)


@contextlib.contextmanager
def reply_dropped():
    from ceph_tpu.msg.messages import MOSDOpReply
    from ceph_tpu.msg.messenger import Network
    lost = []

    def make(orig):
        def send(self, src, dst, msg):
            if not lost and dst == "client.bench" and \
                    isinstance(msg, MOSDOpReply):
                lost.append(msg.tid)
                return
            return orig(self, src, dst, msg)
        return send
    with _patch(Network, "send", make):
        yield


@contextlib.contextmanager
def device_fallback():
    from ceph_tpu.fault import g_faults
    for site in ("device.encode_batch", "device.decode_batch"):
        g_faults.inject(site, mode="always")
    try:
        yield
    finally:
        for site in ("device.encode_batch", "device.decode_batch"):
            g_faults.clear(site)


def mapping_off_device():
    def make(orig):
        def _raw_batch(self, *args, **kw):
            self.use_device = False
            try:
                return orig(self, *args, **kw)
            finally:
                self.use_device = True
        return _raw_batch
    return _patch(_mapping_cls(), "_raw_batch", make)


FAULTS = {f.__name__: f for f in (
    encode_altered, encode_half, encode_stale, read_altered,
    decode_altered, update_stale, update_altered, update_half,
    reply_dropped, device_fallback, mapping_off_device)}

# the faults each traffic mix's cells can have
BY_TRAFFIC = {
    "write_4m": ("encode_altered", "encode_half", "encode_stale",
                 "reply_dropped", "device_fallback"),
    "degraded_read_4m": ("read_altered", "decode_altered",
                         "reply_dropped", "device_fallback"),
    "osd_flap": ("update_stale", "update_altered", "update_half",
                 "mapping_off_device"),
}
