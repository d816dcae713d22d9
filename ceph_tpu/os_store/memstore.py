"""MemStore — transactional in-memory ObjectStore.

Mirrors the reference's test backend (src/os/memstore/MemStore.{h,cc}) and
the ObjectStore transaction model (src/os/ObjectStore.h): collections (one
per PG shard) hold objects with byte data, xattrs and omap; mutations are
queued as Transactions whose ops apply atomically and in order.  BlueStore's
block/WAL machinery is host-I/O out of scope for a TPU build (SURVEY.md
§2.9) — this is the durability stand-in that keeps the OSD data path
honest: every shard write and recovery push lands here through the same
Transaction ABI the reference uses.

Device-resident shard bodies: an object's ``data`` may be a
``DeviceShard`` (os_store/device_shard.py) instead of a bytearray — a
whole-body handle written via ``Transaction.write_shard`` that stays in
HBM until a host read materializes it (the accounted
``memstore.fetch_shard`` d2h).  ``stat``/``save`` work unchanged via
``len()``/``bytes()``; any byte-granular mutation (write/zero/truncate)
materializes first, so splicing semantics are identical either way.

Adopted bodies: every queued write holds an immutable ``bytes`` (the
caller's own ``bytes``, or the one copy ``Transaction.write`` makes of
anything else), and a write that replaces a whole body makes that
``bytes`` the body, as upstream's MemStore splices a message's
bufferlist into the object: no zero-fill and no second copy.  A body is
then a ``bytearray`` (private to the store), a ``bytes`` or a
``DeviceShard``; every in-place edit copies the latter two first
(``_Stage.body``).
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np
from ..common.lockdep import DebugRLock
from ..trace.span import g_tracer
from .device_shard import DeviceShard, g_device_budget


@dataclass(frozen=True, order=True)
class hobject_t:
    """Object identity inside a collection (simplified hobject)."""
    oid: str
    shard: int = -1  # EC shard id, -1 = whole/replicated

    def __str__(self):
        return f"{self.oid}" if self.shard < 0 else f"{self.oid}({self.shard})"


class _Object:
    __slots__ = ("data", "attrs", "omap")

    def __init__(self):
        self.data = bytearray()
        self.attrs: Dict[str, bytes] = {}
        self.omap: Dict[str, bytes] = {}


# transaction op codes (subset of ObjectStore::Transaction ops)
OP_TOUCH = "touch"
OP_WRITE = "write"
OP_WRITE_SHARD = "write_shard"  # whole-body replace, handle-typed
OP_ZERO = "zero"
OP_TRUNCATE = "truncate"
OP_REMOVE = "remove"
OP_SETATTR = "setattr"
OP_RMATTR = "rmattr"
OP_OMAP_SETKEYS = "omap_setkeys"
OP_OMAP_RMKEYS = "omap_rmkeys"
OP_MKCOLL = "mkcoll"
OP_RMCOLL = "rmcoll"


class Transaction:
    """Ordered batch of mutations applied atomically
    (os/ObjectStore.h Transaction)."""

    def __init__(self):
        self.ops: List[Tuple] = []

    def touch(self, cid: str, oid: hobject_t):
        self.ops.append((OP_TOUCH, cid, oid))

    def write(self, cid: str, oid: hobject_t, offset: int, data):
        """Queue *data* at *offset* as an immutable ``bytes``: a
        ``bytes`` is queued itself, anything else is copied once (so a
        caller that reuses its buffer cannot reach the store), and
        ``_apply`` may make the queued ``bytes`` a body."""
        self.ops.append((OP_WRITE, cid, oid, offset, bytes(data)))

    def write_shard(self, cid: str, oid: hobject_t, shard):
        """Replace the whole object body with *shard* (a ``DeviceShard``
        handle or host bytes) without coercing — the zero-copy write
        path's store op: a resident body is queued and applied with no
        byte movement at all."""
        self.ops.append((OP_WRITE_SHARD, cid, oid, shard))

    def zero(self, cid: str, oid: hobject_t, offset: int, length: int):
        self.ops.append((OP_ZERO, cid, oid, offset, length))

    def truncate(self, cid: str, oid: hobject_t, size: int):
        self.ops.append((OP_TRUNCATE, cid, oid, size))

    def remove(self, cid: str, oid: hobject_t):
        self.ops.append((OP_REMOVE, cid, oid))

    def setattr(self, cid: str, oid: hobject_t, name: str, value: bytes):
        self.ops.append((OP_SETATTR, cid, oid, name, bytes(value)))

    def rmattr(self, cid: str, oid: hobject_t, name: str):
        self.ops.append((OP_RMATTR, cid, oid, name))

    def omap_setkeys(self, cid: str, oid: hobject_t,
                     keys: Dict[str, bytes]):
        self.ops.append((OP_OMAP_SETKEYS, cid, oid, dict(keys)))

    def omap_rmkeys(self, cid: str, oid: hobject_t, keys: List[str]):
        self.ops.append((OP_OMAP_RMKEYS, cid, oid, list(keys)))

    def create_collection(self, cid: str):
        self.ops.append((OP_MKCOLL, cid))

    def remove_collection(self, cid: str):
        self.ops.append((OP_RMCOLL, cid))

    def append(self, other: "Transaction"):
        self.ops.extend(other.ops)

    def empty(self) -> bool:
        return not self.ops


class _Stage:
    """One transaction's copy-on-write view of the store.

    Each collection the transaction names is a shallow copy: a new dict
    of the committed objects.  An object gets a private ``_Object`` on
    its first touch (``obj``), with its own attr and omap dicts but the
    committed body still shared; the body is copied only when an op
    edits it in place (``body``), and then only the bytes the edit
    keeps.  So nothing the committed store holds is ever mutated, and an
    op that raises leaves it exactly as it was.  ``staged_bytes`` and
    ``staged_objs`` count the body bytes copied and the objects copied
    to stage; ``adopted_bytes`` the body bytes that became a body by
    reference."""

    __slots__ = ("base", "colls", "staged_bytes", "staged_objs",
                 "adopted_bytes")

    def __init__(self, base: Dict[str, Dict[hobject_t, _Object]],
                 cids) -> None:
        self.base = base
        self.colls = dict(base)
        for cid in cids:
            coll = base.get(cid)
            if coll is not None:
                self.colls[cid] = dict(coll)
        self.staged_bytes = 0
        self.staged_objs = 0
        self.adopted_bytes = 0

    def _committed(self, cid: str, oid: hobject_t) -> Optional[_Object]:
        coll = self.base.get(cid)
        return None if coll is None else coll.get(oid)

    def coll(self, cid: str) -> Dict[hobject_t, _Object]:
        if cid not in self.colls:
            raise KeyError(f"no collection {cid}")
        return self.colls[cid]

    def obj(self, cid: str, oid: hobject_t, create: bool = False) -> _Object:
        """The staged object, private to this transaction."""
        c = self.coll(cid)
        o = c.get(oid)
        if o is None:
            if not create:
                raise KeyError(f"no object {oid} in {cid}")
            o = c[oid] = _Object()
        elif o is self._committed(cid, oid):
            mine = c[oid] = _Object()
            mine.data = o.data
            mine.attrs = dict(o.attrs)
            mine.omap = dict(o.omap)
            self.staged_objs += 1
            o = mine
        return o

    def body(self, cid: str, oid: hobject_t, o: _Object,
             keep: Optional[int] = None) -> bytearray:
        """*o*'s body as a bytearray this transaction may edit in place.
        A resident shard materializes (byte-granular edits need bytes);
        an adopted ``bytes`` body, or one still shared with the
        committed object, is copied, its first *keep* bytes only where
        the edit rewrites or drops the rest (None: all of it)."""
        d = o.data
        if isinstance(d, DeviceShard):
            o.data = d = bytearray(d.materialize())
        else:
            base = self._committed(cid, oid)
            if not isinstance(d, bytearray) or \
                    (base is not None and d is base.data):
                o.data = d = bytearray(memoryview(d)[:keep])
                self.staged_bytes += len(d)
        return d


_MAGIC = b"CTPUSTOR"
_VERSION = 1


class MemStore:
    def __init__(self):
        self.colls: Dict[str, Dict[hobject_t, _Object]] = {}
        self.committed_txns = 0
        self._write_lock = DebugRLock("MemStore::write_lock")

    # ---- lifecycle / durability -------------------------------------------
    def mount(self) -> None:
        pass

    def umount(self) -> None:
        pass

    def save(self, path: str) -> None:
        """Persist every collection to *path* (length-prefixed binary; the
        BlueStore-durability stand-in: checkpoint = this file, resume =
        ``MemStore.load``)."""
        import struct as _s

        def pstr(b: bytes) -> bytes:
            return _s.pack("<I", len(b)) + b

        out = [_MAGIC, _s.pack("<IQ", _VERSION, self.committed_txns),
               _s.pack("<I", len(self.colls))]
        for cid in sorted(self.colls):
            coll = self.colls[cid]
            out.append(pstr(cid.encode()))
            out.append(_s.pack("<I", len(coll)))
            for ho in sorted(coll):
                o = coll[ho]
                out.append(pstr(ho.oid.encode()))
                out.append(_s.pack("<i", ho.shard))
                out.append(pstr(bytes(o.data)))
                out.append(_s.pack("<I", len(o.attrs)))
                for k in sorted(o.attrs):
                    out.append(pstr(k.encode()))
                    out.append(pstr(o.attrs[k]))
                out.append(_s.pack("<I", len(o.omap)))
                for k in sorted(o.omap):
                    out.append(pstr(k.encode()))
                    out.append(pstr(o.omap[k]))
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(b"".join(out))
        os.replace(tmp, path)  # atomic like a journal commit

    @classmethod
    def load(cls, path: str) -> "MemStore":
        import struct as _s
        with open(path, "rb") as f:
            buf = f.read()
        if buf[:8] != _MAGIC:
            raise ValueError(f"{path}: not a ceph_tpu store file")
        pos = 8
        version, txns = _s.unpack_from("<IQ", buf, pos)
        pos += 12
        if version != _VERSION:
            raise ValueError(f"{path}: store version {version}")

        def rstr() -> bytes:
            nonlocal pos
            (n,) = _s.unpack_from("<I", buf, pos)
            pos += 4
            b = buf[pos:pos + n]
            pos += n
            return b

        store = cls()
        store.committed_txns = txns
        (ncolls,) = _s.unpack_from("<I", buf, pos)
        pos += 4
        for _ in range(ncolls):
            cid = rstr().decode()
            (nobjs,) = _s.unpack_from("<I", buf, pos)
            pos += 4
            coll: Dict[hobject_t, _Object] = {}
            for _o in range(nobjs):
                oid = rstr().decode()
                (shard,) = _s.unpack_from("<i", buf, pos)
                pos += 4
                obj = _Object()
                obj.data = bytearray(rstr())
                (nattrs,) = _s.unpack_from("<I", buf, pos)
                pos += 4
                for _a in range(nattrs):
                    k = rstr().decode()
                    obj.attrs[k] = rstr()
                (nomap,) = _s.unpack_from("<I", buf, pos)
                pos += 4
                for _m in range(nomap):
                    k = rstr().decode()
                    obj.omap[k] = rstr()
                coll[hobject_t(oid, shard)] = obj
            store.colls[cid] = coll
        return store

    # ---- transactions -----------------------------------------------------
    def queue_transaction(self, t: Transaction) -> None:
        """Apply atomically; an op that raises leaves the store as it was.

        Thread-safe for writers (the threaded op queue commits from
        worker threads; the reference ObjectStore is too): the whole
        stage-and-swap runs under a mutex, while readers see either the
        old or the new dict via the atomic reference swap.  Staging is
        copy-on-write per object (``_Stage``): no committed object is
        ever edited, so a reader holding the old dict or an old object
        keeps seeing the old state."""
        with self._write_lock:
            scope = g_tracer.span(prof="os.queue_transaction",
                                  staged_bytes=0, staged_objs=0,
                                  adopted_bytes=0)
            with scope:
                stage = _Stage(self.colls,
                               {op[1] for op in t.ops if len(op) > 1})
                self._apply(stage, t)
                self.colls = stage.colls
                self.committed_txns += 1
                scope.set(staged_bytes=stage.staged_bytes,
                          staged_objs=stage.staged_objs,
                          adopted_bytes=stage.adopted_bytes)

    @staticmethod
    def _apply(stage: _Stage, t: Transaction) -> None:
        obj, body = stage.obj, stage.body
        for op in t.ops:
            code = op[0]
            if code == OP_MKCOLL:
                stage.colls.setdefault(op[1], {})
            elif code == OP_RMCOLL:
                stage.colls.pop(op[1], None)
            elif code == OP_TOUCH:
                obj(op[1], op[2], create=True)
            elif code == OP_WRITE:
                _, cid, oid, offset, data = op
                o = obj(cid, oid, create=True)
                end = offset + len(data)
                if offset == 0 and end >= len(o.data):
                    # the write replaces the whole body: the queued
                    # bytes become it, with no zero-fill and no copy
                    o.data = data
                    stage.adopted_bytes += end
                else:
                    buf = body(cid, oid, o,
                               offset if end >= len(o.data) else None)
                    if len(buf) < end:
                        buf.extend(b"\0" * (end - len(buf)))
                    buf[offset:end] = data
            elif code == OP_WRITE_SHARD:
                _, cid, oid, shard = op
                obj(cid, oid, create=True).data = shard
            elif code == OP_ZERO:
                _, cid, oid, offset, length = op
                o = obj(cid, oid, create=True)
                end = offset + length
                buf = body(cid, oid, o,
                           offset if end >= len(o.data) else None)
                if len(buf) < end:
                    buf.extend(b"\0" * (end - len(buf)))
                buf[offset:end] = b"\0" * length
            elif code == OP_TRUNCATE:
                _, cid, oid, size = op
                buf = body(cid, oid, obj(cid, oid, create=True), size)
                if len(buf) > size:
                    del buf[size:]
                else:
                    buf.extend(b"\0" * (size - len(buf)))
            elif code == OP_REMOVE:
                stage.coll(op[1]).pop(op[2], None)
            elif code == OP_SETATTR:
                _, cid, oid, name, value = op
                obj(cid, oid, create=True).attrs[name] = value
            elif code == OP_RMATTR:
                _, cid, oid, name = op
                obj(cid, oid).attrs.pop(name, None)
            elif code == OP_OMAP_SETKEYS:
                _, cid, oid, keys = op
                obj(cid, oid, create=True).omap.update(keys)
            elif code == OP_OMAP_RMKEYS:
                _, cid, oid, keys = op
                o = obj(cid, oid)
                for k in keys:
                    o.omap.pop(k, None)
            else:
                raise ValueError(f"unknown op {code}")

    # ---- reads ------------------------------------------------------------
    def collection_exists(self, cid: str) -> bool:
        return cid in self.colls

    def list_collections(self) -> List[str]:
        return sorted(self.colls)

    def exists(self, cid: str, oid: hobject_t) -> bool:
        return oid in self.colls.get(cid, {})

    def _maybe_corrupt(self, cid: str, oid: hobject_t,
                       o: _Object) -> None:
        """Fault site ``store.shard_corrupt``: flip one stored body
        byte (bitrot) — works on resident handles and host bytes alike
        so the crc EIO path is testable in both representations.  An
        adopted ``bytes`` body is replaced by a flipped copy, so later
        reads still see the rot."""
        from ..fault import g_faults  # lazy: fault imports trace
        if not g_faults.site_armed("store.shard_corrupt"):
            return
        if not g_faults.should_fire("store.shard_corrupt",
                                    f"{cid}/{oid}"):
            return
        d = o.data
        if isinstance(d, DeviceShard):
            d.corrupted()
        elif len(d):
            if not isinstance(d, bytearray):
                o.data = d = bytearray(d)
            d[0] ^= 0x01

    def read(self, cid: str, oid: hobject_t, offset: int = 0,
             length: int = 0) -> bytes:
        o = self.colls[cid][oid]
        self._maybe_corrupt(cid, oid, o)
        d = o.data
        if isinstance(d, DeviceShard):
            d = d.materialize()
        if length == 0:
            length = len(d) - offset
        return bytes(d[offset:offset + length])

    def read_shard(self, cid: str, oid: hobject_t):
        """The whole body WITHOUT forcing host bytes: a resident
        ``DeviceShard`` comes back as the handle itself (LRU-touched);
        host-bytes bodies come back as bytes (an adopted ``bytes`` body
        itself, a ``bytearray`` copied out).  The zero-copy read path
        for in-process fabrics."""
        o = self.colls[cid][oid]
        self._maybe_corrupt(cid, oid, o)
        d = o.data
        if isinstance(d, DeviceShard):
            g_device_budget.touch(d)
            return d
        return bytes(d)

    def stat(self, cid: str, oid: hobject_t) -> int:
        return len(self.colls[cid][oid].data)

    def getattr(self, cid: str, oid: hobject_t, name: str) -> bytes:
        return self.colls[cid][oid].attrs[name]

    def getattrs(self, cid: str, oid: hobject_t) -> Dict[str, bytes]:
        return dict(self.colls[cid][oid].attrs)

    def omap_get(self, cid: str, oid: hobject_t) -> Dict[str, bytes]:
        return dict(self.colls[cid][oid].omap)

    def list_objects(self, cid: str) -> List[hobject_t]:
        return sorted(self.colls.get(cid, {}))
