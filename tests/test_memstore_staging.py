"""Object-granular copy-on-write staging in ``MemStore.queue_transaction``.

A transaction stages shallow copies of the collections it names, gives
each object it touches a private ``_Object`` that still shares the
committed body, and copies a body only when an op edits it in place.
These tests hold that to the guarantees the store gave when it
deep-cloned whole collections: a failed transaction changes nothing, a
reader's snapshot never changes under it, and the journal replays to
the live state.  A whole-body write is adopted: the ``bytes`` the
transaction queued (the caller's own ``bytes``, else its one copy)
becomes the body by reference, and any later in-place edit copies it
first.  Each runs on a ``MemStore`` and on an unmounted and a
mounted ``WALStore`` (which calls ``MemStore.queue_transaction`` inside
its journal write).
"""
import contextlib
import os

import numpy as np
import pytest

from ceph_tpu.os_store import memstore
from ceph_tpu.os_store.memstore import MemStore, Transaction, hobject_t
from ceph_tpu.os_store.walstore import WALStore

CID = "1.0s0"
A, B = hobject_t("a", 0), hobject_t("b", 0)


def payload(n: int, seed: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


@pytest.fixture(params=["memstore", "walstore_unmounted",
                        "walstore_mounted"])
def store(request, tmp_path):
    if request.param == "memstore":
        s = MemStore()
    else:
        s = WALStore(str(tmp_path / "osd"))
        if request.param == "walstore_mounted":
            s.mount()
    t = Transaction()
    t.create_collection(CID)
    t.create_collection("1.0_meta")
    for oid, seed in ((A, 1), (B, 2)):
        t.write(CID, oid, 0, payload(4096, seed))
        t.setattr(CID, oid, "v", b"1")
        t.omap_setkeys(CID, oid, {"k": b"v"})
    s.queue_transaction(t)
    yield s
    if request.param == "walstore_mounted":
        s.umount()


class _Recorder:
    """Stands in for ``g_tracer``: keeps the args of each
    ``os.queue_transaction`` span as ``set`` leaves them."""

    def __init__(self):
        self.spans = []

    def span(self, *, prof, **args):
        assert prof == "os.queue_transaction"
        rec = dict(args)
        self.spans.append(rec)

        class _Scope(contextlib.nullcontext):
            def set(self, **more):
                rec.update(more)

        return _Scope()


@pytest.fixture
def spans(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(memstore, "g_tracer", rec)
    return rec.spans


def state(s: MemStore):
    return {cid: {oid: (bytes(o.data), dict(o.attrs), dict(o.omap))
                  for oid, o in coll.items()}
            for cid, coll in s.colls.items()}


def test_failed_transaction_changes_nothing(store):
    colls, objs = store.colls, dict(store.colls[CID])
    before, txns = state(store), store.committed_txns
    mounted = getattr(store, "_wal_f", None) is not None
    wal = os.path.join(store.dir, "wal.bin") if mounted else None
    wal_size = os.path.getsize(wal) if mounted else None
    t = Transaction()
    t.write(CID, A, 100, b"X" * 64)              # in place
    t.truncate(CID, B, 10)                       # shrink
    t.setattr(CID, A, "v", b"2")
    t.omap_setkeys(CID, B, {"k": b"w", "k2": b"x"})
    t.remove(CID, B)
    t.write(CID, hobject_t("new", 0), 0, b"fresh")
    t.rmattr(CID, hobject_t("missing", 0), "v")  # raises
    with pytest.raises(KeyError):
        store.queue_transaction(t)
    assert store.colls is colls
    assert store.committed_txns == txns
    for oid, o in objs.items():
        assert store.colls[CID][oid] is o
    assert state(store) == before
    if mounted:
        assert os.path.getsize(wal) == wal_size  # journal rewound
        replayed = WALStore(store.dir)
        replayed.mount()
        assert state(replayed) == before
        replayed.umount()


@pytest.mark.parametrize("edit", [
    lambda t: t.write(CID, A, 100, b"X" * 64),      # partial, in place
    lambda t: t.write(CID, A, 4000, b"Y" * 200),    # past the end
    lambda t: t.zero(CID, A, 8, 16),
    lambda t: t.truncate(CID, A, 1000),             # shrink
    lambda t: t.truncate(CID, A, 8192),             # grow
    lambda t: (t.truncate(CID, A, 0), t.write(CID, A, 0, b"Z" * 9)),
], ids=["write", "write_past_end", "zero", "shrink", "grow", "rewrite"])
def test_reader_snapshot_keeps_old_bytes(store, edit):
    old_colls, old = store.colls, store.colls[CID][A]
    body, attrs = bytes(old.data), dict(old.attrs)
    t = Transaction()
    edit(t)
    t.setattr(CID, A, "v", b"2")
    store.queue_transaction(t)
    assert store.colls is not old_colls
    assert old_colls[CID][A] is old
    assert bytes(old.data) == body and old.attrs == attrs
    assert store.colls[CID][A] is not old
    assert store.read(CID, A) != body
    # a second in-place edit of the now-committed body leaves the
    # first snapshot alone too
    t = Transaction()
    t.write(CID, A, 0, b"Q" * 8)
    store.queue_transaction(t)
    assert bytes(old.data) == body


def test_untouched_objects_and_collections_are_shared(store):
    b, meta = store.colls[CID][B], store.colls["1.0_meta"]
    t = Transaction()
    t.write(CID, A, 10, b"hello")
    store.queue_transaction(t)
    assert store.colls[CID][B] is b
    assert store.colls["1.0_meta"] is meta


@pytest.mark.parametrize("case,want_bytes,want_objs", [
    ("fresh_object", 0, 0),
    ("rewrite_existing", 0, 1),
    ("offset_write_existing", 4096, 1),
    ("shrink_existing", 1000, 1),
    ("attrs_only", 0, 1),
])
def test_staged_bytes(store, spans, case, want_bytes, want_objs):
    t = Transaction()
    if case == "fresh_object":
        t.truncate(CID, hobject_t("c", 0), 0)
        t.write(CID, hobject_t("c", 0), 0, payload(4096, 3))
    elif case == "rewrite_existing":
        t.truncate(CID, A, 0)
        t.write(CID, A, 0, payload(4096, 3))
    elif case == "offset_write_existing":
        t.write(CID, A, 512, b"X" * 16)
    elif case == "shrink_existing":
        t.truncate(CID, A, 1000)
    else:
        t.setattr(CID, A, "v", b"2")
        t.omap_setkeys(CID, A, {"k2": b"x"})
    store.queue_transaction(t)
    assert spans[-1]["staged_bytes"] == want_bytes
    assert spans[-1]["staged_objs"] == want_objs


def test_resident_body_is_shared_by_handle(spans):
    jnp = pytest.importorskip("jax.numpy")
    from ceph_tpu.os_store import DeviceShard
    from ceph_tpu.utils.crc32c import crc32c
    data = payload(2048, 4)
    sh = DeviceShard(jnp.asarray(np.frombuffer(data, np.uint8)),
                     len(data), crc32c(data))
    s = MemStore()
    t = Transaction()
    t.create_collection(CID)
    t.write_shard(CID, A, sh)
    s.queue_transaction(t)
    t = Transaction()
    t.setattr(CID, A, "v", b"2")
    s.queue_transaction(t)
    assert s.colls[CID][A].data is sh
    assert spans[-1]["staged_bytes"] == 0
    t = Transaction()
    t.write(CID, A, 4, b"abcd")                  # materializes, no stage copy
    s.queue_transaction(t)
    assert s.read(CID, A) == data[:4] + b"abcd" + data[8:]
    assert spans[-1]["staged_bytes"] == 0


def test_shard_corrupt_flips_the_live_body(store):
    from ceph_tpu.fault import g_faults
    assert isinstance(store.colls[CID][A].data, bytes)   # adopted
    body = store.read(CID, A)
    g_faults.inject("store.shard_corrupt", mode="once")
    try:
        got = store.read(CID, A)
    finally:
        g_faults.clear()
    assert got[0] == body[0] ^ 0x01 and got[1:] == body[1:]
    assert store.read(CID, A) == got


@pytest.mark.parametrize("wal_max_bytes", [1 << 30, 16 << 10],
                         ids=["replay_only", "with_checkpoints"])
def test_wal_replay_equals_live(tmp_path, wal_max_bytes):
    d = str(tmp_path / "osd")
    live = WALStore(d, wal_max_bytes=wal_max_bytes)
    live.mount()
    rng = np.random.default_rng(5)
    t = Transaction()
    t.create_collection(CID)
    live.queue_transaction(t)
    oids = [hobject_t(f"o{i}", 0) for i in range(6)]
    for step in range(60):
        oid = oids[int(rng.integers(len(oids)))]
        t = Transaction()
        kind = int(rng.integers(6))
        off = int(rng.integers(0, 3000))
        if kind == 0:
            t.truncate(CID, oid, 0)
            t.write(CID, oid, 0, payload(2048, step))
        elif kind == 1:
            t.write(CID, oid, off, payload(300, step))
        elif kind == 2:
            t.zero(CID, oid, off, 100)
        elif kind == 3:
            t.truncate(CID, oid, off)
        elif kind == 4:
            t.setattr(CID, oid, "v", bytes([step]))
            t.omap_setkeys(CID, oid, {f"k{step}": b"x"})
        else:
            t.remove(CID, oid)
        if step % 7 == 3:
            t.rmattr(CID, hobject_t("missing", 0), "v")
            with pytest.raises(KeyError):
                live.queue_transaction(t)
            continue
        live.queue_transaction(t)
    want = state(live)
    replayed = WALStore(d, wal_max_bytes=wal_max_bytes)
    replayed.mount()                             # no umount: a crash
    assert state(replayed) == want
    assert replayed.committed_txns == live.committed_txns
    replayed.umount()
    live._wal_f.close()


def _frozen_view(data: bytes) -> memoryview:
    """A read-only memoryview over a read-only ndarray."""
    a = np.frombuffer(bytearray(data), dtype=np.uint8)
    a.flags.writeable = False
    return a.data


@pytest.mark.parametrize("kind", ["bytes", "readonly_view"])
def test_read_only_write_is_adopted(store, spans, kind):
    data = payload(8192, 7)
    buf = data if kind == "bytes" else _frozen_view(data)
    t = Transaction()
    t.truncate(CID, A, 0)
    t.write(CID, A, 0, buf)
    t.write(CID, hobject_t("c", 0), 0, buf)      # a new object
    store.queue_transaction(t)
    a, c = store.colls[CID][A].data, store.colls[CID][hobject_t("c", 0)].data
    assert type(a) is bytes and type(c) is bytes
    if kind == "bytes":
        assert a is buf and c is buf
    else:                       # one copy each, made at queue time
        assert a is not c
    assert spans[-1]["adopted_bytes"] == 2 * len(data)
    assert spans[-1]["staged_bytes"] == 0
    assert store.read(CID, A) == data
    assert store.stat(CID, A) == len(data)
    got = store.read_shard(CID, A)
    assert got == data and isinstance(got, bytes)
    if kind == "bytes":
        assert got is buf
    # a write at offset 0 at least as long as the body adopts too
    longer = payload(9000, 8)
    t = Transaction()
    t.write(CID, A, 0, longer)
    store.queue_transaction(t)
    assert store.colls[CID][A].data is longer
    assert spans[-1]["adopted_bytes"] == len(longer)


@pytest.mark.parametrize("kind", ["bytearray", "ndarray", "writable_view"])
def test_writable_buffer_is_copied(store, kind):
    data = payload(4096, 9)
    if kind == "bytearray":
        buf = bytearray(data)
    elif kind == "ndarray":
        buf = np.frombuffer(bytearray(data), dtype=np.uint8)
    else:
        buf = memoryview(bytearray(data))
    t = Transaction()
    t.truncate(CID, A, 0)
    t.write(CID, A, 0, buf)
    store.queue_transaction(t)
    buf[0] ^= 0xFF
    buf[-1] ^= 0xFF
    assert store.colls[CID][A].data is not buf
    assert store.read(CID, A) == data


@pytest.mark.parametrize("kind", ["memoryview", "ndarray"])
def test_read_only_view_over_a_writable_base_is_copied(store, kind):
    """A read-only view says nothing of its owner: the owner may still
    write the bytes, so the store keeps a copy, never the view."""
    data = payload(4096, 16)
    base = bytearray(data)
    if kind == "memoryview":
        buf = memoryview(base).toreadonly()
    else:
        buf = np.frombuffer(base, dtype=np.uint8)[:]
        buf.flags.writeable = False
    assert memoryview(buf).readonly
    t = Transaction()
    t.write(CID, A, 0, buf)
    store.queue_transaction(t)
    base[0] ^= 0xFF
    base[-1] ^= 0xFF
    assert type(store.colls[CID][A].data) is bytes
    assert store.read(CID, A) == data


@pytest.mark.parametrize("edit,kept", [
    (lambda t: t.write(CID, A, 100, b"X" * 64), 4096),
    (lambda t: t.write(CID, A, 4000, b"Y" * 200), 4000),
    (lambda t: t.zero(CID, A, 8, 16), 4096),
    (lambda t: t.truncate(CID, A, 1000), 1000),
    (lambda t: t.truncate(CID, A, 8192), 4096),
], ids=["write", "write_past_end", "zero", "shrink", "grow"])
@pytest.mark.parametrize("kind", ["bytes", "readonly_view"])
def test_edit_of_adopted_body_copies_on_write(store, spans, kind, edit,
                                              kept):
    data = payload(4096, 10)
    buf = data if kind == "bytes" else _frozen_view(data)
    t = Transaction()
    t.write(CID, A, 0, buf)
    store.queue_transaction(t)
    old_colls, old = store.colls, store.colls[CID][A]
    body = old.data
    assert type(body) is bytes and body == data
    assert (body is buf) == (kind == "bytes")
    t = Transaction()
    edit(t)
    store.queue_transaction(t)
    assert spans[-1]["staged_bytes"] == kept
    assert spans[-1]["adopted_bytes"] == 0
    assert bytes(buf) == data                    # the buffer is untouched
    assert old_colls[CID][A] is old and old.data is body
    assert body == data
    assert store.read(CID, A) != data
    # an edit inside the same transaction as the adopting write
    t = Transaction()
    t.write(CID, B, 0, buf)
    t.write(CID, B, 4, b"abcd")
    store.queue_transaction(t)
    assert store.read(CID, B) == data[:4] + b"abcd" + data[8:]
    assert bytes(buf) == data
    assert spans[-1]["adopted_bytes"] == len(data)
    assert spans[-1]["staged_bytes"] == len(data)


def test_failed_transaction_after_adopting_write(store, spans):
    before, colls = state(store), store.colls
    t = Transaction()
    t.truncate(CID, A, 0)
    t.write(CID, A, 0, payload(8192, 11))
    t.write(CID, hobject_t("new", 0), 0, _frozen_view(payload(64, 12)))
    t.rmattr(CID, hobject_t("missing", 0), "v")  # raises
    with pytest.raises(KeyError):
        store.queue_transaction(t)
    assert store.colls is colls
    assert state(store) == before


def test_shard_corrupt_flips_an_adopted_view(store):
    from ceph_tpu.fault import g_faults
    data = payload(4096, 13)
    buf = _frozen_view(data)
    t = Transaction()
    t.write(CID, A, 0, buf)
    store.queue_transaction(t)
    g_faults.inject("store.shard_corrupt", mode="once")
    try:
        got = store.read(CID, A)
    finally:
        g_faults.clear()
    assert got[0] == data[0] ^ 0x01 and got[1:] == data[1:]
    assert store.read(CID, A) == got             # the rot stays
    assert bytes(buf) == data                    # the sender's buffer not


def test_save_load_and_wal_replay_keep_an_adopted_body(tmp_path):
    data, view = payload(6000, 14), _frozen_view(payload(5000, 15))
    d = str(tmp_path / "osd")
    live = WALStore(d, wal_max_bytes=1 << 30)
    live.mount()
    t = Transaction()
    t.create_collection(CID)
    t.write(CID, A, 0, data)
    t.write(CID, B, 0, view)
    live.queue_transaction(t)
    assert live.colls[CID][A].data is data
    assert type(live.colls[CID][B].data) is bytes
    want = state(live)
    replayed = WALStore(d)
    replayed.mount()                             # no umount: a crash
    assert state(replayed) == want
    replayed.umount()
    live.save(str(tmp_path / "snap"))
    assert state(MemStore.load(str(tmp_path / "snap"))) == want
    live.umount()
