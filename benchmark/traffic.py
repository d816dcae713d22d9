"""The benchmark's own load generator: seeded ops, closed loop, wall clock.

The sound parts of the program's ``SyntheticClient``
(``ceph_tpu/load/traffic.py``) copied here so that no change to the
program can move the yardstick: payloads and keys drawn from the seed,
and a client that submits ``MOSDOp`` s without blocking on the reply
and takes each reply as the fabric delivers it.  What differs from the
original:

- ops are issued on the wall clock, not in ``run_traffic``'s rounds: a
  closed loop issues the next op from inside the completion of the one
  before, so an op is timed from the moment its slot freed;
- a read is checked against the body the benchmark wrote, byte for
  byte, when its reply arrives.

Each run draws every size, key and payload from ``--seed`` alone.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np

from ceph_tpu.client.rados import RadosClient
from ceph_tpu.msg.messages import (CEPH_OSD_OP_READ, CEPH_OSD_OP_WRITEFULL,
                                   MOSDOp, MOSDOpReply, new_trace_id)
from ceph_tpu.trace.oplat import stamp_client

EAGAIN = -11
MAX_ATTEMPTS = 64


class PayloadPool:
    """Distinct object bodies from one seeded buffer: body *i* is the
    slice at a seed-drawn offset, so no two objects share their bytes
    and nothing is generated inside the window.  A body is a view of the
    buffer; the client copies a write's body into its message when it
    sends it, so the benchmark holds no copy of what it wrote or read."""

    def __init__(self, seed: int, object_bytes: int):
        rng = np.random.default_rng([seed, 0x70A1])
        self.object_bytes = object_bytes
        self.span = object_bytes * 2
        self.buf = rng.bytes(self.span + object_bytes)
        self.base = int(rng.integers(0, object_bytes))

    def offset(self, i: int) -> int:
        # 4099 is prime to the span's powers of two: offsets never repeat
        # within span / 4099 bodies (> 2000 at 4 MiB)
        return (self.base + i * 4099) % self.span

    def body(self, i: int) -> memoryview:
        o = self.offset(i)
        return memoryview(self.buf)[o:o + self.object_bytes]


@dataclass
class Op:
    index: int
    kind: str                  # "write" | "read"
    oid: str
    body: memoryview           # what is written / what a read must return
    t_issue: float = 0.0
    t_done: float = 0.0
    attempts: int = 0
    ok: bool = False
    error: str = ""


class ClosedLoopClient(RadosClient):
    """A RadosClient that keeps ``in_flight`` ops outstanding.

    ``next_op(i)`` draws op *i*; ``issue_more()`` says whether a freed
    slot takes a new op (the window is open).  Every finished op goes to
    ``done``, in completion order."""

    def __init__(self, network, mon, name: str, pool: str,
                 next_op: Callable[[int], Op],
                 issue_more: Callable[[], bool],
                 span: Callable):
        super().__init__(network, mon, name)
        self.pool_id = self.lookup_pool(pool)
        self.next_op = next_op
        self.issue_more = issue_more
        self.span = span
        self.pending: Dict[int, Op] = {}
        self.issued = 0
        self.done: List[Op] = []

    def start(self, in_flight: int) -> None:
        for _ in range(in_flight):
            self._issue_new()

    def _issue_new(self) -> None:
        op = self.next_op(self.issued)
        self.issued += 1
        op.t_issue = time.perf_counter()
        self._send(op)

    def _send(self, op: Op) -> None:
        with self.span("submit"):
            pgid, primary = self._calc_target(self.pool_id, op.oid)
            op.attempts += 1
            self._tid += 1
            tid = self._tid
            self.pending[tid] = op
            if primary < 0:
                # no primary (peering): refresh the map; resent by resend_stalled
                self.mon.send_full_map(self.name)
                return
            msg = MOSDOp(
                tid=tid, pool=pgid[0], oid=op.oid, pgid=pgid,
                op=CEPH_OSD_OP_WRITEFULL if op.kind == "write"
                else CEPH_OSD_OP_READ,
                data=bytes(op.body) if op.kind == "write" else b"",
                epoch=self.osdmap.epoch, trace_id=new_trace_id())
            stamp_client(msg, self.name)
            self.messenger.send_message(msg, f"osd.{primary}")

    def resend_stalled(self) -> int:
        """Send again every op the fabric went quiet on (a lost
        message, a PG without a primary).  Returns how many."""
        stalled = list(self.pending.items())
        self.pending.clear()
        for _tid, op in stalled:
            if op.attempts >= MAX_ATTEMPTS:
                self._finish(op, f"{op.kind} {op.oid}: no reply after "
                             f"{op.attempts} attempts")
            else:
                self._send(op)
        return len(stalled)

    def fail_pending(self, why: str) -> None:
        stalled = list(self.pending.values())
        self.pending.clear()
        for op in stalled:
            self._finish(op, f"{op.kind} {op.oid}: {why}")

    def ms_fast_dispatch(self, msg) -> None:
        if isinstance(msg, MOSDOpReply) and msg.tid in self.pending:
            self._complete(self.pending.pop(msg.tid), msg)
            return
        super().ms_fast_dispatch(msg)

    def _complete(self, op: Op, reply: MOSDOpReply) -> None:
        if reply.result == EAGAIN and op.attempts < MAX_ATTEMPTS:
            self.mon.send_full_map(self.name)
            self._send(op)
            return
        err = ""
        # a read compares against a bytes copy of its body: bytes against
        # a memoryview compares element by element, ~15x slower
        if reply.result != 0:
            err = f"{op.kind} {op.oid}: result {reply.result}"
        elif op.kind == "read" and reply.data != op.body.tobytes():
            err = f"read {op.oid}: bytes differ from the body written"
        self._finish(op, err)

    def _finish(self, op: Op, err: str) -> None:
        op.t_done = time.perf_counter()
        op.ok = not err
        op.error = err
        self.done.append(op)
        if self.issue_more():
            self._issue_new()


def latency_ms(ops: List[Op]) -> np.ndarray:
    return np.asarray([(o.t_done - o.t_issue) * 1e3 for o in ops])


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile, numpy's linear rule; None when empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))
