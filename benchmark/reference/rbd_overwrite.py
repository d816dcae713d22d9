"""Plain reference for overwrites of RBD image objects in an EC pool.

An RBD image on an erasure-coded data pool stores image object *n* as
the RADOS object ``rbd_data.<id>.<n:016x>``; an image write inside one
object is one offset write to it.  The pool stores each object as its
k + m shards (``gf256_rs.all_shards``).  Writes to one object are
applied in the order its primary committed them; the primary's queue
per object is first in, first out, so for one object that is the order
in which their replies reached the client.

This module imports nothing of the program.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, Tuple

import numpy as np

from benchmark.reference import gf256_rs

Write = Tuple[int, int, bytes]       # (object number, offset, bytes)


def bodies_after(prefill: Callable[[int], bytes], writes: Iterable[Write],
                 objnos: Iterable[int]) -> Dict[int, bytes]:
    """The body of each object in *objnos* after the acknowledged
    *writes*, in the order given, on top of ``prefill(objno)``."""
    want = set(objnos)
    out = {n: bytearray(prefill(n)) for n in sorted(want)}
    for objno, offset, data in writes:
        if objno in want:
            body = out[objno]
            if offset + len(data) > len(body):
                body.extend(bytes(offset + len(data) - len(body)))
            body[offset:offset + len(data)] = data
    return {n: bytes(b) for n, b in out.items()}


def expected(prefill: Callable[[int], bytes], writes: Iterable[Write],
             objnos: Iterable[int], k: int, m: int, stripe_unit: int
             ) -> Dict[int, Tuple[bytes, np.ndarray]]:
    """Per object: (its body, its (k + m, len / k) shards)."""
    return {n: (body, gf256_rs.all_shards(body, k, m, stripe_unit))
            for n, body in bodies_after(prefill, writes, objnos).items()}
