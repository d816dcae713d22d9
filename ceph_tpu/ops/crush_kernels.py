"""Vmapped device CRUSH mapper — all PGs in one jitted call.

This is the TPU twin of the host interpreter (ceph_tpu/crush/mapper.py,
semantics of reference src/crush/mapper.c:883-1087).  A (CrushMap, rule) pair
is *compiled* on the host into dense tensors — per-bucket item/weight tables
padded to the max fanout, the crush_ln LUTs, the device in/out weight vector —
and the rule's step program is unrolled at trace time into a fixed tensor
program evaluated for every input x (PG) in one vmapped call:

- straw2 draw: rjenkins hash32_3 in uint32 lanes, crush_ln via two 256-entry
  LUT gathers, the fixed-point s64 division, first-wins argmax
  (mapper.c:322-367) — bit-identical winners.
- firstn/indep retry semantics: the exact r' = rep + parent_r + ftotal
  (firstn) / rep + parent_r + numrep*ftotal (indep) sequences as bounded
  `lax.while_loop`s, collision/out-rejection/NONE conventions preserved
  (mapper.c:443-636, :638-790).
- chooseleaf recursion (vary_r, stable tunables) as a nested bounded loop.

Scope (checked by `compile_map`, everything else falls back to the host
mapper): straw2 buckets only (the modern default since hammer) and
bobtail+ tunables (choose_local_tries == choose_local_fallback_tries == 0).
Rules may chain TAKE / CHOOSE / CHOOSELEAF / SET_* / EMIT steps arbitrarily.

64-bit note: the straw2 divide is exact u64 math, which requires jax x64
mode *during tracing*.  Rather than flipping the global ``jax_enable_x64``
flag at import (a surprising process-wide side effect), the public entry
point (``DeviceCrushMapper.map_batch``) scopes it with the
``jax.enable_x64`` context manager; module-level constants stay numpy so
nothing 64-bit is materialized outside that scope.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crush.constants import (
    CRUSH_BUCKET_STRAW2, CRUSH_ITEM_NONE, CRUSH_ITEM_UNDEF,
    CRUSH_RULE_CHOOSELEAF_FIRSTN, CRUSH_RULE_CHOOSELEAF_INDEP,
    CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSE_INDEP, CRUSH_RULE_EMIT,
    CRUSH_RULE_SET_CHOOSELEAF_STABLE, CRUSH_RULE_SET_CHOOSELEAF_TRIES,
    CRUSH_RULE_SET_CHOOSELEAF_VARY_R, CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
    CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES, CRUSH_RULE_SET_CHOOSE_TRIES,
    CRUSH_RULE_TAKE,
)
from ..crush.ln import LL_NP, RH_LH_NP
from ..crush.types import CrushMap

MAX_DESCENT = 12  # > CRUSH_MAX_DEPTH (crush.h:26)
_U64_MAX = np.uint64(0xFFFFFFFFFFFFFFFF)
_LN_BIAS = np.uint64(0x1000000000000)  # 2^48 (mapper.c:342)

_SEED = np.uint32(1315423911)
_PAD1 = np.uint32(231232)
_PAD2 = np.uint32(1232)


# ---- rjenkins in uint32 lanes (crush/hash.c) ------------------------------

def _mix(a, b, c):
    a = a - b; a = a - c; a = a ^ (c >> 13)
    b = b - c; b = b - a; b = b ^ (a << 8)
    c = c - a; c = c - b; c = c ^ (b >> 13)
    a = a - b; a = a - c; a = a ^ (c >> 12)
    b = b - c; b = b - a; b = b ^ (a << 16)
    c = c - a; c = c - b; c = c ^ (b >> 5)
    a = a - b; a = a - c; a = a ^ (c >> 3)
    b = b - c; b = b - a; b = b ^ (a << 10)
    c = c - a; c = c - b; c = c ^ (b >> 15)
    return a, b, c


def hash32_2(a, b):
    a = a.astype(jnp.uint32); b = b.astype(jnp.uint32)
    h = _SEED ^ a ^ b
    x = jnp.broadcast_to(_PAD1, a.shape)
    y = jnp.broadcast_to(_PAD2, a.shape)
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash32_3(a, b, c):
    a = a.astype(jnp.uint32); b = b.astype(jnp.uint32)
    c = c.astype(jnp.uint32)
    a, b, c = jnp.broadcast_arrays(a, b, c)
    h = _SEED ^ a ^ b ^ c
    x = jnp.broadcast_to(_PAD1, h.shape)
    y = jnp.broadcast_to(_PAD2, h.shape)
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


# ---- crush_ln LUT evaluation (mapper.c:243-290) ---------------------------

def _ln_tables():
    """u64 log LUTs as jnp constants, created at use site so the uint64
    conversion happens inside the caller's enable_x64 scope.  Deliberately
    uncached: under a jit trace the result is a tracer that must not leak
    into module state; XLA folds the constants per compiled program."""
    return jnp.asarray(RH_LH_NP), jnp.asarray(LL_NP)


def crush_ln_dev(u):
    """2^44*log2(u+1) fixed point; u: uint32 in [0, 0xffff]."""
    _RH_LH, _LL = _ln_tables()
    x = (u + jnp.uint32(1)).astype(jnp.uint32)
    blen = jnp.uint32(32) - lax.clz(x & jnp.uint32(0x1FFFF))
    need = (x & jnp.uint32(0x18000)) == 0
    bits = jnp.where(need, jnp.uint32(16) - blen, jnp.uint32(0))
    x = x << bits
    iexpon = jnp.where(need, jnp.uint32(15) - bits, jnp.uint32(15))
    index1 = ((x >> 8) << 1).astype(jnp.int32)
    rh = _RH_LH[index1 - 256]
    lh = _RH_LH[index1 + 1 - 256]
    xl64 = (x.astype(jnp.uint64) * rh) >> jnp.uint64(48)
    index2 = (xl64 & jnp.uint64(0xFF)).astype(jnp.int32)
    ll = _LL[index2]
    return ((iexpon.astype(jnp.uint64) << jnp.uint64(44))
            + ((lh + ll) >> jnp.uint64(4)))


# ---- compiled map ---------------------------------------------------------

class CompiledCrushMap:
    """Dense-tensor form of a straw2 CrushMap (+choose_args) for the device.

    Buckets are indexed by ``-1 - id``.  ``weights`` carries the per-position
    straw2 weight sets (crush.h:273 crush_choose_arg); position 0 is the
    plain item_weights when no choose_args are attached.
    """

    def __init__(self, m: CrushMap,
                 choose_args: Optional[Sequence] = None,
                 allow_legacy: bool = False):
        """``allow_legacy`` additionally admits straw(v1) buckets and
        pre-bobtail local-tries tunables — consumed only by the legacy
        fast path (ops/crush_legacy.py), which models those semantics;
        the plain loop kernel does not."""
        nb = len(m.buckets)
        S = max((b.size for b in m.buckets if b is not None), default=1)
        S = max(S, 1)
        items = np.full((nb, S), CRUSH_ITEM_NONE, dtype=np.int32)
        hash_ids = np.zeros((nb, S), dtype=np.int32)
        sizes = np.zeros(nb, dtype=np.int32)
        types = np.zeros(nb, dtype=np.int32)
        npos = 1
        if choose_args is not None:
            for arg in choose_args:
                if arg is not None and arg.weight_set:
                    npos = max(npos, len(arg.weight_set))
        weights = np.zeros((npos, nb, S), dtype=np.uint32)
        algs = np.zeros(nb, dtype=np.int32)
        straws = np.zeros((nb, S), dtype=np.uint32)
        for bi, b in enumerate(m.buckets):
            if b is None:
                continue
            if b.size and b.alg != CRUSH_BUCKET_STRAW2:
                from ..crush.constants import CRUSH_BUCKET_STRAW
                if not (allow_legacy and b.alg == CRUSH_BUCKET_STRAW):
                    raise ValueError(
                        "device mapper supports straw2 buckets only")
                straws[bi, :b.size] = np.asarray(b.straws,
                                                 dtype=np.uint32)
            algs[bi] = b.alg
            sizes[bi] = b.size
            types[bi] = b.type
            items[bi, :b.size] = b.items
            hash_ids[bi, :b.size] = b.items
            for it in b.items:
                if it >= 0 and it >= m.max_devices:
                    raise ValueError("bucket item beyond max_devices")
                if it < 0 and m.bucket(it) is None:
                    raise ValueError("dangling bucket reference")
            w = np.asarray(b.item_weights, dtype=np.uint32)
            weights[:, bi, :b.size] = w[None, :]
            arg = None
            if choose_args is not None and bi < len(choose_args):
                arg = choose_args[bi]
            if arg is not None:
                if arg.weight_set:
                    for p in range(npos):
                        ws = arg.weight_set[min(p, len(arg.weight_set) - 1)]
                        weights[p, bi, :b.size] = np.asarray(
                            ws.weights, dtype=np.uint32)
                if arg.ids:
                    hash_ids[bi, :b.size] = arg.ids
        if not allow_legacy and (m.choose_local_tries
                                 or m.choose_local_fallback_tries):
            raise ValueError("device mapper requires bobtail+ tunables "
                             "(choose_local_*_tries == 0)")
        self.map = m
        self.nbuckets = nb
        self.max_size = S
        self.npos = npos
        self.algs = np.asarray(algs)
        # straw(v1) scalers only matter to the legacy path; don't pay a
        # device transfer of zeros on every production compile
        self.straws = jnp.asarray(straws) if allow_legacy else None
        self.items = jnp.asarray(items)
        self.hash_ids = jnp.asarray(hash_ids)
        self.sizes = jnp.asarray(sizes)
        self.types = jnp.asarray(types)
        self.weights = jnp.asarray(weights)
        # f32 reciprocals for the fast-path draw (crush_fast.py); 0 marks
        # zero-weight lanes
        with np.errstate(divide="ignore"):
            inv = np.where(weights > 0, 1.0 / weights.astype(np.float64),
                           0.0)
        self.inv_weights = jnp.asarray(inv.astype(np.float32))
        self.lane = jnp.arange(S, dtype=jnp.int32)


def _straw2_choose(C: CompiledCrushMap, bidx, x, r, position):
    """First-wins straw2 argmax over one bucket row (mapper.c:322-367)."""
    ids = C.hash_ids[bidx]
    out_items = C.items[bidx]
    pos = jnp.minimum(position, C.npos - 1)
    ws = C.weights[pos, bidx]
    u = hash32_3(x, ids, r) & jnp.uint32(0xFFFF)
    # draw = -((2^48 - ln) / w); argmax(draw) == first-wins argmin(q)
    q_num = _LN_BIAS - crush_ln_dev(u)
    valid = (C.lane < C.sizes[bidx]) & (ws > 0)
    q = jnp.where(valid, q_num // jnp.maximum(ws, 1).astype(jnp.uint64),
                  _U64_MAX)
    return out_items[jnp.argmin(q)]


_OK, _DEAD, _EMPTY = 0, 1, 2


def _descend(C: CompiledCrushMap, item, x, r, position, target_type):
    """Walk down from *item* until an item of *target_type* is reached.

    Mirrors the itemtype-mismatch descent in both choosers (mapper.c:498-520,
    :691-713): r is constant during the walk.  Returns (item, status) with
    status _DEAD for a wrong-type dead end and _EMPTY for an empty bucket.

    Do-while semantics: the reference always draws one item from the
    starting bucket before any type test (crush_bucket_choose precedes the
    itemtype check, mapper.c:487-498), so a choose step whose target type
    equals the take bucket's own type still descends one level rather than
    returning the take bucket itself.
    """
    def itype(it):
        return jnp.where(it >= 0, 0, C.types[jnp.maximum(-1 - it, 0)])

    def cond(st):
        it, status, depth = st
        return ((status == _OK) & (itype(it) != target_type)
                & (depth < MAX_DESCENT))

    def body(st):
        it, status, depth = st
        dead = it >= 0  # device of the wrong type: no sub-bucket
        bidx = jnp.maximum(-1 - it, 0)
        empty = C.sizes[bidx] == 0
        nxt = _straw2_choose(C, bidx, x, r, position)
        it2 = jnp.where(dead | empty, it, nxt)
        status2 = jnp.where(dead, _DEAD, jnp.where(empty, _EMPTY, status))
        return it2, status2, depth + 1

    first = body((item, jnp.int32(_OK), jnp.int32(0)))
    it, status, depth = lax.while_loop(cond, body, first)
    status = jnp.where((status == _OK) & (itype(it) != target_type),
                       _DEAD, status)
    return it, status


def _is_out(dev_weight, item, x):
    """Weight-based rejection of a device (mapper.c:407-441)."""
    w = dev_weight[jnp.maximum(item, 0)]
    h = hash32_2(x, item) & jnp.uint32(0xFFFF)
    return jnp.where(w >= 0x10000, False,
                     jnp.where(w == 0, True, h >= w))


# ---- choosers (scalar-x; vmapped by the executor) -------------------------

def _choose_firstn(C, dev_weight, take_item, take_ok, x, numrep, target_type,
                   tries, recurse_tries, recurse_to_leaf, vary_r, stable):
    """crush_choose_firstn with bobtail+ tunables (mapper.c:443-636).

    With choose_local_tries == choose_local_fallback_tries == 0 every
    reject/collision restarts the descent from the take bucket with
    ftotal+1 — exactly the modern tunable profiles.  Returns per-slot
    (items, leaves); failed slots hold CRUSH_ITEM_NONE.
    """
    NONE = jnp.int32(CRUSH_ITEM_NONE)
    outs = jnp.full(numrep, NONE)
    out2s = jnp.full(numrep, NONE)
    nsucc = jnp.int32(0)

    for slot in range(numrep):
        rep = jnp.int32(slot)

        def leaf_choose(item, r, nsucc_now, out2s_now):
            """The recursive numrep=1 call (mapper.c:541-558)."""
            sub_r = (r >> (vary_r - 1)) if vary_r else jnp.int32(0)
            rep_in = jnp.int32(0) if stable else nsucc_now

            def lcond(st):
                ft2, leaf, done = st
                return (~done) & (ft2 < recurse_tries)

            def lbody(st):
                ft2, leaf, done = st
                r2 = rep_in + sub_r + ft2
                cand, status = _descend(C, item, x, r2, nsucc_now, 0)
                coll = jnp.any(out2s_now == cand)
                rej = _is_out(dev_weight, cand, x)
                good = (status == _OK) & ~coll & ~rej
                return (ft2 + 1, jnp.where(good, cand, leaf), good)

            _, leaf, ok = lax.while_loop(
                lcond, lbody, (jnp.int32(0), NONE, jnp.bool_(False)))
            return leaf, ok

        def scond(st):
            ftotal, item, leaf, success, aborted = st
            return (~success) & (~aborted) & (ftotal < tries)

        def sbody(st):
            ftotal, item, leaf, success, aborted = st
            r = rep + ftotal
            cand, status = _descend(C, take_item, x, r, nsucc, target_type)
            coll = jnp.any(outs == cand)
            base_rej = (_is_out(dev_weight, cand, x)
                        if target_type == 0 else jnp.bool_(False))
            if recurse_to_leaf:
                lf, lok = leaf_choose(cand, r, nsucc, out2s)
                lf = jnp.where(cand >= 0, cand, lf)
                lok = jnp.where(cand >= 0, True, lok)
                reject = ~lok | base_rej
            else:
                lf = cand
                reject = base_rej
            ok_now = (status == _OK) & ~coll & ~reject
            dead = status == _DEAD
            return (ftotal + 1,
                    jnp.where(ok_now, cand, item),
                    jnp.where(ok_now, lf, leaf),
                    ok_now,
                    dead)

        init = (jnp.int32(0), NONE, NONE, jnp.bool_(False), ~take_ok)
        _, item, leaf, success, _ = lax.while_loop(scond, sbody, init)
        outs = outs.at[slot].set(jnp.where(success, item, NONE))
        out2s = out2s.at[slot].set(jnp.where(success, leaf, NONE))
        nsucc = nsucc + success.astype(jnp.int32)
    return outs, out2s


def _choose_indep(C, dev_weight, take_item, take_ok, x, out_size, numrep,
                  target_type, tries, recurse_tries, recurse_to_leaf,
                  parent_r, position):
    """crush_choose_indep rounds (mapper.c:638-790): UNDEF slots are retried
    with r' = rep + parent_r + numrep*ftotal until tries are exhausted, dead
    ends become CRUSH_ITEM_NONE immediately."""
    NONE = jnp.int32(CRUSH_ITEM_NONE)
    UNDEF = jnp.int32(CRUSH_ITEM_UNDEF)
    outs = jnp.where(take_ok, jnp.full(out_size, UNDEF),
                     jnp.full(out_size, NONE))
    out2s = jnp.full(out_size, UNDEF)

    def leaf_indep(item, r_parent, rep):
        """Inner left=1 recursion (mapper.c:725-741); UNDEF → NONE on exit."""
        def lcond(st):
            ft2, leaf = st
            return (leaf == UNDEF) & (ft2 < recurse_tries)

        def lbody(st):
            ft2, leaf = st
            r2 = rep + r_parent + numrep * ft2
            cand, status = _descend(C, item, x, r2, rep, 0)
            rej = _is_out(dev_weight, cand, x)
            good = (status == _OK) & ~rej
            dead = status == _DEAD
            return (ft2 + 1,
                    jnp.where(good, cand, jnp.where(dead, NONE, leaf)))

        _, leaf = lax.while_loop(lcond, lbody, (jnp.int32(0), UNDEF))
        return jnp.where(leaf == UNDEF, NONE, leaf)

    def rcond(st):
        outs, out2s, ftotal = st
        return jnp.any(outs == UNDEF) & (ftotal < tries)

    def rbody(st):
        outs, out2s, ftotal = st
        for slot in range(out_size):
            rep = jnp.int32(slot)
            unfilled = outs[slot] == UNDEF
            r = rep + parent_r + numrep * ftotal
            cand, status = _descend(C, take_item, x, r, position, target_type)
            coll = jnp.any(outs == cand)
            if recurse_to_leaf:
                sub = leaf_indep(cand, r, rep)
                # a device chosen directly becomes its own leaf
                # (mapper.c:736-739)
                leaf = jnp.where(cand >= 0, cand, sub)
                leaf_fail = jnp.where(cand >= 0, False, sub == NONE)
            else:
                leaf = cand
                leaf_fail = jnp.bool_(False)
            rej = (_is_out(dev_weight, cand, x)
                   if target_type == 0 else jnp.bool_(False))
            dead = status == _DEAD
            good = (status == _OK) & ~coll & ~leaf_fail & ~rej
            new_item = jnp.where(dead, NONE, jnp.where(good, cand, UNDEF))
            new_leaf = jnp.where(dead, NONE, jnp.where(good, leaf, UNDEF))
            outs = outs.at[slot].set(jnp.where(unfilled, new_item, outs[slot]))
            out2s = out2s.at[slot].set(
                jnp.where(unfilled, new_leaf, out2s[slot]))
        return outs, out2s, ftotal + 1

    outs, out2s, _ = lax.while_loop(
        rcond, rbody, (outs, out2s, jnp.int32(0)))
    outs = jnp.where(outs == UNDEF, NONE, outs)
    out2s = jnp.where(out2s == UNDEF, NONE, out2s)
    return outs, out2s


# ---- rule executor --------------------------------------------------------

class DeviceCrushMapper:
    """Evaluates one rule for a batch of x values on the device.

    The rule's steps are unrolled at trace time (crush rules are short
    programs, mapper.c:899-1087); slot lists thread (value, present) pairs
    between steps the way do_rule's w/o vectors do, and EMIT compacts
    present slots in order.
    """

    def __init__(self, compiled: CompiledCrushMap, ruleno: int,
                 result_max: int,
                 choose_args: Optional[Sequence] = None):
        m = compiled.map
        rule = m.rules[ruleno]
        if rule is None:
            raise ValueError(f"no rule {ruleno}")
        self.C = compiled
        self.rule = rule
        self.result_max = result_max
        self._fn = jax.jit(jax.vmap(self._one_x, in_axes=(0, None)))

    def _one_x(self, x, dev_weight):
        C, m, result_max = self.C, self.C.map, self.result_max
        x = x.astype(jnp.uint32)
        NONE = jnp.int32(CRUSH_ITEM_NONE)

        choose_tries = m.choose_total_tries + 1  # mapper.c:905 off-by-one
        choose_leaf_tries = 0
        vary_r = m.chooseleaf_vary_r
        stable = m.chooseleaf_stable

        slots: List[Tuple] = []   # (value tracer, present tracer)
        emitted: List[Tuple] = []

        for step in self.rule.steps:
            op = step.op
            if op == CRUSH_RULE_TAKE:
                ok = (0 <= step.arg1 < m.max_devices
                      or m.bucket(step.arg1) is not None)
                if ok:
                    slots = [(jnp.int32(step.arg1), jnp.bool_(True))]
            elif op == CRUSH_RULE_SET_CHOOSE_TRIES:
                if step.arg1 > 0:
                    choose_tries = step.arg1
            elif op == CRUSH_RULE_SET_CHOOSELEAF_TRIES:
                if step.arg1 > 0:
                    choose_leaf_tries = step.arg1
            elif op in (CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES,
                        CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES):
                if step.arg1 > 0:
                    raise ValueError("local tries unsupported on device")
            elif op == CRUSH_RULE_SET_CHOOSELEAF_VARY_R:
                if step.arg1 >= 0:
                    vary_r = step.arg1
            elif op == CRUSH_RULE_SET_CHOOSELEAF_STABLE:
                if step.arg1 >= 0:
                    stable = step.arg1
            elif op in (CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSELEAF_FIRSTN,
                        CRUSH_RULE_CHOOSE_INDEP, CRUSH_RULE_CHOOSELEAF_INDEP):
                firstn = op in (CRUSH_RULE_CHOOSE_FIRSTN,
                                CRUSH_RULE_CHOOSELEAF_FIRSTN)
                leafy = op in (CRUSH_RULE_CHOOSELEAF_FIRSTN,
                               CRUSH_RULE_CHOOSELEAF_INDEP)
                numrep = step.arg1
                if numrep <= 0:
                    numrep += result_max
                    if numrep <= 0:
                        continue
                new_slots: List[Tuple] = []
                for (val, present) in slots:
                    # devices / NONE inputs contribute nothing (do_rule
                    # skips w[i] without a bucket)
                    ok = present & (val < 0)
                    if firstn:
                        if choose_leaf_tries:
                            recurse = choose_leaf_tries
                        elif m.chooseleaf_descend_once:
                            recurse = 1
                        else:
                            recurse = choose_tries
                        outs, out2s = _choose_firstn(
                            C, dev_weight, val, ok, x, numrep, step.arg2,
                            choose_tries, recurse, leafy, vary_r, stable)
                        sel = out2s if leafy else outs
                        for j in range(numrep):
                            v = sel[j]
                            new_slots.append((v, ok & (v != NONE)))
                    else:
                        recurse = choose_leaf_tries if choose_leaf_tries else 1
                        out_size = min(numrep, result_max)
                        outs, out2s = _choose_indep(
                            C, dev_weight, val, ok, x, out_size, numrep,
                            step.arg2, choose_tries, recurse, leafy,
                            jnp.int32(0), jnp.int32(0))
                        sel = out2s if leafy else outs
                        for j in range(out_size):
                            # indep emits NONE holes, but they are still
                            # skipped by any chained choose step
                            new_slots.append((sel[j], ok))
                slots = new_slots
            elif op == CRUSH_RULE_EMIT:
                emitted.extend(slots)
                slots = []

        if not emitted:
            return (jnp.full(result_max, NONE), jnp.int32(0))
        vals = jnp.stack([v for v, _ in emitted])
        present = jnp.stack([p for _, p in emitted])
        pos = jnp.cumsum(present.astype(jnp.int32)) - 1
        result = jnp.full(result_max, NONE)
        write = present & (pos < result_max)
        result = result.at[jnp.where(write, pos, result_max)].set(
            jnp.where(write, vals, NONE), mode="drop")
        count = jnp.minimum(jnp.sum(present.astype(jnp.int32)), result_max)
        return result, count

    def map_batch(self, xs: np.ndarray, weight: np.ndarray):
        """Map all xs; returns (results [X, result_max] int32, counts [X])."""
        with jax.enable_x64(True):
            xs = jnp.asarray(np.asarray(xs, dtype=np.uint32))
            w = jnp.asarray(np.asarray(weight, dtype=np.uint32))
            res, cnt = self._fn(xs, w)
        return res, cnt


def compile_map(m: CrushMap, choose_args=None) -> CompiledCrushMap:
    """Host-side compilation; raises ValueError if unsupported on device."""
    return CompiledCrushMap(m, choose_args)
