"""Candidate-table CRUSH mapper — the device CRUSH mapper.

crush_do_rule's retry loops are data-dependent: evaluated directly under
vmap, every lane pays for the worst lane.  This module uses a loop-free
formulation instead:

1. *Candidate tables* (the FLOPs): for every x and every retry index r the
   rule could consume, evaluate the full descent (root → failure domain →
   leaf) as pure batched tensor ops — rjenkins hashes plus one straw2 draw
   per level — with ALL retry lanes flattened into one (X*R) batch so the
   whole phase is a single fused walk.  Two draw implementations:

   - **exact-i32 quotient tables** (the common case): when a bucket's item
     weights are uniform (w identical, ≥ 0x10000) the reference draw
     ``div64_s64(crush_ln(u) - 2^48, w)`` is a pure function of u, so a
     per-w 64K i32 table of ``floor(G(u)/w) - 2^31`` reproduces the s64
     ordering *and* its truncation ties exactly (argmin, first index wins
     — mapper.c:322-367's strict-greater update).  Integer-exact: no
     risk analysis, no residuals.
   - **f32 + risk flags** (fallback): non-uniform weights, per-position
     weight sets (choose_args), or pathological w < 0x10000 use
     ``argmin(f32(G) * f32(1/w))`` with a conservative float-error guard;
     ambiguous lanes are flagged for exact replay.

2. *Resolution* (cheap): replay the exact firstn/indep retry semantics
   (mapper.c:443-636, :638-790) as a statically unrolled sequence of masked
   vector ops over the precomputed candidates — collision tests, weight
   rejection, slot fills.  Candidates depend only on the *topology* (bucket
   ids/weights), not on the per-epoch osd reweight vector, so they are
   cached on device across map_batch calls: an epoch change (osd out/down,
   reweight) re-runs only this phase.

3. *Residuals* (exactness escape hatch): flagged lanes — zero on
   integer-table maps, well under 1% otherwise — are recomputed by
   crush/wrapper.py's do_rule_batch (the bit-exact C++ batch evaluator,
   else the Python interpreter), so the combined result equals
   crush_do_rule on every input.

Scope: straw2 maps, layered hierarchies (every descent path from the take
root crosses the same bucket types at the same depths), jewel-style
tunables (stable chooseleaf for firstn; local tries 0), and rules of one
take and at most two choose steps.  Everything else raises UnsupportedRule
(a ValueError); OSDMapMapping and CrushTester then map through
crush/wrapper.py's do_rule_batch.
"""
from __future__ import annotations

import hashlib
import os
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..crush.constants import (
    CRUSH_ITEM_NONE, CRUSH_RULE_CHOOSELEAF_FIRSTN, CRUSH_RULE_CHOOSELEAF_INDEP,
    CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSE_INDEP, CRUSH_RULE_EMIT,
    CRUSH_RULE_SET_CHOOSELEAF_STABLE, CRUSH_RULE_SET_CHOOSELEAF_TRIES,
    CRUSH_RULE_SET_CHOOSELEAF_VARY_R, CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
    CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES, CRUSH_RULE_SET_CHOOSE_TRIES,
    CRUSH_RULE_TAKE,
)
from ..crush.ln import crush_ln_np
from ..crush.types import CrushMap
from ..crush.wrapper import do_rule_batch, native_mapper
from ..trace.devprof import g_devprof
from ..trace.span import g_tracer
from .crush_kernels import CompiledCrushMap, compile_map, hash32_2, hash32_3

NONE = CRUSH_ITEM_NONE


class UnsupportedRule(ValueError):
    pass


def _build_g_table() -> np.ndarray:
    """G[u] = 2^48 - crush_ln(u) for every 16-bit u (exact int64).

    The straw2 draw argmax over draw = -floor(G/w) (mapper.c:322-367)
    becomes a table gather plus a compare.
    """
    us = np.arange(0x10000, dtype=np.uint32)
    g = (np.uint64(1) << np.uint64(48)) - crush_ln_np(us)
    return g.astype(np.int64)


_G_EXACT = _build_g_table()
# numpy, not jnp: a device array here would start the backend on import;
# kernels lift it with jnp.asarray at trace time, like _G_EXACT
_G_F32 = _G_EXACT.astype(np.float64).astype(np.float32)

# conservative relative error of q = f32(G) * f32(1/w): G rounding (2^-24)
# + inv rounding (2^-24) + product rounding (2^-24) -> |q-Q|/Q <= ~3*2^-24
# per candidate; the two-candidate gap test sums both sides' bounds, so
# (q1+q2)*2^-22 covers (q1*err + q2*err) with >2x margin.
_REL_ERR = np.float32(2 ** -22)
# floor(q) ties break by index in the reference; candidates within +-TIE
# of each other could tie after truncation
_TIE_PAD = np.float32(2.0)

# minimum uniform weight eligible for the exact quotient-table path:
# floor(G_max / w) must fit the biased-i32 encoding (G_max = 2^48)
_QTABLE_MIN_W = 0x10000
_QBIAS = np.int64(1) << np.int64(31)


def _quotient_table(w: int) -> np.ndarray:
    """i32 table T[u] = floor(G(u)/w) - 2^31, order- and tie-exact.

    Valid for w >= 0x10000: quotients fit 32 unsigned bits except the
    unique u=0 entry (G=2^48, q=2^48/w may hit exactly 2^32), which is
    clamped by 1 — safe because the runner-up G is 2^48 - 2^44, far more
    than w below the clamp boundary for every w <= 2^31.
    """
    q = _G_EXACT // np.int64(w)
    q = np.minimum(q, (np.int64(1) << np.int64(32)) - 1)
    return (q - _QBIAS).astype(np.int32)


def _is_out_batch(dev_weight, items, x):
    w = dev_weight[jnp.maximum(items, 0)]
    h = hash32_2(x, items) & jnp.uint32(0xFFFF)
    return jnp.where(w >= 0x10000, False, jnp.where(w == 0, True, h >= w))


def _layer_path(m: CrushMap, root: int, target_type: int) -> int:
    """Verify the hierarchy under *root* is layered toward *target_type*;
    returns the number of choose levels needed to reach it."""
    return _layer_path_frontier(m, [root], target_type)


def _layer_path_frontier(m: CrushMap, roots: List[int],
                         target_type: int) -> int:
    depth = 0
    frontier = list(roots)
    while True:
        child_types = set()
        for b in frontier:
            bk = m.bucket(b)
            if bk is None or bk.size == 0:
                raise UnsupportedRule("empty/dangling bucket in path")
            for it in bk.items:
                if it >= 0:
                    child_types.add(0)
                else:
                    sb = m.bucket(it)
                    if sb is None:
                        raise UnsupportedRule("dangling bucket ref")
                    child_types.add(sb.type)
        if len(child_types) != 1:
            raise UnsupportedRule("mixed child types: not layered")
        ct = child_types.pop()
        depth += 1
        if ct == target_type:
            return depth
        if ct == 0:
            raise UnsupportedRule("reached devices before target type")
        next_frontier = []
        for b in frontier:
            next_frontier.extend(m.bucket(b).items)
        frontier = next_frontier
        if depth > 10:
            raise UnsupportedRule("hierarchy too deep")


def _advance(m: CrushMap, frontier: List[int]) -> List[int]:
    """One level down: the sub-buckets the frontier's draws can land in."""
    nxt: List[int] = []
    for b in frontier:
        nxt.extend(i for i in m.bucket(b).items if i < 0)
    return nxt


def _level_frontiers(m: CrushMap, root: int, n_levels: int) -> List[List[int]]:
    """Bucket-id frontier feeding each of the n_levels draws under root."""
    out = []
    frontier = [root]
    for _ in range(n_levels):
        out.append(list(frontier))
        frontier = _advance(m, frontier)
    return out


class FastRule:
    """Compiled single-choose rule: take root; choose[leaf] {firstn,indep}
    n type T; emit."""

    def __init__(self, C: CompiledCrushMap, ruleno: int, result_max: int,
                 tries_cap: int = 4, leaf_tries_cap: int = 4,
                 choose_args=None, exact64: Optional[bool] = None):
        m = C.map
        self.ruleno = ruleno
        self.choose_args = choose_args
        rule = m.rules[ruleno]
        if rule is None:
            raise UnsupportedRule(f"no rule {ruleno}")
        choose_tries = m.choose_total_tries + 1
        leaf_tries = 0
        vary_r = m.chooseleaf_vary_r
        stable = m.chooseleaf_stable
        take = None
        chooses: List = []
        for step in rule.steps:
            if step.op == CRUSH_RULE_SET_CHOOSE_TRIES:
                if step.arg1 > 0:
                    choose_tries = step.arg1
            elif step.op == CRUSH_RULE_SET_CHOOSELEAF_TRIES:
                if step.arg1 > 0:
                    leaf_tries = step.arg1
            elif step.op == CRUSH_RULE_SET_CHOOSELEAF_VARY_R:
                if step.arg1 >= 0:
                    vary_r = step.arg1
            elif step.op == CRUSH_RULE_SET_CHOOSELEAF_STABLE:
                if step.arg1 >= 0:
                    stable = step.arg1
            elif step.op in (CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES,
                             CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES):
                if step.arg1 > 0:
                    raise UnsupportedRule("local tries")
            elif step.op == CRUSH_RULE_TAKE:
                if take is not None:
                    raise UnsupportedRule("multiple takes")
                take = step.arg1
            elif step.op in (CRUSH_RULE_CHOOSE_FIRSTN,
                             CRUSH_RULE_CHOOSELEAF_FIRSTN,
                             CRUSH_RULE_CHOOSE_INDEP,
                             CRUSH_RULE_CHOOSELEAF_INDEP):
                chooses.append(step)
            elif step.op == CRUSH_RULE_EMIT:
                pass
            else:
                raise UnsupportedRule(f"op {step.op}")
        if take is None or not chooses or take >= 0:
            raise UnsupportedRule("rule shape")
        # chained choose steps (set-choose.t shapes): every step but the
        # last selects buckets — resolvable from topology alone, so the
        # whole chain lives in the cached candidate phase; only the last
        # step (devices / chooseleaf) depends on the weight vector
        self.mid_stages: List[dict] = []
        if len(chooses) > 2:
            # a third step's slot room depends on the second's dynamic
            # truncation — not modeled; host fallback
            raise UnsupportedRule("more than two choose steps")
        for step in chooses[:-1]:
            if step.op in (CRUSH_RULE_CHOOSELEAF_FIRSTN,
                           CRUSH_RULE_CHOOSELEAF_INDEP):
                raise UnsupportedRule("chooseleaf before the last step")
            if step.arg2 == 0:
                raise UnsupportedRule("device choose before the last step")
            n = step.arg1
            if n <= 0:
                n += result_max
            if n <= 0:
                raise UnsupportedRule("numrep")
            self.mid_stages.append({
                "firstn": step.op == CRUSH_RULE_CHOOSE_FIRSTN,
                # numrep keeps the step's r spacing; the step can only
                # FILL min(numrep, result_max) slots (out_size room)
                "numrep": n, "slots": min(n, result_max),
                "type": step.arg2,
            })
        choose = chooses[-1]
        self.firstn = choose.op in (CRUSH_RULE_CHOOSE_FIRSTN,
                                    CRUSH_RULE_CHOOSELEAF_FIRSTN)
        self.leafy = choose.op in (CRUSH_RULE_CHOOSELEAF_FIRSTN,
                                   CRUSH_RULE_CHOOSELEAF_INDEP)
        numrep = choose.arg1
        if numrep <= 0:
            numrep += result_max
        if numrep <= 0:
            raise UnsupportedRule("numrep")
        self.numrep = min(numrep, result_max) if not self.firstn else numrep
        self.target_type = choose.arg2
        if self.firstn:
            if self.leafy and not stable:
                # rep' for the leaf draw depends on the dynamic success
                # count without the stable tunable (mapper.c:545)
                raise UnsupportedRule("firstn chooseleaf needs stable=1")
        # firstn indexes weight sets by the DYNAMIC success count
        # (mapper.c:513 passes outpos as the choose_args position, and
        # outpos only advances on success) — so with per-position
        # weight sets the candidates must be materialized for every
        # position the walk could be at; resolution gathers the lane's
        # actual outpos.  indep passes the invocation's constant
        # starting outpos (0 from crush_do_rule) for main draws and
        # rep for leaf draws (mapper.c:723,777), which the pos vector
        # already threads — no extra axis needed.
        self.posP = min(C.npos, self.numrep) if self.firstn else 1
        if self.leafy:
            if leaf_tries:
                recurse = leaf_tries
            elif self.firstn:
                recurse = 1 if m.chooseleaf_descend_once else choose_tries
            else:
                recurse = 1
        else:
            recurse = 1
        self.take = take
        self.vary_r = vary_r
        self.tries = choose_tries
        self.recurse_tries = recurse
        self.n_rounds = min(tries_cap + 1, choose_tries)
        self.n_leaf = min(leaf_tries_cap + 1, recurse)
        # per-stage descent depths along the (validated layered) tree;
        # self.depth stays the TOTAL main depth so the per-level
        # quotient-table eligibility below is unchanged
        frontier = [take]
        base = 0
        self.parents = 1          # lanes per x feeding the last stage
        for st in self.mid_stages:
            # same dynamic-position treatment per stage (each choose
            # step invocation restarts outpos at 0, crush_do_rule
            # passes j=0 per parent)
            st["posP"] = min(C.npos, st["numrep"]) if st["firstn"] else 1
            d = _layer_path_frontier(m, frontier, st["type"])
            st["depth"] = d
            st["base_level"] = base
            st["tries"] = choose_tries
            st["n_rounds"] = min(tries_cap + 1, choose_tries)
            base += d
            for _ in range(d):
                frontier = _advance(m, frontier)
            self.parents *= st["slots"]
        self.base_level = base
        self.depth = base + _layer_path_frontier(m, frontier,
                                                 self.target_type)
        self.last_depth = self.depth - self.base_level
        self.leaf_depth = 0
        if self.leafy and self.target_type != 0:
            # depth below a failure-domain bucket, validated layered
            frontier = [take]
            for _ in range(self.depth):
                nxt = []
                for b in frontier:
                    nxt.extend(i for i in m.bucket(b).items)
                frontier = nxt
            if all(i >= 0 for i in frontier):
                self.leaf_depth = 0
            else:
                self.leaf_depth = _layer_path(m, frontier[0], 0)
                for f in frontier:
                    if _layer_path(m, f, 0) != self.leaf_depth:
                        raise UnsupportedRule("uneven leaf depth")
        self.C = C
        self.result_max = result_max
        self._build_quotient_tables()
        # non-quotient-table levels (non-uniform weights, choose_args,
        # small w) draw EXACTLY with the u64 table-gather + divide —
        # mapper.c's div64_s64 — instead of the f32 approximation,
        # killing the residual-replay tail.  One-time cost in the
        # cached candidate phase; the per-epoch resolve stays 32-bit.
        # Opt out (or auto-fallback when a backend can't lower u64
        # divide) -> f32 + risk flags.
        if exact64 is None:
            exact64 = os.environ.get("CEPH_TPU_CRUSH_EXACT64",
                                     "1") != "0"
        self._exact64 = exact64 and not all(self._lvl_int)
        self._cand_key: Optional[bytes] = None
        self._cand = None
        self._cand_jit = jax.jit(self._candidates)
        self._resolve_jit = jax.jit(self._resolve)
        self._packed_jit = jax.jit(self._resolve_packed)
        self._delta_jit = jax.jit(self._delta, static_argnums=2)
        # per-epoch delta state: device packed result of the previous epoch
        # plus the host-side exact mirror it corresponds to
        self._prev_packed = None
        self._host_out: Optional[np.ndarray] = None
        self._host_counts: Optional[np.ndarray] = None
        self.delta_cap = 8192

    # ---- exact integer draw tables ----------------------------------------
    def _build_quotient_tables(self) -> None:
        """Per-level eligibility + shared per-w i32 quotient tables.

        A level draws with exact integer tables iff every bucket its
        frontier can present has uniform item weights >= _QTABLE_MIN_W and
        no per-position weight set overrides them.
        """
        m = self.C.map
        n_main = self.depth
        n_leaf_lvls = self.leaf_depth if self.leaf_depth else (
            1 if (self.leafy and self.target_type != 0) else 0)
        frontiers = _level_frontiers(m, self.take, n_main)
        if n_leaf_lvls:
            # leaf levels start below every failure-domain bucket
            fd_buckets = _level_frontiers(m, self.take, n_main + 1)[n_main]
            # merge frontiers across all failure-domain roots per level
            merged: List[List[int]] = [[] for _ in range(n_leaf_lvls)]
            for fd in fd_buckets:
                for li, lvl in enumerate(
                        _level_frontiers(m, fd, n_leaf_lvls)):
                    merged[li].extend(lvl)
            frontiers = frontiers + merged
        self.total_levels = len(frontiers)

        w_to_idx = {}
        tables: List[np.ndarray] = []
        nb = self.C.nbuckets
        bucket_qidx = np.zeros(nb, dtype=np.int32)
        lvl_int: List[bool] = []
        # any choose_args disables the integer path: weight_set entries
        # override item_weights even with a single position (npos==1),
        # and the quotient tables are built from raw topology weights
        use_pos_weights = self.C.npos > 1 or self.choose_args is not None
        for lvl in frontiers:
            ok = not use_pos_weights
            for bid in lvl:
                b = m.bucket(bid)
                ws = list(b.item_weights)
                if not ws or min(ws) != max(ws) or ws[0] < _QTABLE_MIN_W:
                    ok = False
                    break
            if ok:
                for bid in lvl:
                    b = m.bucket(bid)
                    w = int(b.item_weights[0])
                    if w not in w_to_idx:
                        w_to_idx[w] = len(tables)
                        tables.append(_quotient_table(w))
                    bucket_qidx[-1 - bid] = w_to_idx[w]
            lvl_int.append(ok)
        self._lvl_int = lvl_int
        if tables:
            self._qtables = jnp.asarray(np.stack(tables))
            self._bucket_qidx = jnp.asarray(bucket_qidx)
        else:
            self._qtables = None
            self._bucket_qidx = None

    # ---- device draws ------------------------------------------------------
    def _straw2_int(self, bidx, x, r):
        """Exact integer straw2 via the quotient table: argmin with
        first-index tie-break == the reference's strict-greater update."""
        C = self.C
        ids = C.hash_ids[bidx]                   # (N, S)
        u = hash32_3(x[:, None], ids, r[:, None]) & jnp.uint32(0xFFFF)
        q = self._qtables[self._bucket_qidx[bidx][:, None],
                          u.astype(jnp.int32)]  # (N, S)
        valid = C.lane[None, :] < C.sizes[bidx][:, None]
        q = jnp.where(valid, q, jnp.int32(0x7FFFFFFF))
        win = jnp.argmin(q, axis=1)
        items = jnp.take_along_axis(C.items[bidx], win[:, None], axis=1)[:, 0]
        return items, jnp.zeros(x.shape, dtype=bool)

    def _straw2_exact64(self, bidx, x, r, pos):
        """Bit-exact straw2 for arbitrary (incl. per-position) weights:
        q = (2^48 - crush_ln(u)) // w in integer 64-bit, argmin with
        first-index tie-break == mapper.c:322-367's strict-greater
        update over div64_s64 draws.  Requires an enable_x64 trace
        scope (prepare_candidates provides it)."""
        C = self.C
        ids = C.hash_ids[bidx]                   # (N, S)
        w = C.weights[jnp.minimum(pos, C.npos - 1), bidx]  # (N, S) u32
        u = hash32_3(x[:, None], ids, r[:, None]) & jnp.uint32(0xFFFF)
        # constant converted at use site so the int64 table survives
        # only inside the x64 trace
        g = jnp.asarray(_G_EXACT)[u.astype(jnp.int32)]
        valid = (C.lane[None, :] < C.sizes[bidx][:, None]) & (w > 0)
        q = jnp.where(valid,
                      g // jnp.maximum(w, 1).astype(jnp.int64),
                      jnp.int64(1) << jnp.int64(62))
        win = jnp.argmin(q, axis=1)
        items = jnp.take_along_axis(C.items[bidx], win[:, None],
                                    axis=1)[:, 0]
        return items, jnp.zeros(x.shape, dtype=bool)

    def _straw2_f32(self, bidx, x, r, pos):
        """f32 draw with exactness guard: lanes whose top-two draws are
        within the float error bound (or the integer floor-tie window) get
        risky=True and are re-evaluated exactly by the caller."""
        C = self.C
        ids = C.hash_ids[bidx]                   # (N, S)
        invw = C.inv_weights[jnp.minimum(pos, C.npos - 1), bidx]  # (N, S)
        u = hash32_3(x[:, None], ids, r[:, None]) & jnp.uint32(0xFFFF)
        g = jnp.asarray(_G_F32)[u.astype(jnp.int32)]
        valid = (C.lane[None, :] < C.sizes[bidx][:, None]) & (invw > 0)
        q = jnp.where(valid, g * invw, jnp.float32(np.inf))
        win = jnp.argmin(q, axis=1)
        q1 = jnp.min(q, axis=1)
        q2 = jnp.min(jnp.where(jax.nn.one_hot(win, q.shape[1], dtype=bool),
                               jnp.float32(np.inf), q), axis=1)
        finite1 = jnp.isfinite(q1)
        finite2 = jnp.isfinite(q2)
        risky = finite1 & finite2 & \
            ((q2 - q1) <= (q1 + q2) * _REL_ERR + _TIE_PAD)
        items = jnp.take_along_axis(C.items[bidx], win[:, None], axis=1)[:, 0]
        return items, risky

    def _descend(self, x, start_bidx, r, pos, base_level: int, depth: int):
        """Fixed-depth descent for a flat batch of lanes: (N,) bucket idx
        -> (N,) item at the target layer, plus the accumulated
        exactness-risk flag.  r is constant through the walk
        (mapper.c:498-520); each level statically picks the integer or f32
        draw."""
        item = None
        bidx = start_bidx
        risky = jnp.zeros(x.shape, dtype=bool)
        for d in range(depth):
            if self._lvl_int[base_level + d]:
                item, rk = self._straw2_int(bidx, x, r)
            elif self._exact64:
                item, rk = self._straw2_exact64(bidx, x, r, pos)
            else:
                item, rk = self._straw2_f32(bidx, x, r, pos)
            risky = risky | rk
            bidx = jnp.maximum(-1 - item, 0)
        return item, risky

    # ---- intermediate (bucket-choosing) stages ----------------------------
    def _mid_candidates(self, st: dict, xl, roots, valid):
        """Candidates + collision-only resolution for one intermediate
        choose step over N parent lanes: returns sel (N, numrep) items
        (NONE-filled for invalid/failed), risky (N,)."""
        N = xl.shape[0]
        n = st["numrep"]
        slots = st["slots"]
        rounds = st["n_rounds"]
        P = st.get("posP", 1)
        if st["firstn"]:
            R = n + rounds - 1
        else:
            R = n * rounds
        r_col = jnp.arange(R, dtype=jnp.uint32)
        if P > 1:
            # per-position candidates: the draw at retry r depends on
            # which weight_set position (the dynamic outpos) it runs at
            xf = jnp.broadcast_to(xl[None, None, :], (R, P, N)).reshape(-1)
            rf = jnp.broadcast_to(r_col[:, None, None],
                                  (R, P, N)).reshape(-1)
            bf = jnp.broadcast_to(roots[None, None, :],
                                  (R, P, N)).reshape(-1)
            pf = jnp.broadcast_to(
                jnp.arange(P, dtype=jnp.int32)[None, :, None],
                (R, P, N)).reshape(-1)
            item, risky_f = self._descend(xf, bf, rf, pf,
                                          st["base_level"], st["depth"])
            cand = item.reshape(R, P, N)
            risky = jnp.any(risky_f.reshape(R, P, N), axis=(0, 1))
        else:
            xf = jnp.broadcast_to(xl[None, :], (R, N)).reshape(-1)
            rf = jnp.broadcast_to(r_col[:, None], (R, N)).reshape(-1)
            bf = jnp.broadcast_to(roots[None, :], (R, N)).reshape(-1)
            pos0 = jnp.zeros((R * N,), dtype=jnp.int32)
            item, risky_f = self._descend(xf, bf, rf, pos0,
                                          st["base_level"], st["depth"])
            cand = item.reshape(R, N)
            risky = jnp.any(risky_f.reshape(R, N), axis=0)
        lanes = jnp.arange(N)
        if st["firstn"]:
            # all numrep ATTEMPTS run (slot = attempt; the reference's
            # outpos append == stable compaction); the room truncation
            # to `slots` happens at fan-out below
            outs = jnp.full((N, n), NONE, dtype=jnp.int32)
            for j in range(n):
                if P > 1:
                    # outpos == successes so far == filled slots < j
                    pos = jnp.minimum(jnp.sum(outs != NONE, axis=1),
                                      P - 1)
                done = jnp.zeros((N,), dtype=bool)
                for ftotal in range(rounds):
                    c_r = cand[j + ftotal]
                    item = c_r[pos, lanes] if P > 1 else c_r
                    coll = jnp.any(outs == item[:, None], axis=1)
                    take = ~coll & ~done
                    outs = outs.at[:, j].set(
                        jnp.where(take, item, outs[:, j]))
                    done = done | ~coll
                if rounds < st["tries"]:
                    risky = risky | ~done
            # firstn feeds the next step COMPACTLY (wsize entries)
            order = jnp.argsort((outs == NONE).astype(jnp.int32),
                                axis=1, stable=True)
            outs = jnp.take_along_axis(outs, order, axis=1)[:, :slots]
        else:
            UNDEF = jnp.int32(0x7FFFFFFE)
            outs = jnp.full((N, slots), UNDEF, dtype=jnp.int32)
            for ftotal in range(rounds):
                for rep in range(slots):
                    item = cand[rep + n * ftotal]
                    unfilled = outs[:, rep] == UNDEF
                    coll = jnp.any(outs == item[:, None], axis=1)
                    take = unfilled & ~coll
                    outs = outs.at[:, rep].set(
                        jnp.where(take, item, outs[:, rep]))
            if rounds < st["tries"]:
                risky = risky | jnp.any(outs == UNDEF, axis=1)
            outs = jnp.where(outs == UNDEF, NONE, outs)
        outs = jnp.where(valid[:, None], outs, NONE)
        return outs, risky

    # ---- candidate phase (topology-only; cached across epochs) -------------
    def _candidates(self, xs):
        """One flattened descent over all (x, parent, retry) lanes.

        Returns cand (R, N) failure-domain items, leaf (R, L, N) leaf
        items (all-NONE when not leafy), risky (X,), valid (N,), and the
        per-lane x vector (N,), where N = X * parents (the intermediate
        stages' fan-out; 1 for single-choose rules)."""
        x = xs.astype(jnp.uint32)
        X = xs.shape[0]
        xl = x
        roots = jnp.full((X,), -1 - self.take, dtype=jnp.int32)
        valid = jnp.ones((X,), dtype=bool)
        risky_lanes = jnp.zeros((X,), dtype=bool)
        for st in self.mid_stages:
            sel, rk = self._mid_candidates(st, xl, roots, valid)
            risky_lanes = risky_lanes | rk
            n = st["slots"]
            # expand lanes: each parent slot becomes a lane
            risky_lanes = jnp.repeat(risky_lanes, n)
            xl = jnp.repeat(xl, n)
            valid = (jnp.repeat(valid, n)) & (sel.reshape(-1) != NONE)
            roots = jnp.maximum(-1 - sel.reshape(-1), 0)
        N = X * self.parents
        P = self.posP
        if self.firstn:
            R = self.numrep + self.n_rounds - 1
        else:
            R = self.numrep * self.n_rounds
        r_col = jnp.arange(R, dtype=jnp.uint32)
        if P > 1:
            # firstn + per-position weight sets: the draw at retry r
            # depends on the dynamic outpos (see __init__) — flatten a
            # position axis into the descent; resolution gathers the
            # lane's actual position.  cand (R, P, N), leaf (R, L, P, N).
            xf = jnp.broadcast_to(xl[None, None, :], (R, P, N)).reshape(-1)
            rf = jnp.broadcast_to(r_col[:, None, None],
                                  (R, P, N)).reshape(-1)
            root = jnp.broadcast_to(roots[None, None, :],
                                    (R, P, N)).reshape(-1)
            pf = jnp.broadcast_to(
                jnp.arange(P, dtype=jnp.int32)[None, :, None],
                (R, P, N)).reshape(-1)
        else:
            xf = jnp.broadcast_to(xl[None, :], (R, N)).reshape(-1)
            rf = jnp.broadcast_to(r_col[:, None], (R, N)).reshape(-1)
            root = jnp.broadcast_to(roots[None, :], (R, N)).reshape(-1)
            pf = jnp.zeros((R * N,), dtype=jnp.int32)
        item, risky_f = self._descend(xf, root, rf, pf,
                                      self.base_level, self.last_depth)
        rk_main = None
        if P > 1:
            # per-draw risk, NOT folded: resolution flags a lane only
            # when a draw it actually EXAMINES (at its dynamic
            # position) was risky — flagging any-position risk would
            # replay ~P times more lanes than necessary
            rk_main = risky_f.reshape(R, P, N)
            cand = item.reshape(R, P, N)
        else:
            risky_lanes = risky_lanes | jnp.any(risky_f.reshape(R, N),
                                                axis=0)
            cand = item.reshape(R, N)

        def finish(leaf, risky_lanes, rk_leaf=None):
            if P > 1:
                # lane-level mid-stage risk + the per-draw tensors
                return (cand, leaf,
                        (risky_lanes, rk_main, rk_leaf), valid, xl)
            risky = jnp.any(risky_lanes.reshape(-1, self.parents), axis=1)
            return cand, leaf, risky, valid, xl

        L = self.n_leaf
        lshape = (R, L, P, N) if P > 1 else (R, L, N)
        zero_lrisk = (jnp.zeros(lshape, dtype=bool) if P > 1 else None)
        if not self.leafy:
            return finish(jnp.full(lshape, NONE, dtype=jnp.int32),
                          risky_lanes, zero_lrisk)
        if self.leaf_depth == 0 and self.target_type == 0:
            # chooseleaf over devices: every leaf attempt is the item itself
            if P > 1:
                # the "leaf draw" IS the main draw: its risk too
                return finish(
                    jnp.broadcast_to(cand[:, None, :, :], lshape),
                    risky_lanes,
                    jnp.broadcast_to(rk_main[:, None, :, :], lshape))
            return finish(jnp.broadcast_to(cand[:, None, :], lshape),
                          risky_lanes)
        # leaf attempts: one flattened batch over lshape
        M = R * P * N if P > 1 else R * N
        if self.firstn:
            sub_r = (rf >> jnp.uint32(self.vary_r - 1)) if self.vary_r \
                else jnp.zeros_like(rf)
            # leaf draw position = the parent step's outpos
            # (mapper.c:561-562: the recursion inherits outpos, and the
            # leaf bucket_choose passes it) — the materialized p axis
            lpos = pf
        else:
            rep = rf % jnp.uint32(self.numrep)
            sub_r = rep + rf  # + numrep*ft2 added per attempt below
            lpos = rep.astype(jnp.int32)
        bidx = jnp.maximum(-1 - item, 0)
        depth = self.leaf_depth if self.leaf_depth else 1
        xleaf = jnp.broadcast_to(xf[None, :], (L, M)).reshape(-1)
        bl = jnp.broadcast_to(bidx[None, :], (L, M)).reshape(-1)
        pl = jnp.broadcast_to(lpos[None, :], (L, M)).reshape(-1)
        ft2 = jnp.arange(L, dtype=jnp.uint32)
        if self.firstn:
            rl = (sub_r[None, :] + ft2[:, None]).reshape(-1)
        else:
            rl = (sub_r[None, :] +
                  jnp.uint32(self.numrep) * ft2[:, None]).reshape(-1)
        lv, lrisky = self._descend(xleaf, bl, rl, pl, self.depth, depth)
        if P > 1:
            leaf = jnp.transpose(lv.reshape(L, R, P, N), (1, 0, 2, 3))
            rk_leaf = jnp.transpose(lrisky.reshape(L, R, P, N),
                                    (1, 0, 2, 3))
            return finish(leaf, risky_lanes, rk_leaf)
        risky_lanes = risky_lanes | jnp.any(lrisky.reshape(L, R, N),
                                            axis=(0, 1))
        leaf = jnp.transpose(lv.reshape(L, R, N), (1, 0, 2))
        return finish(leaf, risky_lanes)

    # ---- resolution phase (per weight vector; cheap) -----------------------
    def _resolve(self, cand, leaf, risky, valid, xl, x, dev_weight):
        """Per-lane resolution: sel (N, numrep) plus residual (X,) —
        a lane's unresolved state rolls up to its x, which replays on
        the host whole."""
        rk_main = rk_leaf = None
        if self.posP > 1:
            risky_lanes, rk_main, rk_leaf = risky
        else:
            risky_lanes = jnp.repeat(risky, self.parents)
        if self.firstn:
            sel, lres = self._resolve_firstn(cand, leaf, risky_lanes,
                                             xl, dev_weight,
                                             rk_main, rk_leaf)
        else:
            # per-parent slot room (crush_do_rule: out_size =
            # min(numrep, result_max - osize), osize advancing only
            # over present parents): slots past the room are never
            # filled by the reference, so retries must not see them
            # as collision targets
            vp = valid.reshape(-1, self.parents).astype(jnp.int32)
            vbefore = jnp.cumsum(vp, axis=1) - vp
            room = jnp.clip(self.result_max - vbefore * self.numrep,
                            0, self.numrep).reshape(-1)
            sel, lres = self._resolve_indep(cand, leaf, risky_lanes,
                                            xl, dev_weight, room)
        sel = jnp.where(valid[:, None], sel, NONE)
        lres = lres & valid
        if self.posP > 1:
            # mid-stage risk must survive even on INVALID lanes (their
            # NONE may itself be the wrong answer), so OR the unmasked
            # lane-level risk back in before the per-x rollup
            residual = jnp.any(
                (lres | risky_lanes).reshape(-1, self.parents), axis=1)
        else:
            residual = risky | jnp.any(
                lres.reshape(-1, self.parents), axis=1)
        return sel, residual

    def _resolve_firstn(self, cand, leaf, risky, x, dev_weight,
                        rk_main=None, rk_leaf=None):
        """firstn: slot j retries r = j + ftotal (mapper.c:493-495); leafy
        failures consume an outer retry (descend_once semantics).

        With per-position weight sets (posP > 1) the candidate arrays
        carry a position axis and each lane gathers at its dynamic
        outpos — the success count so far (mapper.c:513/620-621:
        position == outpos, advancing only on success)."""
        P = self.posP
        if P > 1:
            R = cand.shape[0]
            X = cand.shape[2]
        else:
            R, X = cand.shape
        lanes = jnp.arange(X)
        numrep = self.numrep
        x = x.astype(jnp.uint32)
        residual = risky
        outs = jnp.full((X, numrep), NONE, dtype=jnp.int32)
        leaves = jnp.full((X, numrep), NONE, dtype=jnp.int32)
        for j in range(numrep):
            if P > 1:
                pos = jnp.minimum(jnp.sum(outs != NONE, axis=1), P - 1)
            done = jnp.zeros((X,), dtype=bool)
            for ftotal in range(self.n_rounds):
                r = j + ftotal
                item = cand[r][pos, lanes] if P > 1 else cand[r]
                rdraw = rk_main[r][pos, lanes] if P > 1 else None
                coll = jnp.any(outs == item[:, None], axis=1)
                if self.leafy:
                    # first acceptable leaf attempt, if any
                    lok = jnp.zeros((X,), dtype=bool)
                    lsel = jnp.full((X,), NONE, dtype=jnp.int32)
                    lres = jnp.zeros((X,), dtype=bool)
                    for ft2 in range(self.n_leaf):
                        lf = leaf[r, ft2][pos, lanes] if P > 1 \
                            else leaf[r, ft2]
                        if P > 1:
                            rdraw = rdraw | rk_leaf[r, ft2][pos, lanes]
                        lcoll = jnp.any(leaves == lf[:, None], axis=1)
                        lrej = _is_out_batch(dev_weight, lf, x)
                        good = ~lok & ~lcoll & ~lrej
                        lsel = jnp.where(good, lf, lsel)
                        lok = lok | good
                    # couldn't prove failure within the cap?
                    if self.n_leaf < self.recurse_tries:
                        lres = ~lok
                    ok = ~coll & lok
                    maybe_more = lres
                else:
                    rej = (_is_out_batch(dev_weight, item, x)
                           if self.target_type == 0
                           else jnp.zeros((X,), dtype=bool))
                    ok = ~coll & ~rej
                    lsel = item
                    maybe_more = jnp.zeros((X,), dtype=bool)
                if rdraw is not None:
                    # a risky draw EXAMINED at this lane's position
                    # taints everything from here on
                    residual = residual | (rdraw & ~done)
                take = ok & ~done & ~residual
                outs = outs.at[:, j].set(jnp.where(take, item, outs[:, j]))
                leaves = leaves.at[:, j].set(
                    jnp.where(take, lsel, leaves[:, j]))
                residual = residual | (maybe_more & ~done)
                done = done | ok
            # not done within the materialized rounds, but the reference
            # would keep trying -> must defer to the host
            if self.n_rounds < self.tries:
                residual = residual | ~done
        sel = leaves if self.leafy else outs
        return sel, residual

    def _resolve_indep(self, cand, leaf, risky, x, dev_weight,
                       room=None):
        """indep rounds: r = rep + numrep*ftotal; UNDEF slots retry,
        dead ends become NONE (mapper.c:638-790).  *room* (per-lane)
        caps how many slots this parent may fill when the result is
        narrower than parents*numrep."""
        R, X = cand.shape
        numrep = self.numrep
        x = x.astype(jnp.uint32)
        UNDEF = jnp.int32(0x7FFFFFFE)  # CRUSH_ITEM_UNDEF; never a real item
        outs = jnp.full((X, numrep), UNDEF, dtype=jnp.int32)
        leaves = jnp.full((X, numrep), UNDEF, dtype=jnp.int32)
        residual = risky
        for ftotal in range(self.n_rounds):
            for rep in range(numrep):
                r = rep + numrep * ftotal
                item = cand[r]
                unfilled = outs[:, rep] == UNDEF
                if room is not None:
                    unfilled = unfilled & (jnp.int32(rep) < room)
                coll = jnp.any(outs == item[:, None], axis=1)
                if self.leafy:
                    lok = jnp.zeros((X,), dtype=bool)
                    lsel = jnp.full((X,), NONE, dtype=jnp.int32)
                    for ft2 in range(self.n_leaf):
                        lf = leaf[r, ft2]
                        lrej = _is_out_batch(dev_weight, lf, x)
                        good = ~lok & ~lrej
                        lsel = jnp.where(good, lf, lsel)
                        lok = lok | good
                    if self.n_leaf < self.recurse_tries:
                        residual = residual | (unfilled & ~coll & ~lok)
                    ok = ~coll & lok
                else:
                    rej = (_is_out_batch(dev_weight, item, x)
                           if self.target_type == 0
                           else jnp.zeros((X,), dtype=bool))
                    ok = ~coll & ~rej
                    lsel = item
                take = unfilled & ok
                outs = outs.at[:, rep].set(
                    jnp.where(take, item, outs[:, rep]))
                leaves = leaves.at[:, rep].set(
                    jnp.where(take, lsel, leaves[:, rep]))
        undef = outs == UNDEF
        if room is not None:
            undef = undef & (jnp.arange(numrep)[None, :] < room[:, None])
        unfinished = jnp.any(undef, axis=1)
        if self.n_rounds < self.tries:
            residual = residual | unfinished
        outs = jnp.where(outs == UNDEF, NONE, outs)
        leaves = jnp.where(leaves == UNDEF, NONE, leaves)
        sel = leaves if self.leafy else outs
        return sel, residual

    # ---- device-side compaction + delta fetch ------------------------------
    def _resolve_packed(self, cand, leaf, risky, valid, xl, x, dev_weight):
        """Resolve, compact and pack ON DEVICE: one (X, result_max+1) i32.

        Columns [0, result_max) are the compacted result slots (EMIT
        semantics: firstn drops NONE gaps in slot order, indep keeps
        holes within a parent's block but drops absent parents' blocks);
        the last column is ``count | residual << 16``.  A single small
        array means the per-epoch host fetch is one transfer — the
        device->host round trip, not the resolve, is the remap wall floor.
        """
        sel, residual = self._resolve(cand, leaf, risky, valid, xl, x,
                                      dev_weight)
        P = self.parents
        X = sel.shape[0] // P
        R = self.result_max
        nr = self.numrep
        if self.firstn:
            # per-parent picks concatenate compactly in the reference
            # (outpos appends): a stable global compaction of the
            # (P*numrep)-wide row is the same sequence
            wide = sel.reshape(X, P * nr)
            order = jnp.argsort((wide == NONE).astype(jnp.int32), axis=1,
                                stable=True)
            compact = jnp.take_along_axis(wide, order, axis=1)
            if compact.shape[1] < R:
                compact = jnp.pad(compact,
                                  ((0, 0), (0, R - compact.shape[1])),
                                  constant_values=NONE)
            out = compact[:, :R]
            counts = jnp.minimum(jnp.sum(wide != NONE, axis=1), R)
        else:
            # indep keeps holes, but a parent that was never chosen
            # contributes NOTHING (crush_do_rule skips absent buckets):
            # drop absent parents' blocks, keep block order stable
            sel3 = sel.reshape(X, P, nr)
            vp = valid.reshape(X, P)
            order = jnp.argsort((~vp).astype(jnp.int32), axis=1,
                                stable=True)
            sel3 = jnp.take_along_axis(sel3, order[:, :, None], axis=1)
            wide = sel3.reshape(X, P * nr)
            if wide.shape[1] < R:
                wide = jnp.pad(wide, ((0, 0), (0, R - wide.shape[1])),
                               constant_values=NONE)
            out = wide[:, :R]
            counts = jnp.minimum(
                jnp.sum(vp, axis=1, dtype=jnp.int32) * nr, R)
        tail = counts.astype(jnp.int32) | (residual.astype(jnp.int32) << 16)
        return jnp.concatenate([out, tail[:, None]], axis=1)

    def _delta(self, packed, prev, cap: int):
        """Changed-row extraction vs the previous epoch's packed result.

        A row is "changed" if any packed column differs OR either epoch
        flagged it residual (a residual row's device value is a guess; its
        exact value can move even when the guess doesn't, so it must be
        replayed whenever the weight vector changes).  Returns one flat
        i32 buffer [n_changed, n_residual, idx[cap], rows[cap * (R+1)]]
        so the whole per-epoch result is a single device->host transfer.
        """
        R = self.result_max
        res_new = (packed[:, R] >> 16) != 0
        res_prev = (prev[:, R] >> 16) != 0
        changed = jnp.any(packed != prev, axis=1) | res_new | res_prev
        n = jnp.sum(changed, dtype=jnp.int32)
        idx = jnp.nonzero(changed, size=cap, fill_value=0)[0]
        rows = packed[idx]
        return jnp.concatenate([
            jnp.stack([n, jnp.sum(res_new, dtype=jnp.int32)]),
            idx.astype(jnp.int32),
            rows.reshape(-1),
        ])

    def _replay_exact(self, idxs: np.ndarray, xs: np.ndarray,
                      weight, out: np.ndarray, counts: np.ndarray) -> None:
        """Overwrite the given lanes with the bit-exact mapping
        (crush/wrapper.py's do_rule_batch on this rule's loaded C++
        mapper; the interpreter where it cannot answer)."""
        if len(idxs) == 0:
            return
        rout, rlens, _engine = do_rule_batch(
            self.C.map, self.ruleno, xs[idxs], self.result_max, weight,
            self.choose_args, mapper=self._native_mapper())
        out[idxs] = rout.astype(np.int32)
        counts[idxs] = rlens

    # ---- public -----------------------------------------------------------
    def prepare_candidates(self, xs: np.ndarray) -> None:
        """Compute (or reuse) the device candidate tables for this xs
        batch.  Topology-only: reused across weight vectors/epochs."""
        xs = np.asarray(xs, dtype=np.uint32)
        key = hashlib.sha1(xs.tobytes()).digest()
        if self._cand_key != key:
            g_devprof.install_compile_listener()
            g_devprof.account_h2d("crush.candidates", xs.nbytes)
            with g_devprof.stage("crush.candidates"):
                xd = jnp.asarray(xs)
                self._cand = jax.block_until_ready(
                    self._run_candidates(xd))
            self._cand_x = xd
            self._cand_key = key
            self._prev_packed = None
            self._host_out = None
            self._host_counts = None

    def _run_candidates(self, xd):
        """The candidate trace; exact64 draws need an x64 scope.  A
        backend that cannot lower the u64 divide drops to the f32 +
        risk-flag draw (correctness preserved via residual replay)."""
        if not self._exact64:
            return self._cand_jit(xd)
        try:
            with jax.enable_x64(True):
                return self._cand_jit(xd)
        except Exception as e:
            # only an UNIMPLEMENTED-class lowering failure means the
            # backend can't do u64 divide; transient transport errors
            # must propagate or they'd silently downgrade exactness
            msg = str(e)
            if not any(s in msg for s in ("UNIMPLEMENTED",
                                          "Unimplemented",
                                          "not supported",
                                          "Unsupported")):
                raise
            from ..common.dout import dlog
            dlog("crush", 0,
                 "exact64 draw unavailable on this backend "
                 f"({type(e).__name__}); falling back to f32 + "
                 "residual replay")
            self._exact64_fallback = msg[:200]
            self._exact64 = False
            self._cand_jit = jax.jit(self._candidates)  # fresh trace
            return self._cand_jit(xd)

    def resolve_device(self, weight) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Device-resident resolution against the cached candidates:
        (sel, residual) device arrays.  The per-epoch remap call —
        requires prepare_candidates/map_batch to have run for the batch.
        Not exact on its own: residual lanes still need host replay."""
        if self._cand is None:
            raise RuntimeError("no candidate tables; call "
                               "prepare_candidates(xs) first")
        if isinstance(weight, jnp.ndarray):
            wd = weight
        else:
            w32 = np.asarray(weight, dtype=np.uint32)
            g_devprof.account_h2d("crush.resolve", w32.nbytes)
            wd = jnp.asarray(w32)
        with g_devprof.stage("crush.resolve"):
            return self._resolve_jit(*self._cand, self._cand_x, wd)

    def map_batch(self, xs: np.ndarray, weight: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """Map every x; exact.  Returns (results [X, numrep], counts [X]).

        Candidates are cached on device keyed by the xs batch: calling
        again with the same xs (the whole-map remap on every epoch) only
        re-runs the cheap resolution phase with the new weight vector.
        """
        xs = np.asarray(xs, dtype=np.uint32)
        w32 = np.asarray(weight, dtype=np.uint32)
        self.prepare_candidates(xs)
        R = self.result_max
        X = xs.shape[0]
        g_devprof.account_h2d("crush.map_batch", w32.nbytes)
        wd = jnp.asarray(w32)
        from ..common.kernel_trace import g_kernel_timer
        with g_devprof.stage("crush.map_batch"):
            packed = g_kernel_timer.timed(
                "crush_resolve", self._packed_jit, *self._cand,
                self._cand_x, wd)
        cap = min(self.delta_cap, X)
        if self._prev_packed is not None and self._host_out is not None:
            # per-epoch fast path: fetch only the rows that changed since
            # the previous weight vector (plus residual guesses, which
            # must be re-verified) and patch the host mirror in place.
            fetch = g_tracer.span(prof="crush.fetch", full=0)
            with g_devprof.stage("crush.map_batch"), fetch:
                flat = np.asarray(self._delta_jit(packed,
                                                  self._prev_packed,
                                                  cap))
                n_changed = int(flat[0])
                # rows patched into the host mirror (none on overflow)
                fetch.set(rows=n_changed if n_changed <= cap else 0)
            g_devprof.account_d2h("crush.map_batch", flat.nbytes)
            self._residual_frac = int(flat[1]) / X
            if n_changed <= cap:
                out, counts = self._host_out, self._host_counts
                if n_changed:
                    idxs = flat[2:2 + n_changed].copy()
                    rows = flat[2 + cap:].reshape(cap, R + 1)[:n_changed]
                    out[idxs] = rows[:, :R]
                    counts[idxs] = rows[:, R] & 0xFFFF
                    replay = idxs[(rows[:, R] >> 16) != 0]
                    self._replay_exact(replay, xs, w32, out, counts)
                self._prev_packed = packed
                return out.copy(), counts.copy()
            # overflow: fall through to a full fetch (and grow the cap so
            # sustained churny workloads stop overflowing)
            self.delta_cap = min(2 * self.delta_cap, max(X, 1))
        with g_tracer.span(prof="crush.fetch", rows=X, full=1):
            full = np.asarray(packed)
        g_devprof.account_d2h("crush.map_batch", full.nbytes)
        out = full[:, :R].copy()
        counts = (full[:, R] & 0xFFFF).astype(np.int32)
        residual = (full[:, R] >> 16) != 0
        # exactness escape hatch: recompute flagged lanes exactly.  The
        # C++ batch evaluator replays them ~100x faster than the Python
        # interpreter (OSDMapMapping.h:17's ParallelPGMapper role),
        # choose_args included (serialized into the blob); Python only
        # when the native lib is absent.
        self._residual_frac = float(residual.mean())
        self._replay_exact(np.nonzero(residual)[0], xs, w32, out, counts)
        self._prev_packed = packed
        self._host_out = out
        self._host_counts = counts
        return out.copy(), counts.copy()

    def _native_mapper(self):
        """The C++ mapper loaded with this rule's map, serialized once
        per FastRule rather than once per epoch."""
        if getattr(self, "_nm", None) is None:
            self._nm = native_mapper(self.C.map, self.choose_args)
        return self._nm

    @property
    def residual_fraction(self) -> float:
        return getattr(self, "_residual_frac", 0.0)

    @property
    def integer_exact_levels(self) -> List[bool]:
        """Per-level flag: True = draws use the exact i32 quotient table."""
        return list(self._lvl_int)


def compile_fast_rule(m: CrushMap, ruleno: int, result_max: int,
                      choose_args=None, **kw) -> FastRule:
    C = compile_map(m, choose_args)
    return FastRule(C, ruleno, result_max, choose_args=choose_args, **kw)
