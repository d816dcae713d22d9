"""Legacy-tunables CRUSH fast path (straw v1, local tries, perm fallback).

The candidate-table mapper (crush_fast.py) targets jewel-style tunables,
where every retry is a fresh full descent and r is constant through the
walk.  Pre-bobtail maps — the reference's own golden fixtures
(src/test/cli/crushtool/set-choose.t) among them — run with
``choose_local_tries``/``choose_local_fallback_tries`` > 0: a collision
or rejection first retries AT the failing bucket (flocal++, same
descent), falls back to an exhaustive permutation draw once flocal
crosses ``size>>1``/fallback thresholds (mapper.c bucket_perm_choose),
and only then re-descends.  That breaks the one-retry-one-descent
flattening, so this module uses a different TPU formulation:

1. *Dense draw tables* (topology-only): for every lane (x), every bucket
   b and every retry value r < RMAX, precompute both the bucket's normal
   draw ``T[x, b, r]`` (straw v1 u48 multiply or straw2 s64 quotient —
   exact int64 math under jax x64) and its permutation draw
   ``P[x, b, r]``.  Buckets are few and RMAX is bounded by
   tries + the local window, so the tables are tiny.

2. *Unrolled retry state machine* (per epoch): crush_choose_firstn's
   retry_descent/retry_bucket/perm-fallback loop (mapper.c:443-636)
   becomes a masked vector program over (ftotal, flocal, descent-start)
   integer state; each step gathers its draw from T/P by (bucket, r).
   The chooseleaf recursion (descend_once / chooseleaf_tries) runs as a
   nested, fully-materialized sub-machine — its try count is bounded by
   recurse_tries + the local window, so leaf failure is always proven
   on device.

3. *Residual escape hatch*: lanes that exhaust the materialized outer
   tries (RT < choose_total_tries) are replayed with the host
   interpreter, exactly like crush_fast's residuals.

Scope: firstn steps (indep never had local retries — jewel semantics
apply and crush_fast handles them), single take, chained chooses,
chooseleaf depth 1, vary_r == 0.  This is a correctness/coverage path:
production jewel+ maps keep using crush_fast's cached-candidate design.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..crush.constants import (
    CRUSH_BUCKET_STRAW, CRUSH_BUCKET_STRAW2, CRUSH_ITEM_NONE,
    CRUSH_RULE_CHOOSELEAF_FIRSTN, CRUSH_RULE_CHOOSE_FIRSTN,
    CRUSH_RULE_EMIT, CRUSH_RULE_SET_CHOOSELEAF_STABLE,
    CRUSH_RULE_SET_CHOOSELEAF_TRIES, CRUSH_RULE_SET_CHOOSELEAF_VARY_R,
    CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
    CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES, CRUSH_RULE_SET_CHOOSE_TRIES,
    CRUSH_RULE_TAKE,
)
from ..crush.mapper import crush_do_rule
from ..crush.types import CrushMap
from .crush_fast import UnsupportedRule, _G_EXACT, _layer_path_frontier
from .crush_kernels import CompiledCrushMap, hash32_2, hash32_3

NONE = CRUSH_ITEM_NONE
S64_MIN = -(1 << 63)


class LegacyFastRule:
    """Device evaluation of a firstn rule under legacy tunables."""

    def __init__(self, m: CrushMap, ruleno: int, result_max: int,
                 tries_cap: int = 64):
        self.C = CompiledCrushMap(m, allow_legacy=True)
        self.m = m
        self.ruleno = ruleno
        self.result_max = result_max
        rule = m.rules[ruleno]
        if rule is None:
            raise UnsupportedRule(f"no rule {ruleno}")
        self.tries = m.choose_total_tries + 1
        self.local_retries = m.choose_local_tries
        self.local_fallback = m.choose_local_fallback_tries
        leaf_tries = 0
        vary_r = m.chooseleaf_vary_r
        stable = m.chooseleaf_stable
        take = None
        chooses: List = []
        for step in rule.steps:
            if step.op == CRUSH_RULE_SET_CHOOSE_TRIES:
                if step.arg1 > 0:
                    self.tries = step.arg1
            elif step.op == CRUSH_RULE_SET_CHOOSELEAF_TRIES:
                if step.arg1 > 0:
                    leaf_tries = step.arg1
            elif step.op == CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES:
                if step.arg1 >= 0:
                    self.local_retries = step.arg1
            elif step.op == CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES:
                if step.arg1 >= 0:
                    self.local_fallback = step.arg1
            elif step.op == CRUSH_RULE_SET_CHOOSELEAF_VARY_R:
                if step.arg1 >= 0:
                    vary_r = step.arg1
            elif step.op == CRUSH_RULE_SET_CHOOSELEAF_STABLE:
                if step.arg1 >= 0:
                    stable = step.arg1
            elif step.op == CRUSH_RULE_TAKE:
                if take is not None:
                    raise UnsupportedRule("multiple takes")
                take = step.arg1
            elif step.op in (CRUSH_RULE_CHOOSE_FIRSTN,
                             CRUSH_RULE_CHOOSELEAF_FIRSTN):
                chooses.append(step)
            elif step.op == CRUSH_RULE_EMIT:
                pass
            else:
                raise UnsupportedRule(f"op {step.op}")
        if take is None or take >= 0 or not chooses:
            raise UnsupportedRule("rule shape")
        if vary_r:
            raise UnsupportedRule("legacy machine with vary_r")
        self.stable = stable
        self.take = take
        # per-stage: depth along the layered tree, numrep, leafiness
        self.stages: List[dict] = []
        frontier = [take]
        for si, step in enumerate(chooses):
            leafy = step.op == CRUSH_RULE_CHOOSELEAF_FIRSTN
            if leafy and si != len(chooses) - 1:
                raise UnsupportedRule("chooseleaf before the last step")
            n = step.arg1
            if n <= 0:
                n += result_max
            if n <= 0:
                raise UnsupportedRule("numrep")
            d = _layer_path_frontier(m, frontier, step.arg2)
            st = {"numrep": n, "type": step.arg2, "depth": d,
                  "leafy": leafy}
            if leafy:
                if step.arg2 == 0:
                    st["leaf_depth"] = 0
                else:
                    nxt = list(frontier)
                    for _ in range(d):
                        nxt = [i for b in nxt
                               for i in m.bucket(b).items if i < 0]
                    if not nxt:
                        st["leaf_depth"] = 0
                    else:
                        ld = _layer_path_frontier(m, nxt, 0)
                        if ld != 1:
                            raise UnsupportedRule("legacy leaf depth > 1")
                        st["leaf_depth"] = 1
                if leaf_tries:
                    st["recurse"] = leaf_tries
                elif m.chooseleaf_descend_once:
                    st["recurse"] = 1
                else:
                    st["recurse"] = self.tries
            self.stages.append(st)
            for _ in range(d):
                frontier = [i for b in frontier
                            for i in m.bucket(b).items if i < 0]
        # the local-retry window is an exact bound, not a cap: flocal
        # may reach size + fallback before a descent is forced
        smax = int(self.C.max_size)
        self.kl = smax + self.local_fallback + 1
        self.rt = min(tries_cap, self.tries)
        max_slot = max(st["numrep"] for st in self.stages)
        max_leaf = max((st.get("recurse", 0) + self.kl
                        for st in self.stages if st.get("leafy")),
                       default=0)
        self.rmax = max_slot + self.rt + self.kl + max_leaf + 2
        self._tables_x: Optional[bytes] = None
        self._resolve_jit = jax.jit(self._resolve_all)

    # ---- draw tables -------------------------------------------------------
    def _draw_tables(self, xs):
        """T[x, b, r], P[x, b, r]: normal and permutation draws for
        every bucket and retry value, exact int64."""
        C = self.C
        nb, S = C.nbuckets, C.max_size
        R = self.rmax
        X = xs.shape[0]
        x = xs.astype(jnp.uint32)
        bidx = jnp.arange(nb, dtype=jnp.int32)
        r = jnp.arange(R, dtype=jnp.uint32)
        # normal draw: (X, nb, R)
        ids = C.hash_ids                        # (nb, S)
        u = hash32_3(x[:, None, None, None], ids[None, :, None, :],
                     r[None, None, :, None]) & jnp.uint32(0xFFFF)
        valid = (jnp.arange(S)[None, :] < C.sizes[:, None])  # (nb, S)
        is2 = jnp.asarray(self.C.algs == CRUSH_BUCKET_STRAW2)  # (nb,)
        # straw v1: draw = u16 * straws (fits 48 bits)
        d1 = u.astype(jnp.int64) * C.straws[None, :, None, :].astype(
            jnp.int64)
        # straw2: draw = -((2^48 - crush_ln(u)) // w)  (s64 trunc-to-0)
        g = jnp.asarray(_G_EXACT)[u.astype(jnp.int32)]
        w = C.weights[0][None, :, None, :].astype(jnp.int64)
        d2 = jnp.where(w > 0, -(g // jnp.maximum(w, 1)),
                       jnp.int64(S64_MIN))
        draw = jnp.where(is2[None, :, None, None], d2, d1)
        draw = jnp.where(valid[None, :, None, :], draw,
                         jnp.int64(S64_MIN))
        win = jnp.argmax(draw, axis=3)          # first max wins
        T = jnp.take_along_axis(
            jnp.broadcast_to(C.items[None, :, None, :], draw.shape),
            win[..., None], axis=3)[..., 0]
        # permutation draw (bucket_perm_choose, mapper.c:76-131): a
        # Fisher-Yates prefix keyed on (bucket id, x); the prefix length
        # pr = r % size differs per retry column, so swap step p applies
        # only to columns with pr >= p
        sizes = C.sizes                          # (nb,)
        bucket_id = (-1 - bidx).astype(jnp.uint32)
        pr = jnp.where(sizes[None, :, None] > 0,
                       r[None, None, :].astype(jnp.int32)
                       % jnp.maximum(sizes[None, :, None], 1), 0)
        pr = jnp.broadcast_to(pr, (X, nb, R))
        perm = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                (X, nb, R, S))
        for p in range(S):
            sz = jnp.broadcast_to(sizes[None, :, None], (X, nb, R))
            act = (jnp.int32(p) < sz - 1) & (jnp.int32(p) <= pr) & (sz > 0)
            h = hash32_3(x[:, None], bucket_id[None, :], jnp.uint32(p))
            i = (h % jnp.maximum(sizes[None, :] - p, 1)
                 .astype(jnp.uint32)).astype(jnp.int32)       # (X, nb)
            tgt = jnp.broadcast_to(
                jnp.minimum(jnp.int32(p) + i, S - 1)[:, :, None],
                (X, nb, R))
            do = act & jnp.broadcast_to((i > 0)[:, :, None], (X, nb, R))
            vp = perm[..., p]
            vt = jnp.take_along_axis(perm, tgt[..., None],
                                     axis=3)[..., 0]
            lane = jnp.arange(S, dtype=jnp.int32)
            perm = jnp.where((lane == tgt[..., None]) & do[..., None],
                             vp[..., None], perm)
            perm = perm.at[..., p].set(jnp.where(do, vt, vp))
        slot = jnp.take_along_axis(perm, pr[..., None], axis=3)[..., 0]
        P = jnp.take_along_axis(
            jnp.broadcast_to(C.items[None, :, None, :], (X, nb, R, S)),
            jnp.clip(slot, 0, S - 1)[..., None], axis=3)[..., 0]
        return T, P

    # ---- the retry state machine ------------------------------------------
    def _is_out(self, dev_weight, items, x):
        w = dev_weight[jnp.maximum(items, 0)]
        h = hash32_2(x, items.astype(jnp.uint32)) & jnp.uint32(0xFFFF)
        return jnp.where(w >= 0x10000, False,
                         jnp.where(w == 0, True, h >= w))

    def _gather(self, table, b, r):
        """table (N, nb, R) gathered at per-lane (bucket idx, retry)."""
        N = b.shape[0]
        lane = jnp.arange(N)
        return table[lane, b, jnp.clip(r, 0, self.rmax - 1)]

    def _upper(self, T, roots, slot_r, depth):
        """Pure descent through depth-1 intervening levels at constant
        retry slot_r: returns the bottom bucket idx."""
        b = roots
        for _ in range(max(depth - 1, 0)):
            item = self._gather(T, b, slot_r)
            b = jnp.maximum(-1 - item, 0)
        return b

    def _leaf_machine(self, st, T, P, xl, host_item, op, leaves,
                      dev_weight):
        """chooseleaf recursion (depth 1, vary_r=0): pick ONE device
        from *host_item* avoiding the out2 collisions in *leaves*;
        fully materialized — returns (ok, item).  r = op + ftotal
        (stable pins op to 0)."""
        N = xl.shape[0]
        hb = jnp.maximum(-1 - host_item, 0)
        hsz = self.C.sizes[hb]
        base_r = jnp.zeros((N,), jnp.int32) if self.stable \
            else op.astype(jnp.int32)
        steps = st["recurse"] + self.kl

        def body(_, carry):
            ft, fl, done, dead, pick = carry
            active = ~done & ~dead
            use_perm = (self.local_fallback > 0) & \
                (fl >= (hsz >> 1)) & (fl > self.local_fallback)
            r = base_r + ft
            it_n = self._gather(T, hb, r)
            it_p = self._gather(P, hb, r)
            item = jnp.where(use_perm, it_p, it_n)
            coll = jnp.any(leaves == item[:, None], axis=1)
            rej = self._is_out(dev_weight, item, xl) | (hsz == 0)
            ok = active & ~coll & ~rej
            pick = jnp.where(ok, item, pick)
            done = done | ok
            fail = active & ~ok
            ft2, fl2 = ft + 1, fl + 1
            local = fail & ((coll & (fl2 <= self.local_retries))
                            | ((self.local_fallback > 0)
                               & (fl2 <= hsz + self.local_fallback)))
            desc = fail & ~local & (ft2 < st["recurse"])
            ft = jnp.where(fail, ft2, ft)
            fl = jnp.where(local, fl2, jnp.where(desc, 0, fl))
            dead = dead | (fail & ~local & ~desc)
            return ft, fl, done, dead, pick

        z = jnp.zeros((N,), jnp.int32)
        f = jnp.zeros((N,), bool)
        ft, fl, done, dead, pick = jax.lax.fori_loop(
            0, steps, body,
            (z, z, f, f, jnp.full((N,), NONE, jnp.int32)))
        return done, pick

    def _stage_machine(self, st, T, P, xl, roots, valid, dev_weight):
        """One firstn choose step for N parent lanes: returns
        (outs (N, numrep) — leaf devices when leafy else stage items,
        residual (N,))."""
        N = xl.shape[0]
        n = st["numrep"]
        outs = jnp.full((N, n), NONE, jnp.int32)      # collision scope
        sel = jnp.full((N, n), NONE, jnp.int32)       # emitted values
        residual = jnp.zeros((N,), bool)
        leafy = st.get("leafy", False)
        for j in range(n):

            def body(_, carry, j=j):
                outs, sel, residual, F, ft, fl, done, dead = carry
                active = valid & ~done & ~dead & ~residual
                slot_rF = jnp.int32(j) + F
                bbot = self._upper(T, roots, slot_rF, st["depth"])
                bsz = self.C.sizes[bbot]
                use_perm = (self.local_fallback > 0) & \
                    (fl >= (bsz >> 1)) & (fl > self.local_fallback)
                r = jnp.int32(j) + ft
                it_n = self._gather(T, bbot, r)
                it_p = self._gather(P, bbot, r)
                item = jnp.where(use_perm, it_p, it_n)
                coll = jnp.any(outs == item[:, None], axis=1)
                if leafy:
                    # the recursion's base r is outpos — the count of
                    # SUCCESSFUL slots so far, not the attempt index
                    # (mapper.py _choose_firstn passes outpos; a dead
                    # earlier slot leaves outpos behind j)
                    op = jnp.sum((outs[:, :j] != NONE).astype(jnp.int32),
                                 axis=1) if j else jnp.zeros((N,),
                                                             jnp.int32)
                    lok, lpick = self._leaf_machine(
                        st, T, P, xl, item, op, sel, dev_weight)
                    rej = ~lok
                elif st["type"] == 0:
                    lpick = item
                    rej = self._is_out(dev_weight, item, xl) | (bsz == 0)
                else:
                    lpick = item
                    rej = bsz == 0
                ok = active & ~coll & ~rej
                outs = outs.at[:, j].set(
                    jnp.where(ok, item, outs[:, j]))
                sel = sel.at[:, j].set(
                    jnp.where(ok, lpick if leafy else item, sel[:, j]))
                done = done | ok
                fail = active & ~ok
                ft2, fl2 = ft + 1, fl + 1
                local = fail & ((coll & (fl2 <= self.local_retries))
                                | ((self.local_fallback > 0)
                                   & (fl2 <= bsz + self.local_fallback)))
                desc = fail & ~local & (ft2 < self.tries)
                dead = dead | (fail & ~local & ~desc)
                ft = jnp.where(fail, ft2, ft)
                fl = jnp.where(local, fl2, jnp.where(desc, 0, fl))
                F = jnp.where(desc, ft2, F)
                # past the materialized window the device cannot
                # continue, but the reference would: defer to the host.
                # With rt == tries the step count covers every legal
                # path (local retries overshoot tries by at most the
                # window, which the step count and rmax both include).
                over = (ft >= self.rt) if self.rt < self.tries \
                    else jnp.zeros_like(done)
                residual = residual | (active & ~done & ~dead
                                       & (over | (r >= self.rmax - 1)))
                return outs, sel, residual, F, ft, fl, done, dead

            z = jnp.zeros((N,), jnp.int32)
            f = jnp.zeros((N,), bool)
            outs, sel, residual, _F, _ft, _fl, done, dead = \
                jax.lax.fori_loop(0, self.rt + self.kl, body,
                                  (outs, sel, residual, z, z, z, f, f))
            residual = residual | (valid & ~done & ~dead)
        return sel, residual

    def _resolve_all(self, xs, dev_weight):
        """Full rule evaluation: every stage's machine, chained."""
        X = xs.shape[0]
        x = xs.astype(jnp.uint32)
        T, P = self._draw_tables(xs)
        xl = x
        roots = jnp.full((X,), -1 - self.take, dtype=jnp.int32)
        valid = jnp.ones((X,), bool)
        residual = jnp.zeros((X,), bool)
        parents = 1
        Tl, Pl = T, P
        for si, st in enumerate(self.stages):
            sel, res = self._stage_machine(st, Tl, Pl, xl, roots, valid,
                                           dev_weight)
            residual = residual | jnp.any(
                res.reshape(X, -1), axis=1)
            if si == len(self.stages) - 1:
                final = sel
                break
            # firstn chains compactly: successes first, order kept
            order = jnp.argsort((sel == NONE).astype(jnp.int32), axis=1,
                                stable=True)
            sel = jnp.take_along_axis(sel, order, axis=1)
            n = st["numrep"]
            xl = jnp.repeat(xl, n)
            valid = jnp.repeat(valid, n) & (sel.reshape(-1) != NONE)
            roots = jnp.maximum(-1 - sel.reshape(-1), 0)
            Tl = jnp.repeat(Tl, n, axis=0)
            Pl = jnp.repeat(Pl, n, axis=0)
            parents *= n
        nr = final.shape[1]
        wide = final.reshape(X, parents * nr)
        order = jnp.argsort((wide == NONE).astype(jnp.int32), axis=1,
                            stable=True)
        compact = jnp.take_along_axis(wide, order, axis=1)
        R = self.result_max
        if compact.shape[1] < R:
            compact = jnp.pad(compact, ((0, 0), (0, R - compact.shape[1])),
                              constant_values=NONE)
        out = compact[:, :R]
        counts = jnp.minimum(jnp.sum(wide != NONE, axis=1), R)
        return out, counts.astype(jnp.int32), residual

    # ---- public ------------------------------------------------------------
    def map_batch(self, xs: np.ndarray, weight) -> Tuple[np.ndarray,
                                                         np.ndarray]:
        xs = np.asarray(xs, dtype=np.uint32)
        w32 = np.asarray(weight, dtype=np.uint32)
        with jax.enable_x64(True):
            out_d, cnt_d, res_d = self._resolve_jit(jnp.asarray(xs),
                                                    jnp.asarray(w32))
        out = np.asarray(out_d).astype(np.int32).copy()
        counts = np.asarray(cnt_d).astype(np.int32).copy()
        residual = np.asarray(res_d)
        self._residual_frac = float(residual.mean())
        wl = [int(v) for v in w32]
        for i in np.nonzero(residual)[0]:
            r = crush_do_rule(self.m, self.ruleno, int(xs[i]),
                              self.result_max, wl)
            out[i, :] = NONE
            out[i, :len(r)] = r
            counts[i] = len(r)
        return out, counts

    @property
    def residual_fraction(self) -> float:
        return getattr(self, "_residual_frac", 0.0)
