"""Perf-regression gate over the per-round bench trajectory.

The driver archives one ``BENCH_r<N>.json`` per round: ``{"n": round,
"rc": ..., "parsed": <last JSON line bench.py printed>}``.  Since this
subsystem landed, that line embeds a ``metrics`` list of schema records
(schema.py).  The comparator walks the trajectory newest-first, finds
the most recent round with a comparable reading (same metric name, same
platform, fenced, not suspect), and flags a regression when the current
reading moved beyond tolerance in the bad direction — lower for
throughputs, higher for times.

Legacy rounds (r01-r05) predate the schema and carry only flat unfenced
keys; they are never used as a gate baseline (an unfenced dispatch-rate
number would make every honest fenced number look like a regression).

Configurable fail/warn: CI runs ``python -m ceph_tpu.bench --smoke
--gate warn`` (run-to-run wobble should not break the build);
``--gate fail`` exits non-zero for release gating.
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import Any, Dict, List, Optional

DEFAULT_TOLERANCE = 0.30   # run-to-run spread allowance

# units where a larger value is better; any other unit is lower-better
_HIGHER_BETTER_UNITS = {"GiB/s", "MiB/s", "ops/s"}

# the copy-budget gate (devprof PR): every fenced workload's devflow
# block carries these per-op flow figures; both are lower-better and
# gated alongside the workload's primary value, so a zero-copy refactor
# must move a number CI watches — and a copy regression fails the gate
# like a latency regression.  Unlike wall times these are deterministic
# counts, so the gate uses a tighter tolerance than the timing wobble.
#
# Floors: a device-resident workload's only accounted flow is the
# fence drain — copies_per_op ~ 1/n_steps where n_steps is calibrated
# from a timed probe, so the figure jitters with the same run-to-run
# wobble the timing tolerance exists for.  Values below the floors are
# sub-op-level noise, not a per-op copy chain: both sides under floor
# gates nothing, and "zero-copy baseline" means "under floor", so a
# regression fires only when a real per-op copy appears.
_DEVFLOW_GATED = (("copies_per_op", "copies/op"),
                  ("bytes_per_op", "B/op"))
DEVFLOW_TOLERANCE = 0.10
DEVFLOW_FLOORS = {"copies_per_op": 0.25, "bytes_per_op": 512.0}

# the stage-budget gate (oplat PR): every fenced workload's
# stage_breakdown carries per-stage usec_per_op figures; each is
# lower-better and gated alongside the workload's primary value, so
# the mesh-sharded dispatch and zero-copy refactors must move a
# CI-watched stage number instead of a prose claim.  Stage times are
# wall-clock (not deterministic counts like the copy budget), so the
# tolerance is looser than both the timing and copy gates; the per-op
# floor keeps microsecond-scale stages — scheduling jitter, not a
# budget — from gating anything.  A stage CROSSING the floor from a
# sub-floor baseline is a regression (a new time sink appeared), the
# mirror of the copy gate's zero-copy-baseline rule.
STAGE_TOLERANCE = 0.50
STAGE_FLOOR_USEC_PER_OP = 50.0

# the recovery gate (recovery-storm PR): the ec_recovery_storm
# workload's `recovery` block carries bytes-moved-per-repaired-shard
# per codec family plus the regen/RS ratio; all three are lower-better
# counter-delta figures (deterministic for a fixed object set, like
# the copy budget) gated at the tight tolerance.  Floors: a run that
# repaired nothing reports 0 — below-floor readings gate nothing.
_RECOVERY_GATED = (("bytes_per_repaired_shard_regen", "B/shard", 64.0),
                   ("bytes_per_repaired_shard_rs", "B/shard", 64.0),
                   ("regen_vs_rs_ratio", "ratio", 0.01))
RECOVERY_TOLERANCE = 0.10

# the SKEW GATE (per-chip timing PR): the ec_mesh_skew workload's
# `skew` block records what the chip-health scoreboard saw with one
# chip slowed 10x vs a healthy twin.  Unlike the other gates this one
# is ABSOLUTE (invariants of the ruler itself, no baseline needed):
# detection must fire within SKEW_MAX_DETECTION_PROBES probes, on
# EXACTLY the slowed chip, the TPU_MESH_SKEW health check must raise
# during the run and clear after the fault is removed, and the healthy
# twin must stay quiet — a false suspect is a gate failure, because a
# ruler that cries wolf is worse than no ruler.
SKEW_MAX_DETECTION_PROBES = 8

# the STRAGGLER GATE (rateless coded mesh encode PR; extended to the
# READ path by the meshed-decode PR): every fenced workload carrying a
# `straggler` block is judged by the same absolute invariants —
# ec_mesh_straggler A/Bs the rateless ENCODE path healthy vs
# one-chip-slowed-10x, ec_degraded_read drives the meshed rateless
# DECODE path (shard killed under open-loop traffic, every read a
# survivor-sharded reconstruct) through the identical twin protocol.
# Absolute invariants like the SKEW GATE — the fix either holds or it
# does not:
# - the scoreboard must detect the slowed chip within the probe window
#   and report a nonzero skew ratio (the injected-degradation receipt:
#   a quiet run proves nothing);
# - protected cluster_rollup device_call p999 must stay within ONE
#   log2 bucket of the healthy twin (ratio <= 2.0 on edge-quantized
#   percentiles; measured 1.0 on CPU smoke — the unprotected twin
#   sits ~8 buckets up) AND the exact wall-clock p999 ratio within
#   1.5 (measured 0.9-1.0; the margin absorbs shared-core smoke
#   wobble, the unprotected twin measures 6-7x);
# - every op byte-identical to the unprotected oracle (subset
#   completion + host re-solves invisible in the bytes);
# - zero single-device fallbacks (completion must come from the
#   surviving subset, not the degradation ladder — on the read side a
#   fallback is a `mesh_decode_fallbacks` tick) and at least one
#   subset completion (the protection actually engaged);
# - the healthy twin pays < 2x coded-bandwidth overhead and marks no
#   false suspects.
STRAGGLER_MAX_DETECTION_PROBES = 8
STRAGGLER_MAX_P999_RATIO = 2.0
STRAGGLER_MAX_WALL_P999_RATIO = 1.5
STRAGGLER_MAX_BANDWIDTH_OVERHEAD = 2.0

# the CONTROL GATE (self-tuning control plane PR, docs/CONTROL.md):
# the slo_autotune workload's `control` block records the three
# closed-loop scenarios (abusive client, recovery storm under an SLO
# burn, straggling chip) run on real clusters with the mgr controller
# enabled.  Absolute invariants, baseline or not:
# - every scenario RAISED its pressure, the controller MOVED, and the
#   episode CLEARED back to baseline within the workload's tick
#   budget (zero operator action is the whole point);
# - every pressure-driven move landed inside its knob's
#   floor/ceiling corridor;
# - the disabled-controller twin made ZERO moves (an off controller
#   is observe-only by construction — mgr_control_enable gates every
#   actuation);
# - client ops stayed byte-exact throughout (the control plane never
#   touches the data path).

# the ZERO-COPY gate (device-resident shard store PR,
# docs/DISPATCH.md "Zero-copy write path"): the ec_write_zero_copy
# workload's `zero_copy` block A/Bs the resident write path
# (os_memstore_device_bytes_max large — fused encode+crc, shard bodies
# stay in HBM) against the bytes twin (budget 0).  Absolute
# invariants, baseline or not:
# - the resident leg's write-region d2h stays under the devflow floor
#   (the only fetch is the crc scalar — a shard body crossing back is
#   a regression of the whole point);
# - resident copies_per_op STRICTLY below the bytes twin's (the
#   deleted pack/slice/message copies must show up in the ledger);
# - residency actually engaged (DeviceShard handles live in the store
#   when the write region closes — a 0 here means the fused path
#   silently degraded and the A/B measured nothing);
# - read-backs byte-exact on both legs (lazy materialization is
#   invisible in the bytes).
ZERO_COPY_MAX_D2H_BYTES_PER_OP = 512.0

# the CHAOS GATE (composed-chaos scenario engine PR, docs/CHAOS.md):
# the composed_chaos workload's `chaos` block carries one receipt per
# pinned storyline seed — the engine's own universal-acceptance
# judgment, re-pinned here as absolute invariants so a bench round can
# never ship a storyline regression as a mere throughput wobble:
# - every receipt ACCEPTED (the engine's conjunction of the below);
# - every op byte-exact through the whole storyline (client replies
#   and dispatcher oracles both);
# - zero wedges (no storyline exhausted its settle budget);
# - every expected health check raised AND cleared with a finalized
#   incident bundle whose gseq timeline tells the storyline back, and
#   every collateral raise resolved the same way;
# - zero mesh single-device fallbacks (composed faults must be
#   absorbed by the coded path, never the degradation ladder).


def load_trajectory(root: str) -> List[Dict[str, Any]]:
    """All parseable BENCH_r*.json records under *root*, oldest first.

    Each item: {"round": N, "path": ..., "parsed": <dict or None>}.
    Unreadable or rc-failed rounds still appear (with parsed=None) so
    the gate can report how far back the baseline is.
    """
    out: List[Dict[str, Any]] = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        rec: Dict[str, Any] = {"round": int(m.group(1)), "path": path,
                               "parsed": None}
        try:
            with open(path) as f:
                data = json.load(f)
            parsed = data.get("parsed")
            if isinstance(parsed, dict):
                rec["parsed"] = parsed
        except Exception:
            pass
        out.append(rec)
    out.sort(key=lambda r: r["round"])
    return out


def _fenced_metrics(parsed: Optional[Dict[str, Any]]
                    ) -> Dict[str, Dict[str, Any]]:
    """name -> schema metric for every gate-eligible reading in one
    round's parsed line: fenced, not suspect, schema-carrying."""
    if not parsed:
        return {}
    out: Dict[str, Dict[str, Any]] = {}
    for m in parsed.get("metrics", []) or []:
        if not isinstance(m, dict) or not m.get("fenced"):
            continue
        if m.get("suspect"):
            continue            # a broken-fence reading gates nothing
        name = m.get("name")
        if isinstance(name, str) and name:
            out[name] = m
    return out


def _gate_lower_better(name: str, unit: str, cv: float, bv: float,
                       floor: float, tolerance: float,
                       baseline_round, regressions: List,
                       improvements: List) -> bool:
    """The one lower-better floor/tolerance rule both per-op gates
    (copy budget, stage budget) apply, so the semantics cannot drift:
    a sub-floor baseline is sacred — crossing the floor is a
    regression with no ratio to report, sub-floor drift gates nothing;
    over the floor, movement beyond *tolerance* classifies as
    regression/improvement, and dropping under the floor is always an
    improvement.  Returns True when the pair was actually compared."""
    if bv < floor:
        if cv >= floor:
            regressions.append({
                "name": name, "unit": unit, "value": cv,
                "baseline": bv, "baseline_round": baseline_round,
                "change": None})
            return True
        return False
    change = (cv - bv) / bv
    entry = {"name": name, "unit": unit, "value": cv, "baseline": bv,
             "baseline_round": baseline_round,
             "change": round(change, 4)}
    if cv < floor:
        improvements.append(entry)          # dropped under floor
    elif change > tolerance:
        regressions.append(entry)
    elif change < -tolerance:
        improvements.append(entry)
    return True


def compare_against_trajectory(
        current: List[Dict[str, Any]], trajectory: List[Dict[str, Any]],
        platform: str, tolerance: float = DEFAULT_TOLERANCE
) -> Dict[str, Any]:
    """Gate the *current* schema metrics against the newest comparable
    round per metric.

    Returns {"regressions": [...], "improvements": [...], "compared": N,
    "no_baseline": [names...]}.  A regression entry carries the metric
    name, both values, the baseline round, and the relative change.
    Caller decides warn-vs-fail.
    """
    regressions: List[Dict[str, Any]] = []
    improvements: List[Dict[str, Any]] = []
    no_baseline: List[str] = []
    compared = 0           # metrics with a value baseline
    devflow_compared = 0   # devflow keys with a gated baseline
    stage_compared = 0     # stage usec/op figures with a gated baseline
    recovery_compared = 0  # recovery storm figures with a baseline
    skew_compared = 0      # skew blocks checked (absolute gate)
    straggler_compared = 0  # straggler blocks checked (absolute gate)
    control_compared = 0   # control blocks checked (absolute gate)
    chaos_compared = 0     # chaos blocks checked (absolute gate)
    zero_copy_compared = 0  # zero_copy blocks checked (absolute gate)
    for cur in current:
        if not cur.get("fenced") or cur.get("suspect"):
            continue
        name = cur["name"]
        # ---- SKEW GATE: absolute invariants, runs baseline or not ------
        sk = cur.get("skew")
        if isinstance(sk, dict):
            skew_compared += 1
            regressions.extend(_skew_gate(name, sk))
        # ---- STRAGGLER GATE: absolute invariants, baseline or not ------
        st = cur.get("straggler")
        if isinstance(st, dict):
            straggler_compared += 1
            regressions.extend(_straggler_gate(name, st))
        # ---- CONTROL GATE: absolute invariants, baseline or not --------
        ct = cur.get("control")
        if isinstance(ct, dict):
            control_compared += 1
            regressions.extend(_control_gate(name, ct))
        # ---- CHAOS GATE: absolute invariants, baseline or not ----------
        ch = cur.get("chaos")
        if isinstance(ch, dict):
            chaos_compared += 1
            regressions.extend(_chaos_gate(name, ch))
        # ---- ZERO-COPY gate: absolute invariants, baseline or not ------
        zc = cur.get("zero_copy")
        if isinstance(zc, dict):
            zero_copy_compared += 1
            regressions.extend(_zero_copy_gate(name, zc))
        baseline = None
        baseline_round = None
        for rec in reversed(trajectory):
            parsed = rec["parsed"]
            if not parsed or parsed.get("platform") != platform:
                continue
            prev = _fenced_metrics(parsed).get(name)
            if prev is not None:
                baseline, baseline_round = prev, rec["round"]
                break
        if baseline is None:
            no_baseline.append(name)
            continue
        compared += 1
        cur_v, prev_v = float(cur["value"]), float(baseline["value"])
        higher_better = cur["unit"] in _HIGHER_BETTER_UNITS
        if prev_v <= 0:
            continue
        change = (cur_v - prev_v) / prev_v
        bad = (change < -tolerance) if higher_better \
            else (change > tolerance)
        entry = {"name": name, "unit": cur["unit"], "value": cur_v,
                 "baseline": prev_v, "baseline_round": baseline_round,
                 "change": round(change, 4)}
        if bad:
            regressions.append(entry)
        elif (change > tolerance) if higher_better \
                else (change < -tolerance):
            improvements.append(entry)
        # ---- copy-budget gate: the workload's devflow block ------------
        flow_cur = cur.get("devflow")
        flow_prev = baseline.get("devflow")
        if isinstance(flow_cur, dict) and isinstance(flow_prev, dict):
            for key, unit in _DEVFLOW_GATED:
                devflow_compared += _gate_lower_better(
                    f"{name}.{key}", unit,
                    float(flow_cur.get(key, 0.0) or 0.0),
                    float(flow_prev.get(key, 0.0) or 0.0),
                    DEVFLOW_FLOORS[key], DEVFLOW_TOLERANCE,
                    baseline_round, regressions, improvements)
        # ---- recovery gate: the storm's bytes-per-repaired-shard -------
        rec_cur = cur.get("recovery")
        rec_prev = baseline.get("recovery")
        if isinstance(rec_cur, dict) and isinstance(rec_prev, dict):
            for key, unit, floor in _RECOVERY_GATED:
                recovery_compared += _gate_lower_better(
                    f"{name}.recovery.{key}", unit,
                    float(rec_cur.get(key, 0.0) or 0.0),
                    float(rec_prev.get(key, 0.0) or 0.0),
                    floor, RECOVERY_TOLERANCE,
                    baseline_round, regressions, improvements)
        # ---- stage-budget gate: the workload's stage_breakdown ---------
        sb_cur = (cur.get("stage_breakdown") or {}).get("stages")
        sb_prev = (baseline.get("stage_breakdown") or {}).get("stages")
        if not isinstance(sb_cur, dict) or not isinstance(sb_prev, dict):
            continue        # pre-oplat rounds gate no stages
        for stage in sorted(set(sb_cur) | set(sb_prev)):
            stage_compared += _gate_lower_better(
                f"{name}.stage.{stage}", "usec/op",
                float((sb_cur.get(stage) or {}).get("usec_per_op",
                                                    0.0) or 0.0),
                float((sb_prev.get(stage) or {}).get("usec_per_op",
                                                     0.0) or 0.0),
                STAGE_FLOOR_USEC_PER_OP, STAGE_TOLERANCE,
                baseline_round, regressions, improvements)
    return {"regressions": regressions, "improvements": improvements,
            "compared": compared, "devflow_compared": devflow_compared,
            "stage_compared": stage_compared,
            "recovery_compared": recovery_compared,
            "skew_compared": skew_compared,
            "straggler_compared": straggler_compared,
            "control_compared": control_compared,
            "chaos_compared": chaos_compared,
            "zero_copy_compared": zero_copy_compared,
            "no_baseline": no_baseline,
            "tolerance": tolerance, "platform": platform}


def _skew_gate(name: str, sk: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The skew workload's absolute invariants as regression entries
    (change=None: there is no ratio to report — the ruler either
    works or it does not)."""
    out: List[Dict[str, Any]] = []

    def fail(key: str, value, why: str) -> None:
        out.append({"name": f"{name}.skew.{key}", "unit": "invariant",
                    "value": value, "baseline": why,
                    "baseline_round": None, "change": None})

    det = int(sk.get("detection_probes") or 0)
    if det <= 0:
        fail("detection_probes", det,
             "scoreboard never marked the slowed chip suspect")
    elif det > SKEW_MAX_DETECTION_PROBES:
        fail("detection_probes", det,
             f"detection took more than {SKEW_MAX_DETECTION_PROBES} "
             f"probes")
    if det > 0 and sk.get("detected_chip") != sk.get("slow_chip"):
        fail("detected_chip", sk.get("detected_chip"),
             f"suspect is not the slowed chip "
             f"{sk.get('slow_chip')}")
    if int(sk.get("healthy_false_suspects") or 0) > 0 \
            or sk.get("healthy_raised"):
        fail("healthy_false_suspects",
             sk.get("healthy_false_suspects"),
             "the healthy twin raised a suspect/health check")
    if not sk.get("raised"):
        fail("raised", sk.get("raised"),
             "TPU_MESH_SKEW never raised while the mgr ticked")
    if not sk.get("cleared"):
        fail("cleared", sk.get("cleared"),
             "TPU_MESH_SKEW did not clear after the fault was removed")
    return out


def _control_gate(name: str,
                  ct: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The control-plane workload's absolute invariants as regression
    entries (change=None — a control plane that fails to converge, or
    moves when disabled, either holds its contract or it does not)."""
    out: List[Dict[str, Any]] = []

    def fail(key: str, value, why: str) -> None:
        out.append({"name": f"{name}.control.{key}",
                    "unit": "invariant", "value": value,
                    "baseline": why, "baseline_round": None,
                    "change": None})

    budget = int(ct.get("tick_budget") or 0)
    if int(ct.get("disabled_moves") or 0) != 0:
        fail("disabled_moves", ct.get("disabled_moves"),
             "the disabled-controller twin actuated a knob — "
             "mgr_control_enable no longer gates actuation")
    if not ct.get("byte_exact"):
        fail("byte_exact", ct.get("byte_exact"),
             "client ops diverged while the controller ran — the "
             "control plane touched the data path")
    for scen, block in sorted((ct.get("scenarios") or {}).items()):
        if not isinstance(block, dict):
            fail(scen, block, "scenario block missing")
            continue
        if not block.get("raised"):
            fail(f"{scen}.raised", block.get("raised"),
                 "the scenario never raised its SLO/health pressure "
                 "— the episode is vacuous")
        if int(block.get("moves") or 0) <= 0:
            fail(f"{scen}.moves", block.get("moves"),
                 "the controller never moved a knob under sustained "
                 "pressure")
        conv = int(block.get("converge_ticks") or -1)
        if not block.get("cleared") or conv <= 0 or conv > budget:
            fail(f"{scen}.converge_ticks", conv,
                 f"the episode did not clear back to baseline within "
                 f"{budget} mgr ticks of the pressure ending")
        if not block.get("in_bounds"):
            fail(f"{scen}.in_bounds", block.get("in_bounds"),
                 "a pressure-driven move landed outside its knob's "
                 "floor/ceiling corridor")
    if "admission" in (ct.get("scenarios") or {}):
        adm = ct["scenarios"]["admission"]
        if isinstance(adm, dict) and not adm.get("abuser_correct"):
            fail("admission.abuser_correct",
                 adm.get("abuser_correct"),
                 "the controller tightened a lane other than the "
                 "flooding client's")
    return out


def _straggler_gate(name: str,
                    st: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The straggler workload's absolute invariants as regression
    entries (change=None — the flagship robustness claim either holds
    or it does not)."""
    out: List[Dict[str, Any]] = []

    def fail(key: str, value, why: str) -> None:
        out.append({"name": f"{name}.straggler.{key}",
                    "unit": "invariant", "value": value,
                    "baseline": why, "baseline_round": None,
                    "change": None})

    det = int(st.get("detection_probes") or 0)
    if det <= 0:
        fail("detection_probes", det,
             "scoreboard never marked the slowed chip suspect — no "
             "injected-degradation receipt")
    elif det > STRAGGLER_MAX_DETECTION_PROBES:
        fail("detection_probes", det,
             f"detection took more than "
             f"{STRAGGLER_MAX_DETECTION_PROBES} probes")
    if det > 0 and st.get("detected_chip") != st.get("slow_chip"):
        fail("detected_chip", st.get("detected_chip"),
             f"suspect is not the slowed chip {st.get('slow_chip')}")
    if float(st.get("skew_ratio_detected") or 0.0) <= 0:
        fail("skew_ratio_detected", st.get("skew_ratio_detected"),
             "no skew ratio recorded at detection")
    ratio = float(st.get("protected_p999_ratio") or 0.0)
    if ratio <= 0 or ratio > STRAGGLER_MAX_P999_RATIO:
        fail("protected_p999_ratio", ratio,
             f"protected cluster_rollup device_call p999 beyond "
             f"{STRAGGLER_MAX_P999_RATIO}x the healthy twin "
             f"(log2-edge quantized: 2.0 = one bucket)")
    wall = float(st.get("protected_p999_wall_ratio") or 0.0)
    if wall <= 0 or wall > STRAGGLER_MAX_WALL_P999_RATIO:
        fail("protected_p999_wall_ratio", wall,
             f"protected wall-clock flush p999 beyond "
             f"{STRAGGLER_MAX_WALL_P999_RATIO}x the healthy twin")
    bw = float(st.get("bandwidth_overhead") or 0.0)
    if bw <= 0 or bw >= STRAGGLER_MAX_BANDWIDTH_OVERHEAD:
        fail("bandwidth_overhead", bw,
             f"healthy twin pays >= "
             f"{STRAGGLER_MAX_BANDWIDTH_OVERHEAD}x coded bandwidth")
    if not st.get("byte_identical"):
        fail("byte_identical", st.get("byte_identical"),
             "protected outputs diverged from the unprotected oracle")
    if int(st.get("single_device_fallbacks") or 0) > 0:
        fail("single_device_fallbacks",
             st.get("single_device_fallbacks"),
             "a protected flush degraded to the single-device path")
    if int(st.get("subset_completions") or 0) <= 0:
        fail("subset_completions", st.get("subset_completions"),
             "no flush completed from a strict subset — the "
             "protection never engaged")
    if int(st.get("healthy_false_suspects") or 0) > 0:
        fail("healthy_false_suspects",
             st.get("healthy_false_suspects"),
             "the healthy twin marked a suspect")
    return out


def _zero_copy_gate(name: str,
                    zc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The zero-copy workload's absolute invariants as regression
    entries (change=None — the resident write path either deletes the
    copies or it does not)."""
    out: List[Dict[str, Any]] = []

    def fail(key: str, value, why: str) -> None:
        out.append({"name": f"{name}.zero_copy.{key}",
                    "unit": "invariant", "value": value,
                    "baseline": why, "baseline_round": None,
                    "change": None})

    d2h = float(zc.get("resident_d2h_bytes_per_op") or 0.0)
    if d2h >= ZERO_COPY_MAX_D2H_BYTES_PER_OP:
        fail("resident_d2h_bytes_per_op", d2h,
             f"the resident write path fetched >= "
             f"{ZERO_COPY_MAX_D2H_BYTES_PER_OP} B/op from device — a "
             f"shard body is crossing back on the write path")
    res = float(zc.get("resident_copies_per_op") or 0.0)
    twin = float(zc.get("twin_copies_per_op") or 0.0)
    if not res < twin:
        fail("resident_copies_per_op", res,
             f"resident leg not strictly below the bytes twin's "
             f"{twin} copies/op — the fused path deleted nothing")
    if int(zc.get("resident_shards") or 0) <= 0:
        fail("resident_shards", zc.get("resident_shards"),
             "no DeviceShard was resident when the write region "
             "closed — the fused path silently degraded and the A/B "
             "measured nothing")
    if not zc.get("byte_exact"):
        fail("byte_exact", zc.get("byte_exact"),
             "a read-back diverged from the written payload")
    return out


def _chaos_gate(name: str, ch: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The composed-chaos workload's absolute invariants as regression
    entries (change=None — a storyline either survives the universal
    acceptance or it does not; there is no ratio to report)."""
    out: List[Dict[str, Any]] = []

    def fail(key: str, value, why: str) -> None:
        out.append({"name": f"{name}.chaos.{key}", "unit": "invariant",
                    "value": value, "baseline": why,
                    "baseline_round": None, "change": None})

    receipts = ch.get("receipts") or []
    if not receipts:
        fail("receipts", 0, "no storyline receipts — the chaos "
             "workload executed nothing")
        return out
    for r in receipts:
        if not isinstance(r, dict):
            fail("receipt", r, "malformed storyline receipt")
            continue
        seed = r.get("seed")
        if not r.get("byte_exact"):
            fail(f"seed{seed}.byte_exact", r.get("byte_exact"),
                 "an op or dispatcher oracle diverged from its "
                 "expected bytes under the storyline")
        if r.get("wedged"):
            fail(f"seed{seed}.wedged", r.get("wedged"),
                 "the storyline exhausted its settle budget — a "
                 "composed fault wedged the cluster")
        for chk, row in sorted((r.get("checks") or {}).items()):
            if not isinstance(row, dict) or not all(row.values()):
                fail(f"seed{seed}.{chk}", row,
                     "an expected health check failed to raise, "
                     "clear, or leave a finalized bundle that tells "
                     "the storyline back")
        if not r.get("all_raises_resolved"):
            fail(f"seed{seed}.all_raises_resolved",
                 r.get("all_raises_resolved"),
                 "a collateral health raise never cleared or left "
                 "no finalized incident bundle")
        if not r.get("storyline_told"):
            fail(f"seed{seed}.storyline_told", r.get("storyline_told"),
                 "the cluster journal does not contain the injected "
                 "storyline's promised event types")
        if int(r.get("mesh_fallbacks") or 0) != 0:
            fail(f"seed{seed}.mesh_fallbacks", r.get("mesh_fallbacks"),
                 "a composed fault degraded a flush to the "
                 "single-device fallback path")
        if not r.get("accepted"):
            fail(f"seed{seed}.accepted", r.get("accepted"),
                 "the storyline failed the engine's universal "
                 "acceptance")
    return out
