"""The measurement harness measures the measurer.

Round 5's verdict: the published 807 GiB/s encode number was physically
impossible because the timing loop mistook dispatch acknowledgements
for completions.  These tests pin the properties that make that class
of bug structurally impossible again:

- the fenced timer cannot stop before outputs materialize on the host
  (proved with a delayed-materialization array double that acknowledges
  ``block_until_ready`` instantly — an early-acknowledging
  backend);
- any reading whose implied op rate exceeds the chip's physical peak is
  stamped ``suspect: true``;
- the schema refuses an exact-0.0 timing (round 5's
  ``nonuniform_us: 0.0``: "fast" must never read as "didn't run");
- the regression gate flags fenced metrics that move beyond tolerance
  against the archived trajectory, and never gates on unfenced or
  suspect baselines;
- ``python -m ceph_tpu.bench --smoke`` — the CI tier — exits 0 on CPU
  in seconds with schema-valid fenced metrics.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from ceph_tpu.bench import fence, regress, roofline, schema, stats


# ---- fence -----------------------------------------------------------------

class DelayedArray:
    """Array double mimicking an early-acknowledging handle: the ready
    acknowledgement returns instantly, but the value only exists after
    ``delay`` more seconds of remote execution — observable solely via
    host readback."""

    def __init__(self, delay_s, t_dispatch):
        self._ready_at = t_dispatch + delay_s
        self._payload = np.arange(8, dtype=np.int32)

    def block_until_ready(self):
        return self            # lies, like the transport does

    def __array__(self, dtype=None, copy=None):
        now = time.perf_counter()
        if now < self._ready_at:
            time.sleep(self._ready_at - now)
        return self._payload


def test_fenced_timer_waits_for_materialization():
    """The clock must not stop until the last output's bytes exist on
    the host, even when block_until_ready acknowledges instantly."""
    DELAY = 0.15

    def step(i):
        return DelayedArray(DELAY, time.perf_counter())

    timing = fence.fenced_time(step, n_steps=3, rtt_s=0.0)
    # dispatches are instant; an unfenced timer would read ~0 here.
    assert timing.elapsed_s >= DELAY * 0.95
    assert timing.fenced is True
    assert timing.n_steps == 3


def test_drain_touches_host_bytes():
    done = {"materialized": False}

    class Probe:
        def block_until_ready(self):
            return self

        def __array__(self, dtype=None, copy=None):
            done["materialized"] = True
            return np.zeros(4, dtype=np.int32)

    fence.drain(Probe())
    assert done["materialized"]


def test_fenced_time_on_real_backend():
    """End-to-end on the CPU backend: jit dispatch, drain, sane fields."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x, s: x * s)
    x = jnp.arange(1024, dtype=jnp.int32)
    timing = fence.fenced_time(lambda i: f(x, jnp.int32(i + 1)), 4)
    assert timing.elapsed_s > 0.0
    assert timing.rtt_s >= 0.0
    d = timing.to_dict()
    assert d["fenced"] is True and d["n_steps"] == 4


def test_measure_rtt_custom_maker():
    rtt = fence.measure_rtt(lambda: np.ones(8, dtype=np.int32), repeats=3)
    assert 0.0 <= rtt < 1.0


# ---- roofline --------------------------------------------------------------

def test_roofline_flags_above_peak_reading():
    """807 GiB/s on a v5e implies ~444 int8 TOPS > 394 peak — the exact
    round-5 bogus headline must come back stamped suspect."""
    v = roofline.validate_reading(807.0, roofline.EC_ENCODE_K8M4,
                                  "tpu", "TPU v5 lite")
    assert v["suspect"] is True
    assert v["verdict"] == "suspect"
    assert v["implied_tops"] > v["peak_tops"]


def test_roofline_passes_physical_reading():
    v = roofline.validate_reading(300.0, roofline.EC_ENCODE_K8M4,
                                  "tpu", "TPU v5 lite")
    assert v["suspect"] is False
    assert v["verdict"] == "ok"
    assert 0.0 < v["mfu"] < 1.0


def test_roofline_memory_axis_trips_too():
    # 500 GiB/s of object data = 750 GiB/s of HBM traffic on the encode
    # model — fine for v5e compute but well past a 600 GiB/s host
    v = roofline.validate_reading(500.0, roofline.EC_ENCODE_K8M4, "cpu")
    assert v["suspect"] is True


_CACHE_KNOBS = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_entry_size_bytes",
                "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def jax_cache_config():
    """configure_compile_cache() writes process-global jax config; put
    it back so later tests in this worker keep the session's cache."""
    import jax
    saved = {k: getattr(jax.config, k) for k in _CACHE_KNOBS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_placed_from_outside(monkeypatch, jax_cache_config,
                                           env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is left alone; otherwise the
    cache is the fixed <checkout>/.jax_cache (arch.configure_compile_cache,
    shared by bench.py, chip_smoke.py and conftest.py)."""
    import jax
    from ceph_tpu.arch import configure_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
        assert configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert configure_compile_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir == before


def test_roofline_unknown_backend_never_ok():
    v = roofline.validate_reading(100.0, roofline.EC_ENCODE_K8M4,
                                  "rocm", "gfx90a")
    assert v["verdict"] == "unknown"
    assert v["suspect"] is False and v["peak_tops"] is None


def test_chip_spec_lookup():
    assert roofline.chip_spec("tpu", "TPU v5 lite")["int8_tops"] == 394.0
    assert roofline.chip_spec("tpu", "TPU v4")["int8_tops"] == 275.0
    assert roofline.chip_spec("cpu")["int8_tops"] == 2.0
    # a TPU not in the table is an error, never another chip's peaks
    for kind in ("", "TPU v99"):
        with pytest.raises(KeyError):
            roofline.chip_spec("tpu", kind)


# ---- stats -----------------------------------------------------------------

def test_summarize_median_iqr():
    st = stats.summarize([1.0, 2.0, 3.0, 4.0, 5.0])
    assert st["median"] == 3.0
    assert st["iqr"] == 2.0
    assert st["min"] == 1.0 and st["max"] == 5.0 and st["n"] == 5


def test_repeat_measure_discards_warmup():
    vals = iter([100.0, 1.0, 2.0, 3.0])   # first sample is compile cost
    st = stats.repeat_measure(lambda: next(vals), repeats=3, warmup=1)
    assert st["median"] == 2.0            # 100.0 excluded
    assert st["warmup_samples"] == [100.0]
    assert st["samples"] == [1.0, 2.0, 3.0]


# ---- schema ----------------------------------------------------------------

def test_make_metric_roundtrip():
    m = schema.make_metric(
        "x_gibs", 12.5, "GiB/s", fenced=True, rtt_s=0.07,
        stats=stats.summarize([12.0, 12.5, 13.0]),
        roofline=roofline.validate_reading(
            12.5, roofline.EC_ENCODE_K8M4, "cpu"))
    schema.validate_metric(m)
    assert m["fenced"] is True and m["rtt_ms"] == 70.0
    assert m["stats"]["n"] == 3
    assert m["suspect"] is m["roofline"]["suspect"]


def test_schema_rejects_exact_zero_timing():
    """A 0.0 reading in a time/throughput unit means 'didn't run' — the
    round-5 nonuniform_us:0.0 line must be unpublishable."""
    with pytest.raises(schema.SchemaError, match="0.0"):
        schema.make_metric("crush_remap_device", 0.0, "us", fenced=True)


def test_schema_rejects_missing_fence_field():
    with pytest.raises(schema.SchemaError):
        schema.validate_metric({"schema_version": 1, "name": "x",
                                "value": 1.0, "unit": "GiB/s"})


def test_schema_suspect_must_mirror_roofline():
    m = schema.make_metric(
        "x", 807.0, "GiB/s", fenced=True,
        roofline=roofline.validate_reading(
            807.0, roofline.EC_ENCODE_K8M4, "tpu", "TPU v5 lite"))
    assert m["suspect"] is True
    m["suspect"] = False       # tamper
    with pytest.raises(schema.SchemaError):
        schema.validate_metric(m)


# ---- regression gate -------------------------------------------------------

def _write_round(tmp_path, n, platform, metrics):
    rec = {"n": n, "rc": 0,
           "parsed": {"platform": platform, "metrics": metrics}}
    (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(rec))


def _metric(name, value, unit="GiB/s", fenced=True, suspect=False):
    m = schema.make_metric(name, value, unit, fenced=fenced)
    if suspect:   # hand-build: make_metric would need a roofline dict
        m["suspect"] = True
    return m


def test_gate_flags_throughput_regression(tmp_path):
    _write_round(tmp_path, 6, "cpu", [_metric("enc", 10.0)])
    traj = regress.load_trajectory(str(tmp_path))
    out = regress.compare_against_trajectory(
        [_metric("enc", 5.0)], traj, "cpu", tolerance=0.3)
    assert len(out["regressions"]) == 1
    assert out["regressions"][0]["baseline_round"] == 6
    assert out["regressions"][0]["change"] == -0.5


def test_gate_time_metrics_are_lower_better(tmp_path):
    _write_round(tmp_path, 6, "cpu", [_metric("remap", 10.0, unit="ms")])
    traj = regress.load_trajectory(str(tmp_path))
    out = regress.compare_against_trajectory(
        [_metric("remap", 20.0, unit="ms")], traj, "cpu")
    assert len(out["regressions"]) == 1
    out = regress.compare_against_trajectory(
        [_metric("remap", 5.0, unit="ms")], traj, "cpu")
    assert not out["regressions"] and len(out["improvements"]) == 1


def test_gate_recovery_block_lower_better(tmp_path):
    """The recovery gate: the storm's bytes-per-repaired-shard and the
    regen/RS ratio gate lower-better at the tight tolerance; a ratio
    creeping past tolerance is a regression even when the primary
    value held."""
    def storm(regen, rs, ratio):
        m = _metric("ec_recovery_storm", regen, unit="B/shard")
        m["recovery"] = {"bytes_per_repaired_shard_regen": regen,
                         "bytes_per_repaired_shard_rs": rs,
                         "regen_vs_rs_ratio": ratio}
        return m

    _write_round(tmp_path, 6, "cpu", [storm(5120.0, 32768.0, 0.156)])
    traj = regress.load_trajectory(str(tmp_path))
    # unchanged figures: compared, no regression
    out = regress.compare_against_trajectory(
        [storm(5120.0, 32768.0, 0.156)], traj, "cpu")
    assert out["recovery_compared"] == 3 and not out["regressions"]
    # repair bandwidth doubled: the regen figure AND the ratio regress
    out = regress.compare_against_trajectory(
        [storm(10240.0, 32768.0, 0.3125)], traj, "cpu")
    names = {r["name"] for r in out["regressions"]}
    assert "ec_recovery_storm.recovery.bytes_per_repaired_shard_regen" \
        in names
    assert "ec_recovery_storm.recovery.regen_vs_rs_ratio" in names
    # improvement direction classifies as improvement
    out = regress.compare_against_trajectory(
        [storm(2560.0, 32768.0, 0.078)], traj, "cpu")
    assert not out["regressions"] and out["improvements"]


def test_gate_skew_invariants(tmp_path):
    """The SKEW GATE is absolute (no baseline needed): late or missing
    detection, the wrong chip, a noisy healthy twin, or a health check
    that never raised/cleared each fail the gate on their own."""
    def skew_metric(**over):
        m = _metric("ec_mesh_skew", 12.0, unit="ratio")
        sk = {"mesh_chips": 8, "slow_chip": 5, "delay_us": 30000,
              "threshold": 3.0, "detected_chip": 5,
              "skew_ratio_detected": 12.0, "detection_probes": 3,
              "healthy_false_suspects": 0, "healthy_raised": False,
              "raised": True, "cleared": True}
        sk.update(over)
        m["skew"] = sk
        return m

    # a clean run gates clean — with or without any baseline round
    out = regress.compare_against_trajectory([skew_metric()], [], "cpu")
    assert out["skew_compared"] == 1 and not out["regressions"]
    cases = (
        ({"detection_probes": 0}, "detection_probes"),
        ({"detection_probes":
          regress.SKEW_MAX_DETECTION_PROBES + 1}, "detection_probes"),
        ({"detected_chip": 2}, "detected_chip"),
        ({"healthy_false_suspects": 1}, "healthy_false_suspects"),
        ({"healthy_raised": True}, "healthy_false_suspects"),
        ({"raised": False}, "raised"),
        ({"cleared": False}, "cleared"),
    )
    for over, key in cases:
        out = regress.compare_against_trajectory(
            [skew_metric(**over)], [], "cpu")
        names = {r["name"] for r in out["regressions"]}
        assert f"ec_mesh_skew.skew.{key}" in names, (over, names)


def test_gate_straggler_invariants(tmp_path):
    """The STRAGGLER GATE is absolute (no baseline needed): missing or
    late detection, the wrong chip, a protected p999 beyond the
    calibrated bounds, a byte divergence, a single-device fallback, a
    never-engaged subset completion, >= 2x coded bandwidth, or a noisy
    healthy twin each fail the gate on their own."""
    def straggler_metric(**over):
        m = _metric("ec_mesh_straggler", 1.0, unit="ratio")
        st = {"mesh_chips": 8, "slow_chip": 5, "delay_us": 30000,
              "threshold": 3.0, "detected_chip": 5,
              "skew_ratio_detected": 3.3, "detection_probes": 3,
              "healthy_false_suspects": 0,
              "protected_p999_ratio": 1.0,
              "protected_p999_wall_ratio": 0.95,
              "bandwidth_overhead": 1.25,
              "subset_completions": 40,
              "single_device_fallbacks": 0,
              "byte_identical": True}
        st.update(over)
        m["straggler"] = st
        return m

    # a clean run gates clean — with or without any baseline round
    out = regress.compare_against_trajectory([straggler_metric()], [],
                                             "cpu")
    assert out["straggler_compared"] == 1 and not out["regressions"]
    cases = (
        ({"detection_probes": 0}, "detection_probes"),
        ({"detection_probes":
          regress.STRAGGLER_MAX_DETECTION_PROBES + 1},
         "detection_probes"),
        ({"detected_chip": 2}, "detected_chip"),
        ({"skew_ratio_detected": 0.0}, "skew_ratio_detected"),
        ({"protected_p999_ratio":
          regress.STRAGGLER_MAX_P999_RATIO * 2},
         "protected_p999_ratio"),
        ({"protected_p999_ratio": 0.0}, "protected_p999_ratio"),
        ({"protected_p999_wall_ratio":
          regress.STRAGGLER_MAX_WALL_P999_RATIO + 0.1},
         "protected_p999_wall_ratio"),
        ({"bandwidth_overhead":
          regress.STRAGGLER_MAX_BANDWIDTH_OVERHEAD},
         "bandwidth_overhead"),
        ({"byte_identical": False}, "byte_identical"),
        ({"single_device_fallbacks": 1}, "single_device_fallbacks"),
        ({"subset_completions": 0}, "subset_completions"),
        ({"healthy_false_suspects": 1}, "healthy_false_suspects"),
    )
    for over, key in cases:
        out = regress.compare_against_trajectory(
            [straggler_metric(**over)], [], "cpu")
        names = {r["name"] for r in out["regressions"]}
        assert f"ec_mesh_straggler.straggler.{key}" in names, \
            (over, names)


def test_gate_zero_copy_invariants(tmp_path):
    """The ZERO-COPY GATE is absolute (no baseline needed): a resident
    leg that fetched shard-scale bytes back from device, that did not
    strictly beat the bytes twin's copies/op, that silently degraded
    (nothing resident when the write region closed), or that diverged
    on read-back each fail the gate on their own."""
    def zc_metric(**over):
        m = _metric("ec_write_zero_copy", 100.0, unit="ops_per_sec")
        zc = {"resident_d2h_bytes_per_op": 20.0,
              "resident_copies_per_op": 2.2,
              "twin_copies_per_op": 3.0,
              "resident_shards": 30,
              "byte_exact": True}
        zc.update(over)
        m["zero_copy"] = zc
        return m

    # a clean run gates clean — with or without any baseline round
    out = regress.compare_against_trajectory([zc_metric()], [], "cpu")
    assert out["zero_copy_compared"] == 1 and not out["regressions"]
    cases = (
        ({"resident_d2h_bytes_per_op":
          regress.ZERO_COPY_MAX_D2H_BYTES_PER_OP},
         "resident_d2h_bytes_per_op"),
        ({"resident_copies_per_op": 3.0}, "resident_copies_per_op"),
        ({"resident_copies_per_op": 3.5}, "resident_copies_per_op"),
        ({"resident_shards": 0}, "resident_shards"),
        ({"byte_exact": False}, "byte_exact"),
    )
    for over, key in cases:
        out = regress.compare_against_trajectory(
            [zc_metric(**over)], [], "cpu")
        names = {r["name"] for r in out["regressions"]}
        assert f"ec_write_zero_copy.zero_copy.{key}" in names, \
            (over, names)


def test_gate_control_invariants(tmp_path):
    """The CONTROL GATE is absolute (no baseline needed): a scenario
    that never raised, never moved, failed to converge inside the
    tick budget, moved outside its bounds corridor, a mis-identified
    abuser, a byte divergence, or ANY move from the disabled twin
    each fail the gate on their own."""
    def scenario(**over):
        s = {"raised": True, "moves": 4, "cleared": True,
             "converge_ticks": 6, "in_bounds": True}
        s.update(over)
        return s

    def control_metric(scen_over=None, **over):
        m = _metric("slo_autotune", 6.0, unit="ticks")
        ct = {"disabled_moves": 0, "byte_exact": True,
              "tick_budget": 80,
              "scenarios": {
                  "admission": scenario(abuser_correct=True),
                  "recovery": scenario(),
                  "straggler": scenario()}}
        ct.update(over)
        if scen_over:
            which, so = scen_over
            ct["scenarios"][which] = dict(ct["scenarios"][which],
                                          **so)
        m["control"] = ct
        return m

    # a clean run gates clean — with or without any baseline round
    out = regress.compare_against_trajectory([control_metric()], [],
                                             "cpu")
    assert out["control_compared"] == 1 and not out["regressions"]
    top_cases = (
        ({"disabled_moves": 1}, "disabled_moves"),
        ({"byte_exact": False}, "byte_exact"),
    )
    for over, key in top_cases:
        out = regress.compare_against_trajectory(
            [control_metric(**over)], [], "cpu")
        names = {r["name"] for r in out["regressions"]}
        assert f"slo_autotune.control.{key}" in names, (over, names)
    scen_cases = (
        ({"raised": False}, "raised"),
        ({"moves": 0}, "moves"),
        ({"cleared": False, "converge_ticks": -1}, "converge_ticks"),
        ({"converge_ticks": 81}, "converge_ticks"),
        ({"in_bounds": False}, "in_bounds"),
    )
    for over, key in scen_cases:
        for which in ("admission", "recovery", "straggler"):
            out = regress.compare_against_trajectory(
                [control_metric(scen_over=(which, over))], [], "cpu")
            names = {r["name"] for r in out["regressions"]}
            assert f"slo_autotune.control.{which}.{key}" in names, \
                (which, over, names)
    out = regress.compare_against_trajectory(
        [control_metric(scen_over=("admission",
                                   {"abuser_correct": False}))],
        [], "cpu")
    names = {r["name"] for r in out["regressions"]}
    assert "slo_autotune.control.admission.abuser_correct" in names


def test_gate_within_tolerance_passes(tmp_path):
    _write_round(tmp_path, 6, "cpu", [_metric("enc", 10.0)])
    traj = regress.load_trajectory(str(tmp_path))
    out = regress.compare_against_trajectory(
        [_metric("enc", 8.0)], traj, "cpu", tolerance=0.3)
    assert not out["regressions"] and out["compared"] == 1


def test_gate_ignores_unfenced_and_suspect_baselines(tmp_path):
    # legacy-style round: flat keys only, no schema metrics
    (tmp_path / "BENCH_r05.json").write_text(json.dumps(
        {"n": 5, "parsed": {"platform": "cpu", "value": 999.0}}))
    # a suspect reading must never become the gate baseline either
    _write_round(tmp_path, 6, "cpu",
                 [_metric("enc", 999.0, suspect=True)])
    traj = regress.load_trajectory(str(tmp_path))
    out = regress.compare_against_trajectory(
        [_metric("enc", 5.0)], traj, "cpu")
    assert out["compared"] == 0
    assert out["no_baseline"] == ["enc"]


def test_gate_platform_mismatch_is_no_baseline(tmp_path):
    _write_round(tmp_path, 6, "tpu", [_metric("enc", 500.0)])
    traj = regress.load_trajectory(str(tmp_path))
    out = regress.compare_against_trajectory(
        [_metric("enc", 0.01)], traj, "cpu")
    assert out["compared"] == 0 and not out["regressions"]


def _staged_metric(name, value, stages):
    """A fenced metric carrying a stage_breakdown whose stages are
    {stage: usec_per_op} — the shape the stage-budget gate reads."""
    total = sum(stages.values())
    return schema.make_metric(
        name, value, "GiB/s", fenced=True,
        extra={"stage_breakdown": {
            "wall_s": 1.0, "stage_sum_s": 1.0, "coverage": 1.0,
            "n_ops": 100,
            "stages": {s: {"count": 100, "total_usec": u * 100,
                           "usec_per_op": u,
                           "share": (u / total if total else 0.0),
                           "p50_usec": u, "p99_usec": u}
                       for s, u in stages.items()}}})


def test_stage_gate_flags_slower_stage(tmp_path):
    """The stage-budget gate: a stage's per-op time growing beyond
    STAGE_TOLERANCE is a regression even when the headline value is
    flat — the mesh/zero-copy refactors must move a watched stage
    number, and an accidental stall must fail the same gate."""
    _write_round(tmp_path, 6, "cpu", [_staged_metric(
        "enc", 10.0, {"device_call": 1000.0, "d2h": 200.0})])
    traj = regress.load_trajectory(str(tmp_path))
    out = regress.compare_against_trajectory(
        [_staged_metric("enc", 10.0,
                        {"device_call": 1000.0, "d2h": 800.0})],
        traj, "cpu")
    assert out["stage_compared"] == 2
    names = [r["name"] for r in out["regressions"]]
    assert names == ["enc.stage.d2h"]
    assert out["regressions"][0]["unit"] == "usec/op"
    assert out["regressions"][0]["change"] == 3.0
    # a stage getting faster beyond tolerance is an improvement
    out = regress.compare_against_trajectory(
        [_staged_metric("enc", 10.0,
                        {"device_call": 300.0, "d2h": 200.0})],
        traj, "cpu")
    assert not out["regressions"]
    assert any(i["name"] == "enc.stage.device_call"
               for i in out["improvements"])


def test_stage_gate_floor_semantics(tmp_path):
    """Sub-floor stages (scheduling jitter) gate nothing in either
    direction; a stage CROSSING the floor from a sub-floor baseline is
    flagged as a new time sink, mirroring the copy gate's zero-copy
    baseline rule.  Pre-oplat rounds (no stage_breakdown) gate no
    stages at all."""
    _write_round(tmp_path, 6, "cpu", [_staged_metric(
        "enc", 10.0, {"device_call": 1000.0, "batch_window": 5.0})])
    traj = regress.load_trajectory(str(tmp_path))
    # sub-floor wobble: 5 -> 40 usec/op is under the 50 usec floor
    out = regress.compare_against_trajectory(
        [_staged_metric("enc", 10.0, {"device_call": 1000.0,
                                      "batch_window": 40.0})],
        traj, "cpu")
    assert not out["regressions"]
    # crossing the floor: a new per-op time sink appeared
    out = regress.compare_against_trajectory(
        [_staged_metric("enc", 10.0, {"device_call": 1000.0,
                                      "batch_window": 900.0})],
        traj, "cpu")
    bad = [r for r in out["regressions"]
           if r["name"] == "enc.stage.batch_window"]
    assert bad and bad[0]["change"] is None
    # pre-oplat baseline: value gates, stages don't
    _write_round(tmp_path, 7, "cpu", [_metric("enc2", 10.0)])
    traj = regress.load_trajectory(str(tmp_path))
    out = regress.compare_against_trajectory(
        [_staged_metric("enc2", 10.0, {"device_call": 9999.0})],
        traj, "cpu")
    assert out["stage_compared"] == 0 and not out["regressions"]


def test_load_trajectory_orders_and_survives_junk(tmp_path):
    (tmp_path / "BENCH_r02.json").write_text("not json {")
    _write_round(tmp_path, 10, "cpu", [])
    _write_round(tmp_path, 3, "cpu", [])
    traj = regress.load_trajectory(str(tmp_path))
    assert [r["round"] for r in traj] == [2, 3, 10]
    assert traj[0]["parsed"] is None


# ---- the CI smoke tier -----------------------------------------------------

def test_smoke_mode_end_to_end():
    """`python -m ceph_tpu.bench --smoke` is the per-PR harness check:
    exit 0 on CPU, one schema-valid JSON line, fenced metrics with
    stats and a roofline verdict, in under a minute of measured time
    (the harness now spans 14 workloads — the budget is a
    minutes-scale canary, not a per-workload perf gate; those live in
    regress.py)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.bench", "--smoke"],
        capture_output=True, text=True, timeout=180, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert p.returncode == 0, p.stderr[-2000:]
    line = [ln for ln in p.stdout.splitlines() if ln.strip()][-1]
    out = json.loads(line)
    assert out["mode"] == "smoke" and out["platform"] == "cpu"
    assert out["elapsed_s"] < 60.0
    assert out["decode_parity"] is True
    names = set()
    for m in out["metrics"]:
        schema.validate_metric(m)
        names.add(m["name"])
        assert m["fenced"] is True
        assert {"median", "iqr", "min"} <= set(m["stats"])
        assert m["roofline"]["verdict"] in ("ok", "suspect", "unknown")
    assert {"ec_encode_k8m4_fenced", "ec_decode_k8m4_e2_fenced",
            "ec_dispatch_coalesce_fenced",
            "ec_dispatch_serial_fenced",
            "ec_pipeline_fenced", "ec_pipeline_depth1_fenced",
            "ec_mesh_fenced", "ec_mesh_single_fenced",
            "traffic_harness_smoke", "ec_recovery_storm",
            "ec_mesh_skew", "ec_mesh_straggler",
            "ec_degraded_read", "ec_write_zero_copy"} <= names
    # the coalesce metric carries its serial twin and speedup
    mc = next(m for m in out["metrics"]
              if m["name"] == "ec_dispatch_coalesce_fenced")
    assert mc["serial_gibs"] > 0 and mc["speedup"] > 0
    assert mc["batch_occupancy"] == mc["n_requests"] == 8
    # pipeline acceptance: a SINGLE submitter at depth 8 must fill real
    # batches (mean dispatch occupancy >= 4) and stay byte-identical to
    # the depth-1 passthrough
    mp = next(m for m in out["metrics"]
              if m["name"] == "ec_pipeline_fenced")
    assert mp["pipeline_depth"] == 8
    assert mp["mean_batch_occupancy"] >= 4, mp
    assert mp["identical"] is True
    assert mp["depth1_gibs"] > 0 and mp["speedup"] > 0
    # mesh acceptance (ceph_tpu/mesh): the 8-device CPU mesh smoke is
    # byte-identical to the single-device twin through the REAL
    # dispatch path, and the coalesced flush put work on EVERY chip
    mmesh = next(m for m in out["metrics"]
                 if m["name"] == "ec_mesh_fenced")
    assert mmesh["mesh_chips"] == 8 and mmesh["mesh_size"] == 8
    assert mmesh["identical"] is True
    assert mmesh["n_devices"] == 8
    assert len(mmesh["per_chip_stripes"]) == 8
    assert all(v > 0 for v in mmesh["per_chip_stripes"].values()), \
        mmesh["per_chip_stripes"]
    assert mmesh["single_gibs"] > 0 and mmesh["speedup"] > 0
    assert mmesh["plan_cache"] >= 1
    # the mesh leg's fence is drain_sharded + mesh_roofline: the
    # verdict must come back scaled by the mesh (never suspect on the
    # tiny smoke shapes) and the single twin keeps n_devices == 1
    assert mmesh["roofline"]["verdict"] in ("ok", "unknown")
    m1 = next(m for m in out["metrics"]
              if m["name"] == "ec_mesh_single_fenced")
    assert m1["n_devices"] == 1
    # traffic-harness acceptance (docs/QOS.md): >= 8 concurrent
    # synthetic clients, every op byte-exact, per-client p99 non-empty
    # in the bench JSON
    mt = next(m for m in out["metrics"]
              if m["name"] == "traffic_harness_smoke")
    assert mt["n_clients"] >= 8
    assert mt["byte_exact"] is True and not mt["errors"]
    assert mt["completed"] == mt["total_ops"] \
        == mt["n_clients"] * 32
    assert len(mt["per_client"]) == mt["n_clients"]
    for cname, st in mt["per_client"].items():
        assert st["p99"] > 0.0, (cname, st)
    assert mt["aggregate"]["p99"] > 0.0
    # telemetry acceptance: the end-of-run cluster rollup block rode
    # along, so harness A/B comparisons read ONE cluster tail number
    # per stage (mgr/telemetry.py) instead of per-daemon dumps
    roll = mt["cluster_rollup"]
    assert roll["oplat_p99_usec"].get("reply", 0) > 0, roll
    assert roll["oplat_p99_usec"].get("class_queue", 0) > 0, roll
    assert roll["rates"]["ops"] > 0, roll
    assert roll["samples"] >= 2 and "slo" in roll
    # recovery-storm acceptance (docs/RECOVERY.md): one OSD killed
    # under open-loop traffic at k8m4/d10 — the regenerating family's
    # bytes-moved-per-repaired-shard beats the RS full-stripe baseline
    # under the 0.6 gate, every object is byte-exact after backfill,
    # and the well-behaved clients' rollup raised no TPU_SLO_OPLAT
    mrs = next(m for m in out["metrics"]
               if m["name"] == "ec_recovery_storm")
    rec = mrs["recovery"]
    assert rec["bytes_per_repaired_shard_regen"] > 0
    assert rec["bytes_per_repaired_shard_rs"] > 0
    assert rec["regen_vs_rs_ratio"] <= 0.6, rec
    assert rec["families"]["pm-regen"]["repair_rounds"] > 0
    assert rec["families"]["isa-matrix"]["fullstripe_rounds"] > 0
    assert mrs["identical"] is True
    assert mrs["byte_exact_traffic"] is True
    assert mrs["slo"].get("TPU_SLO_OPLAT") == "ok", mrs["slo"]
    assert mrs["cluster_rollup"]["oplat_p99_usec"].get("reply", 0) > 0
    # straggler-ruler acceptance (ceph_tpu/mesh/chipstat): with one
    # chip slowed 10x via mesh.chip_slowdown the scoreboard marks
    # EXACTLY that chip suspect within the gate's probe window,
    # TPU_MESH_SKEW raises while the mgr ticks and clears after the
    # fault is removed, the healthy twin stays quiet, and skew
    # sampling never touched the data path (byte-identity receipt)
    msk = next(m for m in out["metrics"] if m["name"] == "ec_mesh_skew")
    sk = msk["skew"]
    assert 0 < sk["detection_probes"] <= regress.SKEW_MAX_DETECTION_PROBES
    assert sk["detected_chip"] == sk["slow_chip"]
    assert sk["skew_ratio_detected"] >= sk["threshold"]
    assert sk["healthy_false_suspects"] == 0
    assert sk["healthy_raised"] is False
    assert sk["raised"] is True and sk["cleared"] is True
    assert msk["identical"] is True
    assert out["gate"]["skew_compared"] >= 1
    # straggler-proof encode acceptance (ceph_tpu/mesh/rateless): with
    # one chip slowed 10x the rateless path keeps cluster_rollup
    # device_call p999 next to the healthy twin (the SPMD twin pays
    # the delay), detection receipts present, byte-identity holds,
    # the healthy twin pays < 2x coded bandwidth, and no protected
    # flush fell down the single-device ladder
    mstr = next(m for m in out["metrics"]
                if m["name"] == "ec_mesh_straggler")
    st = mstr["straggler"]
    assert 0 < st["detection_probes"] \
        <= regress.STRAGGLER_MAX_DETECTION_PROBES
    assert st["detected_chip"] == st["slow_chip"]
    assert st["skew_ratio_detected"] > 0
    assert 0 < st["protected_p999_ratio"] \
        <= regress.STRAGGLER_MAX_P999_RATIO
    assert 0 < st["protected_p999_wall_ratio"] \
        <= regress.STRAGGLER_MAX_WALL_P999_RATIO
    assert st["unprotected_p999_wall_ratio"] \
        > st["protected_p999_wall_ratio"]
    assert 1.0 < st["bandwidth_overhead"] \
        < regress.STRAGGLER_MAX_BANDWIDTH_OVERHEAD
    assert st["subset_completions"] > 0
    assert st["single_device_fallbacks"] == 0
    assert st["healthy_false_suspects"] == 0
    assert st["byte_identical"] is True and mstr["identical"] is True
    assert out["gate"]["straggler_compared"] >= 1
    # zero-copy acceptance (ISSUE 20): the resident leg of the A/B did
    # essentially no d2h on the write path (CRC scalars only, under
    # the 512 B/op gate), strictly beat the bytes twin on copies/op,
    # actually kept shards resident, and read back byte-exact
    mzc = next(m for m in out["metrics"]
               if m["name"] == "ec_write_zero_copy")
    zc = mzc["zero_copy"]
    assert zc["resident_d2h_bytes_per_op"] \
        < regress.ZERO_COPY_MAX_D2H_BYTES_PER_OP, zc
    assert zc["resident_copies_per_op"] < zc["twin_copies_per_op"], zc
    assert zc["resident_shards"] > 0
    assert zc["byte_exact"] is True
    assert mzc["twin_ops_per_sec"] > 0
    assert out["gate"]["zero_copy_compared"] >= 1
    # devprof acceptance: EVERY fenced workload emits a devflow block
    # with the gated per-op figures, and the dispatch/pipeline pairs
    # show coalescing as FEWER copies per op (the copy-budget story)
    for m in out["metrics"]:
        flow = m.get("devflow")
        assert isinstance(flow, dict), f"{m['name']}: no devflow block"
        assert {"h2d_bytes", "d2h_bytes", "transfers", "compiles",
                "copies_per_op", "bytes_per_op"} <= set(flow), m["name"]
        assert flow["copies_per_op"] >= 0
    flows = {m["name"]: m["devflow"] for m in out["metrics"]}
    assert flows["ec_dispatch_serial_fenced"]["copies_per_op"] > \
        flows["ec_dispatch_coalesce_fenced"]["copies_per_op"], \
        "coalescing did not reduce copies per op"
    assert flows["ec_pipeline_depth1_fenced"]["copies_per_op"] > \
        flows["ec_pipeline_fenced"]["copies_per_op"]
    assert flows["ec_dispatch_coalesce_fenced"]["h2d_bytes"] > 0
    # the run JSON also ships the per-site ledger (prof dump shape)
    assert flows and out["devprof"]["totals"]["transfers"] > 0
    assert "gf_matmul.encode" in out["devprof"]["sites"]
    # oplat acceptance: EVERY fenced workload emits a stage_breakdown
    # whose stage sum reconciles with its measured wall — coverage ~1
    # for serial regions; under coalescing each op accrues the SHARED
    # device call, so coverage approaches the occupancy (the story in
    # time units), never zero
    for m in out["metrics"]:
        sb = m.get("stage_breakdown")
        assert isinstance(sb, dict), f"{m['name']}: no stage_breakdown"
        assert sb["stages"], f"{m['name']}: empty stage_breakdown"
        assert sb["coverage"] > 0.2, (m["name"], sb)
        assert abs(sb["stage_sum_s"] - sum(
            s["total_usec"] for s in sb["stages"].values()) / 1e6) \
            < 1e-3, m["name"]
        shares = sum(s["share"] for s in sb["stages"].values())
        assert abs(shares - 1.0) < 0.02, (m["name"], shares)
        for st in sb["stages"].values():
            assert st["p50_usec"] <= st["p99_usec"]
    sbs = {m["name"]: m["stage_breakdown"] for m in out["metrics"]}
    # serial fenced regions reconcile tightly with wall
    for name in ("ec_encode_k8m4_fenced", "ec_decode_k8m4_e2_fenced",
                 "ec_dispatch_serial_fenced",
                 "ec_pipeline_depth1_fenced"):
        assert 0.5 <= sbs[name]["coverage"] <= 1.2, (name, sbs[name])
    # the occupancy story in time units (satellite): at depth 8 every
    # op waits in a real collection window (depth-1 flushes its own
    # batch immediately) and accrues the shared batched device call,
    # so per-op batch-window time grows and coverage tracks occupancy
    # while depth-1 stays device_call-dominated at coverage ~1
    p8, p1 = sbs["ec_pipeline_fenced"], sbs["ec_pipeline_depth1_fenced"]
    assert p8["stages"]["batch_window"]["usec_per_op"] > \
        p1["stages"].get("batch_window", {}).get("usec_per_op", 0.0), \
        (p8["stages"], p1["stages"])
    assert p8["coverage"] > 3.0 * p1["coverage"], (p8, p1)
    assert p1["stages"]["device_call"]["share"] > 0.5, p1
    assert sbs["ec_dispatch_coalesce_fenced"]["coverage"] > 2.0
    # the traffic workload decomposes the REAL op path: the mClock
    # class-queue wait under burst intake is a visible stage
    tsb = sbs["traffic_harness_smoke"]
    assert {"admission", "class_queue", "client_lane",
            "dequeue_handoff", "fan_out", "reply"} <= set(tsb["stages"])
    assert tsb["stages"]["class_queue"]["usec_per_op"] > 0
    # the run-level ledger rode along (latency dump shape)
    assert out["oplat"]["ops"] >= mt["completed"]
    assert out["oplat"]["stage_catalog"][0] == "client_flight"
    # the gate ran (warn mode) and the observability counters moved
    assert "gate" in out
    assert "stage_compared" in out["gate"]
    assert out["perf"]["dispatches"] > 0
    assert out["perf"]["fences"] > 0


def test_workload_metrics_in_process():
    """measure_encode/decode produce schema-valid fenced metrics on the
    test backend (tiny shapes — this is a harness test, not a perf
    run), and the shared kernel timer sees the fenced regions when
    tracing is enabled."""
    from ceph_tpu.bench import workloads
    from ceph_tpu.common.kernel_trace import g_kernel_timer
    from ceph_tpu.gf.matrices import gf_gen_rs_matrix

    rng = np.random.default_rng(7)
    matrix = gf_gen_rs_matrix(12, 8)
    batch = rng.integers(0, 256, size=(2, 8, 4096), dtype=np.uint8)
    g_kernel_timer.enable(True)
    try:
        m = workloads.measure_encode(matrix, batch, target_seconds=0.2,
                                     repeats=2, warmup=1)
        schema.validate_metric(m)
        assert m["fenced"] is True and m["value"] > 0
        m2 = workloads.measure_decode(matrix, batch, target_seconds=0.2,
                                      repeats=2, warmup=1)
        schema.validate_metric(m2)
        assert "bench_encode_fenced" in g_kernel_timer.dump()
    finally:
        g_kernel_timer.enable(False)
        g_kernel_timer.reset()
    assert workloads.parity_check(matrix) is True


def test_traffic_workload_in_process():
    """measure_traffic produces a schema-valid metric off a tiny run
    (harness shape test — throughput itself is measured by --smoke)
    and restores the admission config it set."""
    from ceph_tpu.bench import workloads
    from ceph_tpu.common.config import g_conf

    before = g_conf.values.get("osd_op_queue_admission_max")
    m = workloads.measure_traffic(n_clients=4, ops_per_client=8,
                                  n_osds=3, pg_num=4,
                                  admission_max=64, seed=3,
                                  name="traffic_tiny")
    schema.validate_metric(m)
    assert m["fenced"] is True and m["value"] > 0
    assert m["byte_exact"] is True
    assert m["completed"] == m["total_ops"] == 4 * 8
    assert len(m["per_client"]) == 4
    assert m["cluster_rollup"]["samples"] >= 1
    assert g_conf.values.get("mgr_telemetry_retention") is None, \
        "workload leaked the telemetry retention override"
    assert g_conf.values.get("osd_op_queue_admission_max") == before, \
        "workload leaked admission config"


def test_traffic_workload_rollup_survives_tiny_retention():
    """The whole-run cluster_rollup must keep the boot baseline even
    when the operator configured a ring too small for the run's tick
    count — the workload overrides retention for its own cluster and
    restores it after."""
    from ceph_tpu.bench import workloads
    from ceph_tpu.common.config import g_conf
    g_conf.set_val("mgr_telemetry_retention", 2)
    try:
        m = workloads.measure_traffic(n_clients=4, ops_per_client=8,
                                      n_osds=3, pg_num=4, seed=5,
                                      name="traffic_tiny_ret")
        # baseline + at least the final sample survived a ring the
        # operator sized at 2 (which would otherwise evict the boot
        # baseline and truncate the "whole-run" window to its tail)
        assert m["cluster_rollup"]["samples"] >= 2
        assert m["cluster_rollup"]["rates"]["ops"] > 0
        assert g_conf.get_val("mgr_telemetry_retention") == 2, \
            "workload clobbered the operator's retention value"
    finally:
        g_conf.rm_val("mgr_telemetry_retention")


def test_dispatch_coalesce_workload_in_process():
    """measure_dispatch_coalesce leaves the dispatcher drained and the
    config untouched, and both metric records validate."""
    from ceph_tpu.bench import workloads
    from ceph_tpu.common.config import g_conf
    from ceph_tpu.dispatch import g_dispatcher

    before = {n: g_conf.values.get(n) for n in
              ("ec_dispatch_batch_max", "ec_dispatch_batch_window_us")}
    mc, ms = workloads.measure_dispatch_coalesce(
        n_requests=4, object_bytes=16384, target_seconds=0.1,
        repeats=2, warmup=1)
    for m in (mc, ms):
        schema.validate_metric(m)
        assert m["fenced"] is True and m["value"] > 0
    assert mc["batch_occupancy"] == 4
    assert mc["speedup"] > 0 and mc["serial_gibs"] > 0
    assert g_dispatcher.dump()["pending"] == 0
    after = {n: g_conf.values.get(n) for n in before}
    assert after == before, "workload leaked dispatch config"
