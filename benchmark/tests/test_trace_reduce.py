"""trace_reduce on a synthetic trace with known answers, and on a trace
recorded here (host spans only: the CPU has no device plane)."""
import glob
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_f(12)", 100, 300),
                                       ev("jit_g(3)", 600, 100)]),
        NS(name="XLA Ops", events=[ev("fusion.1", 100, 200),
                                   ev("copy.2", 250, 150),
                                   ev("fusion.1", 600, 100),
                                   ev("late", 1500, 100)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("window", 50, 1000),
        ev("network.pump", 60, 500),
        ev("submit", 450, 50),
        ev("network.pump", 700, 340),
        ev("other", 0, 2000)])])
    return [dev, host]


def test_busy_programs_ops_and_gaps():
    r = trace_reduce.reduce_planes(planes(), "window",
                                   ("submit", "network.pump"))
    assert r["window_s"] == pytest.approx(1000e-9)
    # ops union: [100, 400) and [600, 700) inside [50, 1050)
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["programs"] == pytest.approx({"jit_f": 300e-9,
                                           "jit_g": 100e-9})
    assert r["ops"]["fusion.1"] == pytest.approx(300e-9)
    assert "late" not in r["ops"]
    gaps = r["idle_gaps"]
    assert [round(d * 1e9) for _n, d in gaps] == [350, 200, 50]
    # [700, 1050) mid 875: second pump; [400, 600) mid 500: submit
    # (innermost); [50, 100) mid 75: first pump
    assert [n for n, _d in gaps] == ["network.pump", "submit",
                                     "network.pump"]
    b = trace_reduce.breakdown(r, top=2)
    assert b["device_ops"][0][0] == "fusion.1"
    assert len(b["idle_gaps"]) == 2


def test_window_span_required():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(planes()[:1], "window", ())


def test_recorded_host_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from benchmark.harness import profiler
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    with profiler(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("network.pump"):
                f(jnp.ones(8)).block_until_ready()
    r = trace_reduce.reduce_trace(trace_reduce.find_trace(str(tmp_path)),
                                  "window", ("network.pump",))
    assert r["window_s"] > 0
    assert r["devices"] == 0 and r["busy_s"] == 0.0


@pytest.mark.skipif(not glob.glob(os.path.join(DATA, "*.xplane.pb")),
                    reason="no recorded chip trace")
def test_recorded_chip_trace():
    """A short window recorded on a v5e (see data/README)."""
    path = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))[0]
    r = trace_reduce.reduce_trace(path, "window", ("network.pump",))
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["programs"]
    total_gap = sum(d for _n, d in r["idle_gaps"])
    assert total_gap == pytest.approx(r["window_s"] - r["busy_s"],
                                      rel=1e-6)
