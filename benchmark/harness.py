"""One run of one cell: everything that is the same for every cell.

The harness finds what a cell needs by name and never by code:

- the cell (configuration, traffic, chips) in ``BENCHMARK.json``;
- the configuration in the file that ``BENCHMARK.json`` names for it;
- the traffic mix in ``benchmark/traffic/<traffic>.json``, which names
  its driver, ``benchmark/drivers/<driver>.py``;
- each per-layer metric in ``benchmark/metrics/<metric>.py``.

A driver module has ``build(config, traffic, seed, span, log)``, which
does the set-up and returns an object with ``window(seconds)`` and
``check()``.  A metric module has ``LAYER``, ``SOURCE``, ``UNIT``,
``MOVES`` and ``read(run)``, which returns a number or None.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from types import ModuleType, SimpleNamespace
from typing import Callable, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(BENCH_DIR, "out")
WINDOW_SPAN = "window"
HOST_SPANS = ("submit", "network.pump", "tick", "mapping.update")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A cell of BENCHMARK.json with its configuration, traffic, driver
    and metrics resolved."""

    def __init__(self, name: str, bench: Optional[Dict] = None):
        self.bench = bench if bench is not None else \
            load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        confs = {c["name"]: c for c in self.bench["configs"]}
        self.config = load_json(os.path.join(
            ROOT, confs[self.entry["config"]]["file"]))
        self.traffic = load_json(os.path.join(
            BENCH_DIR, "traffic", f"{self.entry['traffic']}.json"))
        self.driver = load_module(
            os.path.join(BENCH_DIR, "drivers",
                         f"{self.traffic['driver']}.py"),
            f"benchmark_driver_{self.traffic['driver']}")
        self.end_to_end = [m for m in self.bench["end_to_end"]
                           if "workloads" not in m
                           or name in m["workloads"]]
        # every per-layer metric lists the cells it reads something in
        self.per_layer = [m for m in self.bench["per_layer"]
                          if name in m["workloads"]]

    def metric_reader(self, entry: Dict) -> ModuleType:
        mod = load_module(os.path.join(BENCH_DIR, "metrics",
                                       f"{entry['name']}.py"),
                          "benchmark_metric_" + entry["name"].replace(
                              ".", "_"))
        for key in ("unit", "source", "moves", "layer"):
            if getattr(mod, key.upper()) != entry[key]:
                raise ValueError(f"metric {entry['name']}: {key} differs "
                                 "between its reader and BENCHMARK.json")
        return mod


def configure_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` when set,
    else at the fixed ``<checkout>/.jax_cache``; every program is kept,
    so only a cell's first run in a checkout compiles."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def require_chips(n: int) -> Dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's default device is {devs[0].platform}, not a "
                     "TPU; the benchmark never runs on the CPU")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


def memory_peak_bytes(n: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileMeter:
    """Programs compiled fresh and loaded from the persistent cache."""

    def __init__(self):
        import jax
        self.fresh = self.hits = 0
        self.compile_s = 0.0
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event != "/jax/core/compile/backend_compile_duration":
            return
        if self._hit:
            self.hits += 1
        else:
            self.fresh += 1
            self.compile_s += duration
        self._hit = False

    def snapshot(self) -> Dict:
        return {"fresh": self.fresh, "cache_hits": self.hits,
                "compile_s": self.compile_s}


def span_factory(tracing: bool) -> Callable:
    """Host spans from the benchmark's own files, into the profiler's
    trace; nothing at all when the run is not traced."""
    if not tracing:
        return lambda _name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


@contextlib.contextmanager
def profiler(trace_dir: str):
    import jax
    from jax.profiler import ProfileOptions
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1      # TraceAnnotation spans, no runtime noise
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def layer_metrics(cell: Cell, run: SimpleNamespace) -> Dict:
    out = {}
    for entry in cell.per_layer:
        value = cell.metric_reader(entry).read(run)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_process: float, require: Callable = require_chips) -> Dict:
    """Set up, measure and check one cell; returns the result line's
    object.  ``require`` is the chip check (tests pass their own)."""
    cell = Cell(name)
    cache = configure_compile_cache()
    device = require(cell.chips)
    meter = CompileMeter()
    log(f"cell {name} seed {seed} seconds {seconds} trace {int(trace)} "
        f"on {device['kind']} x{device['count']}; compile cache {cache}")
    span = span_factory(trace)
    bench = cell.driver.build(cell.config, cell.traffic, seed, span, log)
    # the set-up's garbage is collected in the set-up, not in the window
    gc.collect()
    c_setup = meter.snapshot()
    setup_s = time.perf_counter() - t_process
    log(f"set-up {setup_s:.3f} s: {json.dumps(c_setup)}")
    trace_dir = os.path.join(OUT_DIR, "trace", name)
    ctx = profiler(trace_dir) if trace else contextlib.nullcontext()
    with ctx:
        res = bench.window(seconds)
    c_win = {k: v - c_setup[k] for k, v in meter.snapshot().items()}
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
    log(f"window: {json.dumps(res['info'])}; compiles inside it: "
        f"{json.dumps(c_win)}")
    if c_win["fresh"] or c_win["cache_hits"]:
        log("WARNING: programs compiled or loaded inside the window")
    checks = bench.check()
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    out: Dict = {"correct": bool(correct), "attempted": res["attempted"],
                 "failed": res["failed"]}
    if trace:
        from benchmark import trace_reduce
        from benchmark.peaks import peak_for
        red = trace_reduce.reduce_trace(
            trace_reduce.find_trace(trace_dir), WINDOW_SPAN, HOST_SPANS)
        run = SimpleNamespace(cell=name, result=res, trace=red,
                              peaks=peak_for(device["kind"]),
                              config=cell.config, traffic=cell.traffic)
        out["metrics"] = layer_metrics(cell, run)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = trace_reduce.breakdown(red)
        programs = sorted(red["programs"].items(), key=lambda kv: -kv[1])
        log(f"trace: busy {red['busy_s']:.6f} s of {red['window_s']:.6f} s;"
            f" programs {json.dumps(programs[:12])}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = dict(res["e2e"], setup_s=setup_s)
        metrics = {}
        for m in cell.end_to_end:
            value = e2e.get(m["name"])
            if value is None:
                raise RuntimeError(f"the run gave no {m['name']}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
    out["device"] = device
    out["checks"] = checks
    os.makedirs(OUT_DIR, exist_ok=True)
    record = {"seed": seed, "seconds": seconds, "trace": trace,
              "setup_s": setup_s, "setup_compiles": c_setup,
              "window_compiles": c_win, "info": res["info"],
              "layer": res["layer"], "timeline": res.get("timeline"),
              "result": out}
    if trace:
        record["trace"] = {k: red[k] for k in ("layout", "programs")}
        record["trace"]["idle_gaps_top"] = red["idle_gaps"][:50]
    with open(os.path.join(OUT_DIR, f"{name}.last.json"), "w") as f:
        json.dump(record, f, indent=1, default=str)
    return out


def print_result(out: Dict) -> None:
    for cname, c in out["checks"].items():
        print(f"[benchmark] check {cname}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
