"""The command refuses the CPU, and BENCHMARK.json keeps to the format."""
import json
import os
import re
import subprocess
import sys

from benchmark.harness import BENCH_DIR, ROOT, Cell, load_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "ec_k8m4.write_4m", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""


def test_benchmark_json_format_and_files():
    b = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert len(json.dumps(b)) < 64 * 1024
    assert 1 <= b["run_seconds"] <= 51
    for entry in b["configs"] + b["workloads"] + b["end_to_end"] + \
            b["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        cell = Cell(w["name"], b)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in names and m["moves"] in e2e
            cell.metric_reader(m)        # file exists, keys agree
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
