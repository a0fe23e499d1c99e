"""The harness refuses to run off the chip, never writes a CPU number under a
device metric's name, and finds every cell, configuration and metric of
BENCHMARK.json as files of their own."""

import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import harness

from .conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    cmd = [sys.executable, "benchmark/run.py", "--seed", "1", "--seconds", "1", "--trace", "0", *args]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("workload", [w["name"] for w in harness.load_benchmark()["workloads"]])
def test_no_chip_no_result(workload):
    p = _run(ROOT, "--workload", workload)
    assert p.returncode == harness.EXIT_NO_CHIP, p.stderr[-2000:]
    assert p.stdout.strip() == "" and "no result" in p.stderr


def test_no_program_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "--workload", harness.load_benchmark()["workloads"][0]["name"])
    assert p.returncode == harness.EXIT_NO_PROGRAM and p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        harness.peaks_for("cpu")


def test_without_a_device_trace_no_device_metric_is_written():
    bench = harness.load_benchmark()
    for cell_name in (w["name"] for w in bench["workloads"]):
        cell = harness.Cell(cell_name, bench)
        obs = {"trace": {}, "counters": {}, "host": {}, "work": {}, "window_s": 1.0, "chips": 1, "peak": None}
        assert harness.per_layer_metrics(cell, obs) == {}
        from_trace = {m["name"] for m in cell.metrics("per_layer") if m["source"] == "device_trace"}
        obs.update(counters={"sched_rounds_total": 4, "dispatch_tokens_total": 8, "dispatch_tokens_real_total": 6},
                   host={"data_wait_s": 0.1, "data_waits": 4}, work={"required_flops": 1e12},
                   peak=harness.peaks_for("TPU v5 lite"))
        got = harness.per_layer_metrics(cell, obs)
        assert got and not from_trace & set(got)


def test_benchmark_json_names_files_that_exist():
    bench = harness.load_benchmark()
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/") and os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = harness.Cell(w["name"], bench)
        assert cell.workload["driver"] in ("train", "serve") and cell.workload["limits"]
        assert len(cell.metrics("end_to_end")) >= 2 and cell.metrics("per_layer")
    for m in bench["per_layer"]:
        spec = harness.load_json("metrics", f"{m['name']}.json")
        assert callable(harness.load_reader(spec["reader"])) and m["moves"] in e2e and NAME.match(m["name"])
        movers = {x["name"]: x for x in bench["end_to_end"]}[m["moves"]].get("workloads")
        assert movers is None or set(m["workloads"]) <= set(movers)
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")


def test_percentile_and_check():
    assert harness.percentile([1, 2, 3, 4, 5], 50) == 3 and harness.percentile([7], 95) == 7
    assert harness.percentile(list(range(101)), 95) == 95
    c = harness.Check()
    assert not c.correct  # nothing compared is not correct
    c.add("a", 0.5, 1.0)
    assert c.correct
    c.add("b", float("nan"), 1.0)
    assert not c.correct and c.as_dict()["a"] == {"value": 0.5, "limit": 1.0}
