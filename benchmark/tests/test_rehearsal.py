"""Every cell at a tiny size through run.py on the CPU, both --trace
values, `source: "events"` included, four virtual devices for the
sharded cell; and the shape of the last line."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import cells

TESTS = os.path.dirname(__file__)
BENCH = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
CASES = [(w["name"], "als-tiny.json", t)
         for w in BENCH["workloads"] for t in (0, 1)]
CASES.append(("ml20m-r64.train-coo", "als-tiny-events.json", 0))


def run_py(*args, cwd=cells.ROOT):
    return subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"), *args],
        cwd=cwd, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("workload,overlay,trace", CASES)
def test_cell_at_tiny_size(workload, overlay, trace):
    done = run_py("--workload", workload, "--seed", str(2 ** 31 + 17),
                  "--seconds", "1", "--trace", str(trace), "--rehearse",
                  os.path.join(TESTS, "rehearse", overlay))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    assert any(l.startswith("compared: ") for l in lines[:-1])
    line = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and "rehearsal" in line
    cell = cells.load_cell(workload)
    device = line["device"]
    assert device["platform"] == "cpu" and device["count"] >= cell.chips
    assert "memory_peak_bytes" in device
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}
        assert isinstance(value["value"], (int, float))
    if trace:
        assert 0 < device["busy_s"] <= device["window_s"]
        assert set(line["metrics"]) <= {m["name"] for m in cell.per_layer}
        assert "sweep_device_s" in line["metrics"]
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_no_result_without_a_chip():
    done = run_py("--workload", BENCH["workloads"][0]["name"], "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "NO RESULT" in done.stderr and not done.stdout.strip()


def test_no_result_outside_a_checkout(tmp_path):
    import shutil

    shutil.copytree(cells.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert done.returncode != 0 and not done.stdout.strip()
