"""Every ALS cell at a tiny size through run.py on the CPU, both --trace
values, `source: "events"` included, four virtual devices for the
sharded cell; and the shape of the last line. (The block-stack cells have
overlays and files of their own: test_rehearsal_sequence.py,
test_rehearsal_latent.py.)"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import cells

TESTS = os.path.dirname(__file__)
BENCH = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
CASES = [(w["name"], "als-tiny.json", t)
         for w in BENCH["workloads"] for t in (0, 1)
         if cells.load_cell(w["name"]).traffic["kind"] == "train"]
CASES.append(("ml20m-r64.train-coo", "als-tiny-events.json", 0))


def on_the_cpu(cell) -> set[str]:
    """The per-layer metrics of a cell whose readers have evidence in a
    CPU rehearsal: the job records, the span trees, the warm job and the
    busy seconds. A roofline and the profile view (seconds by scope and
    by span, which need a device plane) have none."""
    readers = {"train-log", "span-self", "warm-job", "trace-busy",
               "seq-counter"}
    names = set()
    for m in cell.per_layer:
        spec = cells.layer_metric_spec(m["name"])
        if spec["reader"] in readers or spec.get("scopes") == "all":
            names.add(m["name"])
    return names


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
        want = on_the_cpu(cell)
        assert set(line["metrics"]) == want
        assert {"sweep_device_s", "stage_persist_s", "device_idle_pct.train",
                "host_prep_s", "persist_serialize_s", "persist_store_s",
                "setup_warm_job_s", "setup_compile_s"} <= want
        assert ("read_scan_s" in want) == (
            cell.traffic["source"] == "events")
        parts = (line["metrics"]["persist_serialize_s"]["value"]
                 + line["metrics"]["persist_store_s"]["value"])
        assert 0 < parts <= line["metrics"]["stage_persist_s"]["value"] + 5e-3
        assert len(line["breakdown"]["device_ops"]) <= 10
        gaps = line["breakdown"]["idle_gaps"]
        # no device plane on the CPU: no view to name a gap from
        assert 0 < len(gaps) <= 10
        assert all(name == "no profile view" for name, _ in gaps)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
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
