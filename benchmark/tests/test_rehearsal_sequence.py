"""The train_sequence cell at a tiny size through run.py on the CPU,
traced and untraced (its own overlay: rehearse/sequence-tiny.json), and
a checkout whose sequence engine takes no block specification."""

import json
import os

import pytest

from benchmark.harness import cells
from benchmark.tests.test_rehearsal import TESTS, on_the_cpu, run_py

CELL = "mellum2-12b-ep4.train-8k"


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_at_tiny_size(trace):
    done = run_py("--workload", CELL, "--seed", str(2 ** 31 + 17),
                  "--seconds", "1", "--trace", str(trace), "--rehearse",
                  os.path.join(TESTS, "rehearse", "sequence-tiny.json"))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    compared = [l for l in lines[:-1] if l.startswith("compared: ")]
    assert len(compared) == 11 and not any("FAILED" in l for l in compared)
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and "rehearsal" in line
    assert line["device"]["platform"] == "cpu"
    cell = cells.load_cell(CELL)
    if trace:
        # the CPU backend has no device plane: the scope, roofline and
        # idle-by-span metrics have nothing to read and are left out
        assert set(line["metrics"]) == on_the_cpu(cell)
        assert {"seq_step_device_s", "seq_expert_load_max_over_mean",
                "seq_expert_tiles_used_share",
                "stage_persist_s.train-sequence",
                "stage_algorithms_s.train-sequence",
                "device_idle_pct.train-sequence", "persist_serialize_s",
                "persist_store_s", "setup_warm_job_s",
                "setup_compile_s"} == on_the_cpu(cell)
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    else:
        assert set(line["metrics"]) == {"setup_s", "train_ratings_per_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_the_cell_reports_every_metric_it_lists():
    cell = cells.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "train_ratings_per_s"]
    bench = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    assert len(cell.per_layer) > len(on_the_cpu(cell)) >= 10
    for m in cell.per_layer:
        spec = cells.layer_metric_spec(m["name"])
        assert hasattr(cells.module_for("readers", spec["reader"]), "read")
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]


def test_readers_find_nothing_where_the_program_has_no_scopes():
    """A trace of a program without the seq.* scopes (the parent's), or a
    job log without the counters: every new reader returns None."""
    evidence = {"jobs": [{}], "trace": {"busy_s": 1.0, "window_s": 2.0},
                "counters": [{}], "config": {}, "traffic": {},
                "device_kind": "TPU v5 lite", "rehearse": False}
    for name in ("seq_step_device_s", "seq_attn_window_device_s",
                 "seq_attn_kernel_roofline", "seq_moe_gmm_roofline",
                 "seq_expert_load_max_over_mean"):
        spec = cells.layer_metric_spec(name)
        reader = cells.module_for("readers", spec["reader"])
        assert reader.read(spec, evidence) is None
        if name != "seq_step_device_s":        # busy seconds need no scope
            assert reader.read(
                spec, dict(evidence, steps_in_window=4)) is None
