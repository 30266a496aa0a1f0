"""The train_sequence_ssm cell at a tiny size through run.py on the CPU,
traced and untraced (its own overlay: rehearse/ssm-tiny.json), what
every new metric's reader returns, and a checkout whose block stack has
no single-mixer blocks."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import cells
from benchmark.tests.test_rehearsal import TESTS, on_the_cpu, run_py

CELL = "nemotron-3-nano-ep16.train-8k-ssm"
SUF = ".train-sequence-ssm"
# `on_the_cpu` knows `span-self` by that name alone: the persist copies
# read through `span-self-loop` and have evidence here too
ON_THE_CPU = {name + SUF for name in (
    "seq_step_device_s", "seq_expert_load_max_over_mean",
    "seq_expert_held_share", "seq_expert_tiles_used_share",
    "stage_persist_s", "stage_algorithms_s", "device_idle_pct",
    "setup_warm_job_s", "setup_compile_s")}
PERSIST = {"persist_serialize_s" + SUF, "persist_store_s" + SUF}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_at_tiny_size(trace):
    done = run_py("--workload", CELL, "--seed", str(2 ** 31 + 17),
                  "--seconds", "1", "--trace", str(trace), "--rehearse",
                  os.path.join(TESTS, "rehearse", "ssm-tiny.json"))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    compared = [l for l in lines[:-1] if l.startswith("compared: ")]
    assert len(compared) == 17 and not any("FAILED" in l for l in compared)
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and "rehearsal" in line
    assert line["device"]["platform"] == "cpu"
    if trace:
        # the CPU backend has no device plane: the scope and roofline
        # metrics have nothing to read and are left out
        assert set(line["metrics"]) == ON_THE_CPU | PERSIST
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert 0.2 < line["metrics"][
            "seq_expert_held_share" + SUF]["value"] < 3
    else:
        assert set(line["metrics"]) == {"setup_s", "train_ratings_per_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_the_cell_reports_every_metric_it_lists():
    cell = cells.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "train_ratings_per_s"]
    assert cell.chips == 1 and on_the_cpu(cell) == ON_THE_CPU
    assert len(cell.per_layer) == 33
    for m in cell.per_layer:
        assert m["workloads"] == [CELL]
        spec = cells.layer_metric_spec(m["name"])
        assert hasattr(cells.module_for("readers", spec["reader"]), "read")
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        if m["name"].endswith("_roofline" + SUF) or m["name"].endswith(
                "_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and spec["reader"] == "seq-roofline-ssm"


def test_the_traffic_is_the_issues():
    traffic = cells.load_cell(CELL).traffic
    assert {k: traffic[k] for k in (
        "kind", "histories", "history_events", "batch_histories", "steps",
        "learning_rate", "zipf_exponent")} == {
            "kind": "train_sequence_ssm", "histories": 96,
            "history_events": 8192, "batch_histories": 2, "steps": 48,
            "learning_rate": 0.0001, "zipf_exponent": 1.1}
    config = cells.load_cell(CELL).config
    assert traffic["history_events"] % config["chunk_size"] == 0
    assert traffic["histories"] == traffic["steps"] * traffic[
        "batch_histories"]                    # every history once a job


def _metric_names():
    bench = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    return [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]


@pytest.mark.parametrize("name", _metric_names())
def test_a_reader_returns_a_number_or_none(name):
    """On a trace of a program without the seq.* scopes (the parent's)
    and a job log without the counters every reader returns None or, for
    what needs neither, a number; on evidence that has them, a number."""
    spec = cells.layer_metric_spec(name)
    reader = cells.module_for("readers", spec["reader"])
    bare = {"jobs": [{}], "trace": {"busy_s": 1.0, "window_s": 2.0},
            "counters": [{}], "config": {}, "traffic": {},
            "steps_in_window": 4, "device_kind": "TPU v5 lite",
            "rehearse": False, "warm_job": {}, "profile": None}
    got = reader.read(spec, bare)
    if name.startswith(("seq_step_device_s", "device_idle_pct")):
        assert got in (0.25, 50.0)           # busy seconds need no scope
    else:
        assert got is None
    if not name.startswith("seq_"):
        return          # a generic metric's copy: test_contract_ssm.py
    scopes = spec.get("scopes") if isinstance(spec.get("scopes"), list) else []
    full = dict(
        bare, counters=[{"expert_tokens_mean": "700.0",
                         "expert_load_max_over_mean": "1.4",
                         "expert_tokens_held_share": "0.9",
                         "expert_tiles_used_share": "0.3"}],
        config=cells.load_cell(CELL).config,
        traffic=cells.load_cell(CELL).traffic,
        trace={"busy_s": 4.0, "window_s": 5.0,
               "scope_s": {s: 0.5 for s in scopes} or {"seq.embed": 0.1}})
    value = reader.read(spec, full)
    assert isinstance(value, float) and value > 0
    if spec["reader"] == "seq-roofline-ssm":
        assert value < 100


def test_a_checkout_without_the_blocks_fails_plainly(tmp_path):
    """The parent's program under this benchmark: exit code 1 within
    seconds, one plain line, no result."""
    fake = tmp_path / "pio_tpu" / "models"
    fake.mkdir(parents=True)
    (tmp_path / "pio_tpu" / "__init__.py").write_text("")
    (fake / "__init__.py").write_text("")
    (fake / "seq_blocks.py").write_text(
        "from dataclasses import dataclass\n\n\n@dataclass\n"
        "class BlockSpec:\n    hidden_size: int = 0\n    loop_steps: int = 0\n")
    spec = tmp_path / "spec.json"
    spec.write_text(json.dumps({"rehearse": True, "config": {}, "out": "x"}))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(tmp_path), cells.ROOT]), JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.drivers.train_sequence_ssm_child",
         str(spec)], env=env, cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert done.returncode == 1
    assert "no single-mixer blocks" in done.stderr
    assert not (tmp_path / "x").exists()
