"""The train_sequence_gated cell at a tiny size through run.py on the
CPU, traced and untraced (its own overlay: rehearse/gated-tiny.json),
what every new metric's reader returns, and a checkout whose block stack
has no head counts by layer kind and no gate."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import cells
from benchmark.tests.test_rehearsal import TESTS, on_the_cpu, run_py

CELL = "laguna-xs2-ep16.train-8k-gated"
SUF = ".train-sequence-gated"
# the accepted metrics whose `workloads` the cell was appended to (the
# driver's contract caps `per_layer` at 128 entries: CHANGES.md, PR 49)
SHARED = {
    "setup_warm_job_s", "setup_compile_s", "setup_trace_s", "setup_lower_s",
    "setup_load_s", "setup_build_s", "setup_cache_misses",
    "setup_before_job_s", "seq_expert_tiles_used_share",
    "seq_expert_held_share", "seq_expert_load_max_over_mean",
    "seq_optimizer_device_s", "seq_moe_shared_device_s",
    "stage_algorithms_s.train-sequence", "seq_step_device_s",
    "device_idle_pct.train-sequence", "seq_attn_window_device_s",
    "seq_attn_full_device_s", "seq_moe_device_s", "seq_mlp_dense_device_s"}
OWN = {name + SUF for name in (
    "seq_step_mfu", "seq_attn_proj_device_s", "seq_attn_kernel_roofline",
    "seq_moe_gmm_roofline", "stage_persist_s", "seq_head_loss_device_s",
    "persist_serialize_s", "persist_store_s", "device_idle_s.persist",
    "device_idle_s.host_prep", "device_idle_s.rest")} | {
        "seq_attn_gate_device_s", "seq_expert_tile_fill"}
NAMES = OWN | SHARED
# the profile view (seconds by scope and by span, the warm job's parts
# by span) needs a device plane: a CPU rehearsal reads the rest
ON_THE_CPU = {"seq_step_device_s", "seq_expert_tile_fill",
              "device_idle_pct.train-sequence", "stage_persist_s" + SUF,
              "seq_expert_held_share", "seq_expert_load_max_over_mean",
              "seq_expert_tiles_used_share", "setup_compile_s",
              "setup_warm_job_s", "stage_algorithms_s.train-sequence"}
# `on_the_cpu` knows `span-self` by that name alone: the persist copies
# read through `span-self-loop` and have evidence here too
PERSIST = {"persist_serialize_s" + SUF, "persist_store_s" + SUF}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_at_tiny_size(trace):
    done = run_py("--workload", CELL, "--seed", str(2 ** 31 + 17),
                  "--seconds", "1", "--trace", str(trace), "--rehearse",
                  os.path.join(TESTS, "rehearse", "gated-tiny.json"))
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
        assert 0 < line["metrics"]["seq_expert_tile_fill"]["value"] <= 1
    else:
        assert set(line["metrics"]) == {"setup_s", "train_ratings_per_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_the_cell_reports_every_metric_it_lists():
    cell = cells.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "train_ratings_per_s"]
    assert cell.chips == 1 and on_the_cpu(cell) == ON_THE_CPU
    assert {m["name"] for m in cell.per_layer} == NAMES
    for m in cell.per_layer:
        assert (m["workloads"] == [CELL]) == (m["name"] in OWN)
        assert m["moves"] == ("setup_s" if m["name"].startswith("setup_")
                              else "train_ratings_per_s")
        spec = cells.layer_metric_spec(m["name"])
        assert hasattr(cells.module_for("readers", spec["reader"]), "read")
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        if "_roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and spec["reader"] == "seq-roofline-gated"


def test_the_traffic_is_the_issues():
    traffic = cells.load_cell(CELL).traffic
    assert {k: traffic[k] for k in (
        "kind", "histories", "history_events", "batch_histories", "steps",
        "learning_rate", "zipf_exponent")} == {
            "kind": "train_sequence_gated", "histories": 128,
            "history_events": 8192, "batch_histories": 2, "steps": 64,
            "learning_rate": 0.0001, "zipf_exponent": 1.1}
    assert traffic["histories"] == traffic["steps"] * traffic[
        "batch_histories"]                    # every history once a job
    assert traffic["steps"] * traffic["batch_histories"] * traffic[
        "history_events"] == 1_048_576        # target events a job


@pytest.mark.parametrize("name", sorted(OWN))
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
    if name.startswith(("persist_", "device_idle_s")):
        return      # a generic metric's copy: its reader is the accepted one
    scopes = spec.get("scopes") if isinstance(spec.get("scopes"), list) else []
    full = dict(
        bare, jobs=[{"persist_s": 2.5}],
        counters=[{"expert_tokens_mean": "500.0",
                   "expert_tile_fill": "0.45"}],
        config=cells.load_cell(CELL).config,
        traffic=cells.load_cell(CELL).traffic,
        trace={"busy_s": 4.0, "window_s": 5.0,
               "scope_s": {s: 0.5 for s in scopes} or {"seq.embed": 0.1}})
    value = reader.read(spec, full)
    assert isinstance(value, float) and value > 0
    if spec["reader"] == "seq-roofline-gated":
        assert value < 100


def test_a_checkout_without_the_layers_fails_plainly(tmp_path):
    """The parent's program under this benchmark: exit code 1 within
    seconds, one plain line, no result."""
    fake = tmp_path / "pio_tpu" / "models"
    fake.mkdir(parents=True)
    (tmp_path / "pio_tpu" / "__init__.py").write_text("")
    (fake / "__init__.py").write_text("")
    (fake / "seq_blocks.py").write_text(
        "from dataclasses import dataclass\n\n\n@dataclass\n"
        "class BlockSpec:\n    hidden_size: int = 0\n"
        "    block_kinds: tuple = ()\n")
    spec = tmp_path / "spec.json"
    spec.write_text(json.dumps({"rehearse": True, "config": {}, "out": "x"}))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(tmp_path), cells.ROOT]), JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.drivers.train_sequence_gated_child",
         str(spec)], env=env, cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert done.returncode == 1
    assert "no query-head counts" in done.stderr
    assert not (tmp_path / "x").exists()
