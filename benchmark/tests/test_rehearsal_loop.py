"""The train_sequence_loop cell at a tiny size through run.py on the
CPU, traced and untraced (its own overlay: rehearse/loop-tiny.json),
what every new metric's reader returns, and a checkout whose block stack
has no loop."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import cells
from benchmark.tests.test_rehearsal import TESTS, on_the_cpu, run_py

CELL = "ouro-2.6b-l4.train-8k-loop"
ON_THE_CPU = {"seq_step_device_s.train-sequence-loop",
              "stage_persist_s.train-sequence-loop",
              "stage_algorithms_s.train-sequence-loop",
              "device_idle_pct.train-sequence-loop",
              "persist_serialize_s.train-sequence-loop",
              "persist_store_s.train-sequence-loop",
              "setup_warm_job_s.train-sequence-loop",
              "setup_compile_s.train-sequence-loop"}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_at_tiny_size(trace):
    done = run_py("--workload", CELL, "--seed", str(2 ** 31 + 17),
                  "--seconds", "1", "--trace", str(trace), "--rehearse",
                  os.path.join(TESTS, "rehearse", "loop-tiny.json"))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    compared = [l for l in lines[:-1] if l.startswith("compared: ")]
    assert len(compared) == 14 and not any("FAILED" in l for l in compared)
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and "rehearsal" in line
    assert line["device"]["platform"] == "cpu"
    if trace:
        # the CPU backend has no device plane: the scope and roofline
        # metrics have nothing to read and are left out
        assert set(line["metrics"]) == ON_THE_CPU
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert all(v["value"] >= 0 for v in line["metrics"].values())
    else:
        assert set(line["metrics"]) == {"setup_s", "train_ratings_per_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_the_cell_is_the_issues():
    cell = cells.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "train_ratings_per_s"]
    # `on_the_cpu` knows `span-self` by that name alone
    assert cell.chips == 1 and on_the_cpu(cell) | {
        "persist_serialize_s.train-sequence-loop",
        "persist_store_s.train-sequence-loop"} == ON_THE_CPU
    assert len(cell.per_layer) == 25
    t = cell.traffic
    assert (t["kind"], t["histories"], t["history_events"],
            t["batch_histories"], t["steps"], t["learning_rate"],
            t["zipf_exponent"]) == (
        "train_sequence_loop", 64, 8192, 2, 32, 1e-4, 1.1)
    assert t["histories"] == t["batch_histories"] * t["steps"]
    for m in cell.per_layer:
        assert m["workloads"] == [CELL]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            spec = cells.layer_metric_spec(m["name"])
            assert m["unit"] == "%" and spec["reader"] == "seq-roofline-loop"


def test_the_configuration_holds_the_catalogs_row():
    """Every key of the published config.json under its own name and
    value, but for the depth, the one key `reduced` lists."""
    config = cells.load_cell(CELL).config
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 4
    assert config["published"] == {"num_hidden_layers": 48}


def _metric_names():
    bench = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    return [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]


@pytest.mark.parametrize("name", _metric_names())
def test_a_reader_returns_a_number_or_none(name):
    """On a trace of a program without the seq.loop scopes (the
    parent's), a job log without a span record and no warm job every
    reader returns None or, for what needs none of them, a number; on
    evidence that has them, a number."""
    spec = cells.layer_metric_spec(name)
    reader = cells.module_for("readers", spec["reader"])
    bare = {"jobs": [{}], "trace": {"busy_s": 1.0, "window_s": 2.0},
            "counters": [{}], "config": {}, "traffic": {},
            "steps_in_window": 4, "device_kind": "TPU v5 lite",
            "rehearse": False}
    got = reader.read(spec, bare)
    if name.startswith(("seq_step_device_s", "device_idle_pct")):
        assert got in (0.25, 50.0)           # busy seconds need no scope
    else:
        assert got is None
    # a program with scopes, none of them the loop's
    if spec["reader"] == "seq-roofline-loop":
        other = dict(bare, trace={"busy_s": 1.0, "window_s": 2.0,
                                  "scope_s": {"seq.moe.gmm": 0.5}},
                     config=cells.load_cell(CELL).config,
                     traffic=cells.load_cell(CELL).traffic)
        assert reader.read(spec, other) is None
    scopes = spec.get("scopes") if isinstance(spec.get("scopes"), list) else []

    def row(name, parent, seconds, **labels):
        return {"name": name, "parent": parent, "start_s": 0.0,
                "duration_s": seconds, "labels": labels}

    # a job's span record, the warm job's with its programs' rows, and
    # the traced window's idle seconds by span
    job = [row("train", None, 60.0, process_age_s="17.5"),
           row("seq.dispatch", "train", 1.0),
           row("persist.d2h", "train", 0.25),
           row("persist.insert", "train", 2.0),
           row("models.file", "persist.insert", 1.5)]
    warm = job + [
        row("compile.trace", "seq.dispatch", 9.0, program="step"),
        row("compile.lower", "seq.dispatch", 2.0, program="jit(step)"),
        row("compile.backend", "seq.dispatch", 3.0, program="jit(step)",
            cache="hit"),
        row("compile.backend", "seq.init", 40.0, program="jit(make)",
            cache="miss")]
    full = dict(
        bare, jobs=[{"persist_s": 2.5, "algorithms_s": 55.0, "spans": job}],
        warm_job={"wall_s": 30.0, "compile_s": 14.0, "spans": warm},
        profile={"idle_by_span": {"models.file": 1.5, "seq.dispatch": 0.01,
                                  "seq.d2h": 0.3, "outside": 0.002}},
        config=cells.load_cell(CELL).config,
        traffic=cells.load_cell(CELL).traffic,
        trace={"busy_s": 7.0, "window_s": 8.0,
               "scope_s": {s: 0.4 for s in scopes} or {"seq.embed": 0.1}})
    value = reader.read(spec, full)
    assert isinstance(value, (int, float)) and value > 0
    if name.startswith("device_idle_s.rest"):
        assert value == pytest.approx(0.302)
    if spec["reader"] == "seq-roofline-loop":
        assert value < 100


def test_a_checkout_without_the_loop_fails_plainly(tmp_path):
    """The parent's program under this benchmark: exit code 1 within
    seconds, one plain line, no result."""
    fake = tmp_path / "pio_tpu" / "models"
    fake.mkdir(parents=True)
    (tmp_path / "pio_tpu" / "__init__.py").write_text("")
    (fake / "__init__.py").write_text("")
    (fake / "seq_blocks.py").write_text(
        "from dataclasses import dataclass\n\n\n@dataclass\n"
        "class BlockSpec:\n    hidden_size: int = 0\n"
        "    kv_lora_rank: int = 0\n    mtp_layers: int = 0\n")
    spec = tmp_path / "spec.json"
    spec.write_text(json.dumps({
        "rehearse": True, "config": {}, "out": "x",
        "traffic": {"kind": "train_sequence_loop"}}))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(tmp_path), cells.ROOT]), JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.drivers.train_sequence_loop_child",
         str(spec)], env=env, cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert done.returncode == 1
    assert "no looped stack" in done.stderr
    assert not (tmp_path / "x").exists()
