"""The span contract of benchmark/tests/test_span_contract.py, for what
PR 45 added: every `layer_metrics/*.json` of the state-space cell names a
reader that exists, and every scope a `seq-scope` or `seq-roofline-ssm`
metric lists there is a `jax.named_scope` path of the step program
pio_tpu/models/seq_blocks.py compiles for the cell's configuration (at
the rehearsal's tiny size); the cell's copies of the generic set-up,
persist and idle metrics read what the generic ones read. A scope
renamed in the program fails here, not a metric silently on the chip."""

import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import engines_sequence as es
from benchmark.harness import cells, profile
from benchmark.tests.test_rehearsal import TESTS

CELL = "nemotron-3-nano-ep16.train-8k-ssm"
SUF = ".train-sequence-ssm"
BENCH = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
OVERLAY = os.path.join(TESTS, "rehearse", "ssm-tiny.json")
NEW = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
LISTED = {m["name"]: m for m in BENCH["per_layer"]}
SCOPES = {"seq.ssm.proj", "seq.ssm.conv", "seq.ssm.scan", "seq.moe.route",
          "seq.moe.gmm", "seq.moe.combine", "seq.moe.shared",
          "seq.attn.proj", "seq.attn.full", "seq.head_loss",
          "seq.optimizer", "seq.embed"}


@pytest.fixture(scope="module")
def scopes():
    from pio_tpu.models import seq_blocks

    cell = cells.load_cell(CELL, OVERLAY)
    spec = seq_blocks.BlockSpec.parse(es.block_spec_of(cell.config))
    optimizer, step = seq_blocks.make_train_step(spec, 0.0197)
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        seq_blocks.param_shapes(spec), is_leaf=lambda x: isinstance(x, tuple))
    text = step.lower(
        shapes, jax.eval_shape(optimizer.init, shapes),
        jax.ShapeDtypeStruct((2, 41), jnp.int32)).compile().as_text()
    return {profile.scope_of_op_name(n)
            for n in profile._OP_NAME.findall(text)}


def test_the_new_entries_are_the_issues():
    assert len(NEW) == 33
    assert {m["moves"] for m in NEW} == {"train_ratings_per_s", "setup_s"}
    assert all(m["name"].startswith("setup_") for m in NEW
               if m["moves"] == "setup_s")
    own = {"seq_ssm_scan_device_s", "seq_ssm_conv_device_s",
           "seq_ssm_proj_device_s", "seq_ssm_scan_roofline"}
    assert {m["name"] for m in NEW if not m["name"].endswith(SUF)} == own
    assert BENCH["configs"][-1]["name"] == "nemotron-3-nano-ep16"
    assert BENCH["configs"][-1]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert BENCH["workloads"][-1] == {
        "name": CELL, "config": "nemotron-3-nano-ep16",
        "traffic": "train-sequence-ssm", "chips": 1,
        "why": BENCH["workloads"][-1]["why"]}
    assert len(BENCH["workloads"]) == 7 and len(BENCH["configs"]) == 7
    # new entries stand at the end of their lists
    assert BENCH["per_layer"][-len(NEW):] == NEW
    assert all(len(entry["why"]) <= 200 for entry in (
        BENCH["workloads"][-1], BENCH["configs"][-1]))


def test_the_step_carries_the_scopes_the_issue_names(scopes):
    assert SCOPES <= scopes, sorted(SCOPES - scopes)


@pytest.mark.parametrize("metric", NEW, ids=[m["name"] for m in NEW])
def test_a_new_metric_reads_what_the_program_writes(metric, scopes):
    spec = cells.layer_metric_spec(metric["name"])
    assert cells.module_for("readers", spec["reader"]).read
    assert spec["layer"] == metric["layer"]
    assert spec["moves"] == metric["moves"]
    if isinstance(spec.get("scopes"), list):
        assert set(spec["scopes"]) <= scopes, (spec["scopes"], sorted(
            s for s in scopes if s))
    if not metric["name"].endswith(SUF):
        return
    generic = metric["name"][:-len(SUF)]
    if generic.startswith(("setup_", "persist_", "device_idle_s.")):
        # a copy of a generic metric reads what the generic one reads
        # (tests/test_benchmark_span_contract.py holds the spans' names)
        theirs = cells.layer_metric_spec(generic)
        same = ("field", "span", "where", "as", "root_label")
        assert {k: spec.get(k) for k in same} == {
            k: theirs.get(k) for k in same}
        # the same reader, for `span-self` and `idle-span` under the name
        # the looped cell's copies gave it (ROADMAP B2 folds the four)
        assert cells.module_for("readers", spec["reader"]).read is \
            cells.module_for("readers", theirs["reader"]).read
        if isinstance(spec.get("spans"), list):
            assert set(spec["spans"]) <= set(theirs["spans"])
        else:
            assert spec.get("spans") == theirs.get("spans")
        if "besides" in spec:
            assert all(name.endswith(SUF) and name in LISTED
                       for name in spec["besides"])
        assert {k: metric[k] for k in ("unit", "better", "source")} == {
            k: LISTED[generic][k] for k in ("unit", "better", "source")}


def test_the_parts_cover_the_step(scopes):
    """Every scope of the compiled step is read by one of the cell's
    part metrics, `seq.embed` alone left (PERF.md section 5 names it)."""
    read = {s for m in NEW
            for s in [cells.layer_metric_spec(m["name"])]
            if s["reader"] == "seq-scope" and isinstance(s["scopes"], list)
            for s in s["scopes"]}
    assert {s for s in scopes if s and s.startswith("seq.")
            and "/" not in s} - read == {"seq.embed"}
