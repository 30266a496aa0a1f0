"""The span contract of benchmark/tests/test_span_contract.py, for what
PR 49 added: every `layer_metrics/*.json` of the cell of gated
grouped-query layers names a reader that exists, and every scope a
`seq-scope` or `seq-roofline-gated` metric lists there is a
`jax.named_scope` path of the step program pio_tpu/models/seq_blocks.py
compiles for the cell's configuration (at the rehearsal's tiny size). A
scope renamed in the program fails here, not a metric silently on the
chip. The entries are found by the cell's name: a later PR's entries
behind them change nothing here."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark import engines_sequence as es
from benchmark.harness import cells, profile
from benchmark.tests.test_rehearsal import TESTS

CELL = "laguna-xs2-ep16.train-8k-gated"
SUF = ".train-sequence-gated"
BENCH = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
OVERLAY = os.path.join(TESTS, "rehearse", "gated-tiny.json")
NEW = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
# accepted metrics whose `workloads` the cell was appended to: their
# readers and spans are the cell's too, and the driver's contract caps
# `per_layer` at 128 entries, which thirty-three new ones would pass
SHARED = [m for m in BENCH["per_layer"]
          if CELL in m.get("workloads", ()) and m not in NEW]
SCOPES = {"seq.attn.proj", "seq.attn.gate", "seq.attn.window",
          "seq.attn.full", "seq.mlp.dense", "seq.moe.route", "seq.moe.gmm",
          "seq.moe.combine", "seq.moe.shared", "seq.head_loss",
          "seq.optimizer", "seq.embed"}
# the scope no metric of the cell reads: the traced line's `breakdown`
# (`scope_step_s`) carries it
LEFT = {"seq.embed"}
ISSUE_LIST = {
    "seq_step_device_s", "seq_step_mfu", "device_idle_pct",
    "device_idle_s.host_prep", "device_idle_s.persist", "device_idle_s.rest",
    "stage_algorithms_s", "stage_persist_s", "persist_serialize_s",
    "persist_store_s", "setup_warm_job_s", "setup_compile_s",
    "setup_trace_s", "setup_lower_s", "setup_load_s", "setup_build_s",
    "setup_cache_misses", "setup_before_job_s", "seq_attn_window_device_s",
    "seq_attn_full_device_s", "seq_attn_proj_device_s",
    "seq_attn_gate_device_s", "seq_attn_kernel_roofline",
    "seq_mlp_dense_device_s", "seq_moe_device_s", "seq_moe_shared_device_s",
    "seq_moe_gmm_roofline", "seq_expert_held_share",
    "seq_expert_load_max_over_mean", "seq_expert_tiles_used_share",
    "seq_expert_tile_fill", "seq_head_loss_device_s",
    "seq_optimizer_device_s"}


@pytest.fixture(scope="module")
def scopes():
    from pio_tpu.models import seq_blocks

    cell = cells.load_cell(CELL, OVERLAY)
    spec = seq_blocks.BlockSpec.parse(es.block_spec_of(cell.config))
    optimizer, step = seq_blocks.make_train_step(spec, 0.0149)
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        seq_blocks.param_shapes(spec), is_leaf=lambda x: isinstance(x, tuple))
    text = step.lower(
        shapes, jax.eval_shape(optimizer.init, shapes),
        jax.ShapeDtypeStruct((2, 41), jnp.int32)).compile().as_text()
    return {profile.scope_of_op_name(n)
            for n in profile._OP_NAME.findall(text)}


def test_the_new_entries_are_the_issues():
    assert len(NEW) == 13 and len(SHARED) == 20
    # a name is the issue's, with a family's suffix where a sibling's
    # entry of that name was there first
    assert {re.sub(r"\.train-sequence(-\w+)?$", "", m["name"])
            for m in NEW + SHARED} == ISSUE_LIST
    assert {m["moves"] for m in NEW} == {"train_ratings_per_s"}
    assert {m["moves"] for m in SHARED} == {"train_ratings_per_s", "setup_s"}
    own = {"seq_attn_gate_device_s", "seq_expert_tile_fill"}
    assert {m["name"] for m in NEW if not m["name"].endswith(SUF)} == own
    # an accepted entry gained the cell at the end of its list and
    # nothing else: the older cells read it as they did
    assert all(m["workloads"][-1] == CELL and len(m["workloads"]) > 1
               for m in SHARED)
    [config] = [c for c in BENCH["configs"] if c["name"] == "laguna-xs2-ep16"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == "benchmark/configs/laguna-xs2-ep16.json"
    assert config["source"] == cells.load_json(os.path.join(
        cells.ROOT, config["file"]))["source"]
    [cell] = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": "laguna-xs2-ep16",
                    "traffic": "train-sequence-gated", "chips": 1,
                    "why": cell["why"]}
    assert [w["config"] for w in BENCH["workloads"]].count(
        "laguna-xs2-ep16") == 1              # one cell, no second
    # the entries stand together, in the order they were appended
    first = BENCH["per_layer"].index(NEW[0])
    assert BENCH["per_layer"][first:first + len(NEW)] == NEW
    assert all(len(entry["why"]) <= 200 for entry in (cell, config))
    layers = {m["layer"] for m in BENCH["per_layer"]
              if m.get("workloads") != [CELL]}
    assert {m["layer"] for m in NEW} <= layers   # no layer of its own name


def test_the_configuration_file_keeps_the_published_widths():
    config = cells.load_cell(CELL).config
    assert {k: config[k] for k in (
        "hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "sliding_window", "intermediate_size",
        "moe_intermediate_size", "shared_expert_intermediate_size",
        "num_experts_per_tok", "moe_routed_scaling_factor",
        "partial_rotary_factor", "num_experts_routed")} == {
            "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 48,
            "num_key_value_heads": 8, "sliding_window": 512,
            "intermediate_size": 8192, "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512, "num_experts_per_tok": 8,
            "moe_routed_scaling_factor": 2.5, "partial_rotary_factor": 0.5,
            "num_experts_routed": 256}
    assert config["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert len(config["num_attention_heads_per_layer"]) == 40
    assert config["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert config["mlp_layer_types"][:2] == ["dense", "sparse"]
    ropes = config["rope_parameters"]
    assert ropes["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
    assert ropes["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert config["gating"] is True
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "num_experts": 256, "vocab_size": 100352}
    assert config["experts_held"] == [0, 16]
    assert "sixteen chips share each layer" in config["deployment"]
    # its keys that are not the model's are the ten engines_sequence strips
    assert set(config) - set(es.block_spec_of(config)) == {
        "name", "source", "source_states", "engine", "deployment",
        "published", "precision", "assumed", "reduced", "check"}
    assumed = " ".join(config["assumed"])
    for what in ("softplus", "RMSNorm", "noaux_tc", "shared expert",
                 "soft-capping", "halves", "initializer_range", "Adam"):
        assert what in assumed, what


def test_the_step_carries_the_scopes_the_issue_names(scopes):
    assert SCOPES <= scopes, sorted(SCOPES - scopes)


@pytest.mark.parametrize("metric", NEW + SHARED,
                         ids=[m["name"] for m in NEW + SHARED])
def test_a_new_metric_reads_what_the_program_writes(metric, scopes):
    spec = cells.layer_metric_spec(metric["name"])
    assert cells.module_for("readers", spec["reader"]).read
    assert spec["layer"] == metric["layer"]
    assert spec["moves"] == metric["moves"]
    if isinstance(spec.get("scopes"), list):
        # an accepted entry may list another family's path beside this one's
        missing = set(spec["scopes"]) - scopes
        assert not missing or (metric in SHARED
                               and missing < set(spec["scopes"])), (
            spec["scopes"], sorted(s for s in scopes if s))


def test_the_parts_and_the_breakdown_cover_the_step(scopes):
    """Every scope of the compiled step is read by one of the cell's
    part metrics, but for the embedding's, which the traced line's
    breakdown carries alone (PERF.md section 5 has its seconds)."""
    read = {s for m in NEW + SHARED
            for s in [cells.layer_metric_spec(m["name"])]
            if s["reader"] == "seq-scope" and isinstance(s["scopes"], list)
            for s in s["scopes"]}
    assert {s for s in scopes if s and s.startswith("seq.")
            and "/" not in s} - read == LEFT
