"""The span contract of benchmark/tests/test_span_contract.py, for what
PR 41 added: every `layer_metrics/*.json` of the looped stack's cell
names a reader that exists, and every scope a `seq-scope` or
`seq-roofline-loop` metric lists there is a `jax.named_scope` path of
the step program pio_tpu/models/seq_blocks.py compiles for the cell's
configuration (at the rehearsal's tiny size); the cell's copies of the
generic set-up, persist and idle metrics read what the generic ones read.
A scope renamed in the program fails here, not a metric silently on the
chip."""

import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import engines_sequence as es
from benchmark.harness import cells, profile
from benchmark.tests.test_rehearsal import TESTS

CELL = "ouro-2.6b-l4.train-8k-loop"
BENCH = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
OVERLAY = os.path.join(TESTS, "rehearse", "loop-tiny.json")
NEW = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
LISTED = {m["name"]: m for m in BENCH["per_layer"]}


@pytest.fixture(scope="module")
def scopes():
    from pio_tpu.models import seq_blocks

    cell = cells.load_cell(CELL, OVERLAY)
    spec = seq_blocks.BlockSpec.parse(es.block_spec_of(cell.config))
    optimizer, step = seq_blocks.make_train_step(spec, 0.0193)
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        seq_blocks.param_shapes(spec), is_leaf=lambda x: isinstance(x, tuple))
    text = step.lower(
        shapes, jax.eval_shape(optimizer.init, shapes),
        jax.ShapeDtypeStruct((2, 97), jnp.int32)).compile().as_text()
    return {profile.scope_of_op_name(n)
            for n in profile._OP_NAME.findall(text)}


def test_the_new_metrics_are_the_issues():
    assert len(NEW) == 25
    assert {m["moves"] for m in NEW} == {"train_ratings_per_s", "setup_s"}
    # what moves the set-up is the generic set-up metrics' copies
    assert all(m["name"].startswith("setup_") for m in NEW
               if m["moves"] == "setup_s")


@pytest.mark.parametrize("metric", NEW, ids=[m["name"] for m in NEW])
def test_a_new_metric_reads_what_the_program_writes(metric, scopes):
    spec = cells.layer_metric_spec(metric["name"])
    assert cells.module_for("readers", spec["reader"]).read
    assert spec["layer"] == metric["layer"]
    assert spec["moves"] == metric["moves"]
    if isinstance(spec.get("scopes"), list):
        # the first path is the loop's own; a flat one beside it holds
        # what the compiler hands out of the loop
        assert spec["scopes"][0] in scopes, (spec["scopes"], sorted(
            s for s in scopes if s))
        assert all(s.split("/")[-1] == spec["scopes"][0].split("/")[-1]
                   for s in spec["scopes"])
    generic = metric["name"][:-len(".train-sequence-loop")]
    if generic.startswith(("setup_", "persist_", "device_idle_s.")):
        # a copy of a generic metric reads what the generic one reads
        # (tests/test_benchmark_span_contract.py holds the spans' names)
        theirs = cells.layer_metric_spec(generic)
        same = ("field", "span", "where", "as", "root_label")
        assert {k: spec.get(k) for k in same} == {
            k: theirs.get(k) for k in same}
        # the same reader, for `span-self` and `idle-span` under a name
        # of its own (benchmark/readers/span_self_loop.py says why)
        assert cells.module_for("readers", spec["reader"]).read is \
            cells.module_for("readers", theirs["reader"]).read
        # of the generic metric's spans, those a sequence job opens
        if isinstance(spec.get("spans"), list):
            assert set(spec["spans"]) <= set(theirs["spans"])
        else:
            assert spec.get("spans") == theirs.get("spans")
        assert {k: metric[k] for k in ("unit", "better", "source")} == {
            k: LISTED[generic][k] for k in ("unit", "better", "source")}
