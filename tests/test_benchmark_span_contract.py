"""The names the benchmark reads off the program's span record are names
the program writes, held in tier-1: for every file under
benchmark/layer_metrics/ whose reader is `span-self`, `idle-span`,
`warm-span` or `seq-counter`, each span, label and counter it lists is
in the `train spans:` record of a tiny job of a cell the metric lists
(the drivers' CPU rehearsals, as benchmark/tests/test_span_contract.py
runs them). A span renamed in the program fails here, in the driver's
run, not as a metric gone silent on the chip."""

import glob
import os
import time

import pytest

from benchmark.harness import cells
from benchmark.tests.test_span_contract import (
    AT_SIZE_ONLY, BENCH, OVERLAYS, TESTS,
)

READERS = ("span-self", "idle-span", "warm-span", "seq-counter")
# the looped cell's copies of the generic persist and idle metrics go by
# readers that are these under a name of their own
# (benchmark/readers/span_self_loop.py says why)
ALIASES = {"span-self-loop": "span-self", "idle-span-loop": "idle-span"}
# a traffic kind a later PR added brings its rehearsal's overlay here
# (benchmark/tests/test_contract_loop.py and test_contract_ssm.py hold the
# looped and the state-space cells' scopes)
OVERLAYS = {**OVERLAYS, "train_sequence_loop": "loop-tiny.json",
            "train_sequence_gated": "gated-tiny.json",
            "train_sequence_ssm": "ssm-tiny.json"}
LISTED = {m["name"]: m for m in BENCH["per_layer"]}


def _metrics() -> list:
    out = []
    for path in sorted(glob.glob(os.path.join(
            cells.BENCH_DIR, "layer_metrics", "*.json"))):
        name = os.path.basename(path)[:-len(".json")]
        spec = cells.load_json(path)
        reader = cells.module_for("readers", spec["reader"]).read
        spec["reader"] = ALIASES.get(spec["reader"], spec["reader"])
        if spec["reader"] in READERS:
            assert reader is cells.module_for("readers", spec["reader"]).read
            out.append(pytest.param(LISTED[name], spec, id=name))
    return out


@pytest.fixture(scope="module")
def records():
    """cell -> its warm job's rows and its window's jobs' rows, from one
    untraced tiny run a cell."""
    found: dict[str, dict] = {}

    def of(name: str) -> dict:
        if name not in found:
            kind = cells.load_cell(name).traffic["kind"]
            cell = cells.load_cell(
                name, os.path.join(TESTS, "rehearse", OVERLAYS[kind]))
            out = cells.module_for("drivers", kind).run(
                cell, seed=2 ** 31 + 37, seconds=1, trace=False,
                t0=time.monotonic(), rehearse=True)
            assert out["correct"] is True
            evidence = out["evidence"]
            found[name] = {
                "warm": evidence["warm_job"]["spans"],
                "jobs": [j["spans"] for j in evidence["jobs"]]}
            assert found[name]["warm"] and all(found[name]["jobs"])
        return found[name]

    return of


def _rows(record: dict) -> list[dict]:
    return record["warm"] + [r for job in record["jobs"] for r in job]


@pytest.mark.parametrize("metric,spec", _metrics())
def test_what_a_metric_reads_is_what_a_tiny_job_writes(metric, spec,
                                                       records):
    cells_of = [records(w) for w in metric["workloads"]]
    written = [r for rec in cells_of for r in _rows(rec)]
    names = {r["name"] for r in written}
    if spec["reader"] == "seq-counter":
        counters = set().union(*(r["labels"] for r in written
                                 if r["name"] == "seq.wait"))
        assert spec["counter"] in counters, sorted(counters)
    elif spec["reader"] == "warm-span" and "root_label" in spec:
        for rec in cells_of:          # every cell has a warm job
            assert spec["root_label"] in rec["warm"][0]["labels"]
    elif spec["reader"] == "warm-span":
        keys = set(spec.get("where", {})) | set(spec.get("where_not", {}))
        for rec in cells_of:
            mine = [r for r in rec["warm"] if r["name"] == spec["span"]]
            assert mine, (spec["span"], sorted(names))
            assert all(keys <= set(r["labels"]) for r in mine)
            # a process's first job alone gets programs ready
            assert not [r for job in rec["jobs"] for r in job
                        if r["name"] == spec["span"]]
    elif spec["spans"] == "rest":
        # what the others leave: they are `idle-span` metrics of its cells
        for name in spec["besides"]:
            theirs = cells.layer_metric_spec(name)["reader"]
            assert ALIASES.get(theirs, theirs) == "idle-span"
            assert set(LISTED[name]["workloads"]) <= set(
                metric["workloads"])
    else:
        prefix = spec["reader"] == "idle-span"
        for want in spec["spans"]:
            if want in AT_SIZE_ONLY:
                continue
            assert any(n.startswith(want) if prefix else n == want
                       for n in names), (want, sorted(names))


# ---------------------------------------------------------------------------
# the reader `warm-span` on records written by hand
# ---------------------------------------------------------------------------

def _row(name, parent, seconds, **labels):
    return {"name": name, "parent": parent, "start_s": 0.0,
            "duration_s": seconds, "labels": labels}


WARM = [
    _row("train", None, 9.0, engine="bench", process_age_s="17.25"),
    _row("train.algorithms", "train", 8.0),
    _row("compile.trace", "als.dispatch", 1.5, program="_train_jit"),
    _row("compile.trace", "als.init", 0.25, program="_normal"),
    _row("compile.lower", "als.dispatch", 0.75, program="jit(_train_jit)"),
    _row("compile.backend", "als.dispatch", 2.0, program="jit(_train_jit)",
         cache="hit", retrieval_s="1.9", saved_s="51.1"),
    _row("compile.backend", "als.init", 0.5, program="jit(_normal)",
         cache="miss"),
    _row("compile.backend", "als.init", 0.125, program="jit(abs)",
         cache="off"),
]
# what the parent of PR 37 logs: a tree, no `compile.*` row, no age
BEFORE = [_row("train", None, 9.0, engine="bench"),
          _row("train.algorithms", "train", 8.0, compile_s="6.2")]


@pytest.mark.parametrize("metric,warm", [
    ("setup_trace_s", 1.75), ("setup_lower_s", 0.75),
    ("setup_load_s", 2.0), ("setup_build_s", 0.625),
    ("setup_cache_misses", 1), ("setup_before_job_s", 17.25),
])
def test_the_warm_span_reader(metric, warm):
    spec = cells.layer_metric_spec(metric)
    read = cells.module_for("readers", spec["reader"]).read
    assert read(spec, {"warm_job": {"spans": WARM}}) == warm
    # a program without the spans, a record dropped, no warm job at all:
    # nothing to read, and nothing raised
    assert read(spec, {"warm_job": {"spans": BEFORE}}) is None
    assert read(spec, {"warm_job": {"spans": []}}) is None
    assert read(spec, {"warm_job": {"wall_s": 9.0}}) is None
    assert read(spec, {}) is None
    # a rehearsal's warm job is the CPU compiler's: left out, as a
    # roofline is (benchmark/tests/test_rehearsal*.py hold the line's set)
    assert read(spec, {"warm_job": {"spans": WARM}, "rehearse": True}) is None


def test_from_the_cache_nothing_was_built():
    hits = [r for r in WARM if r["labels"].get("cache") in (None, "hit")]
    for metric in ("setup_build_s", "setup_cache_misses"):
        spec = cells.layer_metric_spec(metric)
        read = cells.module_for("readers", spec["reader"]).read
        assert read(spec, {"warm_job": {"spans": hits}}) == 0
