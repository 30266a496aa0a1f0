"""What the benchmark reads of the program's own names
(benchmark/harness/program.py, the readers `span-self`, `scope-job`,
`idle-span`, `warm-job`): on the program's recorded profiles of two tiny
`run_train` jobs on a v5e, one chip and four (tests/data/, recorded by
tests/record_train_trace.py: `train` roots, spans, compiled text), and on
hand-written span trees and views for what a recording cannot pin down."""

import json
import logging
import os

import pytest

from benchmark.harness import cells, profile, program

LOG = logging.getLogger("benchmark.tests")
RECORDED = {chips: os.path.join(cells.ROOT, "tests", "data", name)
            for chips, name in ((1, "tiny_one_chip.xplane.pb.gz"),
                                (4, "tiny_sharded.xplane.pb.gz"))}
BENCH = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))


def read(metric: str, evidence: dict):
    spec = cells.layer_metric_spec(metric)
    return cells.module_for("readers", spec["reader"]).read(spec, evidence)


def names_of(reader: str) -> list[str]:
    return [m["name"] for m in BENCH["per_layer"]
            if cells.layer_metric_spec(m["name"])["reader"] == reader]


@pytest.fixture(scope="module", params=RECORDED)
def recorded(request):
    view = program.profile_view(RECORDED[request.param], LOG)
    return request.param, view


def test_the_view_of_a_recorded_profile(recorded):
    chips, view = recorded
    assert view["busy_s"] > 0 and view["idle_s"] > 0
    assert sum(view["scopes"].values()) == pytest.approx(view["busy_s"])
    assert sum(view["idle_by_span"].values()) == pytest.approx(
        view["idle_s"])
    assert ("als.partition" in view["idle_by_span"]) == (chips == 4)
    gaps = program.name_gaps(view["longest_gaps"])
    assert len(gaps) == 10 and all(
        name in view["idle_by_span"] or name == "outside"
        for name, _ in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    json.dumps(view)                  # it travels in the child's out file


def test_the_copy_reduces_as_the_program_does(recorded):
    """benchmark/harness/profile.py is pio_tpu/obs/profile.py's reading
    and reduction, copied: the same result on the same profile."""
    from pio_tpu.obs import profile as theirs

    chips, _ = recorded
    assert profile.reduce(profile.read_profile(RECORDED[chips])) == \
        theirs.reduce(theirs.read_profile(RECORDED[chips]))


def test_the_sweep_parts_add_up_to_the_devices_busy_seconds(recorded):
    chips, view = recorded
    evidence = {"profile": view}
    parts = {name: read(name, evidence) for name in names_of("scope-job")}
    assert len(parts) == 6 and all(v > 0 for v in parts.values())
    scopes = view["scopes"]
    assert parts["sweep_gather_device_s"] == pytest.approx(
        scopes["als.user/als.gather"] + scopes["als.item/als.gather"])
    assert parts["sweep_solve_device_s"] == pytest.approx(sum(
        sec for path, sec in scopes.items()
        if path.endswith(("/als.cg", "/als.gram", "/als.chol"))))
    assert parts["sweep_unphased_device_s"] == pytest.approx(
        scopes["als.user"] + scopes["als.item"] + scopes["unscoped"])
    gathered = sum(sec for path, sec in scopes.items()
                   if path.endswith("/als.all_gather"))
    assert (gathered > 0) == (chips == 4)
    assert sum(parts.values()) + gathered == pytest.approx(view["busy_s"])


def test_the_idle_parts_add_up_to_the_idle_seconds(recorded):
    chips, view = recorded
    evidence = {"profile": view}
    parts = {name: read(name, evidence) for name in names_of("idle-span")}
    assert set(parts) == {"device_idle_s.persist", "device_idle_s.host_prep",
                          "device_idle_s.read", "device_idle_s.rest"}
    assert all(v > 0 for v in parts.values())
    spans = view["idle_by_span"]
    assert parts["device_idle_s.persist"] == pytest.approx(sum(
        spans[s] for s in ("persist.d2h", "persist.pickle", "persist.frame",
                           "persist.insert", "train.barrier",
                           "train.complete")))
    assert parts["device_idle_s.read"] == pytest.approx(spans["train.read"])
    assert sum(parts.values()) == pytest.approx(view["idle_s"], rel=1e-12)


VIEW = {"scopes": {"als.user/als.gram/als.gram.pack": 1.0,
                   "als.item/als.gram": 2.0, "als.user/als.gramx": 4.0,
                   "als.user/als.cg/als.cg.matvec": 8.0, "als.user": 16.0,
                   "als.item": 32.0, "unscoped": 64.0,
                   "seq.mtp/seq.attn.full": 128.0},
        "idle_by_span": {"persist.pickle": 1.0, "models.file": 2.0,
                         "als.prep": 4.0, "als.prepare_more": 8.0,
                         "train.read": 16.0, "events.scan": 32.0,
                         "als.wait": 64.0, "train": 128.0, "outside": 256.0,
                         "persistent": 512.0},
        "idle_s": 1023.0}


def test_a_scope_path_is_matched_by_element_not_by_substring():
    evidence = {"profile": VIEW}
    assert read("sweep_solve_device_s", evidence) == 1.0 + 2.0 + 8.0
    assert read("sweep_unphased_device_s", evidence) == 16.0 + 32.0 + 64.0
    assert read("sweep_flush_device_s", evidence) is None    # no such path


def test_idle_spans_are_matched_by_prefix_and_rest_takes_what_is_left():
    evidence = {"profile": VIEW}
    assert read("device_idle_s.persist", evidence) == 1.0 + 2.0
    # a listed name is a prefix: `als.prep` takes `als.prepare_more` too,
    # `persist.` does not take `persistent`
    assert read("device_idle_s.host_prep", evidence) == 4.0 + 8.0
    assert read("device_idle_s.read", evidence) == 16.0 + 32.0
    assert read("device_idle_s.rest", evidence) == 64 + 128 + 256 + 512.0
    assert sum(read(n, evidence) for n in names_of("idle-span")) == \
        VIEW["idle_s"]


def row(name, parent, start, duration):
    return {"name": name, "parent": parent, "start_s": start,
            "duration_s": duration, "labels": {}}


TREE = [row("train", None, 0.0, 10.0),
        row("train.read", "train", 0.0, 3.0),
        row("events.scan", "train.read", 0.1, 2.5),
        row("train.algorithms", "train", 3.0, 4.0),
        row("als.prep", "train.algorithms", 3.0, 0.5),
        row("als.transfer", "train.algorithms", 3.5, 0.25),
        row("persist.pickle", "train", 7.0, 1.0),
        row("persist.insert", "train", 8.0, 1.5),
        row("models.file", "persist.insert", 8.0, 1.0),
        row("models.file", "persist.insert", 9.0, 0.25),
        row("models.row", "persist.insert", 9.25, 0.125)]


def test_self_time_same_named_rows_and_whole_durations():
    from benchmark.readers import span_self

    assert span_self.job_seconds(TREE, ["persist.insert"], False) == 0.125
    assert span_self.job_seconds(TREE, ["models.file"], False) == 1.25
    assert span_self.job_seconds(TREE, ["train.read"], True) == 3.0
    assert span_self.job_seconds(TREE, ["train.read"], False) == 0.5
    assert span_self.job_seconds(TREE, ["seq.wait"], False) is None
    evidence = {"jobs": [{"spans": TREE}, {"spans": TREE}, {"spans": []}]}
    assert read("persist_store_s", evidence) == 1.5       # insert, whole
    assert read("persist_serialize_s", evidence) == 1.0
    assert read("host_prep_s", evidence) == 0.75
    assert read("read_scan_s", evidence) == 2.5


def test_the_persist_parts_add_up_to_the_stage():
    """`train timing`'s persist is the four persist spans, the barrier
    and the COMPLETED transition; the two metrics are the first four."""
    persist = sum(r["duration_s"] for r in TREE
                  if r["name"].startswith("persist."))
    evidence = {"jobs": [{"spans": TREE, "persist_s": persist}]}
    assert (read("persist_serialize_s", evidence)
            + read("persist_store_s", evidence)
            == read("stage_persist_s", evidence))


def test_the_warm_job():
    warm = {"wall_s": 9.5, "compile_s": 0.25, "programs": 9,
            "cache_hits": 9}
    assert read("setup_warm_job_s", {"warm_job": warm}) == 9.5
    assert read("setup_compile_s", {"warm_job": warm}) == 0.25
    assert read("setup_compile_s", {"warm_job": {"wall_s": 9.5}}) is None


@pytest.mark.parametrize("name", [
    n for r in ("span-self", "scope-job", "idle-span", "warm-job")
    for n in names_of(r)])
def test_nothing_to_read_without_the_evidence(name):
    """A parent that returns no spans, no view and no warm job, a CPU
    rehearsal (view None), `PIO_TPU_TRACE=off` (spans empty)."""
    assert read(name, {"jobs": [{"wall_s": 1.0}], "trace": None}) is None
    assert read(name, {"jobs": [{"spans": []}], "profile": None,
                       "warm_job": {}}) is None


def test_the_span_log_keeps_a_jobs_whole_record():
    spans = program.SpanLog()
    logger = logging.getLogger("benchmark.tests.spans")
    logger.addHandler(spans)
    logger.setLevel(logging.INFO)
    logger.info("train stages: read 0.1s")
    assert spans.rows == [] and spans.labels("seq.wait") == {}
    rows = TREE + [dict(row("seq.wait", "train.algorithms", 3.0, 1.0),
                        labels={"dropped_tokens": "0"})]
    logger.info("train spans: %s", json.dumps(rows))
    assert spans.rows == rows
    assert spans.labels("seq.wait") == {"dropped_tokens": "0"}
    assert spans.labels("als.wait") == {}


def test_gaps_are_named_by_the_span_that_holds_most_of_them():
    gaps = [{"s": 3.0, "chip": "/device:TPU:0",
             "parts": {"persist.pickle": 1.4, "models.file": 1.5,
                       "als.partition": 0.1}},
            {"s": 1.0, "chip": "/device:TPU:0",
             "parts": {"train.complete": 0.2, "outside": 0.8}}]
    assert program.name_gaps(gaps) == [["models.file", 3.0],
                                       ["outside", 1.0]]


def test_no_view_of_a_profile_without_a_device_plane_or_a_root(tmp_path):
    """The benchmark's own small trace holds a TPU plane and no `train`
    root: no view, and the reason is logged."""
    bare = os.path.join(os.path.dirname(__file__), "data",
                        "tiny_tpu.xplane.pb")
    assert program.profile_view(bare, LOG) is None
    # and a child's `trace` then names its gaps so
    os.makedirs(tmp_path / "plugins")
    os.symlink(bare, tmp_path / "plugins" / "t.xplane.pb")
    tr = program.reduce_trace(str(tmp_path), 3, LOG)
    assert tr["profile"] is None and tr["scope_s"] is None
    assert "op_seconds" not in tr
    assert tr["idle_gaps"] and all(
        name == program.NO_VIEW for name, _ in tr["idle_gaps"])
    assert set(program.breakdown(tr)) == {"device_ops", "idle_gaps"}
