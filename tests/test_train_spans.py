"""`run_train` as a span tree (workflow/train.py, controller/engine.py,
workflow/checkpoint.py, ops/als.py through utils/tracing.py): one
`train` trace a job on the `train` surface, every stage a span emitted
where the work happens, the finished tree logged as `train spans:`, and
the two older INFO records printed from the spans' durations in the
words the benchmark's regular expressions read."""

import json
import logging

import numpy as np
import pytest

from benchmark.drivers.train_child import _STAGES, _TIMING
from pio_tpu.data.storage import Storage
from pio_tpu.models.recommendation import ALSAlgorithmParams
from pio_tpu.obs import set_tracing
from pio_tpu.resilience import chaos
from pio_tpu.workflow.context import create_workflow_context
from pio_tpu.workflow.train import load_models, run_train
from tests._tiny_train import memory_storage, tiny_engine, tiny_params

UNDER_ROOT = ["train.setup", "train.read", "train.prepare",
              "train.algorithms", "persist.d2h", "persist.insert",
              "train.barrier", "train.complete", "train.release"]
# the models are pickled and checksummed on their way into the store (PR
# 39): the two spans lie under the store's own, rows of seconds the sink
# added up, not blocks that were entered
UNDER_INSERT = ["persist.pickle", "persist.frame"]
# under train.algorithms, in order, by path; then the labels a span
# must carry (the counts at its boundary)
PATHS = {
    "one-device": ["als.init", "als.prep", "als.transfer", "als.dispatch",
                   "als.wait"],
    "eight-devices": ["als.partition", "als.init", "als.transfer",
                      "als.dispatch", "als.wait"],
}
LABELS = {
    "train": {"engine", "instance", "chips", "process_age_s"},
    "compile.trace": {"program"},
    "compile.lower": {"program"},
    "compile.backend": {"program", "cache"},
    "train.read": {"ratings", "users", "items"},
    "train.algorithms": {"programs", "cache_hits", "compile_s"},
    "als.partition": {"rows_u", "rows_i", "nnz_max_u", "nnz_min_u",
                      "nnz_max_i", "nnz_min_i", "padded_u", "padded_i"},
    "als.prep": {"padded"},
    "als.transfer": {"bytes"},
    "als.dispatch": {"cs", "su", "si", "fill_u", "fill_i", "cg_matvecs"},
    "persist.d2h": {"bytes"},
    "persist.pickle": {"bytes", "ids"},
    "persist.frame": {"bytes"},
    "persist.insert": {"bytes"},
    "train.release": {"bytes"},
}


def _train(path: str, caplog, jobs: int = 1, **run) -> list[str]:
    """-> the messages `run_train` logged, last job's last."""
    storage = memory_storage()
    engine = tiny_engine()
    ctx = create_workflow_context(
        storage, use_mesh=path == "eight-devices")
    with caplog.at_level(logging.INFO, logger="pio_tpu.workflow"):
        for _ in range(jobs):
            caplog.clear()
            run_train(engine, tiny_params(cg_iters=3), storage,
                      engine_id="tiny", ctx=ctx, **run)
    return [r.getMessage() for r in caplog.records]


def _spans(messages: list[str]) -> list[dict]:
    [line] = [m for m in messages if m.startswith("train spans: ")]
    return json.loads(line[len("train spans: "):])


@pytest.mark.parametrize("path", PATHS)
def test_one_tree_with_every_span_of_the_path(path, caplog):
    # the second job: the first one's `compile.*` rows (the programs got
    # ready inside `als.init` and `als.dispatch`) are
    # tests/test_compile_spans.py's
    rows = _spans(_train(path, caplog, jobs=2))
    names = [r["name"] for r in rows]
    assert names.count("train") == 1 and rows[0]["name"] == "train"
    root = rows[0]
    assert root["parent"] is None and root["start_s"] == 0.0
    assert root["labels"]["chips"] == ("8" if path == "eight-devices"
                                       else "1")
    assert [r["name"] for r in rows if r["parent"] == "train"] == UNDER_ROOT
    assert [r["name"] for r in rows
            if r["parent"] == "train.setup"] == ["train.devices"]
    assert [r["name"] for r in rows
            if r["parent"] == "train.algorithms"] == PATHS[path]
    assert [r["name"] for r in rows
            if r["parent"] == "persist.insert"] == UNDER_INSERT
    # a DataSource that reads no events: no `events.*` span; a job after
    # the process's first: no `compile.*` span
    assert len(rows) == (2 + len(UNDER_ROOT) + len(UNDER_INSERT)
                         + len(PATHS[path]))
    assert not [n for n in names if n.startswith("events.")]
    for row in rows:
        assert LABELS.get(row["name"], set()) <= set(row["labels"]), row
        assert "status" not in row
        assert 0.0 <= row["start_s"] <= root["duration_s"]
    by_name = {r["name"]: r for r in rows}
    assert float(root["labels"]["process_age_s"]) >= 0.0
    assert by_name["train.read"]["labels"] == {
        "ratings": "5000", "users": "300", "items": "200"}
    assert by_name["persist.pickle"]["labels"]["ids"] == "500"
    # the frame is the pickle behind its 17-byte header, and the insert
    # says the frame's length once it is known
    framed = int(by_name["persist.pickle"]["labels"]["bytes"]) + 17
    assert by_name["persist.frame"]["labels"]["bytes"] == str(framed)
    assert by_name["persist.insert"]["labels"]["bytes"] == str(framed)
    assert (int(by_name["train.release"]["labels"]["bytes"])
            == int(by_name["persist.d2h"]["labels"]["bytes"]) > 0)
    # the sink's two sums lie end to end inside the insert
    insert, pickled, crc = (by_name[n] for n in (
        "persist.insert", "persist.pickle", "persist.frame"))
    assert insert["start_s"] <= pickled["start_s"] <= crc["start_s"]
    assert (crc["start_s"] + crc["duration_s"]
            <= insert["start_s"] + insert["duration_s"] + 1e-6)
    assert int(by_name["als.transfer"]["labels"]["bytes"]) > 0
    if path == "eight-devices":
        # what crosses from the host is the six COO stacks and nothing
        # else: the initial factors are drawn and split on the devices
        split = by_name["als.partition"]["labels"]
        chunk = ALSAlgorithmParams().chunk
        slots = sum(-(-int(split[k]) // chunk) * chunk
                    for k in ("nnz_max_u", "nnz_max_i"))
        assert int(by_name["als.transfer"]["labels"]["bytes"]) == (
            8 * slots * 3 * 4)
    assert int(by_name["als.dispatch"]["labels"]["cg_matvecs"]) > 0
    # the children cover the root: what is left is its self time
    self_s = root["duration_s"] - sum(
        r["duration_s"] for r in rows if r["parent"] == "train")
    assert 0.0 <= self_s < max(0.02 * root["duration_s"], 0.005)
    kids = sum(r["duration_s"] for r in rows
               if r["parent"] == "train.algorithms")
    assert kids <= by_name["train.algorithms"]["duration_s"]


@pytest.mark.parametrize("path", PATHS)
def test_failing_persist_leaves_the_span_in_error(path, caplog):
    with pytest.raises(chaos.ChaosError):
        with chaos.inject("train.persist", error=1.0):
            _train(path, caplog)
    rows = {r["name"]: r for r in _spans(
        [r.getMessage() for r in caplog.records])}
    failed = rows["persist.insert"]
    assert failed["status"] == "error" and "ChaosError" in failed["error"]
    assert failed["labels"]["chaos"] == "train.persist"
    assert rows["train"]["status"] == "error"
    assert "status" not in rows["persist.d2h"]
    # the store was never reached: nothing was pickled
    assert "persist.pickle" not in rows and "persist.frame" not in rows
    # the barrier is reached on both outcomes; COMPLETED is not
    assert "train.barrier" in rows and "train.complete" not in rows


@pytest.mark.parametrize("users,way", [(40_000, "file"), (300, "inline")])
def test_a_sqlite_store_says_which_way_the_blob_went(users, way, tmp_path,
                                                     caplog):
    """The sqlite Models DAO under `persist.insert`: a model of 1 MiB or
    more is `models.file` (its bytes) then `models.row` (nothing
    inline); a smaller one is the row alone."""
    storage = Storage({
        "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "pio.db"),
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
    })
    engine, params = tiny_engine(n_users=users), tiny_params(cg_iters=3)
    ctx = create_workflow_context(storage, use_mesh=False)
    with caplog.at_level(logging.INFO, logger="pio_tpu.workflow"):
        instance = run_train(engine, params, storage, engine_id="tiny",
                             ctx=ctx)
    rows = _spans([r.getMessage() for r in caplog.records])
    insert = next(r for r in rows if r["name"] == "persist.insert")
    under = [r for r in rows if r["parent"] == "persist.insert"]
    blob = insert["labels"]["bytes"]
    assert (int(blob) >= 1 << 20) == (way == "file")
    sink = ["persist.pickle", "persist.frame"]
    if way == "file":
        # the models write themselves into the file: the sink's two
        # sums are the file span's children
        assert [r["name"] for r in under] == ["models.file", "models.row"]
        assert [r["name"] for r in rows
                if r["parent"] == "models.file"] == sink
        assert under[0]["labels"] == {"bytes": blob}
        assert under[1]["labels"] == {"inline_bytes": "0"}
        # the file is whole and synced before its row is written
        assert (under[0]["start_s"] + under[0]["duration_s"]
                <= under[1]["start_s"] + 1e-6)
    else:
        # under the line the blob is made in memory, then it is a row
        assert [r["name"] for r in under] == sink + ["models.row"]
        assert under[2]["labels"] == {"inline_bytes": blob}
    assert all("status" not in r for r in under)
    # each of the six names once, and the two metrics the benchmark reads
    # from them come to the insert and the d2h, whatever the nesting
    from benchmark.readers.span_self import job_seconds
    for name in ("persist.d2h", "persist.insert", *sink, "models.row"):
        assert [r["name"] for r in rows].count(name) == 1, name
    d2h = next(r for r in rows if r["name"] == "persist.d2h")
    serialize = job_seconds(rows, ["persist.d2h", *sink], total=False)
    store = job_seconds(
        rows, ["persist.insert", "models.file", "models.row"], total=False)
    assert serialize + store == pytest.approx(
        d2h["duration_s"] + insert["duration_s"], abs=1e-5)
    assert serialize >= d2h["duration_s"] and store > 0.0
    # the benchmark's "persist" still holds the whole of it
    assert sum(r["duration_s"] for r in under) <= insert["duration_s"]
    [model] = load_models(storage, engine, params, instance, ctx)
    assert len(model.users.ids()) == users


@pytest.mark.parametrize("backend,under_read", [
    ("eventlog", ["events.scan", "events.tables", "events.index"]),
    ("memory", ["events.scan", "events.index"]),
])
def test_a_job_that_reads_events_says_where_the_read_went(
        backend, under_read, tmp_path, caplog):
    """The template's own DataSource over an event store: under
    `train.read`, `events.scan` (the store's columnarize: one native
    sweep of the `eventlog` store, which says its log's bytes and the
    payload bytes it checksummed: every record, once),
    `events.tables` (that sweep's output copied into NumPy columns and
    Python strings; the other stores fold ids as strings already) and
    `events.index` (the two id indexes)."""
    from benchmark.drivers.train_child import insert_events
    from pio_tpu.controller.engine import EngineParams
    from pio_tpu.models.recommendation import RecommendationEngine

    env = {
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    }
    if backend == "eventlog":
        env.update({"PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
                    "PIO_STORAGE_SOURCES_EL_PATH": str(tmp_path / "log"),
                    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL"})
    storage = Storage(env)
    rng = np.random.default_rng(26)
    pairs = rng.choice(60 * 40, 900, replace=False)   # distinct pairs
    insert_events(storage, "bench", pairs // 40, pairs % 40,
                  rng.integers(1, 6, 900))
    params = EngineParams(
        datasource=("", {"app_name": "bench", "event_names": ["rate"]}),
        algorithms=tiny_params(cg_iters=3).algorithms)
    ctx = create_workflow_context(storage, use_mesh=False)
    with caplog.at_level(logging.INFO, logger="pio_tpu.workflow"):
        run_train(RecommendationEngine.apply(), params, storage,
                  engine_id="bench", ctx=ctx)
    rows = _spans([r.getMessage() for r in caplog.records])
    by_name = {r["name"]: r for r in rows}
    read = by_name["train.read"]
    counts = {"users": str(len(set((pairs // 40).tolist()))),
              "items": str(len(set((pairs % 40).tolist())))}
    assert read["labels"] == {"ratings": "900", **counts}
    under = [r for r in rows if r["parent"] == "train.read"]
    assert [r["name"] for r in under] == under_read
    scan = dict(by_name["events.scan"]["labels"])
    if backend == "eventlog":
        from pio_tpu.native.eventlog import EventLog, ScanFilter, crc_bytes

        path = tmp_path / "log" / "app_1" / "events.log"
        payload = path.stat().st_size - 8 - 8 * 900
        assert int(scan.pop("log_bytes")) == path.stat().st_size
        assert int(scan.pop("crc_bytes")) == payload
        # one sweep a read; the checked count of `stats` is a second one
        log = EventLog(str(path), create=False)
        try:
            at = [crc_bytes()]
            assert len(log.columnarize(ScanFilter()).values) == 900
            at.append(crc_bytes())
            assert log.stats() == (path.stat().st_size, 900)
            at.append(crc_bytes())
        finally:
            log.close()
        assert np.diff(at).tolist() == [payload, payload]
    assert scan == {"rows": "900", **counts}
    assert all(not r["labels"] and "status" not in r for r in under[1:])
    # in order, inside the read, and covering it
    ends = [r["start_s"] + r["duration_s"] for r in under]
    assert all(a <= b["start_s"] + 1e-6 for a, b in zip(ends, under[1:]))
    self_s = read["duration_s"] - sum(r["duration_s"] for r in under)
    assert 0.0 <= self_s < max(0.02 * read["duration_s"], 0.005)


@pytest.mark.parametrize("tracing", ["on", "off"])
def test_the_benchmarks_expressions_still_read_the_records(tracing, caplog):
    set_tracing(tracing == "on")
    try:
        messages = _train("one-device", caplog)
    finally:
        set_tracing(None)
    [stages] = [m for m in map(_STAGES.search, messages) if m]
    [timing] = [m for m in map(_TIMING.search, messages) if m]
    read, prepare, algorithms = map(float, stages.groups())
    assert timing[1].count(".") == 1 and len(timing[1].split(".")[1]) == 3
    assert float(timing[1]) == pytest.approx(
        read + prepare + algorithms, abs=2e-3)
    assert int(timing[3]) >= 0 and int(timing[4]) >= 0
    assert float(timing[5]) >= 0.0
    if tracing == "on":
        rows = {r["name"]: r for r in _spans(messages)}
        assert float(algorithms) == pytest.approx(
            rows["train.algorithms"]["duration_s"], abs=1e-3)
        persist = sum(rows[n]["duration_s"] for n in UNDER_ROOT[4:10])
        assert float(timing[5]) == pytest.approx(persist, abs=1e-3)
    else:
        # PIO_TPU_TRACE=off: no recorder, no tree; the records stay
        assert not [m for m in messages if m.startswith("train spans:")]
