"""Getting programs ready as spans of the job's tree
(utils/compilecache.py `CompileMeter`, workflow/train.py,
controller/engine.py): jax's begin and end events of each trace,
lowering and backend call become `compile.trace` / `compile.lower` /
`compile.backend` spans under the span that was open, inner traces open
none and add nothing, and the one meter a job feeds `train.algorithms`'
labels and the `train timing:` record."""

import json
import logging
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark.drivers.train_child import _TIMING
from pio_tpu.obs import make_recorder, set_tracing
from pio_tpu.utils.compilecache import CompileMeter
from pio_tpu.utils.tracing import Tracer
from pio_tpu.workflow.context import create_workflow_context
from pio_tpu.workflow.train import process_age_s, run_train
from tests._tiny_train import memory_storage, tiny_engine, tiny_params
from tests.test_train_spans import _spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE = ("compile.trace", "compile.lower", "compile.backend")
_sizes = iter(range(301, 400, 2))


def _nested():
    """A fresh `outer` that calls a fresh jitted `inner` and `jnp`
    functions: jax fires a trace event for each of them."""
    @jax.jit
    def inner(x):
        return jnp.sin(x) * jnp.linalg.norm(x)

    @jax.jit
    def outer(x):
        return inner(x) + jnp.cumsum(x)

    return outer


def _metered(work) -> tuple[list[dict], CompileMeter, float]:
    """`work()` inside a `job` trace with a span `stage` open, under a
    meter -> (the trace's rows, the meter, the wall of `work`)."""
    tracer = Tracer(recorder=make_recorder("test"))
    with tracer.trace("job") as (trace_id, _), CompileMeter() as meter:
        with tracer.span("stage"):
            t0 = time.monotonic()
            work()
            wall = time.monotonic() - t0
    return tracer.recorder.span_rows(trace_id), meter, wall


def test_a_nested_jit_is_one_trace_and_its_seconds_count_once():
    outer = _nested()
    x = jnp.ones((next(_sizes),))
    rows, meter, wall = _metered(lambda: outer(x).block_until_ready())
    mine = [r for r in rows if r["name"] in COMPILE]
    assert [r["name"] for r in mine] == list(COMPILE)
    assert [r["labels"]["program"] for r in mine] == [
        "outer", "jit(outer)", "jit(outer)"]
    assert all(r["parent"] == "stage" for r in mine)
    assert meter.programs == 1
    # the parent's meter added `inner`'s trace to `outer`'s, which holds it
    assert 0.0 < meter.seconds <= wall
    assert meter.seconds == pytest.approx(
        sum(r["duration_s"] for r in mine), abs=2e-3)
    # a second call takes the jitted function's fast path: no event
    rows, meter, _ = _metered(lambda: outer(x).block_until_ready())
    assert [r["name"] for r in rows] == ["job", "stage"]
    assert (meter.seconds, meter.programs, meter.cache_hits) == (0.0, 0, 0)


def test_threads_outside_the_trace_count_and_open_no_span():
    """Four workers (partition workers have no trace context) each get a
    program of their own ready at once: a stack of open phases a thread,
    the totals under one lock."""
    work_of = [(_nested(), jnp.ones((next(_sizes),))) for _ in range(4)]

    def work():
        workers = [threading.Thread(
            target=lambda f=f, x=x: f(x).block_until_ready())
            for f, x in work_of]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120)
        assert not any(w.is_alive() for w in workers)

    rows, meter, wall = _metered(work)
    assert [r["name"] for r in rows] == ["job", "stage"]
    # seconds add over threads: at most the wall a worker
    assert meter.programs == 4 and 0.0 < meter.seconds <= 4 * wall


def test_without_a_recorder_the_totals_stay():
    outer = _nested()
    x = jnp.ones((next(_sizes),))
    tracer = Tracer()
    with tracer.trace("job") as (trace_id, _), CompileMeter() as meter:
        outer(x).block_until_ready()
    assert trace_id is None
    assert meter.programs == 1 and meter.seconds > 0.0
    assert tracer.histogram("compile.backend").count == 1


_TWO_PROCESSES = """
import json, sys
import jax, jax.numpy as jnp
from pio_tpu.obs import make_recorder
from pio_tpu.utils.compilecache import CompileMeter, enable_compile_cache
from pio_tpu.utils.tracing import Tracer

assert enable_compile_cache() == (sys.argv[1] or None)
tracer = Tracer(recorder=make_recorder("test"))
with tracer.trace("job") as (trace_id, _), CompileMeter() as meter:
    jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((37, 5))).block_until_ready()
print(json.dumps({"rows": tracer.recorder.span_rows(trace_id),
                  "hits": meter.cache_hits, "programs": meter.programs}))
"""


def test_a_first_process_misses_and_a_second_hits(tmp_path):
    cache = str(tmp_path / "cc")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=cache)
    env.pop("PIO_TPU_COMPILE_CACHE", None)

    def process() -> dict:
        out = subprocess.run(
            [sys.executable, "-c", _TWO_PROCESSES, cache], env=env,
            capture_output=True, text=True, check=True).stdout
        return json.loads(out.strip().splitlines()[-1])

    first, second = process(), process()
    for said in (first, second):
        assert said["programs"] >= 1
        assert [r["name"] for r in said["rows"][1:4]] == list(COMPILE)
    backend = [[r["labels"] for r in said["rows"]
                if r["name"] == "compile.backend"]
               for said in (first, second)]
    assert first["hits"] == 0 and second["hits"] == len(backend[1])
    assert all(b["cache"] == "miss" and "retrieval_s" not in b
               for b in backend[0])
    assert all(b["cache"] == "hit" and float(b["retrieval_s"]) > 0.0
               and "saved_s" in b for b in backend[1])


def test_under_the_kill_switch_no_process_reads_or_writes_the_cache(tmp_path):
    """`PIO_TPU_COMPILE_CACHE=off` with `JAX_COMPILATION_CACHE_DIR` set:
    jax would use that directory by itself (a second process reading
    `hit`, a first one `miss`); the switch turns it off, every row says
    `off` and the directory is never made."""
    cache = tmp_path / "cc"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PIO_TPU_COMPILE_CACHE="off")
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _TWO_PROCESSES, ""], env=env,
            capture_output=True, text=True, check=True).stdout
        said = json.loads(out.strip().splitlines()[-1])
        backend = [r["labels"] for r in said["rows"]
                   if r["name"] == "compile.backend"]
        assert said["programs"] == len(backend) >= 1
        assert said["hits"] == 0
        assert all(set(b) == {"program", "cache"} and b["cache"] == "off"
                   for b in backend), backend
    assert not cache.exists()


def test_with_the_cache_off_a_row_says_so():
    # jax decides once a process whether it uses the cache: reset it
    # around the switch, as tests/test_compilecache.py does
    from jax._src import compilation_cache as jcc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jcc.reset_cache()
    try:
        outer = _nested()
        x = jnp.ones((next(_sizes),))
        rows, _, _ = _metered(lambda: outer(x).block_until_ready())
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        jcc.reset_cache()
    [backend] = [r for r in rows if r["name"] == "compile.backend"]
    assert backend["labels"] == {"program": "jit(outer)", "cache": "off"}


# ---------------------------------------------------------------------------
# a job
# ---------------------------------------------------------------------------

def _jobs(caplog, jobs: int = 2) -> list[list[str]]:
    """`run_train` `jobs` times in this process on shapes no other test
    trains, so the first one gets its programs ready -> each job's
    messages."""
    storage = memory_storage()
    engine = tiny_engine(n_users=next(_sizes), n_items=next(_sizes))
    ctx = create_workflow_context(storage, use_mesh=False)
    out = []
    with caplog.at_level(logging.INFO, logger="pio_tpu.workflow"):
        for _ in range(jobs):
            caplog.clear()
            run_train(engine, tiny_params(cg_iters=3), storage,
                      engine_id="tiny", ctx=ctx)
            out.append([r.getMessage() for r in caplog.records])
    return out


def _timing(messages: list[str]):
    [timing] = [m for m in map(_TIMING.search, messages) if m]
    return float(timing[2]), int(timing[3]), int(timing[4])


def _under(rows: list[dict], name: str) -> list[dict]:
    """The `compile.*` rows below the span `name` (names are unique
    above them in a job's tree)."""
    below, grew = {name}, True
    while grew:
        more = {r["name"] for r in rows if r["parent"] in below} - below
        below |= more
        grew = bool(more)
    return [r for r in rows
            if r["name"] in COMPILE and r["parent"] in below]


def test_a_first_job_has_the_rows_and_a_second_none(caplog):
    first, second = _jobs(caplog)
    rows = _spans(first)
    by_name = {r["name"]: r for r in rows if r["name"] not in COMPILE}
    mine = [r for r in rows if r["name"] in COMPILE]
    seconds, programs, _hits = _timing(first)
    assert programs >= 1 and len(mine) == 3 * programs
    for row in mine:
        # under the span that was open, never under one of its own kind,
        # and inside that span's interval
        parent = by_name[row["parent"]]
        assert parent["name"] in ("als.init", "als.dispatch"), row
        assert parent["start_s"] <= row["start_s"] + 1e-6
        assert (row["start_s"] + row["duration_s"]
                <= parent["start_s"] + parent["duration_s"] + 1e-6)
        assert "status" not in row and row["labels"]["program"]
    assert {r["labels"]["cache"] for r in mine
            if r["name"] == "compile.backend"} <= {"hit", "miss", "off"}
    # the train program's three rows, in order, under the dispatch
    assert [r["name"] for r in mine
            if r["parent"] == "als.dispatch"] == list(COMPILE)
    assert [r["labels"]["program"] for r in mine
            if r["parent"] == "als.dispatch"] == [
                "_train_jit", "jit(_train_jit)", "jit(_train_jit)"]
    # one meter: the stage's labels are its share, the record its total
    stage = by_name["train.algorithms"]["labels"]
    under = _under(rows, "train.algorithms")
    assert int(stage["programs"]) * 3 == len(under)
    assert float(stage["compile_s"]) == pytest.approx(
        sum(r["duration_s"] for r in under), abs=5e-3)
    assert seconds == pytest.approx(
        sum(r["duration_s"] for r in mine), abs=1e-2)
    assert seconds <= by_name["train"]["duration_s"]
    # the second job of the process: the fast path fires no event
    assert not [r for r in _spans(second) if r["name"] in COMPILE]
    assert _timing(second) == (0.0, 0, 0)
    stage = {r["name"]: r for r in _spans(second)}["train.algorithms"]
    assert stage["labels"]["programs"] == "0"
    assert float(stage["labels"]["compile_s"]) == 0.0


def test_tracing_off_drops_the_rows_and_keeps_the_record(caplog):
    set_tracing(False)
    try:
        [messages] = _jobs(caplog, jobs=1)
    finally:
        set_tracing(None)
    assert not [m for m in messages if m.startswith("train spans:")]
    seconds, programs, _hits = _timing(messages)
    assert programs >= 1 and seconds > 0.0


def test_the_root_says_how_old_its_process_was(caplog):
    before = process_age_s()
    [messages] = _jobs(caplog, jobs=1)
    rows = _spans(messages)
    assert before is not None and before >= 0.0      # Linux
    age = float(rows[0]["labels"]["process_age_s"])
    assert before <= age + 1e-3 <= process_age_s() + 1e-3
    [devices] = [r for r in rows if r["name"] == "train.devices"]
    assert devices["parent"] == "train.setup"


@pytest.mark.parametrize("use_mesh", [False, True])
def test_a_job_without_a_context_makes_it_under_train_devices(
        use_mesh, caplog, monkeypatch):
    """As `pio train` runs it: the context, and with it the first call
    for jax's devices, is made inside the `train.devices` span."""
    from pio_tpu.workflow import train

    made = []
    real = train.create_workflow_context

    def slow_to_reach_the_chip(storage, **kw):
        made.append(kw)
        time.sleep(0.05)
        return real(storage, **kw)

    monkeypatch.setattr(train, "create_workflow_context",
                        slow_to_reach_the_chip)
    with caplog.at_level(logging.INFO, logger="pio_tpu.workflow"):
        run_train(tiny_engine(), tiny_params(cg_iters=3), memory_storage(),
                  engine_id="tiny", use_mesh=use_mesh)
    assert made == [{"use_mesh": use_mesh}]
    rows = {r["name"]: r for r in _spans(
        [r.getMessage() for r in caplog.records])}
    assert rows["train"]["labels"]["chips"] == ("8" if use_mesh else "1")
    assert 0.05 <= rows["train.devices"]["duration_s"] <= (
        rows["train.setup"]["duration_s"])


def test_an_engine_trained_outside_a_job_has_no_compile_labels():
    """`Engine.train` without `run_train` (as `pio eval` trains): no
    meter, so `train.algorithms` carries no compile label."""
    tracer = Tracer(recorder=make_recorder("test"))
    engine = tiny_engine()
    ctx = create_workflow_context(memory_storage(), use_mesh=False)
    with tracer.trace("eval") as (trace_id, _):
        engine.train(ctx, tiny_params(cg_iters=3))
    rows = {r["name"]: r for r in tracer.recorder.span_rows(trace_id)}
    assert rows["train.algorithms"]["labels"] == {}
    assert not set(COMPILE) & set(rows)
