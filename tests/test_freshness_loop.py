"""The fold-in worker's own loop (`FoldInWorker.start` / `stop`) under a
concurrent writer: tests/test_freshness.py drives `run_once` by hand; this
runs the thread a deployment runs. It holds no clock: waits are bounded
so a hang fails, and no duration is asserted."""

import threading
import time

import numpy as np
import pytest

from pio_tpu.freshness import FoldInWorker, LocalServingApplier
from tests.test_freshness import foldin_config, ingest, train
from tests.test_serve import call as http_call


def test_worker_thread_folds_under_a_writer_and_a_new_user_serves(
        memory_storage, tmp_path):
    """The loop itself (`start` / `stop`, which no other test runs): a
    writer thread keeps rating for existing users while the worker
    polls; its fold-ins land, then a brand-new user's first events turn
    the live answer from the cold one into a personal one, the worker
    never fails a cycle, and once the writer stops it catches up."""
    from pio_tpu.workflow.serve import ServingConfig, create_query_server

    storage = memory_storage
    engine, ep, ctx, iid, app_id = train(storage)
    http, qs = create_query_server(
        engine, ep, storage,
        ServingConfig(ip="127.0.0.1", port=0, engine_id="rec"), ctx=ctx)
    http.start()
    worker = FoldInWorker(
        storage, foldin_config(tmp_path, poll_interval_s=0.02),
        LocalServingApplier(qs))
    stop = threading.Event()
    rng = np.random.default_rng(1)

    def writer():
        while not stop.is_set():
            ingest(storage, app_id, f"u{rng.integers(0, 20)}",
                   [(f"i{rng.integers(0, 12)}", int(rng.integers(1, 6)))])
            stop.wait(0.005)

    def until(done, what):
        for _ in range(3000):           # a bound on a hang, not a time
            if done():
                return
            time.sleep(0.02)
        pytest.fail(f"{what}: {worker.snapshot()}")

    def answer():
        st, body = http_call(http.port, "POST", "/queries.json",
                             {"user": "newbie", "num": 3})
        assert st == 200
        return body

    load = threading.Thread(target=writer, daemon=True)
    worker.start()
    load.start()
    try:
        until(lambda: worker.folded_total > 0,
              "the worker never applied under the writer")
        cold = answer()
        ingest(storage, app_id, "newbie",
               [("i1", 5), ("i3", 5), ("i7", 1)])
        until(lambda: answer() != cold,
              "the new user's events never became servable")
        assert len(answer()["itemScores"]) == 3
        stop.set()
        load.join(timeout=10)
        until(lambda: worker.queue_depth() == 0,
              "the worker never caught up once the writer stopped")
    finally:
        stop.set()
        worker.stop()
        http.stop()
        qs.close()
    snap = worker.snapshot()
    assert worker.failures == 0 and worker.last_error is None, snap
    assert snap["foldedTotal"] >= 2 and snap["appliedBatches"] >= 2
    with qs._lock:
        assert "newbie" in qs.models[0].users
