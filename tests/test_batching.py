"""Cross-request continuous batching (pio_tpu/serving/batcher.py + the
fleet router coalescer):

  * ContinuousBatcher unit contract: slot-OR-window drain, deadline
    bypass/shed, NO request ever waits past its Deadline (regression),
    per-query solo fallback on batch failure;
  * batched binary wire frames: round-trip, solo interop, every
    truncation + random bit-flips rejected, forged counts die before
    allocation (the CI batching-parity job runs this file unfiltered);
  * single-host e2e: coalesced answers equal to the un-batched
    oracle's, items and order exactly and scores to 4 ULP of float32
    (mixed users, black/whiteList, unknown user), rollout arms
    likewise with per-arm stats counted ONCE per
    query (the hedged/batch double-count regression), /batcher.json +
    key-guarded /batcher/window;
  * 2-shard fleet e2e: coalesced fan-outs bit-identical on exact AND
    clustered retrieval, chaos drill killing a shard mid-coalesced-fan
    (zero 5xx, only the affected queries degrade), pre-batch replica
    400 -> sticky logged-once solo-frame fallback.
"""

import json
import random
import threading
import time
import urllib.error
import urllib.request
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

from pio_tpu.controller import EngineParams
from pio_tpu.data import DataMap, Event
from pio_tpu.data.dao import App
from pio_tpu.models.recommendation import (
    ALSAlgorithmParams,
    DataSourceParams,
    RecommendationEngine,
)
from pio_tpu.resilience import Deadline, DeadlineExceeded, chaos
from pio_tpu.serving.batcher import ContinuousBatcher
from pio_tpu.serving_fleet import rpcwire
from pio_tpu.serving_fleet.fleet import deploy_fleet
from pio_tpu.serving_fleet.plan import shard_of
from pio_tpu.serving_fleet.router import RouterConfig
from pio_tpu.workflow.context import create_workflow_context
from pio_tpu.workflow.serve import (
    QueryServer, ServingConfig, create_query_server,
)
from pio_tpu.workflow.train import load_models, run_train

T0 = datetime(2026, 1, 1, tzinfo=timezone.utc)
N_USERS = 20


def seed_events(storage):
    app_id = storage.get_metadata_apps().insert(App(0, "mlapp"))
    ev = storage.get_events()
    ev.init(app_id)
    rng = np.random.default_rng(0)
    m = 0
    for u in range(N_USERS):
        for i in range(12):
            match = (u % 2) == (i % 2)
            if rng.random() < (0.8 if match else 0.1):
                ev.insert(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap({"rating": 5 if match else 1}),
                    event_time=T0 + timedelta(minutes=m)), app_id)
                m += 1
    return app_id


def train_instance(storage, n_iter=4):
    engine = RecommendationEngine.apply()
    ep = EngineParams(
        datasource=("", DataSourceParams(app_name="mlapp")),
        algorithms=[("als", ALSAlgorithmParams(
            rank=4, num_iterations=n_iter, lambda_=0.05, chunk=1024))],
    )
    ctx = create_workflow_context(storage, use_mesh=False)
    iid = run_train(engine, ep, storage, engine_id="rec", ctx=ctx)
    return engine, ep, ctx, iid


@pytest.fixture()
def trained(memory_storage):
    seed_events(memory_storage)
    engine, ep, ctx, iid = train_instance(memory_storage)
    return memory_storage, engine, ep, ctx, iid


def call(port, method, path, body=None, **params):
    import urllib.parse

    qs = urllib.parse.urlencode(params)
    url = f"http://127.0.0.1:{port}{path}" + (f"?{qs}" if qs else "")
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "{}")


MIXED_QUERIES = [
    {"user": "u0", "num": 4},
    {"user": "u3", "num": 6, "blackList": ["i1", "i5"]},
    {"user": "u5", "num": 3, "whiteList": ["i2", "i7", "i9", "nope"]},
    {"user": "u5", "num": 2, "whiteList": ["i2", "i7", "i9"],
     "blackList": ["i7"]},
    {"user": "ghost", "num": 4},           # unknown user
    {"user": "u7", "num": 50},             # over-fetch past n_items
    {"user": "u11", "num": 5},
    {"user": "u2", "num": 3, "blackList": ["i0"]},
]


def assert_same_answer(got: dict, want: dict, q) -> None:
    """A coalesced answer against the batch-of-one oracle's: the same
    items in the same order, and every score within 4 ULP of float32
    (XLA's CPU matmul sums in another order at another batch shape)."""
    assert set(got) == set(want), q
    assert ([s["item"] for s in got["itemScores"]]
            == [s["item"] for s in want["itemScores"]]), q
    np.testing.assert_array_max_ulp(
        np.array([s["score"] for s in got["itemScores"]], np.float32),
        np.array([s["score"] for s in want["itemScores"]], np.float32),
        maxulp=4)
    for key in set(got) - {"itemScores"}:
        assert got[key] == want[key], (q, key)


def concurrent_http(port, queries, path="/queries.json"):
    """POST each query from its own thread (same-window arrivals) and
    return (status, body) in query order."""
    out = [None] * len(queries)

    def one(i, q):
        out[i] = call(port, "POST", path, body=dict(q))

    threads = [threading.Thread(target=one, args=(i, q))
               for i, q in enumerate(queries)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert all(r is not None for r in out)
    return out


# -- ContinuousBatcher unit contract ------------------------------------------

class FakeServer:
    """Stands in for QueryServer: records solo vs batched dispatches."""

    def __init__(self, batch_delay_s=0.0, fail_batch=False):
        from pio_tpu.utils.tracing import Tracer

        self.tracer = Tracer()
        self.batch_delay_s = batch_delay_s
        self.fail_batch = fail_batch
        self.solo_calls = []
        self.batch_calls = []
        self.lock = threading.Lock()

    def query(self, q):
        with self.lock:
            self.solo_calls.append(dict(q))
        return {"user": q["user"], "via": "solo"}

    def query_batch(self, queries, record=True,
                    observe_batch_errors=True):
        with self.lock:
            self.batch_calls.append([dict(q) for q in queries])
        if self.batch_delay_s:
            time.sleep(self.batch_delay_s)
        if self.fail_batch:
            raise RuntimeError("device fell over")
        return [{"user": q["user"], "via": "batch"} for q in queries]


def test_coalesces_concurrent_queries_into_one_dispatch():
    srv = FakeServer()
    b = ContinuousBatcher(srv, window_s=0.08, max_batch=64)
    try:
        out = [None] * 8

        def one(i):
            out[i] = b.query({"user": f"u{i}"})

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        # every caller got ITS OWN answer back (scatter is positional)
        assert sorted(r["user"] for r in out) == sorted(
            f"u{i}" for i in range(8))
        assert all(r["via"] == "batch" for r in out)
        # one window, one device dispatch — not eight
        assert len(srv.batch_calls) == 1
        assert len(srv.batch_calls[0]) == 8
        st = b.stats()
        assert st["mode"] == "continuous"
        assert st["dispatches"] == 1 and st["coalescedQueries"] == 8
        assert st["meanOccupancy"] == pytest.approx(8 / 64)
    finally:
        b.close()


def test_deadline_doomed_query_bypasses_solo_immediately():
    srv = FakeServer()
    b = ContinuousBatcher(srv, window_s=0.2, max_batch=8)
    try:
        with Deadline.budget(0.05):     # budget < window: can't wait
            t0 = time.monotonic()
            out = b.query({"user": "u1"})
            took = time.monotonic() - t0
        assert out["via"] == "solo"     # never entered the queue
        assert took < 0.15              # did NOT sleep the window
        assert b.stats()["bypassSolo"] == 1
        assert srv.batch_calls == []
    finally:
        b.close()


def test_spent_budget_sheds_before_enqueue():
    srv = FakeServer()
    b = ContinuousBatcher(srv, window_s=0.01, max_batch=8)
    try:
        with Deadline.budget(0.0):
            with pytest.raises(DeadlineExceeded):
                b.query({"user": "u1"})
        assert b.stats()["shed"] == 1
        assert srv.solo_calls == [] and srv.batch_calls == []
    finally:
        b.close()


def test_never_waits_past_deadline_even_when_execution_stalls():
    """THE deadline regression: a stalled device dispatch must not hold
    a request past its budget — the waiter sheds on time instead."""
    srv = FakeServer(batch_delay_s=1.0)   # execution far over budget
    b = ContinuousBatcher(srv, window_s=0.001, max_batch=8,
                          pipeline_depth=1)
    try:
        t0 = time.monotonic()
        with Deadline.budget(0.15):
            with pytest.raises(DeadlineExceeded):
                b.query({"user": "u1"})
        took = time.monotonic() - t0
        assert took < 0.6, f"waited {took:.2f}s past a 0.15s budget"
    finally:
        b.close()


def test_batch_failure_retries_each_query_solo():
    srv = FakeServer(fail_batch=True)
    b = ContinuousBatcher(srv, window_s=0.08, max_batch=8)
    try:
        out = [None] * 3

        def one(i):
            out[i] = b.query({"user": f"u{i}"})

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert all(r["via"] == "solo" for r in out)
        assert sorted(r["user"] for r in out) == ["u0", "u1", "u2"]
        assert len(srv.solo_calls) == 3
    finally:
        b.close()


# -- batched wire frames ------------------------------------------------------

def test_batch_request_roundtrip_and_solo_interop():
    rng = np.random.default_rng(1)
    rows = rng.standard_normal((3, 5)).astype(np.float32)
    for op, enc in (("topk", rpcwire.encode_topk_batch_request),
                    ("candidates",
                     rpcwire.encode_candidates_batch_request)):
        frame = enc(rows, [4, 9, 1], "candidate")
        got, ks, arm, batched = rpcwire.decode_scoring_request(frame, op)
        assert batched and arm == "candidate" and ks == [4, 9, 1]
        assert got.tobytes() == rows.tobytes()
    # a SOLO frame decodes through the same entry point as a 1-row
    # batch with batched=False (the shard answers it with a solo frame)
    solo = rpcwire.encode_topk_request(rows[0], 7)
    got, ks, arm, batched = rpcwire.decode_scoring_request(solo, "topk")
    assert not batched and ks == [7]
    assert got.shape == (1, 5) and got[0].tobytes() == rows[0].tobytes()
    # kind confusion still rejected across the batched layouts
    with pytest.raises(rpcwire.RpcWireError):
        rpcwire.decode_scoring_request(
            rpcwire.encode_candidates_batch_request(rows, [1, 2, 3]),
            "topk")


def test_batch_response_roundtrip_and_solo_frames_rejected():
    resp = rpcwire.encode_topk_batch_response([
        (["i1", "i2"], np.array([4, 9], np.int32),
         np.array([0.5, 0.25], np.float32)),
        ([], np.array([], np.int32), np.array([], np.float32)),
        (["i7"], np.array([2], np.int32), np.array([0.125], np.float32)),
    ])
    out = rpcwire.decode_topk_batch_response(resp)
    assert [list(o["items"]) for o in out] == [["i1", "i2"], [], ["i7"]]
    assert list(out[0]["indices"]) == [4, 9]
    assert list(out[2]["scores"]) == [0.125]
    # a SOLO kind-2 frame must not decode as a batch (and vice versa):
    # this asymmetry is exactly what turns a pre-batch replica into a
    # clean 400 -> sticky solo-frame fallback instead of silent garbage
    solo = rpcwire.encode_topk_response(
        ["i1"], np.array([3], np.int32), np.array([0.5], np.float32))
    with pytest.raises(rpcwire.RpcWireError):
        rpcwire.decode_topk_batch_response(solo)
    with pytest.raises(rpcwire.RpcWireError):
        rpcwire.decode_topk_response(resp)


def test_batch_frames_every_truncation_and_bitflip_rejected():
    rows = np.arange(6, dtype=np.float32).reshape(2, 3)
    req = rpcwire.encode_topk_batch_request(rows, [2, 3])
    resp = rpcwire.encode_topk_batch_response([
        (["i1", "i2"], np.array([0, 1], np.int32),
         np.array([1.0, 0.5], np.float32)),
        (["i3"], np.array([2], np.int32), np.array([0.25], np.float32)),
    ])
    for n in range(len(req)):
        with pytest.raises(rpcwire.RpcWireError):
            rpcwire.decode_scoring_request(req[:n], "topk")
    for n in range(len(resp)):
        with pytest.raises(rpcwire.RpcWireError):
            rpcwire.decode_topk_batch_response(resp[:n])
    rng = random.Random(0)
    for _ in range(64):
        flipped = bytearray(req)
        flipped[rng.randrange(len(req))] ^= 1 << rng.randrange(8)
        with pytest.raises(rpcwire.RpcWireError):
            rpcwire.decode_scoring_request(bytes(flipped), "topk")
    for _ in range(64):
        flipped = bytearray(resp)
        flipped[rng.randrange(len(resp))] ^= 1 << rng.randrange(8)
        with pytest.raises(rpcwire.RpcWireError):
            rpcwire.decode_topk_batch_response(bytes(flipped))


def test_batch_forged_counts_die_before_allocation():
    import struct

    from pio_tpu.utils import durable

    def forged(kind, header):
        hdr = json.dumps(header).encode()
        payload = struct.pack(">BI", kind, len(hdr)) + hdr
        return durable.frame(payload, magic=rpcwire.RPC_MAGIC)

    cases = [
        # batch count itself forged huge
        (forged(1, {"batch": 1 << 40, "d": 4, "ks": [], "arm": "active"}),
         "req"),
        # per-query k forged huge
        (forged(1, {"batch": 1, "d": 4, "ks": [1 << 40],
                    "arm": "active"}), "req"),
        # n*d floats forged over the section cap
        (forged(1, {"batch": 1 << 16, "d": 1 << 16,
                    "ks": [1] * (1 << 16), "arm": "active"}), "req"),
        # response counts forged huge
        (forged(2, {"batch": 1, "counts": [1 << 40], "items": []}),
         "resp"),
        # counts/items sidecar disagreement
        (forged(2, {"batch": 2, "counts": [1, 1], "items": ["only1"]}),
         "resp"),
    ]
    for frame, side in cases:
        t0 = time.monotonic()
        with pytest.raises(rpcwire.RpcWireError):
            if side == "req":
                rpcwire.decode_scoring_request(frame, "topk")
            else:
                rpcwire.decode_topk_batch_response(frame)
        assert time.monotonic() - t0 < 0.1   # rejected from the header


# -- single-host e2e ----------------------------------------------------------

def serve_coalescing(storage, engine, ep, ctx, window_ms=60.0,
                     instance_id=None, **cfg):
    http, qs = create_query_server(
        engine, ep, storage,
        ServingConfig(ip="127.0.0.1", port=0, engine_id="rec",
                      coalesce_window_ms=window_ms, server_key="SRVKEY",
                      **cfg),
        ctx=ctx, instance_id=instance_id)
    http.start()
    return http, qs


def test_single_host_coalesced_bit_parity(trained):
    """Concurrent queries through the coalescing admission stage answer
    as the un-batched predict path does (`assert_same_answer`: items and
    order exactly, scores to 4 ULP) — blackList, whiteList, unknown
    user, over-fetch included — and actually share device dispatches."""
    storage, engine, ep, ctx, iid = trained
    http, qs = serve_coalescing(storage, engine, ep, ctx)
    oracle = QueryServer(
        engine, ep, storage,
        ServingConfig(ip="127.0.0.1", port=0, engine_id="rec"),
        ctx=ctx, instance_id=iid)
    try:
        for _round in range(2):
            out = concurrent_http(http.port, MIXED_QUERIES)
            for q, (status, body) in zip(MIXED_QUERIES, out):
                assert status == 200, (q, body)
                assert_same_answer(body, oracle.query(dict(q)), q)
        _, st = call(http.port, "GET", "/batcher.json")
        assert st["enabled"] and st["mode"] == "continuous"
        assert st["coalescedQueries"] + st["bypassSolo"] >= 16
        # coalescing happened: fewer dispatches than queries
        assert 1 <= st["dispatches"] < st["coalescedQueries"]
        # the occupancy histogram reaches the Prometheus surface
        import urllib.request as _rq

        with _rq.urlopen(f"http://127.0.0.1:{http.port}/metrics",
                         timeout=10) as resp:
            text = resp.read().decode()
        assert "pio_serving_batch_occupancy_bucket" in text
    finally:
        http.stop()
        qs.close()


def test_single_host_rollout_arms_parity_and_single_count(trained):
    """Both rollout arms answer as their own oracle through the
    coalescer (`assert_same_answer`; the per-arm sub-batching contract)
    and every query counts ONCE in its arm's stats — the batch-path/hedged double-count regression."""
    from pio_tpu.rollout import in_canary

    storage, engine, ep, ctx, iid_a = trained
    _, _, _, iid_b = train_instance(storage, n_iter=6)
    http, qs = serve_coalescing(storage, engine, ep, ctx,
                                instance_id=iid_a)
    oracle_a = QueryServer(
        engine, ep, storage,
        ServingConfig(ip="127.0.0.1", port=0, engine_id="rec"),
        ctx=ctx, instance_id=iid_a)
    oracle_b = QueryServer(
        engine, ep, storage,
        ServingConfig(ip="127.0.0.1", port=0, engine_id="rec"),
        ctx=ctx, instance_id=iid_b)
    try:
        pct = 40
        code, out = call(http.port, "POST", "/rollout/deploy",
                         {"pct": pct, "shadowEvery": 10 ** 9,
                          "checkEvery": 10 ** 9},
                         accessKey="SRVKEY")
        assert code == 200, out
        queries = [{"user": f"u{u}", "num": 5} for u in range(N_USERS)]
        results = concurrent_http(http.port, queries)
        n_canary = 0
        for q, (status, body) in zip(queries, results):
            assert status == 200, (q, body)
            canary = in_canary(q["user"], pct)
            n_canary += canary
            want = (oracle_b if canary else oracle_a).query(dict(q))
            assert_same_answer(body, want, q)
        assert 0 < n_canary < N_USERS   # both arms actually exercised
        _, st = call(http.port, "GET", "/rollout/status")
        # exactly one observation per query per arm — a double-counted
        # batch error path or hedged duplicate would break these
        assert st["arms"]["candidate"]["requests"] == n_canary
        assert st["arms"]["active"]["requests"] == N_USERS - n_canary
    finally:
        http.stop()
        qs.close()


def test_deadline_doomed_requests_dispatch_solo_not_queued(trained):
    """A request whose budget is smaller than the coalesce window never
    waits for the window: it dispatches solo within budget (200), and
    the batcher accounts it as a bypass."""
    storage, engine, ep, ctx, iid = trained
    http, qs = serve_coalescing(storage, engine, ep, ctx,
                                window_ms=200.0, request_budget_s=0.1)
    try:
        t0 = time.monotonic()
        status, body = call(http.port, "POST", "/queries.json",
                            body={"user": "u0", "num": 3})
        took = time.monotonic() - t0
        assert status == 200 and body["itemScores"]
        assert took < 2.0       # no 200ms coalesce sleep on this path
        _, st = call(http.port, "GET", "/batcher.json")
        assert st["bypassSolo"] >= 1 and st["coalescedQueries"] == 0
    finally:
        http.stop()
        qs.close()


def test_batcher_window_route_guarded_and_live(trained):
    storage, engine, ep, ctx, iid = trained
    http, qs = serve_coalescing(storage, engine, ep, ctx)
    try:
        # key-guarded mutator (deep-lint GUARDED_PREFIXES covers it)
        status, _ = call(http.port, "POST", "/batcher/window",
                         body={"windowMs": 5.0})
        assert status == 401
        status, out = call(http.port, "POST", "/batcher/window",
                           body={"windowMs": 5.0}, accessKey="SRVKEY")
        assert status == 200 and out["windowMs"] == pytest.approx(5.0)
        _, st = call(http.port, "GET", "/batcher.json")
        assert st["windowMs"] == pytest.approx(5.0)
        # bad values rejected
        status, _ = call(http.port, "POST", "/batcher/window",
                         body={"windowMs": -1}, accessKey="SRVKEY")
        assert status == 400
    finally:
        http.stop()
        qs.close()


# -- 2-shard fleet e2e --------------------------------------------------------

def fleet_coalescing(storage, window_ms=60.0, **kw):
    return deploy_fleet(
        storage, engine_id="rec", n_shards=2, n_replicas=1,
        router_config=RouterConfig(coalesce_window_ms=window_ms,
                                   probe_interval_s=0.2),
        **kw)


def warm_binary(port, n=3):
    """A few sequential queries so every replica's binary wire is
    CONFIRMED — only then does the router send batched frames."""
    for u in range(n):
        status, _ = call(port, "POST", "/queries.json",
                         body={"user": f"u{u}", "num": 3})
        assert status == 200


def test_fleet_coalesced_bit_parity_exact(trained):
    """Concurrent queries through the coalescing router merge into
    batched shard frames and stay BIT-identical to the single-host
    oracle on exact retrieval."""
    storage, engine, ep, ctx, iid = trained
    handle = fleet_coalescing(storage)
    try:
        port = handle.router_http.port
        warm_binary(port)
        algo = engine._doers(ep)[2][0]
        full = load_models(storage, engine, ep, iid, ctx=ctx)[0]
        for _round in range(2):
            out = concurrent_http(port, MIXED_QUERIES)
            for q, (status, body) in zip(MIXED_QUERIES, out):
                assert status == 200, (q, body)
                assert body == algo.predict(full, dict(q)), q
        # the batch route rides the same coalescer
        status, batch = call(port, "POST", "/batch/queries.json",
                             body=[dict(q) for q in MIXED_QUERIES])
        assert status == 200
        assert batch == [algo.predict(full, dict(q))
                         for q in MIXED_QUERIES]
        _, fs = call(port, "GET", "/fleet.json")
        bt = fs["batching"]
        assert bt["enabled"]
        # coalescing actually produced multi-query dispatches
        assert bt["coalescedCalls"] >= 1
        assert bt["coalescedQueries"] >= 2 * bt["coalescedCalls"]
        # replicas accepted batched frames (negotiation confirmed)
        assert all(rep["batchWire"] for g in fs["shards"].values()
                   for rep in g["replicas"])
    finally:
        handle.close()


def test_fleet_coalesced_bit_parity_clustered(trained):
    """Clustered retrieval: the coalesced path must preserve per-query
    k grouping (k shapes the rerank width), so batched answers equal
    the SAME fleet's per-request answers bit-for-bit."""
    storage, *_ = trained
    retrieval = {"mode": "clustered", "dtype": "int8", "nprobe": 1,
                 "rerank_k": 8}
    solo = deploy_fleet(storage, engine_id="rec", n_shards=2,
                        n_replicas=1, retrieval=retrieval)
    handle = fleet_coalescing(storage, retrieval=retrieval)
    try:
        port = handle.router_http.port
        warm_binary(port)
        want = []
        for q in MIXED_QUERIES:
            status, body = call(solo.router_http.port, "POST",
                                "/queries.json", body=dict(q))
            assert status == 200
            want.append(body)
        out = concurrent_http(port, MIXED_QUERIES)
        for q, w, (status, body) in zip(MIXED_QUERIES, want, out):
            assert status == 200, (q, body)
            assert body == w, q
        _, fs = call(port, "GET", "/fleet.json")
        assert fs["batching"]["coalescedCalls"] >= 1
    finally:
        handle.close()
        solo.close()


def test_fleet_chaos_kill_shard_mid_coalesced_fan(trained):
    """Chaos drill on the coalesced plane: one shard group down mid-fan
    -> ZERO 5xx; queries needing the dead shard degrade (flagged), and
    whiteList queries owned entirely by the live shard stay exact."""
    storage, *_ = trained
    handle = fleet_coalescing(storage)
    try:
        port = handle.router_http.port
        warm_binary(port)
        live, dead = 0, 1
        users = [f"u{u}" for u in range(N_USERS)
                 if shard_of(f"u{u}", 2) == live]
        items = [f"i{i}" for i in range(12)
                 if shard_of(f"i{i}", 2) == live]
        assert users and len(items) >= 2
        plain = [{"user": users[0], "num": 3},
                 {"user": users[1 % len(users)], "num": 4}]
        isolated = [{"user": users[0], "num": 2,
                     "whiteList": items[:3]}]
        with chaos.inject(f"fleet.shard{dead}", error=1.0, seed=7):
            out = concurrent_http(port, plain + isolated)
        assert all(status < 500 for status, _ in out), out
        for status, body in out[:len(plain)]:
            assert status == 200 and body.get("degraded") is True
        for status, body in out[len(plain):]:
            assert status == 200 and "degraded" not in body, body
        # drill over: full service returns
        status, body = call(port, "POST", "/queries.json",
                            body={"user": users[0], "num": 3})
        assert status == 200 and not body.get("degraded")
    finally:
        handle.close()


def test_fleet_pre_batch_replica_sticky_fallback_logged_once(
        trained, monkeypatch, caplog):
    """A shard running a pre-batch build 400s the batched frame: the
    router downgrades that replica to solo frames STICKILY (logged
    once), the coalescer re-runs each query solo, and every answer
    stays bit-correct — no 5xx, no retry storm."""
    import logging

    storage, engine, ep, ctx, iid = trained
    handle = fleet_coalescing(storage)
    try:
        port = handle.router_http.port
        warm_binary(port)
        orig = rpcwire.decode_scoring_request

        def pre_batch_decode(data, op):
            rows, ks, arm, batched = orig(data, op)
            if batched:
                # what an old build's solo decoder does to the layout
                raise rpcwire.RpcWireError(
                    "unexpected batch header (pre-batch build)")
            return rows, ks, arm, batched

        monkeypatch.setattr(
            "pio_tpu.serving_fleet.rpcwire.decode_scoring_request",
            pre_batch_decode)
        algo = engine._doers(ep)[2][0]
        full = load_models(storage, engine, ep, iid, ctx=ctx)[0]
        with caplog.at_level(logging.WARNING,
                             logger="pio_tpu.fleet.router"):
            for _round in range(3):
                out = concurrent_http(port, MIXED_QUERIES[:4])
                for q, (status, body) in zip(MIXED_QUERIES, out):
                    assert status == 200, (q, body)
                    assert body == algo.predict(full, dict(q)), q
        downgrades = [r for r in caplog.records
                      if "sticky solo-frame downgrade" in r.message]
        # sticky: at most one downgrade log per replica, ever
        assert 1 <= len(downgrades) <= 2
        _, fs = call(port, "GET", "/fleet.json")
        assert fs["batching"]["fallbackCalls"] >= 1
        assert all(rep["batchWire"] is False
                   for g in fs["shards"].values()
                   for rep in g["replicas"]
                   if rep["batchWire"] is not None)
    finally:
        handle.close()
