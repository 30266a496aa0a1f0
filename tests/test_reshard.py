"""Live elastic resharding tests (pio_tpu/serving_fleet/reshard.py):

  * plan-diff determinism — byte-identical move sets across runs,
    minimal by construction, N' = N is a no-op,
  * PartitionSlice extract / kind-5 wire roundtrip + corruption,
  * the acceptance drill in-process: grow 2 -> 3 under concurrent
    query + fold-in load with ZERO 5xx, oracle bit-parity on both
    sides of the cutover, and the migration visible in /fleet.json,
    /metrics, and `pio reshard --status`,
  * mid-flight dual-routing: a moving partition answers from its new
    owner while the old owner's group is down; fold-ins dual-write so
    none are lost at the cutover,
  * `pio reshard --abort` mid-migration restores the old plan
    BIT-identical (and a failed cutover auto-aborts the same way),
  * a fully-dead retiring source group: the shrink completes by
    rebuilding slices from the durable partition blobs,
  * a slow-marked SUBPROCESS drill (the CI reshard-chaos job's shape:
    real processes, SIGKILL a source shard mid-migration).
"""

import json
import threading
import time

import numpy as np
import pytest

from pio_tpu.resilience import chaos
from pio_tpu.serving_fleet import rpcwire
from pio_tpu.serving_fleet.fleet import deploy_fleet, resolve_fleet_model
from pio_tpu.serving_fleet.plan import (
    N_PARTITIONS,
    compute_reshard_owners,
    default_owners,
    load_plan,
    partition_model,
    partition_of,
    plan_diff,
    slice_partition,
)
from pio_tpu.serving_fleet.reshard import (
    VERDICT_ABORTED,
    VERDICT_COMMITTED,
    ReshardRecord,
    load_reshard_record,
)
from pio_tpu.serving_fleet.router import RouterConfig
from pio_tpu.serving_fleet.shard import ShardConfig, create_shard_server
from pio_tpu.workflow.train import load_models
from test_fleet import call, seed_and_train


@pytest.fixture()
def trained(memory_storage):
    engine, ep, ctx, iid = seed_and_train(memory_storage)
    return memory_storage, engine, ep, ctx, iid


# -- plan-diff determinism ----------------------------------------------------

def test_reshard_owners_deterministic_and_byte_identical():
    """The move set is a pure function of (old owners, N'): two
    computations — and their serialized forms — are identical."""
    old = default_owners(2)
    a = compute_reshard_owners(old, 3)
    b = compute_reshard_owners(tuple(old), 3)
    assert a == b
    assert json.dumps(a) == json.dumps(b)
    assert json.dumps(plan_diff(old, a)) == json.dumps(plan_diff(old, b))
    # ... and across chained resizes
    c1 = compute_reshard_owners(compute_reshard_owners(old, 5), 3)
    c2 = compute_reshard_owners(compute_reshard_owners(old, 5), 3)
    assert c1 == c2


def test_reshard_owners_minimal_and_balanced():
    old = default_owners(2)
    new = compute_reshard_owners(old, 3)
    moves = plan_diff(old, new)
    # the diff is exactly the changed partitions — an unmoved partition
    # can never appear
    changed = [p for p in range(N_PARTITIONS) if old[p] != new[p]]
    assert [m[0] for m in moves] == changed
    assert all(old[p] == o and new[p] == n for p, o, n in moves)
    # every shard survives with a balanced share (32 partitions over 3
    # shards: 11/11/10), and the grow moved only the overflow
    counts = [new.count(s) for s in range(3)]
    assert sorted(counts) == [10, 11, 11]
    assert len(moves) == new.count(2)      # only partitions shard 2 gained
    # shrink: every partition on the removed shard moves, nothing else
    back = compute_reshard_owners(new, 2)
    shrink = plan_diff(new, back)
    assert {m[0] for m in shrink} >= {p for p in range(N_PARTITIONS)
                                      if new[p] == 2}
    assert all(o != n for _, o, n in shrink)
    assert max(back) <= 1


def test_reshard_noop_when_already_at_target():
    old = default_owners(3)
    assert compute_reshard_owners(old, 3) == old
    assert plan_diff(old, compute_reshard_owners(old, 3)) == ()


def test_reshard_record_roundtrip():
    rec = ReshardRecord(
        instance_id="i1", plan_version_old=1, plan_version_new=2,
        n_shards_old=2, n_shards_new=3, owners_old=default_owners(2),
        owners_new=compute_reshard_owners(default_owners(2), 3),
        moving=((7, 1, 2), (9, 0, 2)), staged=(7,))
    assert ReshardRecord.from_json(rec.to_json()) == rec


# -- slice / kind-5 wire ------------------------------------------------------

def test_partition_slice_wire_roundtrip(trained):
    storage, *_, iid = trained
    _, model = resolve_fleet_model(storage, "rec")
    part = partition_model(model, iid, 2)[0]
    p = partition_of(part.user_ids[0])
    sl = slice_partition(part, p)
    assert sl.user_ids                     # the slice is non-trivial
    out = rpcwire.decode_partition_slice(rpcwire.encode_partition_slice(sl))
    assert out.partition == sl.partition and out.instance_id == iid
    assert out.user_ids == sl.user_ids and out.item_ids == sl.item_ids
    np.testing.assert_array_equal(out.user_rows, sl.user_rows)
    np.testing.assert_array_equal(out.item_gidx, sl.item_gidx)
    np.testing.assert_array_equal(out.item_rows, sl.item_rows)


def test_partition_slice_wire_rejects_corruption(trained):
    storage, *_, iid = trained
    _, model = resolve_fleet_model(storage, "rec")
    part = partition_model(model, iid, 2)[0]
    data = bytearray(rpcwire.encode_partition_slice(
        slice_partition(part, partition_of(part.user_ids[0]))))
    data[len(data) // 2] ^= 0xFF
    with pytest.raises(Exception):
        rpcwire.decode_partition_slice(bytes(data))


# -- in-process end-to-end ----------------------------------------------------

def _fleet(storage, n_shards=2, n_replicas=2, **kw):
    return deploy_fleet(
        storage, engine_id="rec", n_shards=n_shards, n_replicas=n_replicas,
        router_config=RouterConfig(
            breaker_min_calls=2, breaker_open_s=0.5, probe_interval_s=0.2),
        **kw)


def _join_group(storage, shard_index, n_shards, n_replicas=2):
    """Boot the NEW shard group a grow adds (join-reshard mode: empty,
    awaiting staged slices)."""
    servers, urls = [], []
    for _r in range(n_replicas):
        http, srv = create_shard_server(storage, ShardConfig(
            ip="127.0.0.1", port=0, shard_index=shard_index,
            n_shards=n_shards, engine_id="rec", join_reshard=True))
        http.start()
        servers.append((http, srv))
        urls.append(f"http://127.0.0.1:{http.port}")
    return servers, urls


def _wait_reshard_done(port, timeout=60):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        _, st = call(port, "GET", "/reshard/status")
        if not st.get("inFlight"):
            return st
        time.sleep(0.05)
    raise AssertionError(f"reshard still in flight after {timeout}s: {st}")


def _oracle(trained):
    storage, engine, ep, ctx, iid = trained
    algo = engine._doers(ep)[2][0]
    full = load_models(storage, engine, ep, iid, ctx=ctx)[0]
    return lambda q: algo.predict(full, dict(q))


def test_grow_2_to_3_zero_5xx_under_load(trained):
    """The acceptance drill: reshard 2 -> 3 while queries and fold-ins
    hammer the router — zero 5xx, bit-parity on both sides of the
    cutover, migration visible on every surface."""
    storage, *_ = trained
    predict = _oracle(trained)
    handle = _fleet(storage)
    port = handle.router_http.port
    queries = [{"user": f"u{u}", "num": 4} for u in range(12)]
    for q in queries:
        s, out = call(port, "POST", "/queries.json", body=dict(q))
        assert s == 200 and out == predict(q), q

    statuses: list[int] = []
    lock = threading.Lock()
    stop = threading.Event()

    def hammer(w):
        while not stop.is_set():
            s, _ = call(port, "POST", "/queries.json",
                        body={"user": f"u{w}", "num": 3})
            with lock:
                statuses.append(s)

    fold_rows: dict[str, list[float]] = {}

    def folder():
        i = 0
        while not stop.is_set():
            uid = f"u{i % 8}"
            row = [float(i + 1)] * 4
            out = handle.router.upsert_users({uid: row}, staleness_s=0.1)
            if out.get("ok"):
                fold_rows[uid] = row
            i += 1
            time.sleep(0.02)

    threads = [threading.Thread(target=hammer, args=(w,)) for w in range(3)]
    threads.append(threading.Thread(target=folder))
    new_servers, urls = _join_group(storage, shard_index=2, n_shards=3)
    try:
        for t in threads:
            t.start()
        time.sleep(0.2)                         # load flowing
        s, out = call(port, "POST", "/reshard/begin",
                      body={"nShards": 3, "endpoints": [urls]})
        assert s == 200, out
        assert out["inFlight"] and out["planVersionNew"] == 2
        st = _wait_reshard_done(port)
        time.sleep(0.3)                         # post-cutover traffic too
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert st["verdict"] == VERDICT_COMMITTED, st
        assert st["partitionsStaged"] == st["partitionsMoving"] > 0
        # ZERO 5xx across the whole migration
        assert all(s < 500 for s in statuses), \
            [s for s in statuses if s >= 500][:5]
        # oracle bit-parity for users the fold-in thread never touched
        for q in queries[8:]:
            s, out = call(port, "POST", "/queries.json", body=dict(q))
            assert s == 200 and out == predict(q), q
        # no fold-in lost: every acked row is the one served, wherever
        # its partition landed
        plan = handle.router.plan
        for uid, row in fold_rows.items():
            rep_urls = handle.endpoints + [urls]
            owner = plan.owner_of(uid)
            url = rep_urls[owner][0].rsplit(":", 1)
            s, got = call(int(url[1]), "POST", "/shard/user_row",
                          body={"user": uid})
            assert s == 200 and got["found"], (uid, owner, got)
            assert got["row"] == row, uid
        # visible on every surface
        s, fs = call(port, "GET", "/fleet.json")
        assert fs["plan"]["nShards"] == 3
        assert fs["plan"]["planVersion"] == 2
        assert fs["reshard"]["verdict"] == VERDICT_COMMITTED
        assert fs["reshardPartitionsPending"] == 0
        s, _ = call(port, "GET", "/readyz")
        assert s == 200
        # durable: the record and the new plan survive the router
        assert load_plan(storage, plan.instance_id).plan_version == 2
        rec = load_reshard_record(storage, plan.instance_id)
        assert rec.verdict == VERDICT_COMMITTED
        assert set(rec.staged) == {m[0] for m in rec.moving}
    finally:
        stop.set()
        for http, _ in new_servers:
            http.stop()
        handle.close()


def _pause_at(point_name):
    """Patch chaos.maybe_inject to block at one named point until
    released — the deterministic mid-migration window the dual-route
    and abort tests need."""
    reached = threading.Event()
    release = threading.Event()
    orig = chaos.maybe_inject

    def patched(point):
        if point == point_name:
            reached.set()
            release.wait(timeout=60)
        return orig(point)

    return patched, reached, release


def test_midflight_dual_route_and_foldin(trained, monkeypatch):
    """With every partition staged but the cutover pending: a fold-in
    dual-writes to BOTH owners of a moving partition, and with the old
    owner's whole group down the router serves the moving user's row
    from the NEW owner — no 5xx, no unknown-user masquerade."""
    storage, *_ = trained
    handle = _fleet(storage)
    port = handle.router_http.port
    patched, reached, release = _pause_at("reshard.cutover")
    monkeypatch.setattr(chaos, "maybe_inject", patched)
    new_servers, urls = _join_group(storage, shard_index=2, n_shards=3)
    try:
        s, out = call(port, "POST", "/reshard/begin",
                      body={"nShards": 3, "endpoints": [urls]})
        assert s == 200, out
        assert reached.wait(timeout=60), "migration never hit the cutover"
        _, st = call(port, "GET", "/reshard/status")
        assert st["inFlight"] and st["partitionsStaged"] == \
            st["partitionsMoving"]
        moving = {m["partition"]: (m["from"], m["to"]) for m in st["moves"]}
        uid = next(f"u{u}" for u in range(20)
                   if partition_of(f"u{u}") in moving)
        src, dst = moving[partition_of(uid)]
        assert dst == 2
        # fold-in lands on BOTH owners: the old owner's active arm and
        # the new owner's arriving copy
        row = [0.25, -0.5, 0.75, 1.0]
        out = handle.router.upsert_users({uid: row}, staleness_s=0.1)
        assert out.get("ok") and out.get("reshardDualFailures") == 0, out
        s, got = call(int(urls[0].rsplit(":", 1)[1]), "POST",
                      "/shard/user_row", body={"user": uid})
        assert s == 200 and got["found"] and got["row"] == row, got
        # old owner's group goes fully down mid-migration: the router
        # dual-routes the moving user's read to the new owner — a 200
        # with real scores, not a 5xx and not found:false
        for h, _srv in handle.shards[2 * src:2 * src + 2]:
            h.stop()
        s, out = call(port, "POST", "/queries.json",
                      body={"user": uid, "num": 3})
        assert s == 200, out
        assert out["itemScores"], "dual-routed read lost the user row"
        release.set()
        st = _wait_reshard_done(port)
        assert st["verdict"] == VERDICT_COMMITTED, st
        # the dual-written fold-in survived the cutover onto the new
        # owner's merged partition
        s, got = call(int(urls[0].rsplit(":", 1)[1]), "POST",
                      "/shard/user_row", body={"user": uid})
        assert s == 200 and got["found"] and got["row"] == row, got
    finally:
        release.set()
        for http, _ in new_servers:
            http.stop()
        handle.close()


@pytest.mark.parametrize("when", ["during_prepare", "after_prepare"])
def test_midflight_resident_foldin_survives_cutover(trained, monkeypatch,
                                                    when):
    """A fold-in for a user whose partition STAYS on its shard, landing
    after that shard read its partition for the new arm (mid-prepare) or
    after the arm was built (before activate): the activated arm serves
    the acked row, not the snapshot's."""
    from pio_tpu.serving_fleet import shard as shard_mod

    storage, *_ = trained
    handle = _fleet(storage)
    port = handle.router_http.port
    old = default_owners(2)
    new = compute_reshard_owners(old, 3)
    uid = next(f"u{u}" for u in range(20)
               if old[partition_of(f"u{u}")] == new[partition_of(f"u{u}")])
    folded: list[list[float]] = []
    fold_lock = threading.Lock()

    def fold():
        with fold_lock:
            row = [len(folded) + 0.5, -0.25, 2.0, 1.5]
            out = handle.router.upsert_users({uid: row}, staleness_s=0.1)
            assert out.get("ok"), out
            folded.append(row)

    patched, reached, release = _pause_at("reshard.cutover")
    monkeypatch.setattr(chaos, "maybe_inject", patched)
    if when == "during_prepare":
        orig = shard_mod._prepare_arm

        def prepare_arm(part, rparams=None):
            # each owner replica has read its partition by now
            if uid in part.user_ids:
                fold()
            return orig(part, rparams)

        monkeypatch.setattr(shard_mod, "_prepare_arm", prepare_arm)
    new_servers, urls = _join_group(storage, shard_index=2, n_shards=3)
    try:
        s, out = call(port, "POST", "/reshard/begin",
                      body={"nShards": 3, "endpoints": [urls]})
        assert s == 200, out
        assert reached.wait(timeout=60), "migration never hit the cutover"
        if when == "after_prepare":
            fold()
        assert folded
        release.set()
        st = _wait_reshard_done(port)
        assert st["verdict"] == VERDICT_COMMITTED, st
        owner = handle.router.plan.owner_of(uid)
        assert owner == old[partition_of(uid)]
        for url in handle.endpoints[owner]:
            s, got = call(int(url.rsplit(":", 1)[1]), "POST",
                          "/shard/user_row", body={"user": uid})
            assert s == 200 and got["found"], got
            assert got["row"] == folded[-1], (got, folded)
    finally:
        release.set()
        for http, _ in new_servers:
            http.stop()
        handle.close()


def test_abort_midflight_restores_old_plan_bit_identical(trained,
                                                         monkeypatch):
    storage, *_ = trained
    predict = _oracle(trained)
    handle = _fleet(storage)
    port = handle.router_http.port
    old_plan_json = handle.router.plan.to_json()
    reached = threading.Event()
    release = threading.Event()
    orig = chaos.maybe_inject

    def patched(point):
        if point == "reshard.cutover":
            reached.set()
            # abort-aware pause: wake as soon as the operator aborts,
            # so abort() never waits out its worker-join timeout
            deadline = time.monotonic() + 60
            while (not release.is_set()
                   and not handle.router.reshard._abort.is_set()
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        return orig(point)

    monkeypatch.setattr(chaos, "maybe_inject", patched)
    new_servers, urls = _join_group(storage, shard_index=2, n_shards=3)
    try:
        s, out = call(port, "POST", "/reshard/begin",
                      body={"nShards": 3, "endpoints": [urls]})
        assert s == 200, out
        assert reached.wait(timeout=60)
        s, out = call(port, "POST", "/reshard/abort")
        assert s == 200, out
        st = _wait_reshard_done(port)
        assert st["verdict"] == VERDICT_ABORTED, st
        # BIT-identical restore: plan object, durable plan, topology
        assert handle.router.plan.to_json() == old_plan_json
        assert load_plan(storage,
                         handle.router.plan.instance_id).to_json() \
            == old_plan_json
        s, fs = call(port, "GET", "/fleet.json")
        assert fs["plan"]["nShards"] == 2
        assert fs["plan"]["planVersion"] == 1
        assert sorted(int(k) for k in fs["shards"]) == [0, 1]
        # serving never skipped a beat — parity against the oracle
        for u in range(10):
            q = {"user": f"u{u}", "num": 4}
            s, out = call(port, "POST", "/queries.json", body=dict(q))
            assert s == 200 and out == predict(q), q
        s, _ = call(port, "GET", "/readyz")
        assert s == 200
        rec = load_reshard_record(storage, handle.router.plan.instance_id)
        assert rec.verdict == VERDICT_ABORTED
        # a second migration can start after the abort (the record does
        # not wedge the fleet) — and N' = N is a clean no-op
        s, out = call(port, "POST", "/reshard/begin", body={"nShards": 2})
        assert s == 200 and out.get("noop"), out
    finally:
        release.set()
        for http, _ in new_servers:
            http.stop()
        handle.close()


def test_failed_cutover_auto_aborts(trained):
    """A cutover that dies (chaos at reshard.cutover) converges to a
    clean ABORTED record with the old plan untouched — the operator
    never has to untangle a half-flipped fleet."""
    storage, *_ = trained
    handle = _fleet(storage)
    port = handle.router_http.port
    old_plan_json = handle.router.plan.to_json()
    new_servers, urls = _join_group(storage, shard_index=2, n_shards=3)
    try:
        with chaos.inject("reshard.cutover", error=1.0, seed=3) as monkey:
            s, out = call(port, "POST", "/reshard/begin",
                          body={"nShards": 3, "endpoints": [urls]})
            assert s == 200, out
            st = _wait_reshard_done(port)
        assert monkey.injected["reshard.cutover"]["error"] >= 1
        assert st["verdict"] == VERDICT_ABORTED, st
        assert handle.router.plan.to_json() == old_plan_json
        s, out = call(port, "POST", "/queries.json",
                      body={"user": "u1", "num": 3})
        assert s == 200 and out["itemScores"]
    finally:
        for http, _ in new_servers:
            http.stop()
        handle.close()


def test_transfer_chaos_absorbed_by_retry(trained):
    """Injected faults at reshard.transfer (every attempt rolls the
    dice) are absorbed by the per-partition retry policy — the
    migration still commits."""
    storage, *_ = trained
    handle = _fleet(storage)
    port = handle.router_http.port
    new_servers, urls = _join_group(storage, shard_index=2, n_shards=3)
    try:
        # seed chosen so the roll sequence injects several failures but
        # never three in a row for one partition (the retry budget)
        with chaos.inject("reshard.transfer", error=0.4, seed=1) as monkey:
            s, out = call(port, "POST", "/reshard/begin",
                          body={"nShards": 3, "endpoints": [urls]})
            assert s == 200, out
            st = _wait_reshard_done(port)
        assert st["verdict"] == VERDICT_COMMITTED, st
        assert monkey.injected.get("reshard.transfer",
                                   {}).get("error", 0) >= 1
    finally:
        for http, _ in new_servers:
            http.stop()
        handle.close()


def test_shrink_with_dead_source_rebuilds_from_storage(trained):
    """The SIGKILL bar, in-process: the RETIRING group dies before the
    shrink — every one of its partitions is rebuilt from the durable
    partition blobs and the migration still commits."""
    storage, *_ = trained
    predict = _oracle(trained)
    handle = _fleet(storage, n_shards=3, n_replicas=2)
    port = handle.router_http.port
    try:
        # kill ALL of shard 2 (the group a 3 -> 2 shrink retires)
        for http, _srv in handle.shards[4:6]:
            http.stop()
        s, out = call(port, "POST", "/reshard/begin", body={"nShards": 2})
        assert s == 200, out
        st = _wait_reshard_done(port)
        assert st["verdict"] == VERDICT_COMMITTED, st
        s, fs = call(port, "GET", "/fleet.json")
        assert fs["plan"]["nShards"] == 2
        assert sorted(int(k) for k in fs["shards"]) == [0, 1]
        for u in range(10):
            q = {"user": f"u{u}", "num": 4}
            s, out = call(port, "POST", "/queries.json", body=dict(q))
            assert s == 200 and out == predict(q), q
    finally:
        handle.close()


def test_reshard_refuses_bad_requests(trained):
    storage, *_ = trained
    handle = _fleet(storage)
    port = handle.router_http.port
    try:
        s, out = call(port, "POST", "/reshard/abort")
        assert s == 409 and "no reshard" in out["message"]
        s, out = call(port, "POST", "/reshard/begin", body={"nShards": 0})
        assert s == 409
        s, out = call(port, "POST", "/reshard/begin",
                      body={"nShards": N_PARTITIONS + 1})
        assert s == 409
        # growing without endpoints for the new group is refused
        s, out = call(port, "POST", "/reshard/begin", body={"nShards": 3})
        assert s == 409 and "endpoint" in out["message"]
        s, out = call(port, "GET", "/reshard/status")
        assert s == 200 and out == {"inFlight": False,
                                    "planVersion": 1}
    finally:
        handle.close()


def test_reshard_gauges_on_metrics(trained):
    storage, *_ = trained
    handle = _fleet(storage)
    port = handle.router_http.port
    new_servers, urls = _join_group(storage, shard_index=2, n_shards=3)
    try:
        s, out = call(port, "POST", "/reshard/begin",
                      body={"nShards": 3, "endpoints": [urls]})
        assert s == 200, out
        _wait_reshard_done(port)
        import urllib.request

        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "pio_reshard_partitions_moved_total" in text
        assert "pio_reshard_partitions_pending_total" in text
        s, mj = call(port, "GET", "/metrics.json")
        assert mj["reshard"]["partitionsPending"] == 0
        assert mj["reshard"]["partitionsMoved"] > 0
    finally:
        for http, _ in new_servers:
            http.stop()
        handle.close()


# -- cli ----------------------------------------------------------------------

def test_cli_reshard_and_doctor_fleet(trained, cli):
    """`pio reshard --shards 3` drives (and follows) the migration;
    `pio reshard --status` and `pio doctor --fleet` report it done."""
    storage, *_ = trained
    handle = _fleet(storage)
    port = handle.router_http.port
    new_servers, urls = _join_group(storage, shard_index=2, n_shards=3)
    try:
        code, captured = cli("reshard", "--shards", "3",
                             "--endpoint", ",".join(urls),
                             "--port", str(port))
        assert code == 0, captured.out
        assert "COMMITTED" in captured.out
        code, captured = cli("reshard", "--status", "--port", str(port))
        assert code == 0
        st = json.loads(captured.out)
        assert st["verdict"] == VERDICT_COMMITTED and not st["inFlight"]
        url = f"http://127.0.0.1:{port}"
        code, captured = cli("doctor", "--fleet", "--router-url", url)
        assert code == 0, captured.out
        assert "reshard: last migration COMMITTED" in captured.out
        assert "3 shards" in captured.out
        assert "[WARN] plan-version disagreement" not in captured.out
        code, captured = cli("doctor", "--fleet", "--router-url", url,
                             "--json")
        assert code == 0
        report = json.loads(captured.out)
        assert report["planVersion"] == 2
        assert report["stalePlanReplicas"] == []
        assert report["reshard"]["verdict"] == VERDICT_COMMITTED
        # nothing in flight -> --abort is a refusal, not a crash
        code, captured = cli("reshard", "--abort", "--port", str(port))
        assert code == 1
    finally:
        for http, _ in new_servers:
            http.stop()
        handle.close()


# -- subprocess drill (the CI reshard-chaos job's shape) ----------------------

@pytest.mark.slow
def test_subprocess_reshard_sigkill_drill(tmp_path):
    """The ISSUE chaos bar as REAL processes over shared sqlite: grow
    2 -> 3 under concurrent query load, SIGKILL one source-shard
    replica mid-migration -> the transfer fails over to the surviving
    replica, the plan converges to N' = 3, zero 5xx throughout."""
    import os
    import signal
    import socket
    import subprocess
    import sys

    from pio_tpu.data.storage import Storage
    from pio_tpu.serving_fleet.plan import persist_fleet_artifacts
    from pio_tpu.serving_fleet.router import create_fleet_router

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    db = tmp_path / "fleet.db"
    env_map = {
        "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQL_PATH": str(db),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQL",
    }
    storage = Storage(env=env_map)
    try:
        _engine, _ep, _ctx, iid = seed_and_train(storage)
        _, model = resolve_fleet_model(storage, "rec")
        plan = persist_fleet_artifacts(storage, iid, model, 2, 2)
    finally:
        storage.close()

    proc_env = dict(os.environ, JAX_PLATFORMS="cpu", **env_map)

    def spawn(shard_index: int, n_shards: int, port: int,
              join: bool = False) -> subprocess.Popen:
        argv = [sys.executable, "-m", "pio_tpu.serving_fleet", "shard",
                "--shard-index", str(shard_index),
                "--n-shards", str(n_shards),
                "--engine-id", "rec", "--port", str(port)]
        if join:
            argv.append("--join-reshard")
        else:
            argv += ["--instance-id", iid]
        return subprocess.Popen(argv, env=proc_env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT)

    ports = [[free_port() for _ in range(2)] for _ in range(2)]
    new_ports = [free_port() for _ in range(2)]
    procs = {(s, r): spawn(s, 2, ports[s][r])
             for s in range(2) for r in range(2)}
    for r in range(2):
        procs[(2, r)] = spawn(2, 3, new_ports[r], join=True)

    def wait_ready(port: int, timeout=60):
        deadline = time.monotonic() + timeout
        # pio: lint-ok[bare-retry] test poll waiting for a freshly
        # spawned shard subprocess to bind and report ready
        while time.monotonic() < deadline:
            try:
                s, _ = call(port, "GET", "/readyz")
                if s == 200:
                    return
            except OSError:
                pass
            time.sleep(0.2)
        raise AssertionError(f"shard on port {port} never became ready")

    handle = None
    storage = Storage(env=env_map)
    try:
        for group in ports:
            for p in group:
                wait_ready(p)
        for p in new_ports:
            wait_ready(p)
        router_http, router = create_fleet_router(
            storage,
            RouterConfig(engine_id="rec", breaker_min_calls=2,
                         breaker_open_s=0.5, probe_interval_s=0.2),
            plan,
            [[f"http://127.0.0.1:{p}" for p in group] for group in ports],
        )
        router_http.start()
        handle = (router_http, router)
        rport = router_http.port

        statuses: list[int] = []
        lock = threading.Lock()
        stop = threading.Event()

        def hammer(w):
            while not stop.is_set():
                st, _body = call(rport, "POST", "/queries.json",
                                 body={"user": f"u{w}", "num": 3})
                with lock:
                    statuses.append(st)

        threads = [threading.Thread(target=hammer, args=(w,))
                   for w in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        # slow every partition transfer so the SIGKILL lands MID-flight
        with chaos.inject("reshard.transfer", slow=1.0, slow_s=0.2):
            s, out = call(
                rport, "POST", "/reshard/begin",
                body={"nShards": 3, "endpoints":
                      [[f"http://127.0.0.1:{p}" for p in new_ports]]})
            assert s == 200, out
            time.sleep(0.5)           # a few transfers through
            procs[(0, 0)].kill()      # SIGKILL a source replica
            st = _wait_reshard_done(rport, timeout=120)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert st["verdict"] == VERDICT_COMMITTED, st
        assert all(s < 500 for s in statuses), \
            [s for s in statuses if s >= 500][:5]
        s, fs = call(rport, "GET", "/fleet.json")
        assert fs["plan"]["nShards"] == 3
        assert fs["plan"]["planVersion"] == 2
        # full post-cutover service across every shard, no degradation
        for u in range(8):
            s, body = call(rport, "POST", "/queries.json",
                           body={"user": f"u{u}", "num": 3})
            assert s == 200 and body["itemScores"], (u, body)
            assert not body.get("degraded"), (u, body)
    finally:
        stop.set()
        if handle is not None:
            handle[0].stop()
            handle[1].close()
        storage.close()
        for p in procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
