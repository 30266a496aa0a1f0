"""Replicated event store (data/backends/replicated.py): quorum
writes, hinted handoff, anti-entropy scrub (docs/storage.md
"Replication").

Coverage map:
  * quorum semantics: ack at W, QuorumLostError (transient) below W,
    per-replica chaos points, config validation;
  * hinted handoff: durable hints for a down replica BEFORE the ack,
    drain on rejoin (including a WIPED rejoiner), truncation + bit-flip
    fuzz over the FrameLog (corrupt hint => skipped + counted, never a
    crash or a half-applied write — tests/test_columnar_wire.py's
    frame-fuzz shape);
  * reads: failover bit-parity with one replica down (find rows AND
    find_columnar frames identical to a single healthy backend),
    bounded read-repair on a get() divergence;
  * scrub: bucket-digest divergence detection + union repair, doctor
    --storage surface, /metrics gauges on the event server;
  * a slow-marked SUBPROCESS drill (the CI storage-chaos job's shape):
    SIGKILL one of 3 storage-server replicas mid-ingest under
    concurrent load (W=2), every 201-acked event readable from the
    surviving quorum, rejoin -> hint drain + scrub -> convergence,
    `pio doctor --storage` exits 0.
"""

import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

import pytest

from pio_tpu.data.backends.memory import MemoryBackend
from pio_tpu.data.backends.replicated import (
    QuorumLostError, ReplicatedEventsDAO,
)
from pio_tpu.data.datamap import DataMap
from pio_tpu.data.event import Event
from pio_tpu.data.storage import Storage, StorageClientConfig, StorageError
from pio_tpu.resilience import is_transient
from pio_tpu.utils.durable import LOG_MAGIC, FrameLog, frame

APP = 1


def mem_events():
    return MemoryBackend(StorageClientConfig()).events()


def make_dao(tmp_path, n=3, quorum=2, **kw):
    replicas = [mem_events() for _ in range(n)]
    dao = ReplicatedEventsDAO(
        replicas, write_quorum=quorum, hint_dir=str(tmp_path / "hints"),
        **kw)
    dao.init(APP)
    return dao, replicas


def ev(i, name="rate"):
    return Event(event=name, entity_type="user", entity_id=f"u{i}",
                 target_entity_type="item", target_entity_id=f"i{i}",
                 properties=DataMap({"rating": i % 5 + 1}))


class DeadDAO:
    """Every call fails like a dead transport."""

    def __getattr__(self, name):
        def boom(*a, **k):
            raise ConnectionError("replica dead")

        return boom


# -- quorum writes -----------------------------------------------------------

def test_quorum_write_replicates_to_all(tmp_path):
    dao, replicas = make_dao(tmp_path)
    ids = dao.insert_batch([ev(i) for i in range(10)], APP)
    assert len(set(ids)) == 10
    for r in replicas:
        got = sorted(e.event_id for e in r.find(APP, limit=-1))
        assert got == sorted(ids)
    dao.close()


def test_single_insert_and_get_and_delete(tmp_path):
    dao, replicas = make_dao(tmp_path)
    eid = dao.insert(ev(1), APP)
    assert dao.get(eid, APP).entity_id == "u1"
    assert dao.delete(eid, APP) is True
    for r in replicas:
        assert r.get(eid, APP) is None
    assert dao.get(eid, APP) is None
    dao.close()


def test_write_quorum_validation(tmp_path):
    with pytest.raises(StorageError):
        ReplicatedEventsDAO([mem_events()], write_quorum=2,
                            hint_dir=str(tmp_path / "h"))
    with pytest.raises(StorageError):
        ReplicatedEventsDAO([], hint_dir=str(tmp_path / "h"))


def test_one_replica_down_write_acks_and_hints(tmp_path):
    dao, replicas = make_dao(tmp_path)
    dao.replicas[2] = DeadDAO()
    ids = dao.insert_batch([ev(i) for i in range(5)], APP)
    assert len(ids) == 5                      # acked at 2/3
    st = dao.replication_status()
    assert st["replicas"][2]["hintDepth"] == 1
    assert st["replicas"][2]["hintOldestAgeSeconds"] is not None
    assert st["counters"]["hinted"] == 1
    # surviving quorum serves every acked event immediately
    assert sorted(e.event_id for e in dao.find(APP, limit=-1)) \
        == sorted(ids)
    dao.close()


def test_quorum_lost_raises_transient(tmp_path):
    dao, _ = make_dao(tmp_path)
    dao.replicas[1] = DeadDAO()
    dao.replicas[2] = DeadDAO()
    with pytest.raises(QuorumLostError) as exc:
        dao.insert_batch([ev(1)], APP)
    # transient => the event server's spill/503 degradation applies,
    # and no hint was appended (the write was NOT acked)
    assert is_transient(exc.value)
    assert dao.replication_status()["hintDepthTotal"] == 0
    dao.close()


def test_chaos_point_per_replica(tmp_path):
    from pio_tpu.resilience import chaos

    dao, _ = make_dao(tmp_path)
    with chaos.inject("storage.replica1", error=1.0, seed=3) as monkey:
        ids = dao.insert_batch([ev(i) for i in range(3)], APP)
    assert len(ids) == 3                       # quorum held via 0 + 2
    assert any(p.startswith("storage.replica1.") for p in monkey.injected)
    assert dao.replication_status()["replicas"][1]["hintDepth"] == 1
    dao.close()


# -- hinted handoff ----------------------------------------------------------

def test_hint_drain_on_rejoin_wiped_replica(tmp_path):
    dao, replicas = make_dao(tmp_path)
    dao.insert_batch([ev(i) for i in range(6)], APP)
    dao.replicas[2] = DeadDAO()
    ids2 = dao.insert_batch([ev(i, "buy") for i in range(3)], APP)
    # rejoin with a WIPED store (worst case: fresh disk)
    fresh = mem_events()
    dao.replicas[2] = fresh
    dao.breakers[2].reset()
    assert dao.drain_hints(2) is True
    assert dao.hint_logs[2].depth() == 0
    got = {e.event_id for e in fresh.find(APP, limit=-1)}
    assert set(ids2) <= got                    # hinted writes replayed
    # the scrubber converges the pre-outage events the hints predate
    dao.scrub(APP, repair=True)
    assert dao.scrub(APP, repair=False)["divergentBuckets"] == 0
    all_ids = {e.event_id for e in dao.replicas[0].find(APP, limit=-1)}
    assert {e.event_id for e in fresh.find(APP, limit=-1)} == all_ids
    dao.close()


def test_hints_survive_process_restart(tmp_path):
    dao, _ = make_dao(tmp_path)
    dao.replicas[2] = DeadDAO()
    ids = dao.insert_batch([ev(i) for i in range(4)], APP)
    dao.close()
    # a new DAO over the same hint dir picks the pending hints up
    fresh = mem_events()
    replicas2 = [mem_events(), mem_events(), fresh]
    dao2 = ReplicatedEventsDAO(
        replicas2, write_quorum=2, hint_dir=str(tmp_path / "hints"))
    assert dao2.hint_logs[2].depth() == 1
    assert dao2.replication_status()["replicas"][2][
        "hintOldestAgeSeconds"] is not None
    assert dao2.drain_hints(2) is True
    assert {e.event_id for e in fresh.find(APP, limit=-1)} == set(ids)
    dao2.close()


def test_corrupt_hint_skipped_counted_rest_applied(tmp_path):
    dao, _ = make_dao(tmp_path)
    dao.replicas[2] = DeadDAO()
    ids_a = dao.insert_batch([ev(1)], APP)
    ids_b = dao.insert_batch([ev(2)], APP)
    ids_c = dao.insert_batch([ev(3)], APP)
    log_path = dao.hint_logs[2].path
    with open(log_path, "r+b") as f:
        data = bytearray(f.read())
        # flip a byte inside the SECOND record's payload region
        recs = []
        off = 0
        while off < len(data):
            nxt = data.find(LOG_MAGIC, off + 1)
            recs.append((off, len(data) if nxt < 0 else nxt))
            if nxt < 0:
                break
            off = nxt
        start, end = recs[1]
        data[(start + end) // 2] ^= 0xFF
        f.seek(0)
        f.write(data)
    # hand the healed replica over and drain: records 1 and 3 apply,
    # record 2 is skipped + counted — never a crash, never half-applied
    fresh = mem_events()
    dao.replicas[2] = fresh
    dao.breakers[2].reset()
    dao.hint_logs[2] = FrameLog(log_path)      # re-scan the damaged file
    assert dao.drain_hints(2) is True
    got = {e.event_id for e in fresh.find(APP, limit=-1)}
    assert set(ids_a) <= got and set(ids_c) <= got
    assert not (set(ids_b) & got)
    assert dao.hint_logs[2].corrupt_total >= 1
    dao.close()


def test_framelog_truncation_fuzz(tmp_path):
    """Every truncation length of a 3-record log: the scan never raises,
    yields a prefix of the intact records, and counts the torn tail."""
    path = str(tmp_path / "t.hints")
    log = FrameLog(path)
    payloads = [f"record-{i}".encode() * (i + 1) for i in range(3)]
    for p in payloads:
        log.append(p)
    with open(path, "rb") as f:
        full = f.read()
    # record end offsets: a cut exactly at one is a CLEAN prefix (no
    # partial record to count); any other cut must count the torn tail
    boundaries = []
    off = 0
    for p in payloads:
        off += len(frame(p, magic=LOG_MAGIC))
        boundaries.append(off)
    for cut in range(len(full)):
        trunc = str(tmp_path / "trunc.hints")
        with open(trunc, "wb") as f:
            f.write(full[:cut])
        got, corrupt, _ = FrameLog(trunc).scan()
        assert got == payloads[:len(got)]      # always an intact prefix
        if cut in (0, *boundaries):
            assert corrupt == 0
        else:
            assert corrupt >= 1                # torn tail counted


def test_framelog_bitflip_fuzz(tmp_path):
    """64 random single-bit flips: the scan never raises and every
    yielded payload is one of the originals, bit-exact (a flipped
    record can vanish, never mutate silently)."""
    path = str(tmp_path / "b.hints")
    log = FrameLog(path)
    payloads = [os.urandom(40 + 13 * i) for i in range(4)]
    # regenerate payloads without LOG_MAGIC inside so resync cannot be
    # fooled by payload bytes in this test (production tolerates it as
    # an extra skip+count, asserted separately below)
    payloads = [p.replace(LOG_MAGIC[:2], b"zz") for p in payloads]
    for p in payloads:
        log.append(p)
    with open(path, "rb") as f:
        full = bytearray(f.read())
    rng = random.Random(7)
    for _ in range(64):
        data = bytearray(full)
        pos = rng.randrange(len(data))
        data[pos] ^= 1 << rng.randrange(8)
        flip = str(tmp_path / "flip.hints")
        with open(flip, "wb") as f:
            f.write(data)
        got, corrupt, _ = FrameLog(flip).scan()
        for g in got:
            assert g in payloads
        assert len(got) >= len(payloads) - 2   # one flip kills <= 1 record
        if len(got) < len(payloads):
            assert corrupt >= 1
    # a payload CONTAINING the record magic still round-trips intact
    tricky = str(tmp_path / "tricky.hints")
    tl = FrameLog(tricky)
    tl.append(b"xx" + LOG_MAGIC + b"yy")
    got, _, _ = FrameLog(tricky).scan()
    assert got == [b"xx" + LOG_MAGIC + b"yy"]


def test_framelog_rewrite_preserves_concurrent_appends(tmp_path):
    path = str(tmp_path / "c.hints")
    log = FrameLog(path)
    for i in range(3):
        log.append(f"r{i}".encode())
    payloads, _, scanned = log.scan()
    log.append(b"late")                        # lands after the scan
    log.rewrite_prefix(payloads[2:], scanned)  # drop the first two
    got, _, _ = log.scan()
    assert got == [b"r2", b"late"]
    assert log.depth() == 2


# -- reads -------------------------------------------------------------------

def test_read_bit_parity_one_replica_down(tmp_path):
    """Acceptance: find/find_columnar through the replicated DAO with
    one replica down are bit-identical to a single healthy backend —
    same rows, same ordering, same columnar frame bytes."""
    from pio_tpu.data.columnar import encode_columnar_events

    dao, replicas = make_dao(tmp_path)
    dao.insert_batch([ev(i) for i in range(30)], APP)
    oracle = replicas[1]
    frame_single = encode_columnar_events(oracle.find_columnar(APP))
    rows_single = list(oracle.find(APP, limit=-1))
    dao.replicas[0] = DeadDAO()
    assert encode_columnar_events(dao.find_columnar(APP)) == frame_single
    assert list(dao.find(APP, limit=-1)) == rows_single
    # default-limit + reversed paths stay delegated verbatim too
    assert list(dao.find(APP, limit=5, reversed=True)) \
        == list(oracle.find(APP, limit=5, reversed=True))
    dao.close()


def test_reads_prefer_replicas_without_pending_hints(tmp_path):
    dao, _ = make_dao(tmp_path)
    dao.insert_batch([ev(i) for i in range(3)], APP)
    dao.replicas[0] = DeadDAO()
    dao.insert_batch([ev(9, "buy")], APP)      # replica 0 gets a hint
    dao.replicas[0] = mem_events()             # rejoined but EMPTY,
    dao.breakers[0].reset()                    # hints not drained yet
    order = dao._read_order()
    assert order[0] != 0                       # known-stale read last
    assert len(list(dao.find(APP, limit=-1))) == 4
    dao.close()


def test_get_read_repairs_diverged_replica(tmp_path):
    dao, replicas = make_dao(tmp_path)
    ids = dao.insert_batch([ev(1)], APP)
    # manufacture divergence: remove the event from replica 0 only
    replicas[0].delete(ids[0], APP)
    got = dao.get(ids[0], APP)
    assert got is not None
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if replicas[0].get(ids[0], APP) is not None:
            break
        time.sleep(0.02)
    assert replicas[0].get(ids[0], APP) is not None
    assert dao.replication_status()["counters"]["readRepairs"] >= 1
    dao.close()


def test_aggregate_and_columnarize_failover(tmp_path):
    dao, _ = make_dao(tmp_path)
    dao.insert_batch(
        [Event(event="$set", entity_type="user", entity_id="u1",
               properties=DataMap({"plan": "pro"}))], APP)
    dao.insert_batch([ev(i) for i in range(4)], APP)
    dao.replicas[0] = DeadDAO()
    agg = dao.aggregate_properties(APP, "user")
    assert agg["u1"].fields["plan"] == "pro"
    cols = dao.columnarize(APP, entity_type="user", event_names=["rate"],
                           target_entity_type="item")
    assert len(cols.values) == 4
    dao.close()


class TransientStorageErrorDAO:
    """A remote replica's failure shape: StorageError WRAPPING a
    transport error (transient via the cause chain) — what
    RemoteBackend raises for an unreachable storage server."""

    def __getattr__(self, name):
        def boom(*a, **k):
            from pio_tpu.utils.httpclient import HttpClientError

            raise StorageError("storage server unreachable") \
                from HttpClientError(0, "connection refused")

        return boom


def test_find_lazy_pager_first_fetch_fails_over(tmp_path):
    """A remote replica's unbounded find is a LAZY pager whose first
    RPC fires at iteration: a replica that dies there must fail over to
    a healthy sibling, not surface a ConnectionError in the caller's
    loop (the fold-in history-read path)."""
    dao, replicas = make_dao(tmp_path)
    dao.insert_batch([ev(i) for i in range(5)], APP)
    oracle_rows = list(replicas[1].find(APP, limit=-1))

    class LazyDeath:
        def find(self, *a, **k):
            def gen():
                raise ConnectionError("first page RPC failed")
                yield  # pragma: no cover

            return gen()

        def close(self):
            pass

    dao.replicas[0] = LazyDeath()
    assert list(dao.find(APP, limit=-1)) == oracle_rows
    # the lazy failure was recorded against replica 0's breaker
    assert dao.breakers[0].snapshot().failures >= 1
    dao.close()


def test_framelog_corrupt_counts_stable_across_scans(tmp_path):
    """Re-scanning the SAME on-disk damage re-observes it, never
    re-counts: pending is a gauge, total finalizes at compaction."""
    path = str(tmp_path / "s.hints")
    log = FrameLog(path)
    for i in range(3):
        log.append(f"r{i}".encode())
    with open(path, "r+b") as f:
        data = bytearray(f.read())
        data[len(data) // 2] ^= 0xFF
        f.seek(0)
        f.write(data)
    reopened = FrameLog(path)                  # restart over the damage
    assert reopened.corrupt_pending >= 1
    assert reopened.corrupt_total == 0
    pend = reopened.corrupt_pending
    for _ in range(3):                         # repeated scans: stable
        reopened.scan()
    assert reopened.corrupt_pending == pend
    assert reopened.corrupt_total == 0
    payloads, corrupt, scanned = reopened.scan()
    reopened.rewrite_prefix(payloads, scanned, corrupt_dropped=corrupt)
    assert reopened.corrupt_total == pend      # finalized exactly once
    assert reopened.corrupt_pending == 0


def test_replicated_types_require_distinct_paths(tmp_path):
    """File-backed replicas without one DISTINCT path each would all
    share a single default store — quorum green, zero actual copies."""
    from pio_tpu.data.backends.replicated import ReplicatedBackend

    with pytest.raises(StorageError, match="one _PATHS entry per type"):
        ReplicatedBackend(StorageClientConfig(properties={
            "TYPES": "sqlite,sqlite,sqlite",
            "HINT_DIR": str(tmp_path / "h")}))
    with pytest.raises(StorageError, match="must be distinct"):
        ReplicatedBackend(StorageClientConfig(properties={
            "TYPES": "sqlite,sqlite",
            "PATHS": f"{tmp_path}/a.db,{tmp_path}/a.db",
            "HINT_DIR": str(tmp_path / "h")}))
    # all-memory replica sets are each their own store: paths optional
    b = ReplicatedBackend(StorageClientConfig(properties={
        "TYPES": "memory,memory", "HINT_DIR": str(tmp_path / "h2")}))
    b.close()


# -- anti-entropy scrub ------------------------------------------------------

def test_scrub_treats_transient_storageerror_as_down(tmp_path):
    """A merely-DOWN remote replica raises StorageError wrapping a
    transport failure: scrub must SKIP it (unreachable), not digest it
    as empty — the latter fakes total divergence and a repair storm."""
    dao, _ = make_dao(tmp_path)
    dao.insert_batch([ev(i) for i in range(6)], APP)
    dao.replicas[2] = TransientStorageErrorDAO()
    res = dao.scrub(APP, repair=False)
    assert res["replicasScrubbed"] == 2        # down replica skipped
    assert res["divergentBuckets"] == 0
    # repair mode survives the down replica the same way
    res = dao.scrub(APP, repair=True)
    assert res["repairedEvents"] == 0
    dao.close()

def test_scrub_detects_and_repairs_divergence(tmp_path):
    dao, replicas = make_dao(tmp_path)
    dao.insert_batch([ev(i) for i in range(8)], APP)
    # silent divergence no hint knows about (bit-rot class): replica 2
    # misses two events
    victims = [e for e in replicas[2].find(APP, limit=-1)][:2]
    for v in victims:
        replicas[2].delete(v.event_id, APP)
    check = dao.scrub(APP, repair=False)
    assert check["divergentBuckets"] >= 1
    fix = dao.scrub(APP, repair=True)
    assert fix["repairedEvents"] == 2
    assert dao.scrub(APP, repair=False)["divergentBuckets"] == 0
    ids0 = {e.event_id for e in replicas[0].find(APP, limit=-1)}
    assert {e.event_id for e in replicas[2].find(APP, limit=-1)} == ids0
    # scrub state is persisted for doctor
    assert dao.replication_status()["scrub"]["lastResult"][
        "divergentBuckets"] == 0
    dao.close()


def test_background_scrub_converges(tmp_path):
    replicas = [mem_events() for _ in range(3)]
    dao = ReplicatedEventsDAO(
        replicas, write_quorum=2, hint_dir=str(tmp_path / "h"),
        scrub_interval_s=0.1)
    dao.init(APP)
    dao.insert_batch([ev(i) for i in range(5)], APP)
    victim = next(iter(replicas[1].find(APP, limit=-1)))
    replicas[1].delete(victim.event_id, APP)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if replicas[1].get(victim.event_id, APP) is not None:
            break
        time.sleep(0.05)
    assert replicas[1].get(victim.event_id, APP) is not None
    dao.close()


# -- storage locator / backend config ----------------------------------------

def replicated_env(tmp_path, n=3, quorum=2):
    return {
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_SOURCES_R_TYPE": "replicated",
        "PIO_STORAGE_SOURCES_R_TYPES": ",".join(["memory"] * n),
        "PIO_STORAGE_SOURCES_R_WRITE_QUORUM": str(quorum),
        "PIO_STORAGE_SOURCES_R_HINT_DIR": str(tmp_path / "hints"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "R",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    }


def test_replicated_backend_via_storage_locator(tmp_path):
    s = Storage(env=replicated_env(tmp_path))
    dao = s.get_events()
    dao.init(APP)
    ids = dao.insert_batch([ev(i) for i in range(3)], APP)
    assert len(list(dao.find(APP, limit=-1))) == 3
    assert isinstance(dao, ReplicatedEventsDAO)  # ResilientDAO-transparent
    assert dao.replication_status()["writeQuorum"] == 2
    # events-only: metadata through this source is a loud error
    from pio_tpu.data.backends.replicated import ReplicatedBackend

    b = ReplicatedBackend(StorageClientConfig(
        properties={"TYPES": "memory,memory",
                    "HINT_DIR": str(tmp_path / "h2")}))
    with pytest.raises(StorageError):
        b.apps()
    b.close()
    s.close()
    assert ids


def test_replicated_backend_requires_urls_or_types(tmp_path):
    from pio_tpu.data.backends.replicated import ReplicatedBackend

    with pytest.raises(StorageError):
        ReplicatedBackend(StorageClientConfig(properties={}))


def test_event_server_spills_on_quorum_loss(tmp_path):
    """The degradation chain end to end: quorum lost (2 of 3 replicas
    dead) is transient, so the event server answers 201 {spilled:true}
    instead of failing the ingest — and the spill drain redelivers with
    the SAME id once quorum returns."""
    from pio_tpu.data.dao import AccessKey, App
    from pio_tpu.server.eventserver import (
        EventServerConfig, create_event_server,
    )
    from tests.test_eventserver import RATE, call

    s = Storage(env=replicated_env(tmp_path))
    app_id = s.get_metadata_apps().insert(App(0, "testapp"))
    s.get_metadata_access_keys().insert(AccessKey("KEY", app_id, ()))
    dao = s.get_events()
    dao.init(app_id)
    srv = create_event_server(
        s, EventServerConfig(ip="127.0.0.1", port=0)).start()
    try:
        dead = [dao.replicas[1], dao.replicas[2]]
        dao.replicas[1] = DeadDAO()
        dao.replicas[2] = DeadDAO()
        st, out = call(srv, "POST", "/events.json", body=RATE,
                       accessKey="KEY")
        assert (st, out.get("spilled")) == (201, True)
        eid = out["eventId"]
        # quorum returns: the drain lands the receipt's exact id
        dao.replicas[1], dao.replicas[2] = dead
        for br in dao.breakers:
            br.reset()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if dao.get(eid, app_id) is not None:
                break
            time.sleep(0.05)
        assert dao.get(eid, app_id) is not None
    finally:
        srv.stop()
        s.close()


def test_binary_batches_from_concurrent_clients_reach_every_replica(
        tmp_path):
    """The bulk wire into the replicated store (R=3, W=2), four
    keep-alive clients at once: every slot of every batch is acked 201
    with an id of its own, and each of the three replicas holds exactly
    the acked ids, with no hint left to deliver."""
    import http.client

    from pio_tpu.data.columnar import (
        COLUMNAR_CONTENT_TYPE, encode_api_batch,
    )
    from pio_tpu.data.dao import AccessKey, App
    from pio_tpu.server.eventserver import (
        EventServerConfig, create_event_server,
    )

    s = Storage(env=replicated_env(tmp_path))
    app_id = s.get_metadata_apps().insert(App(0, "testapp"))
    s.get_metadata_access_keys().insert(AccessKey("KEY", app_id, ()))
    dao = s.get_events()
    dao.init(app_id)
    srv = create_event_server(
        s, EventServerConfig(ip="127.0.0.1", port=0)).start()
    clients, batches, per_batch = 4, 3, 40
    acked, errors = [], []

    def client(c):
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        try:
            for b in range(batches):
                body = encode_api_batch([
                    {"event": "rate", "entityType": "user",
                     "entityId": f"u{c}-{b}-{j}",
                     "targetEntityType": "item", "targetEntityId": f"i{j}",
                     "properties": {"rating": j % 5 + 1}}
                    for j in range(per_batch)])
                conn.request(
                    "POST", "/batch/events.json?accessKey=KEY", body=body,
                    headers={"Content-Type": COLUMNAR_CONTENT_TYPE})
                resp = conn.getresponse()
                slots = json.loads(resp.read())
                assert resp.status == 200, slots
                assert [x["status"] for x in slots] == [201] * per_batch
                acked.extend(x["eventId"] for x in slots)
        except Exception as e:  # noqa: BLE001 - raised below
            errors.append(e)
        finally:
            conn.close()

    try:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert len(set(acked)) == clients * batches * per_batch
        for replica in dao.replicas:
            held = [e.event_id for e in replica.find(app_id, limit=-1)]
            assert sorted(held) == sorted(acked)
        status = dao.replication_status()
        assert [r["hintDepth"] for r in status["replicas"]] == [0, 0, 0]
        assert status["counters"]["hinted"] == 0
    finally:
        srv.stop()
        s.close()


def test_event_server_metrics_export_replication_gauges(tmp_path):
    import urllib.request

    from pio_tpu.data.dao import AccessKey, App
    from pio_tpu.server.eventserver import (
        EventServerConfig, create_event_server,
    )
    from tests.test_eventserver import RATE, call

    s = Storage(env=replicated_env(tmp_path))
    app_id = s.get_metadata_apps().insert(App(0, "testapp"))
    s.get_metadata_access_keys().insert(AccessKey("KEY", app_id, ()))
    dao = s.get_events()
    dao.init(app_id)
    srv = create_event_server(
        s, EventServerConfig(ip="127.0.0.1", port=0,
                             metrics_key="MK")).start()
    try:
        dao.replicas[2] = DeadDAO()
        st, _ = call(srv, "POST", "/events.json", body=RATE,
                     accessKey="KEY")
        assert st == 201
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics?accessKey=MK"
        ).read().decode()
        assert 'replica_hint_depth{' in text
        assert 'replica="2"' in text
        assert "scrub_divergent_buckets" in text
        assert "quorum_write_seconds_bucket" in text
        assert 'le="+Inf"' in text
        assert "quorum_write_seconds_count" in text
    finally:
        srv.stop()
        s.close()


def test_doctor_storage_reports_and_exits_on_quorum(tmp_path, capsys):
    from pio_tpu.data.dao import App
    from pio_tpu.data.storage import set_storage
    from pio_tpu.tools.cli import main

    s = Storage(env=replicated_env(tmp_path))
    app_id = s.get_metadata_apps().insert(App(0, "docapp"))
    dao = s.get_events()
    dao.init(app_id)
    dao.insert_batch([ev(i) for i in range(4)], app_id)
    set_storage(s)
    try:
        assert main(["doctor", "--storage"]) == 0
        out = capsys.readouterr().out
        assert "write quorum 2" in out
        assert "0 divergent bucket(s)" in out
        # JSON mode carries the machine-readable convergence verdict
        assert main(["doctor", "--storage", "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["quorumOk"] is True
        assert doc["divergentBuckets"] == 0
        # lost quorum (2 of 3 replicas dead at probe time) -> exit 1
        dao.probes[1] = DeadDAO().boom
        dao.probes[2] = dao.probes[1]
        assert main(["doctor", "--storage"]) == 1
        assert "quorum LOST" in capsys.readouterr().out
    finally:
        set_storage(None)
        s.close()


def test_doctor_storage_scrub_repairs(tmp_path, capsys):
    from pio_tpu.data.dao import App
    from pio_tpu.data.storage import set_storage
    from pio_tpu.tools.cli import main

    s = Storage(env=replicated_env(tmp_path))
    app_id = s.get_metadata_apps().insert(App(0, "docapp"))
    dao = s.get_events()
    dao.init(app_id)
    dao.insert_batch([ev(i) for i in range(4)], app_id)
    victim = next(iter(dao.replicas[0].find(app_id, limit=-1)))
    dao.replicas[0].delete(victim.event_id, app_id)
    set_storage(s)
    try:
        assert main(["doctor", "--storage", "--scrub"]) == 0
        out = capsys.readouterr().out
        assert "1 event(s) repaired" in out
        assert dao.replicas[0].get(victim.event_id, app_id) is not None
    finally:
        set_storage(None)
        s.close()


def test_sticky_columnar_downgrade_logged_once(tmp_path, caplog):
    """Satellite: RemoteEvents.find_columnar against a pre-binary
    storage server downgrades to paged JSON ONCE per client — logged
    the first time, and the dead route is never retried."""
    import logging

    from pio_tpu.data.dao import App
    from pio_tpu.server.storageserver import (
        StorageServerConfig, create_storage_server,
    )
    from pio_tpu.utils.httpclient import HttpClientError

    backing = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    server = create_storage_server(
        backing, StorageServerConfig(ip="127.0.0.1", port=0))
    server.start()
    try:
        client = Storage(env={
            "PIO_STORAGE_SOURCES_NET_TYPE": "remote",
            "PIO_STORAGE_SOURCES_NET_URL":
                f"http://127.0.0.1:{server.port}",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "NET",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "NET",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "NET",
        })
        app_id = client.get_metadata_apps().insert(App(0, "wireapp"))
        dao = client.get_events()
        dao.init(app_id)
        dao.insert_batch([ev(i) for i in range(3)], app_id)
        # emulate a pre-binary server: 404 the columnar route only
        real = dao.b._http.request
        hits = {"columnar": 0}

        def gated(method, path, *a, **kw):
            if path == "/rpc/columnar":
                hits["columnar"] += 1
                raise HttpClientError(404, "no such route")
            return real(method, path, *a, **kw)

        dao.b._http.request = gated
        with caplog.at_level(logging.WARNING, "pio_tpu.remote"):
            cols1 = dao.find_columnar(app_id)
            cols2 = dao.find_columnar(app_id)
        assert len(cols1) == 3 and len(cols2) == 3
        downgrades = [r for r in caplog.records
                      if "downgrading find_columnar" in r.message]
        assert len(downgrades) == 1            # logged once, sticky
        assert hits["columnar"] == 1           # dead route never retried
    finally:
        server.stop()
        backing.close()


def test_sharded_composition_per_group_replication(tmp_path):
    """`URLS=a|b,c|d` under the sharded backend: each shard group is a
    ReplicatedEventsDAO over its replica storage servers; killing one
    replica of one group leaves every read and write working."""
    from pio_tpu.data.backends.sharded import ShardedEventsDAO
    from pio_tpu.data.dao import App
    from pio_tpu.server.storageserver import (
        StorageServerConfig, create_storage_server,
    )

    servers, backings = [], []
    for _ in range(4):
        b = Storage(env={
            "PIO_STORAGE_SOURCES_M_TYPE": "memory",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
        })
        srv = create_storage_server(
            b, StorageServerConfig(ip="127.0.0.1", port=0))
        srv.start()
        servers.append(srv)
        backings.append(b)
    try:
        u = [f"http://127.0.0.1:{s.port}" for s in servers]
        client = Storage(env={
            "PIO_STORAGE_SOURCES_M_TYPE": "memory",
            "PIO_STORAGE_SOURCES_SH_TYPE": "sharded",
            "PIO_STORAGE_SOURCES_SH_URLS":
                f"{u[0]}|{u[1]},{u[2]}|{u[3]}",
            "PIO_STORAGE_SOURCES_SH_HINT_DIR": str(tmp_path / "sh"),
            "PIO_STORAGE_SOURCES_SH_WRITE_QUORUM": "1",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SH",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
        })
        app_id = client.get_metadata_apps().insert(App(0, "shr"))
        dao = client.get_events()
        dao.init(app_id)
        ids = dao.insert_batch([ev(i) for i in range(20)], app_id)
        assert isinstance(dao, ShardedEventsDAO)
        assert all(isinstance(s, ReplicatedEventsDAO)
                   for s in dao.shards)
        before = sorted(e.event_id for e in dao.find(app_id, limit=-1))
        assert before == sorted(ids)
        # the composed topology carries the replication surface too:
        # aggregated status with per-group quorum verdicts + scrub
        st = dao.replication_status(probe=True)
        assert st["n"] == 4 and len(st["groups"]) == 2
        assert st["quorumOk"] is True
        assert any(str(r["replica"]).startswith("shard1/")
                   for r in st["replicas"])
        assert dao.scrub(app_id, repair=False)["divergentBuckets"] == 0
        servers[1].stop()                      # one replica of shard 0
        after = sorted(e.event_id for e in dao.find(app_id, limit=-1))
        assert after == before
        more = dao.insert_batch([ev(i, "buy") for i in range(6)], app_id)
        assert len(more) == 6                  # quorum held per group
        st = dao.replication_status(probe=True)
        assert st["groups"][0]["liveReplicas"] == 1
        assert st["quorumOk"] is True          # W=1 per group still holds
        client.close()
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 - one already stopped
                pass
        for b in backings:
            b.close()


# -- subprocess drill (the CI storage-chaos job's shape) ----------------------

DRILL_N = 3
DRILL_QUORUM = 2


def _storage_server_env(db_path: str) -> dict:
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_PLATFORM": "cpu",
        "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQL_PATH": db_path,
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQL",
    })
    return env


def _wait_health(port: int, timeout_s: float = 60.0) -> None:
    import urllib.request

    deadline = time.monotonic() + timeout_s
    # pio: lint-ok[bare-retry] boot-poll of a fresh subprocess, not a
    # production retry path: fixed cadence until /healthz answers
    while time.monotonic() < deadline:
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=2)
            return
        except OSError:
            time.sleep(0.25)
    raise TimeoutError(f"storage server on :{port} never became healthy")


@pytest.mark.slow
def test_subprocess_replica_kill_drill(tmp_path):
    """The acceptance drill: 3 storage-server replica SUBPROCESSES over
    their own sqlite stores, replicated W=2 through a live event
    server; SIGKILL one replica mid-ingest under concurrent load ->
    every 201-acked event is readable from the surviving quorum
    immediately; restart the replica over the SAME store -> hint drain
    + scrub converge it; `pio doctor --storage` reports zero divergent
    buckets and exits 0."""
    import urllib.request

    from pio_tpu.data.dao import AccessKey, App
    from pio_tpu.data.storage import set_storage
    from pio_tpu.server.eventserver import (
        EventServerConfig, create_event_server,
    )
    from pio_tpu.tools.cli import main

    import socket

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    ports = [free_port() for _ in range(DRILL_N)]
    dbs = [str(tmp_path / f"replica{i}.db") for i in range(DRILL_N)]

    def spawn(i: int) -> subprocess.Popen:
        return subprocess.Popen(
            [sys.executable, "-m", "pio_tpu", "storageserver",
             "--port", str(ports[i])],
            env=_storage_server_env(dbs[i]),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )

    procs = [spawn(i) for i in range(DRILL_N)]
    ev_server = None
    client = None
    try:
        for p in ports:
            _wait_health(p)
        client = Storage(env={
            "PIO_STORAGE_SOURCES_M_TYPE": "memory",
            "PIO_STORAGE_SOURCES_R_TYPE": "replicated",
            "PIO_STORAGE_SOURCES_R_URLS": ",".join(
                f"http://127.0.0.1:{p}" for p in ports),
            "PIO_STORAGE_SOURCES_R_WRITE_QUORUM": str(DRILL_QUORUM),
            "PIO_STORAGE_SOURCES_R_HINT_DIR": str(tmp_path / "hints"),
            "PIO_STORAGE_SOURCES_R_TIMEOUT": "5",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "R",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
        })
        app_id = client.get_metadata_apps().insert(App(0, "drill"))
        client.get_metadata_access_keys().insert(
            AccessKey("DK", app_id, ()))
        dao = client.get_events()
        dao.init(app_id)
        ev_server = create_event_server(
            client, EventServerConfig(ip="127.0.0.1", port=0)).start()

        acked: list[str] = []
        acked_lock = threading.Lock()
        stop = threading.Event()
        errors: list[str] = []

        def ingest(worker: int) -> None:
            k = 0
            # pio: lint-ok[bare-retry] the drill's load generator, not a
            # retry loop: any non-201 outcome FAILS the drill loudly
            while not stop.is_set():
                batch = [
                    {"event": "rate", "entityType": "user",
                     "entityId": f"w{worker}u{k}-{j}",
                     "targetEntityType": "item",
                     "targetEntityId": f"i{j}",
                     "properties": {"rating": 3}}
                    for j in range(10)
                ]
                req = urllib.request.Request(
                    f"http://127.0.0.1:{ev_server.port}"
                    "/batch/events.json?accessKey=DK",
                    data=json.dumps(batch).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST")
                try:
                    with urllib.request.urlopen(req, timeout=30) as resp:
                        slots = json.loads(resp.read())
                except Exception as e:  # noqa: BLE001 - surfaced below
                    errors.append(f"worker {worker}: {e}")
                    return
                with acked_lock:
                    for s in slots:
                        if s.get("status") == 201 and not s.get("spilled"):
                            acked.append(s["eventId"])
                        elif s.get("status") not in (201,):
                            errors.append(
                                f"worker {worker}: slot {s}")
                            return
                k += 1
                time.sleep(0.01)

        workers = [threading.Thread(target=ingest, args=(wk,))
                   for wk in range(3)]
        for t in workers:
            t.start()
        time.sleep(1.0)
        procs[2].kill()                        # SIGKILL mid-ingest
        procs[2].wait(timeout=10)
        time.sleep(2.0)                        # keep ingesting degraded
        stop.set()
        for t in workers:
            t.join(timeout=30)
        assert not errors, errors[:3]
        assert len(acked) > 20

        # every 201-acked event readable from the surviving quorum NOW
        have = {e.event_id for e in dao.find(app_id, limit=-1)}
        missing = [a for a in acked if a not in have]
        assert not missing, f"{len(missing)} acked events unreadable"
        st = dao.replication_status()
        assert st["replicas"][2]["hintDepth"] >= 1

        # rejoin over the SAME sqlite store; drain + scrub converge it
        procs[2] = spawn(2)
        _wait_health(ports[2])
        dao.breakers[2].reset()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if dao.hint_logs[2].depth() == 0:
                break
            time.sleep(0.25)
        assert dao.hint_logs[2].depth() == 0, "hints never drained"
        dao.scrub(app_id, repair=True)
        assert dao.scrub(app_id, repair=False)["divergentBuckets"] == 0

        # the rejoined replica alone holds every acked event
        rejoined = {e.event_id
                    for e in dao.replicas[2].find(app_id, limit=-1)}
        assert set(acked) <= rejoined

        # the operator's verdict: doctor --storage converges + exit 0
        set_storage(client)
        try:
            assert main(["doctor", "--storage", "--json"]) == 0
        finally:
            set_storage(None)
    finally:
        stop_err = None
        if ev_server is not None:
            ev_server.stop()
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                stop_err = "storage server needed SIGKILL at teardown"
        if client is not None:
            client.close()
        assert stop_err is None, stop_err
