"""Storage locator + metadata DAOs, run against memory and sqlite backends
(the reference's parameterized LEventsSpec pattern)."""

import os
from datetime import datetime, timedelta, timezone

import pytest

from pio_tpu.data import DataMap, Event
from pio_tpu.data.backends.sqlite import EXTERNAL_BLOB_BYTES, SqliteBackend
from pio_tpu.data.dao import (
    AccessKey, App, Channel, EngineInstance, EvaluationInstance, Model,
)
from pio_tpu.data.storage import (
    Storage, StorageClientConfig, StorageError, parse_env,
)
from pio_tpu.utils.durable import ModelIntegrityError, frame, unframe

T0 = datetime(2020, 1, 1, tzinfo=timezone.utc)


def test_parse_env_sources_and_repos():
    env = {
        "PIO_STORAGE_SOURCES_PGSQL_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_PGSQL_PATH": "/tmp/x.db",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "PGSQL",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
    }
    sources, repos = parse_env(env)
    assert sources["PGSQL"].type == "sqlite"
    assert sources["PGSQL"].properties["PATH"] == "/tmp/x.db"
    assert repos == {"METADATA": "PGSQL", "EVENTDATA": "MEM"}


def test_zero_config_defaults():
    sources, repos = parse_env({})
    assert set(repos) == {"METADATA", "EVENTDATA", "MODELDATA"}
    assert sources[repos["METADATA"]].type == "sqlite"


def test_unknown_backend_type():
    env = {
        "PIO_STORAGE_SOURCES_X_TYPE": "hbase9000",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "X",
    }
    s = Storage(env=env)
    with pytest.raises(StorageError):
        s.get_metadata_apps()


def test_verify_all(memory_storage):
    assert memory_storage.verify_all() == []


def test_apps_crud(any_storage):
    apps = any_storage.get_metadata_apps()
    app_id = apps.insert(App(0, "myapp", "desc"))
    assert app_id is not None
    assert apps.get(app_id).name == "myapp"
    assert apps.get_by_name("myapp").id == app_id
    assert apps.insert(App(0, "myapp")) is None  # duplicate name
    apps.update(App(app_id, "myapp2", None))
    assert apps.get_by_name("myapp2") is not None
    assert len(apps.get_all()) == 1
    apps.delete(app_id)
    assert apps.get(app_id) is None


def test_access_keys(any_storage):
    ak = any_storage.get_metadata_access_keys()
    key = ak.insert(AccessKey("", 7, ("rate", "buy")))
    assert key and len(key) == 64
    got = ak.get(key)
    assert got.appid == 7 and got.events == ("rate", "buy")
    key2 = ak.insert(AccessKey("fixed-key", 7))
    assert key2 == "fixed-key"
    assert ak.insert(AccessKey("fixed-key", 8)) is None  # duplicate
    assert {k.key for k in ak.get_by_appid(7)} == {key, "fixed-key"}
    ak.delete(key)
    assert ak.get(key) is None


def test_channels(any_storage):
    ch = any_storage.get_metadata_channels()
    cid = ch.insert(Channel(0, "mobile", 7))
    assert cid is not None
    assert ch.insert(Channel(0, "bad name!", 7)) is None  # invalid name
    assert ch.insert(Channel(0, "x" * 17, 7)) is None  # too long
    assert [c.name for c in ch.get_by_appid(7)] == ["mobile"]
    ch.delete(cid)
    assert ch.get(cid) is None


def _instance(i, status, start_minutes):
    return EngineInstance(
        id=i, status=status,
        start_time=T0 + timedelta(minutes=start_minutes), end_time=T0,
        engine_id="eng", engine_version="1", engine_variant="default",
        engine_factory="mod.Factory",
    )


def test_engine_instances_latest_completed(any_storage):
    ei = any_storage.get_metadata_engine_instances()
    ei.insert(_instance("a", "COMPLETED", 0))
    ei.insert(_instance("b", "COMPLETED", 10))
    ei.insert(_instance("c", "INIT", 20))
    latest = ei.get_latest_completed("eng", "1", "default")
    assert latest.id == "b"
    assert ei.get_latest_completed("eng", "2", "default") is None
    from dataclasses import replace
    ei.update(replace(ei.get("c"), status="COMPLETED"))
    assert ei.get_latest_completed("eng", "1", "default").id == "c"


def test_evaluation_instances(any_storage):
    dao = any_storage.get_metadata_evaluation_instances()
    iid = dao.insert(EvaluationInstance(
        id="", status="INIT", start_time=T0, end_time=T0,
        evaluation_class="ev.Cls",
    ))
    got = dao.get(iid)
    assert got.status == "INIT"
    from dataclasses import replace
    dao.update(replace(got, status="EVALCOMPLETED", evaluator_results="r=1"))
    assert dao.get_completed()[0].evaluator_results == "r=1"


def test_models_blob(any_storage):
    models = any_storage.get_model_data_models()
    blob = b"\x00\x01binary\xff" * 100
    models.insert(Model("inst1", blob))
    assert models.get("inst1").models == blob
    models.insert(Model("inst1", b"v2"))  # upsert
    assert models.get("inst1").models == b"v2"
    models.delete("inst1")
    assert models.get("inst1") is None


# ---------------------------------------------------------------------------
# the sqlite backend's Models DAO: blobs of 1 MiB or more are files under
# PATH.models/, their row holds NULL (pio_tpu/data/backends/sqlite.py)
# ---------------------------------------------------------------------------

def _sqlite_backend(path):
    return SqliteBackend(StorageClientConfig(properties={"PATH": str(path)}))


def _big_blob(extra=1, fill=b"\xa5"):
    """A content-framed blob of 1 MiB + `extra` bytes, as
    `models_to_bytes` frames a model."""
    blob = frame(fill * (EXTERNAL_BLOB_BYTES + extra - 17))
    assert len(blob) == EXTERNAL_BLOB_BYTES + extra
    return blob


def _inline_len(be, model_id):
    """Bytes the row itself holds: None without a row, 0 for NULL."""
    rows = be._db.query(
        "SELECT IFNULL(LENGTH(models), 0) FROM models WHERE id=?",
        (model_id,))
    return rows[0][0] if rows else None


def _blob_files(path):
    d = str(path) + ".models"
    return sorted(os.listdir(d)) if os.path.isdir(d) else []


def _case_external_round_trip(be, path):
    blob = _big_blob()
    be.models().insert(Model("inst:1/a", blob))
    assert _inline_len(be, "inst:1/a") == 0
    assert len(_blob_files(path)) == 1
    assert be.models().get("inst:1/a").models == blob
    # an unframed payload over the line comes back byte for byte too
    raw = bytes(range(256)) * 4097
    be.models().insert(Model("raw", raw))
    assert _inline_len(be, "raw") == 0
    assert be.models().get("raw").models == raw
    # a second connection (another process) finds the same model
    other = _sqlite_backend(path)
    assert unframe(other.models().get("inst:1/a").models) == unframe(blob)
    other.close()


def _case_under_the_line_inline(be, path):
    blob = b"j" * (EXTERNAL_BLOB_BYTES - 1)
    be.models().insert(Model("small", blob))
    assert _inline_len(be, "small") == len(blob)
    assert _blob_files(path) == []
    assert be.models().get("small").models == blob


def _case_old_inline_row_reads(be, path):
    # a database written before PR 25: the large blob is in the row
    blob = _big_blob()
    be._db.exec("INSERT INTO models (id, models) VALUES (?,?)",
                ("old", blob))
    assert be.models().get("old").models == blob
    assert _blob_files(path) == []


def _case_external_then_inline_one_copy(be, path):
    be.models().insert(Model("m", _big_blob()))
    assert len(_blob_files(path)) == 1
    be.models().insert(Model("m", b"v2"))
    assert _inline_len(be, "m") == 2 and _blob_files(path) == []
    assert be.models().get("m").models == b"v2"


def _case_inline_then_external_one_copy(be, path):
    be.models().insert(Model("m", b"v1"))
    blob = _big_blob()
    be.models().insert(Model("m", blob))
    assert _inline_len(be, "m") == 0 and len(_blob_files(path)) == 1
    assert be.models().get("m").models == blob


def _case_delete_row_and_file(be, path):
    be.models().insert(Model("m", _big_blob()))
    be.models().insert(Model("keep", _big_blob(2)))
    be.models().delete("m")
    assert be.models().get("m") is None and _inline_len(be, "m") is None
    assert len(_blob_files(path)) == 1
    assert be.models().get("keep").models == _big_blob(2)
    be.models().delete("never-stored")


def _damaged(be, path, damage):
    from pio_tpu.workflow.checkpoint import models_from_bytes

    # a model's frame is checked by the model's reader, once; a raw
    # payload's wrapper by the DAO
    be.models().insert(Model("m", _big_blob()))
    be.models().insert(Model("raw", b"r" * (1 << 20)))
    for name in _blob_files(path):
        file = os.path.join(str(path) + ".models", name)
        with open(file, "rb") as f:
            data = f.read()
        with open(file, "wb") as f:
            f.write(damage(data))
    with pytest.raises(ModelIntegrityError):
        models_from_bytes(be.models().get("m").models)
    with pytest.raises(ModelIntegrityError):
        be.models().get("raw")


def _case_truncated_file_raises(be, path):
    _damaged(be, path, lambda data: data[:len(data) // 2])


def _case_bit_flip_raises(be, path):
    _damaged(be, path,
             lambda data: data[:5000] + bytes([data[5000] ^ 1]) + data[5001:])


def _case_file_without_row_is_no_model(be, path):
    # a crash between the file and the row: the file is never seen
    be.models().insert(Model("m", _big_blob()))
    be._db.exec("DELETE FROM models WHERE id=?", ("m",))
    assert len(_blob_files(path)) == 1
    assert be.models().get("m") is None
    # and the next insert of the id overwrites it
    be.models().insert(Model("m", _big_blob(3)))
    assert len(_blob_files(path)) == 1
    assert be.models().get("m").models == _big_blob(3)
    # the other way round (a copy of PATH without PATH.models/) is loud
    os.unlink(os.path.join(str(path) + ".models", _blob_files(path)[0]))
    with pytest.raises(ModelIntegrityError, match="pio.db.models"):
        be.models().get("m")


def _case_memory_stays_inline(be, path):
    for config in (StorageClientConfig(properties={"PATH": ":memory:"}),
                   StorageClientConfig(properties={"PATH": str(path)},
                                       test=True)):
        mem = SqliteBackend(config)
        blob = _big_blob()
        mem.models().insert(Model("m", blob))
        assert _inline_len(mem, "m") == len(blob)
        assert mem.models().get("m").models == blob
        mem.models().delete("m")
        assert mem.models().get("m") is None
        mem.close()
    assert _blob_files(path) == []


def _case_long_id(be, path):
    long_id = "fleet:" + "n\u00e9/" * 90 + ":plan"
    be.models().insert(Model(long_id, _big_blob()))
    be.models().insert(Model(long_id + "x", _big_blob(2)))
    assert len(_blob_files(path)) == 2
    assert be.models().get(long_id).models == _big_blob()
    be.models().insert(Model(long_id, b"{}"))       # inline, file removed
    assert len(_blob_files(path)) == 1
    be.models().delete(long_id + "x")
    assert _blob_files(path) == []


@pytest.mark.parametrize("case", [
    _case_external_round_trip, _case_under_the_line_inline,
    _case_old_inline_row_reads, _case_external_then_inline_one_copy,
    _case_inline_then_external_one_copy, _case_delete_row_and_file,
    _case_truncated_file_raises, _case_bit_flip_raises,
    _case_file_without_row_is_no_model, _case_memory_stays_inline,
    _case_long_id,
], ids=lambda f: f.__name__[len("_case_"):])
def test_sqlite_models_external_blobs(tmp_path, case):
    path = tmp_path / "pio.db"
    be = _sqlite_backend(path)
    try:
        case(be, path)
    finally:
        be.close()


def test_sqlite_large_model_stays_out_of_the_page_store(tmp_path):
    """The mechanism: a 4 MiB model is one file; neither the database
    nor its WAL grows by it (inline it was in both: written twice)."""
    path = tmp_path / "pio.db"
    be = _sqlite_backend(path)
    try:
        blob = _big_blob(3 << 20)
        be.models().insert(Model("inst", blob))
        in_pages = sum(
            os.path.getsize(str(path) + suffix) for suffix in ("", "-wal")
            if os.path.exists(str(path) + suffix))
        assert in_pages < (1 << 20), in_pages
        files = _blob_files(path)
        assert len(files) == 1
        assert os.path.getsize(
            os.path.join(str(path) + ".models", files[0])) == len(blob)
        assert be.models().get("inst").models == blob
    finally:
        be.close()


def test_localfs_models(tmp_path):
    env = {
        "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_FS_PATH": str(tmp_path / "models"),
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
    }
    s = Storage(env=env)
    models = s.get_model_data_models()
    models.insert(Model("m/1", b"data"))
    assert models.get("m/1").models == b"data"
    models.delete("m/1")
    assert models.get("m/1") is None


# ---------------------------------------------------------------------------
# events DAO
# ---------------------------------------------------------------------------

def _rate(uid, iid, minutes, rating=None):
    props = {"rating": rating} if rating is not None else {}
    return Event(
        event="rate", entity_type="user", entity_id=uid,
        target_entity_type="item", target_entity_id=iid,
        properties=DataMap(props), event_time=T0 + timedelta(minutes=minutes),
    )


def test_events_crud(any_storage):
    ev = any_storage.get_events()
    assert ev.init(1)
    eid = ev.insert(_rate("u1", "i1", 0, 4.0), 1)
    got = ev.get(eid, 1)
    assert got.entity_id == "u1" and got.properties.get("rating") == 4.0
    assert got.event_id == eid
    assert ev.delete(eid, 1)
    assert ev.get(eid, 1) is None
    assert not ev.delete(eid, 1)


def test_events_namespace_isolation(any_storage):
    ev = any_storage.get_events()
    ev.init(1)
    ev.init(1, channel_id=5)
    ev.insert(_rate("u1", "i1", 0), 1)
    ev.insert(_rate("u2", "i2", 0), 1, channel_id=5)
    assert [e.entity_id for e in ev.find(1, limit=-1)] == ["u1"]
    assert [e.entity_id for e in ev.find(1, channel_id=5, limit=-1)] == ["u2"]
    assert ev.remove(1, channel_id=5)
    ev.init(1, channel_id=5)
    assert list(ev.find(1, channel_id=5, limit=-1)) == []


def test_events_same_id_across_namespaces(any_storage):
    # Round-1 advisor repro: a client-supplied event id that exists in a
    # DIFFERENT (app, channel) namespace must not be touched by an insert —
    # uniqueness is per-namespace, as in the reference's table-per-app layout
    # (hbase/HBEventsUtil.scala tableName).
    import dataclasses

    ev = any_storage.get_events()
    ev.init(1)
    ev.init(2)
    ev.init(1, channel_id=7)
    e1 = dataclasses.replace(_rate("u1", "i1", 0, 5.0), event_id="E1")
    e2 = dataclasses.replace(_rate("u9", "i9", 1, 1.0), event_id="E1")
    assert ev.insert(e1, 1) == "E1"
    assert ev.insert(e2, 2) == "E1"          # other app, same id
    assert ev.insert(e2, 1, channel_id=7) == "E1"  # other channel, same id
    assert ev.get("E1", 1).entity_id == "u1"  # app1's event survived
    assert ev.get("E1", 2).entity_id == "u9"
    assert ev.get("E1", 1, channel_id=7).entity_id == "u9"
    # re-insert into the SAME namespace still upserts
    e1b = dataclasses.replace(_rate("u1", "i1", 0, 2.0), event_id="E1")
    assert ev.insert(e1b, 1) == "E1"
    assert ev.get("E1", 1).properties.get("rating") == 2.0
    assert len(list(ev.find(1, limit=-1))) == 1


def test_sqlite_migrates_old_global_pk(tmp_path):
    # Databases created before round 2 had `id TEXT PRIMARY KEY` on events;
    # opening one must rebuild the table to per-namespace uniqueness without
    # losing rows.
    import sqlite3

    from pio_tpu.data.storage import StorageClientConfig
    from pio_tpu.data.backends.sqlite import SqliteBackend

    path = str(tmp_path / "old.db")
    conn = sqlite3.connect(path)
    conn.executescript(
        """
        CREATE TABLE events (
          id TEXT PRIMARY KEY, app_id INTEGER NOT NULL, channel_id INTEGER,
          event TEXT NOT NULL, entity_type TEXT NOT NULL,
          entity_id TEXT NOT NULL, target_entity_type TEXT,
          target_entity_id TEXT, properties TEXT, event_time TEXT NOT NULL,
          event_time_ms INTEGER NOT NULL, tags TEXT, pr_id TEXT,
          creation_time TEXT NOT NULL);
        CREATE TABLE event_namespaces (
          app_id INTEGER NOT NULL, channel_id INTEGER,
          PRIMARY KEY (app_id, channel_id));
        INSERT INTO event_namespaces VALUES (1, NULL);
        INSERT INTO events VALUES (
          'E1', 1, NULL, 'rate', 'user', 'u1', 'item', 'i1', '{"rating": 4}',
          '2020-01-01T00:00:00+00:00', 1577836800000, '[]', NULL,
          '2020-01-01T00:00:00+00:00');
        """
    )
    conn.commit()
    conn.close()

    b = SqliteBackend(StorageClientConfig(properties={"PATH": path}))
    ev = b.events()
    assert ev.get("E1", 1).entity_id == "u1"   # row survived migration
    ev.init(2)
    import dataclasses
    assert ev.insert(
        dataclasses.replace(_rate("u2", "i2", 0), event_id="E1"), 2) == "E1"
    assert ev.get("E1", 1).entity_id == "u1"   # old namespace untouched
    b.close()


def test_events_uninitialized_namespace_raises(any_storage):
    ev = any_storage.get_events()
    with pytest.raises(StorageError):
        ev.insert(_rate("u1", "i1", 0), 99)
    with pytest.raises(StorageError):
        list(ev.find(99))
    with pytest.raises(StorageError):
        ev.get("x", 99)
    with pytest.raises(StorageError):
        ev.delete("x", 99)


def test_events_find_filters(any_storage):
    ev = any_storage.get_events()
    ev.init(2)
    for m in range(10):
        ev.insert(_rate(f"u{m % 3}", f"i{m}", m), 2)
    ev.insert(Event(event="$set", entity_type="item", entity_id="i0",
                    properties=DataMap({"cat": "a"}),
                    event_time=T0 + timedelta(minutes=100)), 2)

    # time range [2, 5)
    out = list(ev.find(2, start_time=T0 + timedelta(minutes=2),
                       until_time=T0 + timedelta(minutes=5), limit=-1))
    assert len(out) == 3

    # entity filters
    assert all(e.entity_id == "u1"
               for e in ev.find(2, entity_type="user", entity_id="u1", limit=-1))
    # event names
    assert len(list(ev.find(2, event_names=["$set"], limit=-1))) == 1
    # target entity: don't-care vs must-be-absent
    assert len(list(ev.find(2, limit=-1))) == 11
    assert len(list(ev.find(2, target_entity_type=None, limit=-1))) == 1
    assert len(list(ev.find(2, target_entity_type="item",
                            target_entity_id="i4", limit=-1))) == 1
    # ordering + limit + reversed
    first_two = list(ev.find(2, limit=2))
    assert [e.event_time for e in first_two] == sorted(
        e.event_time for e in first_two)
    newest = next(iter(ev.find(2, limit=1, reversed=True)))
    assert newest.event == "$set"


def test_events_default_limit_is_20(any_storage):
    ev = any_storage.get_events()
    ev.init(3)
    for m in range(30):
        ev.insert(_rate("u", f"i{m}", m), 3)
    assert len(list(ev.find(3))) == 20  # reference default page size
    assert len(list(ev.find(3, limit=-1))) == 30


def test_events_aggregate_properties(any_storage):
    ev = any_storage.get_events()
    ev.init(4)
    ev.insert(Event(event="$set", entity_type="item", entity_id="i1",
                    properties=DataMap({"cat": "a", "price": 10}),
                    event_time=T0), 4)
    ev.insert(Event(event="$unset", entity_type="item", entity_id="i1",
                    properties=DataMap({"price": None}),
                    event_time=T0 + timedelta(minutes=1)), 4)
    ev.insert(Event(event="$set", entity_type="user", entity_id="u1",
                    properties=DataMap({"x": 1}), event_time=T0), 4)
    ev.insert(_rate("u1", "i1", 2), 4)

    props = ev.aggregate_properties(4, entity_type="item")
    assert set(props) == {"i1"}
    assert props["i1"].fields == {"cat": "a"}
    props_u = ev.aggregate_properties(4, entity_type="user")
    assert props_u["u1"].fields == {"x": 1}


def test_find_single_entity(any_storage):
    ev = any_storage.get_events()
    ev.init(5)
    for m in range(5):
        ev.insert(_rate("u1", f"i{m}", m), 5)
    ev.insert(_rate("u2", "i9", 9), 5)
    out = list(ev.find_single_entity(5, "user", "u1", limit=3))
    assert len(out) == 3
    assert out[0].target_entity_id == "i4"  # newest first
