"""A job's models go from their host arrays to their store's file in one
pass (workflow/checkpoint.py HostModels, utils/durable.py stream_frame,
the sqlite and localfs Models DAOs): the file is byte for byte the frame
of the protocol-5 pickle, what the parent wrote still loads, damage is
refused at load, a failed write leaves the file that was there, nothing
of the blob's size is built on the way, and each payload byte meets the
checksum once on the way in and once on the way back."""

import os
import pickle
import tracemalloc

import jax.numpy as jnp
import numpy as np
import pytest

from pio_tpu.data.backends.localfs import LocalFSBackend
from pio_tpu.data.backends.sqlite import EXTERNAL_BLOB_BYTES, SqliteBackend
from pio_tpu.data.bimap import EntityIdIndex
from pio_tpu.data.dao import Model
from pio_tpu.data.storage import StorageClientConfig
from pio_tpu.utils import durable
from pio_tpu.utils.durable import HEADER_BYTES, ModelIntegrityError, frame
from pio_tpu.workflow.checkpoint import (
    HostModels, models_from_bytes, models_on_host, models_to_bytes,
)

DATA = os.path.join(os.path.dirname(__file__), "data")
STORES = ["sqlite", "localfs"]


def _models(mb: float = 3.0, seed: int = 0) -> list:
    """Two models of `mb` MB together: arrays in both memory orders, a
    device array, ids, a string."""
    rng = np.random.default_rng(seed)
    rows = -(-int(mb * (1 << 20)) // (3 * 64 * 4))
    return [
        {"w": rng.standard_normal((rows, 64), dtype=np.float32),
         "t": np.asfortranarray(
             rng.standard_normal((rows, 64), dtype=np.float32)),
         "d": jnp.asarray(rng.standard_normal((rows, 64), dtype=np.float32)),
         "name": "first"},
        {"ids": EntityIdIndex([f"u{n}" for n in range(100)]),
         "steps": np.arange(9)},
    ]


def _store(kind: str, tmp_path):
    """-> (the Models DAO, the file a model of that id is kept in)."""
    if kind == "sqlite":
        be = SqliteBackend(StorageClientConfig(
            properties={"PATH": str(tmp_path / "pio.db")}))
        return be.models(), lambda mid: be.models()._file(mid)
    be = LocalFSBackend(StorageClientConfig(
        properties={"PATH": str(tmp_path / "models")}))
    return be.models(), lambda mid: be.models()._path(mid)


def _litter(path: str) -> list[str]:
    folder = os.path.dirname(path)
    return [n for n in os.listdir(folder) if ".tmp." in n]


def _same(a, b) -> None:
    assert a[0]["name"] == b[0]["name"]
    for k in ("w", "t", "d"):
        np.testing.assert_array_equal(np.asarray(a[0][k]), b[0][k])
    assert a[1]["ids"].ids() == b[1]["ids"].ids()
    np.testing.assert_array_equal(a[1]["steps"], b[1]["steps"])


@pytest.mark.parametrize("kind", STORES)
def test_the_streamed_file_is_the_frame_of_the_pickle(kind, tmp_path):
    models = _models()
    host = models_on_host(models)
    assert host.min_bytes >= EXTERNAL_BLOB_BYTES
    dao, file_of = _store(kind, tmp_path)
    dao.insert(Model("m/1", host))
    with open(file_of("m/1"), "rb") as f:
        on_disk = f.read()
    assert on_disk == frame(pickle.dumps(host.on_host, protocol=5))
    assert host.framed_bytes == len(on_disk)
    assert host.min_bytes <= len(on_disk) - HEADER_BYTES
    # the same writer into memory, and through the DAO's read
    assert bytes(host) == on_disk == models_to_bytes(models)
    assert dao.get("m/1").models == on_disk
    _same(models, models_from_bytes(dao.get("m/1").models))
    assert _litter(file_of("m/1")) == []


def test_under_the_line_a_source_is_a_row(tmp_path):
    """A source that is not sure to reach 1 MiB is made in memory and
    kept by its length, as `bytes` are."""
    dao, file_of = _store("sqlite", tmp_path)
    host = models_on_host(_models(mb=0.1))
    assert host.min_bytes < EXTERNAL_BLOB_BYTES
    dao.insert(Model("small", host))
    assert not os.path.exists(file_of("small"))
    assert dao.get("small").models == bytes(host)
    # ids push a frame over the line that its arrays do not reach: it is
    # a file all the same, by the length it turned out to have
    wide = models_on_host([{"ids": EntityIdIndex(
        [f"user-{n:012d}" for n in range(70_000)])}])
    assert wide.min_bytes == 0
    dao.insert(Model("wide", wide))
    assert os.path.getsize(file_of("wide")) == wide.framed_bytes
    assert wide.framed_bytes >= EXTERNAL_BLOB_BYTES
    [back] = models_from_bytes(dao.get("wide").models)
    assert back["ids"].id_of(69_999) == "user-000000069999"


@pytest.mark.parametrize("name", ["plain", "ids"])
def test_a_blob_the_parent_wrote_loads(name):
    """tests/data/models_pr38_*.bin: `models_to_bytes` of the commit
    before this writer (PR 38), one model without an id index and one
    with two in their old state (`bimap` and `_id_array` as objects)."""
    with open(os.path.join(DATA, f"models_pr38_{name}.bin"), "rb") as f:
        blob = f.read()
    [model] = models_from_bytes(blob)
    rng = np.random.default_rng(38)
    if name == "plain":
        want = [{"w": rng.standard_normal((7, 5)).astype(np.float32),
                 "b": np.arange(6, dtype=np.int64), "name": "plain",
                 "f": np.asfortranarray(rng.standard_normal((3, 4)))}]
        for k in ("w", "b", "f"):
            np.testing.assert_array_equal(model[k], want[0][k])
        # without an id index the two writers' bytes are the same
        assert models_to_bytes(want) == blob
        return
    assert b"_id_array" in blob and b"utf8" not in blob
    assert model.users.ids() == ["u0", "ü1", "用户2", "u 3", "u\x004"]
    assert model.items.ids() == ["i0", "i1", "i2"]
    assert model.users.index_of("用户2") == 2 and "i1" in model.items
    assert model.factors.user_factors.shape == (5, 4)
    # written again it is the joined state, and reads the same
    again = models_to_bytes([model])
    assert b"_id_array" not in again and b"utf8" in again
    assert models_from_bytes(again)[0].users.ids() == model.users.ids()


def _damage(data: bytes, how: str) -> bytes:
    if how == "header":
        return data[:HEADER_BYTES - 5]
    if how == "middle":
        return data[:len(data) // 2]
    if how == "last-byte":
        return data[:-1]
    flipped = bytearray(data)
    flipped[HEADER_BYTES + (len(data) - HEADER_BYTES) * 2 // 3] ^= 0x10
    return bytes(flipped)


@pytest.mark.parametrize("how", ["header", "middle", "last-byte", "bit"])
@pytest.mark.parametrize("kind", STORES)
def test_a_damaged_file_is_refused_at_load(kind, how, tmp_path):
    dao, file_of = _store(kind, tmp_path)
    dao.insert(Model("m", models_on_host(_models())))
    path = file_of("m")
    with open(path, "rb") as f:
        data = f.read()
    with open(path, "wb") as f:  # pio: lint-ok[durable-write] the damage
        f.write(_damage(data, how))
    with pytest.raises(ModelIntegrityError,
                       match="crc32c" if how == "bit" else "truncated"):
        models_from_bytes(dao.get("m").models)


@pytest.mark.parametrize("after", [0, 100_000, 2_500_000])
@pytest.mark.parametrize("kind", STORES)
def test_a_write_that_fails_midway_leaves_the_file_that_was_there(
        kind, after, tmp_path, monkeypatch):
    dao, file_of = _store(kind, tmp_path)
    first = _models(seed=1)
    dao.insert(Model("m", models_on_host(first)))
    before = open(file_of("m"), "rb").read()

    write = durable.FrameSink.write

    def failing(self, piece):
        if self.length + memoryview(piece).nbytes > after:
            raise OSError(28, "No space left on device")
        return write(self, piece)

    monkeypatch.setattr(durable.FrameSink, "write", failing)
    with pytest.raises(OSError, match="No space"):
        dao.insert(Model("m", models_on_host(_models(seed=2))))
    monkeypatch.undo()
    assert _litter(file_of("m")) == []
    assert open(file_of("m"), "rb").read() == before
    _same(first, models_from_bytes(dao.get("m").models))
    # and the store takes the next write
    dao.insert(Model("m", models_on_host(_models(seed=2))))
    _same(_models(seed=2), models_from_bytes(dao.get("m").models))


@pytest.mark.parametrize("kind", STORES)
def test_a_streamed_write_syncs_in_the_order_a_blob_does(
        kind, tmp_path, monkeypatch):
    """tmp file written, flushed and fsynced, renamed, the directory
    fsynced; in the sqlite store the row after all of it."""
    dao, file_of = _store(kind, tmp_path)
    dao.insert(Model("warm", b"x" * (EXTERNAL_BLOB_BYTES + 1)))
    calls: list = []
    real_fsync, real_replace = os.fsync, os.replace

    def fsync(fd):
        target = os.readlink(f"/proc/self/fd/{fd}")
        calls.append(("fsync", "dir" if os.path.isdir(target) else (
            "tmp" if ".tmp." in target else "other"),
            os.path.getsize(target) if ".tmp." in target else None))
        return real_fsync(fd)

    def replace(src, dst):
        calls.append(("replace", ".tmp." in src, dst))
        return real_replace(src, dst)

    monkeypatch.setattr(os, "fsync", fsync)
    monkeypatch.setattr(os, "replace", replace)
    if kind == "sqlite":
        from pio_tpu.data.backends.sqlcommon import SqlModels

        row = SqlModels.insert
        monkeypatch.setattr(
            SqlModels, "insert",
            lambda self, m: (calls.append(("row", m.models)), row(self, m)))
    orders = []
    for payload in (models_to_bytes(_models()), models_on_host(_models())):
        calls.clear()
        dao.insert(Model("m", payload))
        orders.append([c for c in calls if c[:2] != ("fsync", "other")])
    streamed = orders[1]
    size = os.path.getsize(file_of("m"))
    assert streamed[:3] == [("fsync", "tmp", size),
                            ("replace", True, file_of("m")),
                            ("fsync", "dir", None)]
    assert streamed[3:] == ([("row", None)] if kind == "sqlite" else [])
    assert orders[0] == streamed


def test_the_instance_completes_after_the_file_is_synced(
        tmp_path, monkeypatch):
    """`run_train` through the sqlite store's file path: COMPLETED is
    written only once the insert has come back from its fsyncs."""
    from pio_tpu.data.storage import Storage
    from pio_tpu.workflow.context import create_workflow_context
    from pio_tpu.workflow.train import run_train
    from tests._tiny_train import tiny_engine, tiny_params

    storage = Storage({
        "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "pio.db"),
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
    })
    seen: list = []
    # the DAO's own class behind the resilience guard
    instances = storage.get_metadata_engine_instances().__class__
    real_fsync, real_update = os.fsync, instances.update

    def fsync(fd):
        seen.append("fsync")
        return real_fsync(fd)

    def update(self, instance):
        seen.append(instance.status)
        return real_update(self, instance)

    monkeypatch.setattr(os, "fsync", fsync)
    monkeypatch.setattr(instances, "update", update)
    run_train(tiny_engine(n_users=40_000), tiny_params(cg_iters=3), storage,
              engine_id="tiny",
              ctx=create_workflow_context(storage, use_mesh=False))
    assert seen.count("COMPLETED") == 1 and "fsync" in seen
    assert seen.index("COMPLETED") > max(
        n for n, what in enumerate(seen) if what == "fsync")


@pytest.mark.parametrize("kind", STORES)
def test_persisting_64_mb_allocates_nothing_of_its_size(kind, tmp_path):
    dao, file_of = _store(kind, tmp_path)
    host = models_on_host(_models(mb=64.0))
    assert host.min_bytes >= 64 << 20
    tracemalloc.start()
    try:
        start, _ = tracemalloc.get_traced_memory()
        tracemalloc.reset_peak()
        dao.insert(Model("big", host))
        _, peak = tracemalloc.get_traced_memory()
        streamed = peak - start
        # the instrument does see a blob when one is made
        tracemalloc.reset_peak()
        start, _ = tracemalloc.get_traced_memory()
        blob = bytes(host)
        _, peak = tracemalloc.get_traced_memory()
        in_memory = peak - start
    finally:
        tracemalloc.stop()
    assert os.path.getsize(file_of("big")) == len(blob) > 64 << 20
    assert in_memory >= 64 << 20
    # everything the insert held at its worst moment, not one allocation
    assert streamed < 16 << 20, streamed


@pytest.mark.parametrize("kind", STORES)
def test_every_payload_byte_meets_the_checksum_once_each_way(
        kind, tmp_path, monkeypatch):
    dao, file_of = _store(kind, tmp_path)
    summed: list[int] = []
    real = durable.crc32c

    def counting(data, value=0):
        summed.append(memoryview(data).nbytes)
        return real(data, value)

    monkeypatch.setattr(durable, "crc32c", counting)
    host = models_on_host(_models())
    dao.insert(Model("m", host))
    payload = host.framed_bytes - HEADER_BYTES
    assert sum(summed) == payload and len(summed) > 3
    summed.clear()
    record = dao.get("m")
    assert summed == []                 # the reader's frame is its own
    models_from_bytes(record.models)
    assert summed == [payload]


def test_the_sink_checksums_where_the_bytes_lie():
    """A piece is `bytes` or a PickleBuffer over an array in either
    memory order; the C routine and the table give the same sum."""
    import io

    arrays = [np.arange(35, dtype=np.float32).reshape(5, 7),
              np.asfortranarray(np.arange(35.0).reshape(5, 7))]
    buf = io.BytesIO()
    sink = durable.stream_frame(
        buf, lambda s: pickle.dump(arrays, s, protocol=5))
    payload = pickle.dumps(arrays, protocol=5)
    assert buf.getvalue() == frame(payload)
    assert sink.crc == durable.crc32c(payload)
    assert durable.crc32c(memoryview(payload)) == sink.crc
    table = 0xFFFFFFFF
    for b in payload:
        table = durable._TABLE[(table ^ b) & 0xFF] ^ (table >> 8)
    assert table ^ 0xFFFFFFFF == sink.crc


IDS = {
    "non-ascii": ["ü1", "用户2", "🙂", "a b", "naïvé"],
    "empty": [],
    "one": ["only"],
    "empty-string-id": ["", "x"],
    "separator": ["a\x00b", "\x00", "c"],
    "every-separator": [chr(c) + "x" for c in range(32)] + ["plain"],
    "lone-surrogate": ["\ud800x", "y"],
    "not-strings": [3, 1, 2],
}


@pytest.mark.parametrize("case", IDS)
def test_an_id_index_round_trips(case):
    ids = IDS[case]
    index = EntityIdIndex(ids)
    state = index.__getstate__()
    joined = case not in ("every-separator", "not-strings")
    assert ("utf8" in state) == joined
    if joined:
        assert set(state) == {"n", "sep", "utf8"}
        assert all(state["sep"] not in i for i in ids)
        assert state["sep"] == ("\x01" if case == "separator" else "\x00")
    back = pickle.loads(pickle.dumps(index, protocol=5))
    assert back.ids() == ids and len(back) == len(ids)
    assert back._id_array.dtype == object
    assert back._id_array.shape == (len(ids),)
    assert back.bimap.to_dict() == {i: n for n, i in enumerate(ids)}
    assert back.bimap.inverse().to_dict() == dict(enumerate(ids))
    for n, i in enumerate(ids):
        assert back.index_of(i) == n and back.id_of(n) == i and i in back
    assert list(back.encode(ids)) == list(range(len(ids)))
    assert back.decode(list(range(len(ids)))[::-1]) == ids[::-1]
    grown = back.extended(["new"] if "new" not in ids else ["newer"])
    assert len(grown) == len(ids) + 1 and len(back) == len(ids)


def test_an_id_index_is_one_buffer_in_the_pickle():
    ids = [f"user-{n}" for n in range(20_000)]
    pieces: list = []

    class Sink:
        def write(self, piece):
            pieces.append(memoryview(piece).nbytes)

    pickle.dump(EntityIdIndex(ids), Sink(), protocol=5)
    utf8 = len("\x00".join(ids).encode())
    assert utf8 in pieces           # handed over whole, as it lies
    assert sum(pieces) < utf8 + 200


def test_a_source_is_bytes_to_a_store_that_keeps_bytes():
    from pio_tpu.data.backends import wire
    from tests._tiny_train import memory_storage

    host = models_on_host(_models(mb=0.1))
    assert isinstance(host, HostModels)
    blob = bytes(host)
    dao = memory_storage().get_model_data_models()
    dao.insert(Model("m", host))
    assert dao.get("m").models == blob
    assert wire.model_from_wire(
        wire.model_to_wire(Model("m", host))).models == blob
    assert Model("m", blob).blob_bytes() is blob
    assert Model("m", None).blob_bytes() is None
