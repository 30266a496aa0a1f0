"""Native event-log backend: DAO parity with the memory backend, durability,
crash recovery, and columnarize parity with the Python path.

The reference runs the same LEventsSpec body against HBase and JDBC
(data/.../storage/LEventsSpec.scala:22-75); here the spec body runs against
memory and the native log, asserting identical results.
"""

from __future__ import annotations

import os
import struct
import zlib
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

from pio_tpu.data.backends.eventlog import EventLogBackend
from pio_tpu.data.backends.memory import MemoryBackend
from pio_tpu.data.datamap import DataMap
from pio_tpu.data.event import Event
from pio_tpu.data.eventstore import to_interactions
from pio_tpu.data.storage import StorageClientConfig, StorageError
from pio_tpu.native.eventlog import (
    DEDUP_NONE, EventLog, ScanFilter, crc_bytes, el_crc32, pack_event,
)

UTC = timezone.utc
T0 = datetime(2026, 1, 1, tzinfo=UTC)


def mk(i, event="rate", u="u1", it="i1", rating=None, t=None, **kw):
    props = {"rating": rating} if rating is not None else {}
    return Event(
        event=event,
        entity_type="user",
        entity_id=u,
        target_entity_type="item" if it else None,
        target_entity_id=it,
        properties=DataMap(props),
        event_time=t or (T0 + timedelta(minutes=i)),
        event_id=f"ev{i}",
        **kw,
    )


CORPUS = [
    mk(0, rating=4.0),
    mk(1, event="buy", u="u1", it="i2"),
    mk(2, u="u2", it="i1", rating=2.5),
    mk(3, event="view", u="u2", it="i3"),
    mk(4, event="$set", u="u3", it=None),
    mk(5, u="u3", it="i2", rating=5.0),
    mk(6, event="rate", u="u1", it="i1", rating=1.0),  # re-rate (dedup last)
]


@pytest.fixture(params=["memory", "eventlog"])
def events_dao(request, tmp_path):
    if request.param == "memory":
        b = MemoryBackend(StorageClientConfig())
    else:
        b = EventLogBackend(
            StorageClientConfig(properties={"PATH": str(tmp_path / "el")})
        )
    dao = b.events()
    dao.init(1)
    yield dao
    b.close()


def _load(dao):
    for e in CORPUS:
        dao.insert(e, 1)


class TestEventsSpec:
    """Same spec body across backends (LEventsSpec parity)."""

    def test_insert_get(self, events_dao):
        _load(events_dao)
        e = events_dao.get("ev0", 1)
        assert e == CORPUS[0]
        assert events_dao.get("missing", 1) is None

    def test_find_filters(self, events_dao):
        _load(events_dao)
        assert len(list(events_dao.find(1, limit=-1))) == len(CORPUS)
        assert {e.event_id for e in events_dao.find(1, entity_id="u1", limit=-1)} == {
            "ev0", "ev1", "ev6"
        }
        assert {
            e.event_id
            for e in events_dao.find(1, event_names=["buy", "view"], limit=-1)
        } == {"ev1", "ev3"}
        # target-entity tri-state: None = must be absent
        assert {
            e.event_id
            for e in events_dao.find(1, target_entity_type=None, limit=-1)
        } == {"ev4"}
        assert {
            e.event_id
            for e in events_dao.find(1, target_entity_id="i2", limit=-1)
        } == {"ev1", "ev5"}

    def test_find_time_range_and_limit(self, events_dao):
        _load(events_dao)
        out = list(
            events_dao.find(
                1,
                start_time=T0 + timedelta(minutes=2),
                until_time=T0 + timedelta(minutes=5),
                limit=-1,
            )
        )
        assert [e.event_id for e in out] == ["ev2", "ev3", "ev4"]
        newest = list(events_dao.find(1, limit=2, reversed=True))
        assert [e.event_id for e in newest] == ["ev6", "ev5"]

    def test_delete(self, events_dao):
        _load(events_dao)
        assert events_dao.delete("ev1", 1) is True
        assert events_dao.delete("ev1", 1) is False
        assert events_dao.get("ev1", 1) is None
        assert len(list(events_dao.find(1, limit=-1))) == len(CORPUS) - 1

    def test_delete_many(self, events_dao):
        """Bulk delete (retention cleanups): counts only events that
        existed; deleted + unknown + duplicate ids are not double-counted.
        The eventlog backend overrides this with a single-scan tombstone
        batch — the spec body must hold for it and the base loop alike."""
        _load(events_dao)
        n = events_dao.delete_many(["ev1", "ev2", "nope", "ev2"], 1)
        assert n == 2
        assert events_dao.get("ev1", 1) is None
        assert events_dao.get("ev2", 1) is None
        assert len(list(events_dao.find(1, limit=-1))) == len(CORPUS) - 2
        # repeat is a no-op
        assert events_dao.delete_many(["ev1", "ev2"], 1) == 0
        assert events_dao.delete_many([], 1) == 0

    def test_channels_isolated(self, events_dao):
        events_dao.init(1, 7)
        events_dao.insert(CORPUS[0], 1)
        events_dao.insert(CORPUS[2], 1, 7)
        assert [e.event_id for e in events_dao.find(1, limit=-1)] == ["ev0"]
        assert [e.event_id for e in events_dao.find(1, 7, limit=-1)] == ["ev2"]

    def test_uninitialized_namespace_raises(self, events_dao):
        with pytest.raises(StorageError):
            list(events_dao.find(99, limit=-1))

    def test_remove_namespace(self, events_dao):
        _load(events_dao)
        assert events_dao.remove(1) is True
        with pytest.raises(StorageError):
            list(events_dao.find(1, limit=-1))


class TestDurability:
    def _backend(self, path):
        return EventLogBackend(
            StorageClientConfig(properties={"PATH": str(path)})
        )

    def test_reopen_persists(self, tmp_path):
        b = self._backend(tmp_path / "el")
        dao = b.events()
        dao.init(1)
        _load(dao)
        dao.delete("ev3", 1)
        b.close()

        b2 = self._backend(tmp_path / "el")
        dao2 = b2.events()
        assert {e.event_id for e in dao2.find(1, limit=-1)} == {
            e.event_id for e in CORPUS if e.event_id != "ev3"
        }
        assert dao2.get("ev0", 1) == CORPUS[0]
        b2.close()

    def test_torn_tail_write_recovered(self, tmp_path):
        b = self._backend(tmp_path / "el")
        dao = b.events()
        dao.init(1)
        _load(dao)
        b.close()
        log_path = tmp_path / "el" / "app_1" / "events.log"
        size = os.path.getsize(log_path)
        # simulate a crash mid-append: a partial frame at the tail
        with open(log_path, "ab") as f:
            f.write((9999).to_bytes(4, "little") + b"\x01\x02\x03")
        assert os.path.getsize(log_path) > size

        b2 = self._backend(tmp_path / "el")
        dao2 = b2.events()
        assert len(list(dao2.find(1, limit=-1))) == len(CORPUS)
        # and the log still accepts appends after recovery
        dao2.insert(mk(7, u="u9", it="i9"), 1)
        assert dao2.get("ev7", 1) is not None
        b2.close()

    def test_corrupt_record_skipped(self, tmp_path):
        b = self._backend(tmp_path / "el")
        dao = b.events()
        dao.init(1)
        _load(dao)
        b.close()
        log_path = tmp_path / "el" / "app_1" / "events.log"
        # flip a byte inside the first record's payload
        with open(log_path, "r+b") as f:
            f.seek(30)
            c = f.read(1)
            f.seek(30)
            f.write(bytes([c[0] ^ 0xFF]))
        b2 = self._backend(tmp_path / "el")
        dao2 = b2.events()
        found = list(dao2.find(1, limit=-1))
        assert len(found) == len(CORPUS) - 1  # bad crc record dropped
        b2.close()


def _frames(log_path) -> list[tuple[int, int]]:
    """(payload offset, payload length) of every frame, by `struct` alone."""
    with open(log_path, "rb") as f:
        blob = f.read()
    out, pos = [], 8
    while pos + 8 <= len(blob):
        (n,) = struct.unpack_from("<I", blob, pos)
        out.append((pos + 8, n))
        pos += 8 + n
    assert pos == len(blob)
    return out


def _fnv1a(s: str) -> int:
    h = 1469598103934665603
    for b in s.encode("utf-8"):
        h = ((h ^ b) * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    return h


def _payload_by_hand(e: Event) -> bytes:
    """The record payload of native/eventlog.cpp's header comment, packed
    without a call into the library."""
    def us(t):
        return (t - datetime(1970, 1, 1, tzinfo=UTC)) // timedelta(
            microseconds=1)

    def s16(s):
        b = (s or "").encode("utf-8")
        return struct.pack("<H", len(b)) + b

    target = e.target_entity_type is not None
    props = e.properties.to_json().encode("utf-8")
    return (
        struct.pack(
            "<qhqh6QB", us(e.event_time), 0, us(e.creation_time), 0,
            _fnv1a(e.event), _fnv1a(e.entity_type), _fnv1a(e.entity_id),
            _fnv1a(e.target_entity_type) if target else 0,
            _fnv1a(e.target_entity_id) if target else 0,
            _fnv1a(e.event_id), 1 if target else 0)
        + s16(e.event) + s16(e.entity_type) + s16(e.entity_id)
        + s16(e.target_entity_type) + s16(e.target_entity_id)
        + s16(e.event_id) + s16(None) + s16("")
        + struct.pack("<I", len(props)) + props)


def _rows(cols):
    return sorted(
        (cols.users[u], cols.items[i], float(v), int(t))
        for u, i, v, t in zip(cols.user_idx, cols.item_idx, cols.values,
                              cols.times_us))


_RATES = ScanFilter(entity_type="user", event_names=["rate", "buy"])


class TestChecksum:
    """The record check (native/eventlog.cpp crc32_of, several bytes a
    step): zlib's values on every length and alignment, a log framed
    without the library read back whole, and one flipped byte anywhere in
    a payload costing that record and no other."""

    @pytest.mark.parametrize("start", [*range(8), "1MB+5"])
    def test_crc_is_zlibs(self, start):
        rng = np.random.default_rng(38)
        if start == "1MB+5":
            buf = rng.integers(0, 256, (1 << 20) + 5, np.uint8).tobytes()
            assert el_crc32(buf) == zlib.crc32(buf)
            return
        buf = rng.integers(0, 256, 112, np.uint8)
        # read in place, `start` bytes past a multiple of 8
        start += -buf.ctypes.data % 8
        before = crc_bytes()
        for n in range(81):
            part = buf[start:start + n]
            assert n == 0 or part.ctypes.data % 8 == start % 8
            assert el_crc32(part) == zlib.crc32(part.tobytes()), n
        assert crc_bytes() - before == sum(range(81))

    def test_a_log_framed_with_struct_and_zlib_reads_back_whole(self, tmp_path):
        path = tmp_path / "events.log"
        with open(path, "wb") as f:
            f.write(b"PIOEVLG1")
            for e in CORPUS:
                payload = _payload_by_hand(e)
                f.write(struct.pack("<II", len(payload), zlib.crc32(payload)))
                f.write(payload)
        payload_bytes = os.path.getsize(path) - 8 - 8 * len(CORPUS)
        log = EventLog(str(path), create=False)
        try:
            assert log.end() == os.path.getsize(path)
            before = crc_bytes()
            assert log.stats() == (os.path.getsize(path), len(CORPUS))
            assert crc_bytes() - before == payload_bytes
            assert log.scan(ScanFilter()) == CORPUS
            cols = log.columnarize(_RATES, value_key="rating",
                                   default_value=4.0)
            assert {(cols.users[u], cols.items[i]): v for u, i, v in zip(
                cols.user_idx, cols.item_idx, cols.values)} == {
                    ("u1", "i1"): 1.0, ("u1", "i2"): 4.0, ("u2", "i1"): 2.5,
                    ("u3", "i2"): 5.0}
        finally:
            log.close()
        # and what the library writes is what `struct` + zlib would
        mine = tmp_path / "mine.log"
        log = EventLog(str(mine))
        for e in CORPUS:
            log.append(e)
        log.close()
        with open(mine, "rb") as f:
            blob = f.read()
        for (at, n), e in zip(_frames(mine), CORPUS, strict=True):
            assert blob[at:at + n] == pack_event(e) == _payload_by_hand(e)
            assert struct.unpack_from("<I", blob, at - 4)[0] == zlib.crc32(
                blob[at:at + n])

    @pytest.mark.parametrize("where", [0, 16 * 2 + 8 + 3, *range(-7, 0)],
                             ids=lambda o: f"payload[{o}]")
    def test_one_flipped_byte_drops_that_record_alone(self, where, tmp_path):
        """A payload's first byte, one inside a whole step, and each of
        its last seven."""
        # the victim's payload ends 15 bytes past a multiple of 16, so its
        # last 7 bytes are past the last whole step of 8 or of 16
        victim = next(
            e for pad in range(16)
            for e in [mk(3, u="u2" + "x" * pad, it="i3", rating=3.0)]
            if len(pack_event(e)) % 16 == 15)
        corpus = CORPUS[:3] + [victim] + CORPUS[4:]
        path = tmp_path / "events.log"
        log = EventLog(str(path))
        for e in corpus:
            log.append(e)
        whole = _rows(log.columnarize(_RATES, dedup=DEDUP_NONE))
        log.close()
        at, n = _frames(path)[3]
        with open(path, "r+b") as f:
            f.seek(at + where % n)
            c = f.read(1)
            f.seek(at + where % n)
            f.write(bytes([c[0] ^ 0x10]))
        log = EventLog(str(path), create=False)
        try:
            kept = corpus[:3] + corpus[4:]
            assert log.stats() == (os.path.getsize(path), len(kept))
            assert log.scan(ScanFilter()) == kept
            assert _rows(log.columnarize(_RATES, dedup=DEDUP_NONE)) == [
                r for r in whole if r[0] != victim.entity_id]
        finally:
            log.close()


class TestColumnarize:
    @pytest.fixture()
    def dao(self, tmp_path):
        b = EventLogBackend(
            StorageClientConfig(properties={"PATH": str(tmp_path / "el")})
        )
        dao = b.events()
        dao.init(1)
        yield dao
        b.close()

    def _as_dict(self, inter_like, users, items):
        return {
            (users[u], items[i]): v
            for u, i, v in zip(
                inter_like.user_idx, inter_like.item_idx, inter_like.values
            )
        }

    def test_parity_with_python_path(self, dao):
        _load(dao)
        cols = dao.columnarize(
            1, entity_type="user", event_names=["rate", "buy"],
            value_key="rating", default_value=4.0, dedup="last",
        )
        events = [
            e
            for e in dao.find(1, entity_type="user",
                              event_names=["rate", "buy"], limit=-1)
        ]
        ref = to_interactions(
            events,
            value_fn=lambda e: float(e.properties.get_or_else("rating", 4.0)),
            dedup="last",
        )
        native = {
            (cols.users[u], cols.items[i]): v
            for u, i, v in zip(cols.user_idx, cols.item_idx, cols.values)
        }
        python = {
            (ref.users.bimap.inverse()[u], ref.items.bimap.inverse()[i]): v
            for u, i, v in zip(ref.user_idx, ref.item_idx, ref.values)
        }
        assert native == python
        assert native[("u1", "i1")] == 1.0  # dedup last kept the re-rate

    def test_value_event_restriction(self, dao):
        # a buy event that *has* a rating property must still take the
        # implicit default when value_event="rate"
        dao.insert(mk(0, event="rate", u="a", it="x", rating=2.0), 1)
        dao.insert(mk(1, event="buy", u="b", it="x", rating=9.0), 1)
        cols = dao.columnarize(
            1, event_names=["rate", "buy"], value_key="rating",
            default_value=4.0, value_event="rate", dedup="none",
        )
        got = {
            (cols.users[u], cols.items[i]): v
            for u, i, v in zip(cols.user_idx, cols.item_idx, cols.values)
        }
        assert got == {("a", "x"): 2.0, ("b", "x"): 4.0}

    def test_dedup_sum_and_tombstones(self, dao):
        dao.insert(mk(0, event="view", u="a", it="x"), 1)
        dao.insert(mk(1, event="view", u="a", it="x"), 1)
        dao.insert(mk(2, event="view", u="a", it="y"), 1)
        dao.delete("ev2", 1)
        cols = dao.columnarize(
            1, event_names=["view"], value_key=None, default_value=1.0,
            dedup="sum",
        )
        got = {
            (cols.users[u], cols.items[i]): v
            for u, i, v in zip(cols.user_idx, cols.item_idx, cols.values)
        }
        assert got == {("a", "x"): 2.0}

    def test_eventstore_interactions_fast_path(self, dao, monkeypatch):
        """EventStore.interactions must produce identical interactions via
        native columnarize and via the find+to_interactions fallback."""
        from pio_tpu.data import storage as storage_mod
        from pio_tpu.data.dao import App
        from pio_tpu.data.eventstore import EventStore

        _load(dao)

        class FakeStorage:
            def get_metadata_apps(self):
                class A:
                    def get_by_name(self, name):
                        return App(1, name)
                return A()

            def get_metadata_channels(self):
                class C:
                    def get_by_appid(self, appid):
                        return []
                return C()

            def get_events(self):
                return dao

        store = EventStore(FakeStorage())
        fast = store.interactions(
            "app", event_names=["rate", "buy"], value_key="rating",
            default_value=4.0, dedup="last",
        )
        monkeypatch.delattr(type(dao), "columnarize")
        slow = store.interactions(
            "app", event_names=["rate", "buy"], value_key="rating",
            default_value=4.0, dedup="last",
        )
        f = {
            (fast.users.bimap.inverse()[u], fast.items.bimap.inverse()[i]): v
            for u, i, v in zip(fast.user_idx, fast.item_idx, fast.values)
        }
        s = {
            (slow.users.bimap.inverse()[u], slow.items.bimap.inverse()[i]): v
            for u, i, v in zip(slow.user_idx, slow.item_idx, slow.values)
        }
        assert f == s and len(f) > 0


class TestTimePrecision:
    def test_microsecond_and_zone_roundtrip(self, tmp_path):
        b = EventLogBackend(
            StorageClientConfig(properties={"PATH": str(tmp_path / "el")})
        )
        dao = b.events()
        dao.init(1)
        tz = timezone(timedelta(hours=5, minutes=30))
        e = mk(0, t=datetime(2026, 3, 4, 5, 6, 7, 891234, tzinfo=tz))
        dao.insert(e, 1)
        got = dao.get("ev0", 1)
        assert got.event_time == e.event_time
        assert got.event_time.utcoffset() == timedelta(hours=5, minutes=30)
        b.close()


class TestSuppliedIdIdempotency:
    def test_retried_insert_with_same_id_appends_once(self, tmp_path):
        """Phantom-retry contract (resilience.RetryPolicy / spill drain):
        re-inserting a caller-supplied id within the recent window must
        not append a second record — the log is append-only, so a dup
        would be counted twice by find()/columnarize()."""
        b = EventLogBackend(
            StorageClientConfig(properties={"PATH": str(tmp_path / "el")})
        )
        dao = b.events()
        dao.init(1)
        e = mk(0)  # mk assigns event_id "ev0"
        assert dao.insert(e, 1) == e.event_id
        assert dao.insert(e, 1) == e.event_id      # retry: deduped
        assert len(list(dao.find(1, limit=-1))) == 1
        # fresh events without ids are unaffected
        from pio_tpu.data.event import Event

        fresh = Event(event="rate", entity_type="user", entity_id="u9")
        id_a = dao.insert(fresh, 1)
        id_b = dao.insert(fresh, 1)                # no id: two inserts
        assert id_a != id_b
        assert len(list(dao.find(1, limit=-1))) == 3
        b.close()
