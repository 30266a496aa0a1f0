"""The training read against a plain fold that shares no code with it.

`EventStore.interactions`, called with the recommendation DataSource's
arguments, over the `eventlog`, `sqlite` and `memory` stores, compared
with benchmark/reference/events.py on seeded random events: re-rated
pairs, `buy` events beside `rate`, events of other names and between
other entity types (to be filtered out), events without a target, equal
event times, deleted events. Ids and values are equal exactly.

The tie rule (of equal event times the later in the log wins) holds in
all three stores. Where they differ is the order in which ids get their
indexes, and each keeps a rule of its own, stated in ID_ORDER below.
"""

import functools
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

from benchmark.reference import events as reference
from pio_tpu.data.dao import App
from pio_tpu.data.event import Event
from pio_tpu.data.eventstore import EventStore
from pio_tpu.data.storage import Storage

T0 = datetime(2020, 1, 1, tzinfo=timezone.utc)
# ids are indexed in order of first appearance: `eventlog` walks the log
# (the native sweep reads records in file order), `sqlite` and `memory`
# walk it sorted by event time, equal times in log order (the columnar
# fold's stable sort over `find`'s order)
ID_ORDER = {"eventlog": "log", "sqlite": "time", "memory": "time"}


def _storage(backend: str, tmp_path) -> Storage:
    env = {
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
        "PIO_STORAGE_SOURCES_EV_TYPE": backend,
    }
    if backend != "memory":
        env["PIO_STORAGE_SOURCES_EV_PATH"] = str(
            tmp_path / ("log" if backend == "eventlog" else "events.db"))
    return Storage(env)


def _log(seed: int, n: int = 600) -> list[tuple]:
    """The reference's records (event, entityType, entityId,
    targetEntityType, targetEntityId, properties, eventTime, position),
    in which everything the fold decides comes up many times: 20 users x
    15 items, so most pairs are rated again; whole-second times from a
    range of 10, so equal times are common and the log is not in time
    order."""
    rng = np.random.default_rng(seed)
    kinds = {   # what the DataSource keeps, then what it drops
        "rate": ("rate", "user", "item"),
        "buy": ("buy", "user", "item"),
        "another name": ("view", "user", "item"),
        "another target type": ("rate", "user", "category"),
        "another entity type": ("rate", "admin", "item"),
        "no target": ("$set", "user", None),
    }
    out = []
    for position in range(n):
        kind = rng.choice(list(kinds), p=[0.55, 0.2, 0.1, 0.05, 0.05, 0.05])
        event, etype, ttype = kinds[kind]
        out.append((
            event, etype, f"u{rng.integers(20)}", ttype,
            f"i{rng.integers(15)}" if ttype else None,
            {} if kind == "buy" else {"rating": float(rng.integers(1, 6))},
            T0 + timedelta(seconds=int(rng.integers(10))), position))
    return out


def _event(record: tuple) -> Event:
    event, etype, eid, ttype, tid, properties, when, _ = record
    return Event(event, etype, eid, ttype, tid, properties, event_time=when)


@pytest.mark.parametrize("dedup", ["last", "sum", "none"])
@pytest.mark.parametrize("backend", ["eventlog", "sqlite", "memory"])
def test_interactions_equal_the_plain_fold(backend, dedup, tmp_path):
    storage = _storage(backend, tmp_path)
    app_id = storage.get_metadata_apps().insert(App(0, "app"))
    dao = storage.get_events()
    dao.init(app_id)
    records = _log(seed=26)
    ids = []
    for lo in range(0, len(records), 100):    # several batches, one log
        ids += dao.insert_batch(
            [_event(r) for r in records[lo:lo + 100]], app_id)
    deleted = list(range(7, len(records), 23))
    for n in deleted:
        assert dao.delete(ids[n], app_id)

    got = EventStore(storage).interactions(
        app_name="app", entity_type="user", target_entity_type="item",
        event_names=["rate", "buy"], value_key="rating", default_value=4.0,
        value_event="rate", dedup=dedup)

    fold = functools.partial(
        reference.fold, event_names=["rate", "buy"], value_event="rate",
        value_key="rating", implicit_value=4.0, dedup=dedup, deleted=deleted)
    users, items, rows = fold(records)
    # the log makes the fold decide: pairs rated again, and again at the
    # same second; every kind of event to drop is there
    kept = sum(r[0] in ("rate", "buy") and r[1] == "user" and r[3] == "item"
               and r[7] not in deleted for r in records)
    assert 100 < len(rows) <= kept < len(records) - len(deleted)
    assert (len(rows) == kept) == (dedup == "none")
    assert len(users) == 20 and len(items) == 15
    assert got.values.dtype == np.float32
    assert reference.triples(
        got.users.ids(), got.items.ids(),
        zip(got.user_idx.tolist(), got.item_idx.tolist(),
            got.values.tolist())) == reference.triples(users, items, rows)

    # and index for index, in the order this store walks the log
    if ID_ORDER[backend] == "time":
        # stable: equal times stay in log order
        users, items, rows = fold(sorted(records, key=lambda r: r[6]))
    assert got.users.ids() == users and got.items.ids() == items
    assert list(zip(got.user_idx.tolist(), got.item_idx.tolist(),
                    got.values.tolist())) == rows


@pytest.mark.parametrize("dedup", ["last", "sum"])
def test_many_pairs_of_few_items_through_the_native_dedup_table(dedup,
                                                                tmp_path):
    """The `eventlog` sweep's (user, item) table, past several of its
    doublings, where a pair's item alone must not decide its slot:
    12,000 users x 3 items, a third of the pairs rated again."""
    storage = _storage("eventlog", tmp_path)
    app_id = storage.get_metadata_apps().insert(App(0, "app"))
    dao = storage.get_events()
    dao.init(app_id)
    rng = np.random.default_rng(27)
    pairs = [(u, i) for u in range(12_000) for i in range(3)]
    pairs += [pairs[n] for n in rng.choice(len(pairs), 12_000)]
    order = rng.permutation(len(pairs))
    records = [("rate", "user", f"u{pairs[n][0]}", "item", f"i{pairs[n][1]}",
                {"rating": float(rng.integers(1, 6))},
                T0 + timedelta(seconds=int(rng.integers(1000))), position)
               for position, n in enumerate(order)]
    dao.insert_batch([_event(r) for r in records], app_id)
    got = EventStore(storage).interactions(
        app_name="app", entity_type="user", target_entity_type="item",
        event_names=["rate"], value_key="rating", default_value=4.0,
        value_event="rate", dedup=dedup)
    users, items, rows = reference.fold(records, ["rate"], dedup=dedup)
    assert len(rows) == 36_000 == len(got)
    assert got.users.ids() == users and got.items.ids() == items
    assert list(zip(got.user_idx.tolist(), got.item_idx.tolist(),
                    got.values.tolist())) == rows
