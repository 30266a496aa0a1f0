"""Engine-facing event read API + columnarization.

Mirrors the reference's stable engine API (data/.../store/PEventStore.scala:54,94
and LEventStore.scala): app-name-keyed reads for training and serve-time.
Where the reference hands engines an RDD[Event], the TPU build hands host
numpy columns ready for `device_put` — `to_interactions` is the bridge from
ragged events to static-shape arrays (SURVEY.md section 7 "Dynamic shapes").
"""

from __future__ import annotations

from dataclasses import dataclass
from datetime import datetime
from typing import Callable, Iterable, Sequence

import numpy as np

from pio_tpu.data.bimap import EntityIdIndex
from pio_tpu.data.dao import EventsDAO
from pio_tpu.data.datamap import PropertyMap
from pio_tpu.data.event import Event
from pio_tpu.data.storage import Storage, StorageError, get_storage
from pio_tpu.utils import tracing


class EventStore:
    """App-name keyed event reads (PEventStore/LEventStore equivalent)."""

    def __init__(self, storage: Storage | None = None):
        self.storage = storage or get_storage()

    def _resolve(self, app_name: str, channel_name: str | None) -> tuple[int, int | None]:
        """App/channel name -> ids (reference Common.scala appNameToId)."""
        app = self.storage.get_metadata_apps().get_by_name(app_name)
        if app is None:
            raise StorageError(f"App {app_name!r} does not exist")
        if channel_name is None:
            return app.id, None
        for ch in self.storage.get_metadata_channels().get_by_appid(app.id):
            if ch.name == channel_name:
                return app.id, ch.id
        raise StorageError(
            f"Channel {channel_name!r} does not exist in app {app_name!r}"
        )

    def _dao(self) -> EventsDAO:
        return self.storage.get_events()

    def find(
        self,
        app_name: str,
        channel_name: str | None = None,
        start_time: datetime | None = None,
        until_time: datetime | None = None,
        entity_type: str | None = None,
        entity_id: str | None = None,
        event_names: Sequence[str] | None = None,
        target_entity_type=...,
        target_entity_id=...,
    ) -> list[Event]:
        """Training read: all matching events (reference PEventStore.find)."""
        app_id, channel_id = self._resolve(app_name, channel_name)
        return list(
            self._dao().find(
                app_id=app_id,
                channel_id=channel_id,
                start_time=start_time,
                until_time=until_time,
                entity_type=entity_type,
                entity_id=entity_id,
                event_names=event_names,
                target_entity_type=target_entity_type,
                target_entity_id=target_entity_id,
                limit=-1,
            )
        )

    def aggregate_properties(
        self,
        app_name: str,
        entity_type: str,
        channel_name: str | None = None,
        start_time: datetime | None = None,
        until_time: datetime | None = None,
        required: Iterable[str] | None = None,
    ) -> dict[str, PropertyMap]:
        """Reference PEventStore.aggregateProperties."""
        app_id, channel_id = self._resolve(app_name, channel_name)
        return self._dao().aggregate_properties(
            app_id=app_id,
            entity_type=entity_type,
            channel_id=channel_id,
            start_time=start_time,
            until_time=until_time,
            required=required,
        )

    def interactions(
        self,
        app_name: str,
        channel_name: str | None = None,
        entity_type: str | None = "user",
        target_entity_type=...,
        event_names: Sequence[str] | None = None,
        start_time: datetime | None = None,
        until_time: datetime | None = None,
        value_key: str | None = "rating",
        default_value: float = 1.0,
        value_event: str | None = None,
        dedup: str = "last",
    ) -> Interactions:
        """Training read straight to COO interactions.

        Every EventsDAO carries a `columnarize` now (dao.py): one C++
        sweep on the native log backend, the server-side RPC on
        remote/sharded, and the vectorized columnar fold
        (data/columnar.py) on the local memory/SQL backends — per-event
        Python objects never materialize on this path. The find +
        to_interactions row fold below remains only for duck-typed
        third-party DAOs (and as the parity oracle in tests).
        `value_key` reads a numeric property (None = always
        default_value); `value_event` restricts that read to one event
        name (others take default_value) — the reference recommendation
        template's rate-vs-buy rule.

        Inside a `pio train` this is `train.read`, and its parts are
        spans of the job (docs/observability.md "Training"):
        `events.scan` and `events.tables` where the store columnarizes
        (native/eventlog.py; the DAO's default), `events.index` here.
        """
        app_id, channel_id = self._resolve(app_name, channel_name)
        dao = self._dao()
        if hasattr(dao, "columnarize"):
            cols = dao.columnarize(
                app_id=app_id,
                channel_id=channel_id,
                start_time=start_time,
                until_time=until_time,
                entity_type=entity_type,
                event_names=event_names,
                target_entity_type=target_entity_type,
                value_key=value_key,
                default_value=default_value,
                dedup=dedup,
                value_event=value_event,
            )
            with tracing.span("events.index"):
                return Interactions(
                    user_idx=cols.user_idx.astype(np.int32),
                    item_idx=cols.item_idx.astype(np.int32),
                    values=cols.values,
                    users=EntityIdIndex(cols.users),
                    items=EntityIdIndex(cols.items),
                )

        events = self.find(
            app_name=app_name,
            channel_name=channel_name,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            target_entity_type=target_entity_type,
            event_names=event_names,
        )
        return to_interactions(
            events,
            value_fn=make_value_fn(value_key, default_value, value_event),
            dedup=dedup,
        )

    def find_by_entity(
        self,
        app_name: str,
        entity_type: str,
        entity_id: str,
        channel_name: str | None = None,
        event_names: Sequence[str] | None = None,
        target_entity_type=...,
        limit: int | None = None,
        latest: bool = True,
    ) -> list[Event]:
        """Serve-time read for one entity (reference LEventStore.findByEntity,
        used by the ecommerce template's business rules)."""
        app_id, channel_id = self._resolve(app_name, channel_name)
        return list(
            self._dao().find_single_entity(
                app_id=app_id,
                entity_type=entity_type,
                entity_id=entity_id,
                channel_id=channel_id,
                event_names=event_names,
                target_entity_type=target_entity_type,
                limit=limit,
                latest=latest,
            )
        )


# ---------------------------------------------------------------------------
# columnarization: ragged events -> static-shape arrays
# ---------------------------------------------------------------------------

@dataclass
class Interactions:
    """COO user-item interactions + the id indexes to decode them.

    The TPU-native replacement for the RDD[Rating] every reference template
    builds (e.g. custom-query/.../DataSource.scala): numpy columns ready for
    device_put, with EntityIdIndex handling string-id <-> dense-index."""

    user_idx: np.ndarray   # int32 (n,)
    item_idx: np.ndarray   # int32 (n,)
    values: np.ndarray     # float32 (n,)
    users: EntityIdIndex
    items: EntityIdIndex

    @property
    def n_users(self) -> int:
        return len(self.users)

    @property
    def n_items(self) -> int:
        return len(self.items)

    def __len__(self) -> int:
        return len(self.values)

    def sanity_check(self):
        if len(self.values) == 0:
            raise ValueError(
                "Interactions is empty. Please check if DataSource generates"
                " TrainingData and eventWindow is set properly."
            )


def make_value_fn(value_key: str | None, default_value: float,
                  value_event: str | None):
    """THE value-extraction semantics of the training read, shared by
    every columnarize fold (EventStore.interactions' client fallback,
    the storage server's RPC fallback, the sharded cross-type fallback)
    so the dialects cannot drift: `value_key` reads a numeric property
    (None = always default), `value_event` restricts that read to one
    event name (others take default) — the reference recommendation
    template's rate-vs-buy rule."""

    def value_fn(e: Event) -> float:
        if value_key is not None and (
            value_event is None or e.event == value_event
        ):
            return float(e.properties.get_or_else(value_key, default_value))
        return default_value

    return value_fn


def columnarize_via_find(dao, app_id: int, channel_id: int | None = None,
                         start_time: datetime | None = None,
                         until_time: datetime | None = None,
                         entity_type: str | None = None,
                         event_names: Sequence[str] | None = None,
                         target_entity_type=...,
                         value_key: str | None = "rating",
                         default_value: float = 1.0,
                         dedup: str = "last",
                         value_event: str | None = None) -> Interactions:
    """Generic columnarize over a bare EventsDAO (by app_id, not app
    name): find + fold. The shared fallback for DAOs without a native
    columnarize — used by the storage server's RPC handler and the
    sharded backend's cross-type path."""
    events = dao.find(
        app_id, channel_id,
        start_time=start_time, until_time=until_time,
        entity_type=entity_type, event_names=event_names,
        target_entity_type=target_entity_type, limit=-1,
    )
    return to_interactions(
        events,
        value_fn=make_value_fn(value_key, default_value, value_event),
        dedup=dedup,
    )


def interactions_to_columns(inter: Interactions):
    """Interactions -> native.eventlog.Columns (times_us empty: the
    fold dedups before times could be aligned)."""
    import numpy as np

    from pio_tpu.native.eventlog import Columns

    return Columns(
        user_idx=inter.user_idx.astype(np.uint32),
        item_idx=inter.item_idx.astype(np.uint32),
        values=inter.values,
        times_us=np.empty(0, dtype=np.int64),
        users=inter.users.ids(),
        items=inter.items.ids(),
    )


def to_interactions(
    events: Iterable[Event],
    value_fn: Callable[[Event], float | None] = None,
    users: EntityIdIndex | None = None,
    items: EntityIdIndex | None = None,
    dedup: str = "last",
) -> Interactions:
    """Events -> COO interactions.

    value_fn maps an event to a float value (None = skip the event); default
    reads properties["rating"] falling back to 1.0 (implicit). dedup: "last"
    keeps the latest (u,i) value by eventTime (the MLRatings convention of
    the reference templates), "sum" accumulates, "none" keeps duplicates.
    """
    evs = sorted(events, key=lambda e: e.event_time)
    if value_fn is None:
        def value_fn(e):  # noqa: F811 - documented default
            return float(e.properties.get_or_else("rating", 1.0))

    triples: dict[tuple[str, str], float] | list = (
        {} if dedup in ("last", "sum") else []
    )
    for e in evs:
        if e.target_entity_id is None:
            continue
        v = value_fn(e)
        if v is None:
            continue
        key = (e.entity_id, e.target_entity_id)
        if dedup == "last":
            triples[key] = float(v)
        elif dedup == "sum":
            triples[key] = triples.get(key, 0.0) + float(v)
        else:
            triples.append((key, float(v)))

    items_list = triples.items() if isinstance(triples, dict) else triples
    pairs = [k for k, _ in items_list]
    vals = np.array([v for _, v in items_list], dtype=np.float32)
    if users is None:
        users = EntityIdIndex(u for u, _ in pairs)
    if items is None:
        items = EntityIdIndex(i for _, i in pairs)
    known = [
        (ui, ii, v)
        for (u, i), v in zip(pairs, vals)
        if (ui := users.bimap.get(u, -1)) >= 0
        and (ii := items.bimap.get(i, -1)) >= 0
    ]
    if known:
        u_idx, i_idx, v = (np.array(x) for x in zip(*known))
    else:
        u_idx = np.zeros(0, np.int32)
        i_idx = np.zeros(0, np.int32)
        v = np.zeros(0, np.float32)
    return Interactions(
        user_idx=u_idx.astype(np.int32),
        item_idx=i_idx.astype(np.int32),
        values=v.astype(np.float32),
        users=users,
        items=items,
    )
