"""Plain reference for the training read: events -> interactions.

The rule is the recommendation template's DataSource (apache/predictionio
examples/scala-parallel-recommendation, DataSource.scala) as this system
states it: of the events between the named entity types keep those with
a named event name; the rating event's value is `properties[value_key]`,
any other event's the fixed implicit value; of several events of one
(user, item) pair the latest `eventTime` wins and, of equal times, the
later in the log; ids are indexed in order of first appearance; deleted
events do not count.

Plain Python over a list, one record at a time; shares no code with
pio_tpu. A record is

    (event, entityType, entityId, targetEntityType, targetEntityId,
     properties, eventTime, position)

with `properties` a dict, `eventTime` anything that orders (a datetime,
a number of seconds) and `position` the record's place in the log.
"First appearance" is in the order of the list handed over.
"""

from __future__ import annotations


def fold(records, event_names, value_event="rate", value_key="rating",
         implicit_value=4.0, entity_type="user", target_entity_type="item",
         dedup="last", deleted=()):
    """-> (user ids, item ids, rows): rows are (user index, item index,
    value) in order of the pair's first appearance (`dedup` "last" keeps
    a pair's latest value, "sum" adds them up) or, with `dedup` "none",
    one row an event in the order given. `deleted` holds the positions
    of deleted events."""
    if dedup not in ("last", "sum", "none"):
        raise ValueError(f"unknown dedup {dedup!r}")
    deleted = set(deleted)
    users: dict[str, int] = {}
    items: dict[str, int] = {}
    rows: list[list] = []
    newest: dict[tuple[int, int], tuple] = {}   # pair -> (row, time, pos)
    for (event, etype, eid, ttype, tid, properties, when, position) in records:
        if position in deleted or event not in event_names:
            continue
        if etype != entity_type or ttype != target_entity_type or tid is None:
            continue
        if event == value_event and value_key in properties:
            value = float(properties[value_key])
        else:
            value = float(implicit_value)
        pair = (users.setdefault(eid, len(users)),
                items.setdefault(tid, len(items)))
        if dedup == "none" or pair not in newest:
            if dedup != "none":
                newest[pair] = (len(rows), when, position)
            rows.append([pair[0], pair[1], value])
            continue
        row, seen_when, seen_position = newest[pair]
        if dedup == "sum":
            rows[row][2] += value
        elif (when, position) > (seen_when, seen_position):
            rows[row][2] = value
            newest[pair] = (row, when, position)
    return list(users), list(items), [tuple(r) for r in rows]


def triples(user_ids, item_ids, rows) -> list[tuple[str, str, float]]:
    """The rows by id, sorted: what two reads of one log must agree on
    whatever order they index ids in."""
    return sorted((user_ids[u], item_ids[i], v) for u, i, v in rows)
