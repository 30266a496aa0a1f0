"""benchmark/reference/events.py alone: the plain fold of events to
interactions, rule by rule, on logs small enough to read."""

import pytest

from benchmark.reference.events import fold, triples


def rec(position, event, user, item, when, rating=None, etype="user",
        ttype="item"):
    return (event, etype, user, ttype, item,
            {} if rating is None else {"rating": rating}, when, position)


def test_the_named_events_between_the_named_entity_types():
    log = [
        rec(0, "rate", "u1", "i1", 10, 3),
        rec(1, "view", "u1", "i2", 11, 5),                   # another name
        rec(2, "rate", "u2", "c1", 12, 5, ttype="category"),
        rec(3, "rate", "a1", "i1", 13, 5, etype="admin"),
        ("$set", "user", "u3", None, None, {"rating": 5}, 14, 4),
        rec(5, "buy", "u2", "i1", 15),
    ]
    users, items, rows = fold(log, ["rate", "buy"])
    assert users == ["u1", "u2"] and items == ["i1"]
    assert rows == [(0, 0, 3.0), (1, 0, 4.0)]
    assert fold(log, ["rate"])[2] == [(0, 0, 3.0)]


def test_the_rating_is_the_rating_events_property_and_4_otherwise():
    log = [
        rec(0, "rate", "u1", "i1", 1, 2),
        rec(1, "rate", "u1", "i2", 2),           # a rate with no rating
        rec(2, "buy", "u1", "i3", 3, 1),         # a buy's rating is not read
    ]
    assert [v for _, _, v in fold(log, ["rate", "buy"])[2]] == [2.0, 4.0, 4.0]
    assert [v for _, _, v in fold(
        log, ["rate", "buy"], implicit_value=1.0)[2]] == [2.0, 1.0, 1.0]


def test_the_latest_event_time_wins_wherever_it_is_in_the_log():
    log = [
        rec(0, "rate", "u1", "i1", 50, 5),       # the latest, written first
        rec(1, "rate", "u1", "i1", 10, 1),
        rec(2, "buy", "u1", "i1", 20),
    ]
    assert fold(log, ["rate", "buy"])[2] == [(0, 0, 5.0)]
    assert fold(log[1:], ["rate", "buy"])[2] == [(0, 0, 4.0)]


def test_of_equal_times_the_later_in_the_log_wins():
    log = [
        rec(0, "rate", "u1", "i1", 10, 1),
        rec(1, "rate", "u1", "i1", 10, 2),
        rec(2, "rate", "u1", "i1", 10, 3),
    ]
    assert fold(log, ["rate"])[2] == [(0, 0, 3.0)]
    # the position decides, not the order the records are handed over in
    assert fold(log[::-1], ["rate"])[2] == [(0, 0, 3.0)]


def test_deleted_events_do_not_count():
    log = [
        rec(0, "rate", "u1", "i1", 10, 1),
        rec(1, "rate", "u1", "i1", 20, 5),
        rec(2, "rate", "u2", "i2", 30, 2),
    ]
    assert fold(log, ["rate"], deleted=[1])[2] == [(0, 0, 1.0), (1, 1, 2.0)]
    users, items, rows = fold(log, ["rate"], deleted=[2])
    assert users == ["u1"] and items == ["i1"] and rows == [(0, 0, 5.0)]


def test_ids_are_indexed_in_order_of_first_appearance():
    log = [
        rec(0, "rate", "u9", "i5", 3, 1),
        rec(1, "rate", "u2", "i5", 1, 2),
        rec(2, "rate", "u9", "i1", 2, 3),
        rec(3, "rate", "u0", "i7", 0, 4),
    ]
    users, items, rows = fold(log, ["rate"])
    assert users == ["u9", "u2", "u0"] and items == ["i5", "i1", "i7"]
    assert rows == [(0, 0, 1.0), (1, 0, 2.0), (0, 1, 3.0), (2, 2, 4.0)]
    by_time = sorted(log, key=lambda r: r[6])
    assert fold(by_time, ["rate"])[0] == ["u0", "u2", "u9"]
    assert triples(*fold(by_time, ["rate"])) == triples(users, items, rows)


@pytest.mark.parametrize("dedup,rows", [
    ("last", [(0, 0, 2.0), (0, 1, 4.0)]),
    ("sum", [(0, 0, 8.0), (0, 1, 4.0)]),
    ("none", [(0, 0, 5.0), (0, 1, 4.0), (0, 0, 2.0), (0, 0, 1.0)]),
])
def test_dedup(dedup, rows):
    log = [
        rec(0, "rate", "u1", "i1", 10, 5),
        rec(1, "buy", "u1", "i2", 11),
        rec(2, "rate", "u1", "i1", 30, 2),
        rec(3, "rate", "u1", "i1", 20, 1),
    ]
    assert fold(log, ["rate", "buy"], dedup=dedup)[2] == rows
    with pytest.raises(ValueError):
        fold(log, ["rate"], dedup="first")
