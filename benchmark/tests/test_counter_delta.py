"""``readers/counter_delta.py``: a counter neither snapshot holds is no
value, unless the metric's file says it counts from its first count."""

import pytest

from benchmark.readers import counter_delta

NAMES = ["nomad.x.a", "nomad.x.b"]


def ctx(before: dict, after: dict) -> dict:
    return {"before": {"counters": before}, "after": {"counters": after}}


@pytest.mark.parametrize("before, after, want", [
    # the program lacks the counters (a parent from before them): no value
    ({}, {}, None),
    ({"nomad.other": 3.0}, {"nomad.other": 9.0}, None),
    # held by the later snapshot only: the first count fell in the window
    ({}, {"nomad.x.a": 2.0}, 2.0),
    # held by the earlier snapshot only reads as what it says, a fall
    ({"nomad.x.b": 2.0}, {}, -2.0),
    # held by both, and one of the two names by neither
    ({"nomad.x.a": 5.0}, {"nomad.x.a": 12.0}, 7.0),
    ({"nomad.x.a": 5.0, "nomad.x.b": 1.0},
     {"nomad.x.a": 5.0, "nomad.x.b": 4.0}, 3.0),
    # there and level: a sound 0
    ({"nomad.x.a": 5.0}, {"nomad.x.a": 5.0}, 0.0),
])
def test_delta_or_no_value(before, after, want):
    assert counter_delta.read(ctx(before, after), NAMES) == want


def test_an_alarm_known_to_count_from_its_first_count_reads_zero():
    assert counter_delta.read(ctx({}, {}), NAMES, from_first_count=True) == 0.0
    assert counter_delta.read(
        ctx({}, {"nomad.x.a": 1.0}), NAMES, from_first_count=True) == 1.0
