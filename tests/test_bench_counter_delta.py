"""``benchmark/readers/counter_delta.py``, mirrored from
``benchmark/tests/test_counter_delta.py`` (which tier-1 does not run): a
counter neither snapshot holds is no value, unless the metric's file says
it counts from its first count."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.readers import counter_delta  # noqa: E402

NAMES = ["nomad.x.a", "nomad.x.b"]


@pytest.mark.parametrize("before, after, from_first_count, want", [
    # the program lacks the counters (a parent from before them): no value
    ({}, {}, False, None),
    ({"nomad.other": 3.0}, {"nomad.other": 9.0}, False, None),
    # held by the later snapshot only: the first count fell in the window
    ({}, {"nomad.x.a": 2.0}, False, 2.0),
    # held by the earlier snapshot only reads as what it says, a fall
    ({"nomad.x.b": 2.0}, {}, False, -2.0),
    # held by both, and one of the two names by neither
    ({"nomad.x.a": 5.0}, {"nomad.x.a": 12.0}, False, 7.0),
    ({"nomad.x.a": 5.0, "nomad.x.b": 1.0},
     {"nomad.x.a": 5.0, "nomad.x.b": 4.0}, False, 3.0),
    # there and level: a sound 0
    ({"nomad.x.a": 5.0}, {"nomad.x.a": 5.0}, False, 0.0),
    # an alarm known to count from its first count reads 0 before it
    ({}, {}, True, 0.0),
    ({}, {"nomad.x.a": 1.0}, True, 1.0),
])
def test_delta_or_no_value(before, after, from_first_count, want):
    ctx = {"before": {"counters": before}, "after": {"counters": after}}
    got = counter_delta.read(ctx, NAMES, from_first_count=from_first_count)
    assert got == want
