"""Helpers over the program's recorded eval traces (``obs/trace.py``).

A trace is one eval: ``{"eval_id", "tags", "spans": [{"name",
"duration_ms", "start_unix", "tags"}]}``. ``readers/pass_wall.py`` groups
spans into passes by their ``pass_id``.
"""

from __future__ import annotations


def quantile(values: list, q: float):
    """Nearest-rank quantile; ``None`` for no values."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, min(len(s) - 1, int(-(-q * len(s) // 1)) - 1))]


def spans_named(traces: list, name: str) -> list:
    return [
        s for t in traces for s in t.get("spans", ()) if s.get("name") == name
    ]
