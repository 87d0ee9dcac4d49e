"""Helpers over the program's recorded eval traces (``obs/trace.py``).

A trace is one eval: ``{"eval_id", "tags", "spans": [{"name",
"duration_ms", "start_unix", "tags"}]}``. Phases shared by a batched pass
(``snapshot``, ``invoke_scheduler``) are copied into every member's trace,
tagged ``shared``; members of one pass are found by that copy.
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


def passes(traces: list) -> list:
    """Batched passes in ``traces``: each a list of member traces, found
    by the shared ``snapshot`` span (same duration to the last digit)."""
    groups: dict = {}
    for t in traces:
        for s in t.get("spans", ()):
            if s.get("name") == "snapshot" and s.get("tags", {}).get("shared"):
                groups.setdefault(s.get("duration_ms"), []).append(t)
                break
    return list(groups.values())
