"""Wall time of a scheduling pass at quantile ``q``, solo and batched
passes alike. A pass is every span tagged with one ``pass_id``; its wall
time runs from the first start to the last end of its phases named in
``phases`` (the part of a pass that runs on the worker thread), over all
members' traces. With ``untraced``: that wall time less the union of the
pass's leaf stages inside it, i.e. what the spans miss; a span named in
``whole`` counts as a stage with its children (``prepare`` is reconcile
and asks, and on the solo path holds ``flatten`` besides). The copies of a
phase in the other members' traces (tagged ``leader_eval``) are left out:
they would cover what the leader's stages leave open. ``holding`` keeps
the passes that hold a phase of that name (``invoke_scheduler``: the
passes that score; a deregistration's pass only snapshots and prepares,
and half the passes of a cell are such, so a median over all of them
sits on the edge between two kinds). Returns nothing where no span
carries a ``pass_id`` (a program older than the pass record)."""

from benchmark.spans import quantile


def union_s(intervals: list) -> float:
    """Seconds covered by ``[(start, end)]``."""
    total, cursor = 0.0, None
    for start, end in sorted(intervals):
        if cursor is None or start > cursor:
            total += end - start
            cursor = end
        elif end > cursor:
            total += end - cursor
            cursor = end
    return total


def end(span) -> float:
    return span["start_unix"] + (span.get("duration_ms") or 0.0) / 1000.0


def top_level(span, by_id: dict):
    """The ancestor of ``span`` just below its trace's root (``span``
    itself where it is one; the root for the root)."""
    while by_id.get(span["parent_id"], {}).get("parent_id") is not None:
        span = by_id[span["parent_id"]]
    return span


def passes(traces: list, phases: list, whole=()) -> dict:
    """``{pass_id: {"phases": [span], "leaves": [span]}}``: the named
    phases of each pass and, at or below them, the childless spans and
    those named in ``whole``."""
    out: dict = {}
    for t in traces:
        spans = t.get("spans", ())
        by_id = {s["span_id"]: s for s in spans}
        parents = {s["parent_id"] for s in spans}
        for s in spans:
            top = top_level(s, by_id)
            tags = top.get("tags", {})
            if (
                top.get("parent_id") is None
                or "pass_id" not in tags
                or "leader_eval" in tags
                or top["name"] not in phases
            ):
                continue
            p = out.setdefault(tags["pass_id"], {"phases": [], "leaves": []})
            if s is top:
                p["phases"].append(s)
            if s["span_id"] not in parents or s["name"] in whole:
                p["leaves"].append(s)
    return out


def read(ctx, q, phases, untraced=False, holding=None, whole=()):
    values = []
    for p in passes(ctx["traces"], phases, whole).values():
        if holding and not any(s["name"] == holding for s in p["phases"]):
            continue
        t0 = min(s["start_unix"] for s in p["phases"])
        t1 = max(end(s) for s in p["phases"])
        wall = t1 - t0
        if untraced:
            wall -= union_s([
                (max(s["start_unix"], t0), min(end(s), t1))
                for s in p["leaves"]
            ])
        values.append(wall * 1000.0)
    return quantile(values, q)
