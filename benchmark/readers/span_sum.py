"""Sum, in ms, of the durations of every span called ``span`` in the
window's traces: a total, for an interval most passes pay nothing of and
a few pay much (a median reads 0 there). Returns nothing where no trace
holds such a span (a program from before it)."""

from benchmark.spans import spans_named


def read(ctx, span):
    spans = spans_named(ctx["traces"], span)
    if not spans:
        return None
    return sum(s.get("duration_ms") or 0.0 for s in spans)
