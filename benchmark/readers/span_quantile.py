"""Quantile ``q`` over every span called ``span``: of its duration, or of
the numeric tag ``tag`` where one is named."""

from benchmark.spans import quantile, spans_named


def read(ctx, span, q, tag=None):
    spans = spans_named(ctx["traces"], span)
    if tag is None:
        values = [s.get("duration_ms") or 0.0 for s in spans]
    else:
        values = [
            float(s["tags"][tag]) for s in spans if tag in s.get("tags", {})
        ]
    return quantile(values, q)
