"""Quantile ``q`` of the client-side placement latency (done - due, ms)
over the window's registrations: the same samples as the end-to-end
latency metrics, for a cell in which that quantile swings too widely to
carry a bound. ``half`` ("first" or "second") keeps the registrations due
in that half of the window: the two medians side by side say how far the
latency climbs while the run's dead allocations pile up in the store."""

from benchmark.spans import quantile


def read(ctx, q, half=None):
    mid = (ctx["t_open"] + ctx["t_close"]) / 2.0
    keep = {
        None: lambda r: True,
        "first": lambda r: r.due < mid,
        "second": lambda r: r.due >= mid,
    }[half]
    return quantile(
        [(r.done - r.due) * 1000.0
         for r in ctx["registers"] if r.ok and keep(r)], q,
    )
