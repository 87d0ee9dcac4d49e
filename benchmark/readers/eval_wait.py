"""What an eval waited for the worker, at quantile ``q`` over the evals
whose ``triggered_by`` is one of ``kinds``: the union, on one clock, of
its ``dequeue`` (ready in the broker until a worker took it), ``solo_wait``
(set aside by a batched pass until its own solo pass began),
``overlay.wait`` (another pass's read-then-write of the shared overlay) and
``join_commit`` spans (the last commit thread still running; a member's copy
of the leader's counts, the member waited too). ``broker_wait_p50_ms`` reads
the first of the four over every eval of a cell; this reads all four over
the evals on the timed request's path. Returns nothing where no trace holds
a ``solo_wait`` or an ``overlay.wait`` (a program from before them: the
union of the two older spans alone would be another quantity)."""

from benchmark.readers.pass_wall import end, union_s
from benchmark.spans import quantile, spans_named

WAITS = ("dequeue", "solo_wait", "overlay.wait", "join_commit")
MARKERS = ("solo_wait", "overlay.wait")


def read(ctx, q, kinds):
    traces = ctx["traces"]
    if not any(spans_named(traces, name) for name in MARKERS):
        return None
    values = [
        union_s([
            (s["start_unix"], end(s))
            for s in t.get("spans", ()) if s.get("name") in WAITS
        ]) * 1000.0
        for t in traces if t.get("tags", {}).get("triggered_by") in kinds
    ]
    return quantile(values, q)
