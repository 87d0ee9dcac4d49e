"""What a drain's evals spent its time on, at quantile ``q`` over the
``drain`` background spans that began and ended in the window. A drain's
evals are the traces whose root carries the span's ``node_id`` and whose
first span began inside it; their spans are clipped to it.

``part`` ``busy``: the union of every span of theirs that is no wait
(``eval_wait.WAITS``) and not the root: some eval of the drain was being
worked on, in a pass or its commit. One wave run as one pass would leave one
pass of it.

``part`` ``wait``: the union of all their spans less that: the drain had
evals in hand and none was being worked on: they queued for the worker,
for the commit thread or for the overlay behind work that was not this
drain's.

Work comes first: while one eval of a wave runs, the wave's others wait
for it, and counting that as wait would leave of ``busy`` the last pass
alone. The two sum to the time any span of the drain's evals covers.
Returns nothing where the program keeps no background ring, writes no
``drain`` span or tags no trace with ``node_id``."""

from benchmark.readers.eval_wait import WAITS
from benchmark.readers.pass_wall import end, union_s
from benchmark.spans import quantile


def split(drain, traces) -> tuple:
    """(busy s, wait s) of one ``drain`` span over ``traces``."""
    d0, d1 = drain["start_unix"], end(drain)
    busy, covered = [], []
    for t in traces:
        spans = [s for s in t.get("spans", ()) if s["parent_id"] is not None]
        if (
            t.get("tags", {}).get("node_id") != drain["tags"].get("node_id")
            or not spans
            or not d0 <= min(s["start_unix"] for s in spans) < d1
        ):
            continue
        for s in spans:
            a, b = max(s["start_unix"], d0), min(end(s), d1)
            if b > a:
                covered.append((a, b))
                if s.get("name") not in WAITS:
                    busy.append((a, b))
    busy_s = union_s(busy)
    return busy_s, union_s(covered) - busy_s


def read(ctx, q, part):
    from nomad_tpu.obs.recorder import flight_recorder
    from nomad_tpu.obs.trace import global_tracer

    held = getattr(flight_recorder, "background", None)
    unix_at = getattr(global_tracer, "unix_at", None)
    traces = [t for t in ctx["traces"] if "node_id" in t.get("tags", {})]
    if held is None or unix_at is None or not traces:
        return None
    t0, t1 = unix_at(ctx["t_open"]), unix_at(ctx["t_close"])
    values = [
        split(s, traces)[part == "wait"] * 1000.0
        for s in held()
        if s.get("name") == "drain" and t0 <= s["start_unix"]
        and end(s) <= t1
    ]
    return quantile(values, q)
