"""What the loss of a rack cost, at quantile ``q``, from the requests of the
window that ended well (``node_loss/driver.py``: one a job hit by a
failure, due at the failure, done when the job is back at its count).

``part`` ``busy`` / ``wait``: per request, the traces of the job's node
evals whose root carries one of the failure's nodes as ``node_id`` and
whose first span began inside the request's interval, their spans clipped
to it, split by ``drain_evals.split``: ``busy`` the union of
the spans that are no wait (``eval_wait.WAITS``), some eval of the job's
was being worked on; ``wait`` what their spans cover beyond that, the
job's evals only queued. ``part`` ``recover``: per failure due in the
window, due -> its last job done: the operator's time to recover.

Returns nothing where the requests are not a failure's (another
deployment), no trace carries ``node_id``, or no request ended well."""

from benchmark.readers import drain_evals
from benchmark.spans import quantile

_FAILED = "the failure's nodes"


def split(t0: float, t1: float, traces: list) -> tuple:
    """(busy s, wait s) of ``traces`` clipped to ``[t0, t1)``: as
    ``drain_evals.split`` splits a drain, over a span of that interval that
    every one of ``traces`` belongs to."""
    interval = {"start_unix": t0, "duration_ms": (t1 - t0) * 1000.0,
                "tags": {"node_id": _FAILED}}
    return drain_evals.split(interval, [
        dict(t, tags=dict(t.get("tags", {}), node_id=_FAILED))
        for t in traces
    ])


def read(ctx, q, part):
    from nomad_tpu.obs.trace import global_tracer

    unix_at = getattr(global_tracer, "unix_at", None)
    losses = [
        r for r in ctx["requests"]
        if hasattr(r, "failure") and r.ok is True and r.done is not None
    ]
    if unix_at is None or not losses:
        return None
    if part == "recover":
        by_failure: dict = {}
        for r in ctx["requests"]:
            if hasattr(r, "failure"):
                by_failure.setdefault(id(r.failure), []).append(r)
        values = [
            (max(r.done for r in reqs) - reqs[0].failure.due) * 1000.0
            for reqs in by_failure.values()
            if all(r.ok is True for r in reqs)
        ]
        return quantile(values, q)
    by_job: dict = {}
    for t in ctx["traces"]:
        tags = t.get("tags", {})
        if "node_id" in tags:
            by_job.setdefault(tags.get("job_id"), []).append(t)
    if not by_job:
        return None
    values = []
    for r in losses:
        nodes = set(r.failure.node_ids)
        mine = [t for t in by_job.get(r.job_id, ())
                if t["tags"]["node_id"] in nodes]
        busy_s, wait_s = split(unix_at(r.due), unix_at(r.done), mine)
        values.append((wait_s if part == "wait" else busy_s) * 1000.0)
    return quantile(values, q)
