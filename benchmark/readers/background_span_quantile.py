"""Quantile ``q`` over the background spans called ``span`` that started
in the window: of their duration, or of the numeric tag ``tag``. A
background span is work of the program that belongs to no eval (a tick of
the deployment watcher, the clients' alloc sync); the program keeps them in
a ring of its own beside the eval traces (``obs/recorder.py``
``background``), so the harness's listener does not hold them. Returns
nothing where the program has no such ring (a program from before it) or
no span of the name."""

from benchmark.spans import quantile


def read(ctx, span, q, tag=None):
    from nomad_tpu.obs.recorder import flight_recorder
    from nomad_tpu.obs.trace import global_tracer

    held = getattr(flight_recorder, "background", None)
    unix_at = getattr(global_tracer, "unix_at", None)
    if held is None or unix_at is None:
        return None
    t0, t1 = unix_at(ctx["t_open"]), unix_at(ctx["t_close"])
    spans = [
        s for s in held()
        if s.get("name") == span and t0 <= s["start_unix"] < t1
    ]
    if tag is None:
        values = [s.get("duration_ms") or 0.0 for s in spans]
    else:
        values = [
            float(s["tags"][tag]) for s in spans if tag in s.get("tags", {})
        ]
    return quantile(values, q)
