"""``background_span_quantile`` over a window the program's background ring
still holds whole. The ring keeps the newest spans only
(``DEFAULT_BACKGROUND_CAPACITY`` in ``obs/recorder.py``); where it is full
and its oldest span began after the window opened, spans of the window may
have been dropped, and a quantile over what is left would read part of
them: this says so on stderr and reads nothing."""

import sys

from benchmark.readers import background_span_quantile


def read(ctx, span, q, tag=None):
    from nomad_tpu.obs import recorder
    from nomad_tpu.obs.trace import global_tracer

    held = getattr(recorder.flight_recorder, "background", None)
    unix_at = getattr(global_tracer, "unix_at", None)
    if held is None or unix_at is None:
        return None
    spans = held()
    full = len(spans) >= getattr(
        recorder, "DEFAULT_BACKGROUND_CAPACITY", float("inf"))
    if full and spans[0]["start_unix"] >= unix_at(ctx["t_open"]):
        print(
            f"held_span_quantile: the background ring no longer holds the "
            f"window's start; {span!r} is not read", file=sys.stderr,
        )
        return None
    return background_span_quantile.read(ctx, span, q, tag)
