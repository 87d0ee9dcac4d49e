"""Time a pass spends in the span ``span``, whatever it holds below it, at
quantile ``q``: per pass (``pass_wall.passes``), the sum of the spans of
that name below its ``phases``; passes without one are left out.
``pass_stage_sum`` reads childless stages; this reads a stage that has
children of its own (``preempt.rank`` holds the kernel's dispatch)."""

from benchmark.readers.pass_wall import passes
from benchmark.spans import quantile


def read(ctx, span, q, phases):
    sums = []
    for p in passes(ctx["traces"], phases, whole=(span,)).values():
        stage = [
            s.get("duration_ms") or 0.0
            for s in p["leaves"] if s["name"] == span
        ]
        if stage:
            sums.append(sum(stage))
    return quantile(sums, q)
