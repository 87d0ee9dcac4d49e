"""Time a pass spends in the stage ``span``, at quantile ``q``: per pass
(``pass_wall.passes``), the sum of the spans of that name below its
``phases``; passes without one are left out. For a stage a pass enters
more than once (``explain``: the candidates' provenance before the repair,
the committed rows after it)."""

from benchmark.readers.pass_wall import passes
from benchmark.spans import quantile


def read(ctx, span, q, phases):
    sums = []
    for p in passes(ctx["traces"], phases).values():
        stage = [
            s.get("duration_ms") or 0.0
            for s in p["leaves"] if s["name"] == span
        ]
        if stage:
            sums.append(sum(stage))
    return quantile(sums, q)
