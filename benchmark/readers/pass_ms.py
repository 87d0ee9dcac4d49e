"""Host time of a batched pass at quantile ``q``: the shared ``snapshot``
and ``invoke_scheduler`` spans once, plus every member's ``prepare``."""

from benchmark.spans import passes, quantile


def read(ctx, q):
    out = []
    for members in passes(ctx["traces"]):
        total, shared_seen = 0.0, set()
        for t in members:
            for s in t.get("spans", ()):
                name = s.get("name")
                if name == "prepare":
                    total += s.get("duration_ms") or 0.0
                elif name in ("snapshot", "invoke_scheduler") and (
                    name not in shared_seen
                ):
                    shared_seen.add(name)
                    total += s.get("duration_ms") or 0.0
        out.append(total)
    return quantile(out, q)
