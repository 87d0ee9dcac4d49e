"""How late the load generator ran: quantile ``q`` of sent - due (ms)."""

from benchmark.spans import quantile


def read(ctx, q):
    return quantile(
        [(r.sent - r.due) * 1000.0 for r in ctx["requests"]
         if r.sent is not None], q
    )
