"""Evals completed per dequeue: each eval of a dequeue of ``b`` counts
``1/b`` of a pass (``batch_size`` on the trace's root)."""


def read(ctx):
    sizes = [
        t.get("tags", {}).get("batch_size") for t in ctx["traces"]
    ]
    sizes = [b for b in sizes if b]
    if not sizes:
        return None
    return len(sizes) / sum(1.0 / b for b in sizes)
