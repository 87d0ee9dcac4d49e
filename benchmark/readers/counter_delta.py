"""Sum of the deltas, over the window, of the program counters ``names``."""


def read(ctx, names):
    before, after = ctx["before"]["counters"], ctx["after"]["counters"]
    return float(sum(after.get(n, 0) - before.get(n, 0) for n in names))
