"""Sum of the deltas, over the window, of the program counters ``names``.

The registry makes a counter at its first increment, so one that neither
snapshot holds is either a counter this program lacks (a parent commit from
before the counter) or one that has not counted yet. The first reads as no
value, not as 0: a 0 there would read as a sound alarm. ``from_first_count``
in the metric's file says the second: an alarm the program is known to
have, which stays silent in a sound run and reads 0 then."""


def read(ctx, names, from_first_count=False):
    before, after = ctx["before"]["counters"], ctx["after"]["counters"]
    if not from_first_count and not any(
        n in before or n in after for n in names
    ):
        return None
    return float(sum(after.get(n, 0) - before.get(n, 0) for n in names))
