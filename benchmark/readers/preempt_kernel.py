"""Device time of the preemption ranking (profiler trace, programs named
after ``kernels``) per ranking pass (``preempt.rank`` spans, one per group
that placed by evicting); with ``roofline`` the share of that time the
chip needed at least (``preempt_cost.py`` over the table of peaks, from
the ``nodes`` and ``v_bucket`` each ``preempt.victims`` span recorded).
Returns nothing where there is nothing to read: a rehearsal, no device
plane, no call of these kernels, or a program that writes no such span."""

from benchmark.preempt_cost import least_seconds
from benchmark.spans import spans_named
from benchmark.trace_reduce import kernel_seconds


def read(ctx, kernels, roofline=False):
    if ctx["rehearse"] or not ctx["reduced"]["modules"]:
        return None
    calls, seconds = kernel_seconds(ctx["reduced"], kernels)
    n_ranks = len(spans_named(ctx["traces"], "preempt.rank"))
    if not calls or seconds <= 0 or not n_ranks:
        return None
    if not roofline:
        return seconds * 1000.0 / n_ranks
    ranks = [
        (int(s["tags"]["nodes"]), int(s["tags"]["v_bucket"]))
        for s in spans_named(ctx["traces"], "preempt.victims")
        if s.get("tags", {}).get("victims")
    ]
    if not ranks:
        return None
    least = least_seconds(ctx["peaks"], ctx["device_kind"], ranks)
    ctx["preempt_roofline"] = {
        **least, "kernel_seconds": seconds, "rankings": len(ranks),
        "calls": calls,
    }
    return 100.0 * least["seconds"] / seconds
