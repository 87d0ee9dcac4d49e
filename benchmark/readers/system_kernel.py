"""Device time of the system pass's dense score (profiler trace, programs
named after ``kernels``) per group a system pass scored (``system.place``
spans, one a group); with ``roofline`` the share of that time the chip
needed at least (``system_cost.py`` over the table of peaks, the fleet's
nodes once a call). Returns nothing where there is nothing to read: a
rehearsal, no device plane, no call of these kernels, or a program that
writes no such span."""

from benchmark.spans import spans_named
from benchmark.system_cost import least_seconds
from benchmark.trace_reduce import kernel_seconds


def read(ctx, kernels, roofline=False):
    if ctx["rehearse"] or not ctx["reduced"]["modules"]:
        return None
    calls, seconds = kernel_seconds(ctx["reduced"], kernels)
    n_passes = len(spans_named(ctx["traces"], "system.place"))
    if not calls or seconds <= 0 or not n_passes:
        return None
    if not roofline:
        return seconds * 1000.0 / n_passes
    least = least_seconds(
        ctx["peaks"], ctx["device_kind"], [int(ctx["fleet"]["n"])] * calls
    )
    ctx["system_roofline"] = {
        **least, "kernel_seconds": seconds, "calls": calls,
        "passes": n_passes,
    }
    return 100.0 * least["seconds"] / seconds
