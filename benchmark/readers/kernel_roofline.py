"""Share of the roofline the placement kernels reached: the least time
the chip could take for the window's placements (``kernel_cost.py``, from
the cell's shapes and the table of peaks) over the kernels' device time.
Returns nothing where no kernel ran on a device in the trace."""

from benchmark.kernel_cost import least_seconds
from benchmark.trace_reduce import traced_kernel_time


def read(ctx, kernels):
    found = traced_kernel_time(ctx, kernels)
    if found is None:
        return None
    seconds, n_passes = found
    n_jobs = sum(1 for r in ctx["registers"] if r.ok)
    least = least_seconds(
        ctx["peaks"], ctx["device_kind"], ctx["fleet"], ctx["traffic"],
        n_jobs, n_passes,
    )
    # which bound, and what went into the share: run.py logs it
    ctx["roofline"] = {
        **least, "kernel_seconds": seconds, "jobs": n_jobs,
        "passes": n_passes,
    }
    return 100.0 * least["seconds"] / seconds
