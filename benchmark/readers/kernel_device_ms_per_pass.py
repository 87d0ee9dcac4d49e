"""Device time of the placement kernels (profiler trace, programs named
after ``kernels``) per scoring pass (``kernel.place`` spans)."""

from benchmark.trace_reduce import traced_kernel_time


def read(ctx, kernels):
    found = traced_kernel_time(ctx, kernels)
    if found is None:
        return None
    seconds, n_passes = found
    return seconds * 1000.0 / n_passes
