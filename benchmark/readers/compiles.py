"""XLA traces (compiles) of any kernel inside the window, from
``utils/backend.kernel_profile()``: 0 when warm-up covered every shape."""


def read(ctx):
    before, after = ctx["before"]["kernels"], ctx["after"]["kernels"]
    return float(sum(
        k["traces"] - before.get(name, {"traces": 0})["traces"]
        for name, k in after.items()
    ))
