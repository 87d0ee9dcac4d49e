"""Part ``warm``: today's warm-up and pre-fill; the number of jobs the
driver is to keep live is this module's own, one more than the pre-fill
places at the rehearsal's size (160 allocations in jobs of 8)."""

from benchmark import warm as default
from benchmark.warm import settle_admission, warm_shapes  # noqa: F401

STEADY_JOBS = 21


def prefill(server, config, traffic, specs, make_job, seed, log) -> tuple:
    live, requests, _steady = default.prefill(
        server, config, traffic, specs, make_job, seed, log
    )
    return live, requests, STEADY_JOBS
