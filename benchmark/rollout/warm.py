"""Set-up through the served path: c2m-10k's warm-up and pre-fill
(``benchmark/warm.py``: 200 jobs of 250 at version 0), then ``warm_cycles``
arrivals as the window has them, at the window's rate and with the clients'
acknowledgements, until every rollout they started has ended. A rollout's
passes are solo passes of ``max_parallel`` (a service's round) or ``count``
(a batch job's replacement) asks on spread counts that do not start at 0:
shapes the pre-fill never reaches. The cycle's six shapes come round every
six arrivals (the traffic file's ``shuffle_block``), so ``warm_cycles`` of a
multiple of six reach each of them, and the window opens on a store that
already holds successful deployments."""

from __future__ import annotations

from benchmark import warm as base
from benchmark.gen.arrivals import arrival_times
from benchmark.rollout.driver import Driver, clock
from benchmark.warm import SetupFailure, settle_admission, warm_shapes

__all__ = ["warm_shapes", "prefill", "settle_admission"]


def prefill(server, config: dict, traffic: dict, specs, make_job,
            seed: int, log) -> tuple:
    """Returns the live jobs' specs, the one updated longest ago first,
    every request sent, and the number of live jobs."""
    sent: list = []

    def kept():
        for spec in specs:
            sent.append(spec)
            yield spec

    _live, requests, n_jobs = base.prefill(
        server, config, traffic, kept(), make_job, seed, log
    )
    cycles = int(traffic["warm_cycles"])
    rate = float(traffic["arrivals"]["rate_per_s"])
    due = arrival_times(traffic, seed, 3.0 * cycles / rate + 60.0)[:cycles]
    driver = Driver(
        server, iter(()), make_job, sent[:n_jobs], n_jobs, patient=True,
        traffic=traffic, seed=seed,
    )
    driver.patience_s = 30.0  # a rollout takes under 3 s
    # the window's arrivals and the clients between them, as
    # ``Driver.run_open`` runs them, without a window to open
    store = server.store
    t_begin = clock()
    for offset in due:
        while clock() < t_begin + offset:
            seen = store.latest_index
            if not driver.collect():
                driver._wait(
                    seen, min(0.25, max(0.0, t_begin + offset - clock()))
                )
        driver.send_register(t_begin + offset)
    driver.drain(driver.patience_s)
    bad = [r for r in driver.requests if r.ok is not True]
    if bad or driver.rolling:
        raise SetupFailure(
            f"warm-up cycles: {len(bad)} requests failed "
            f"({bad[0].job_id}: {bad[0].note})" if bad else
            f"warm-up cycles: {len(driver.rolling)} rollouts did not end"
        )
    log(f"warm-up: {cycles} rollouts ended; "
        f"{len(driver.finished)} jobs on their next version")
    return list(driver.fifo), requests + driver.requests, n_jobs
