"""Set-up through the served path: c2m-10k's warm-up and pre-fill
(``benchmark/warm.py``: 200 services of 250), every allocation acknowledged
``running`` by its client (the drainer counts a group's serving
allocations by their clients' word; a fleet nobody has acknowledged drains
nothing), then ``warm_cycles`` drains as the window has them, at the
window's rate, with the clients' acknowledgements and the return to
eligible, until every one has ended. A migration's passes are solo passes
of one ask on spread counts that start from the job's other allocations,
beside passes that place nothing: shapes the pre-fill never reaches. The
order of nodes brings both classes round in three arrivals, and the
window opens on a fleet that already has drained and returned nodes.

The operator's return of a node is ``Server.update_node_eligibility``
(Nomad's ``Node.UpdateEligibility``). A program from before that call
cannot run the deployment: importing this part then fails, and ``run.py``
ends the run there, before it takes the device."""

from __future__ import annotations

import copy

from benchmark import warm as base
from benchmark.drain.driver import Driver, clock
from benchmark.gen.arrivals import arrival_times
from benchmark.warm import SetupFailure, settle_admission, warm_shapes
from nomad_tpu.server import Server

if not hasattr(Server, "update_node_eligibility"):
    raise ImportError(
        "the program has no Server.update_node_eligibility: it cannot set "
        "a drained node eligible again as its operator does"
    )

__all__ = ["warm_shapes", "prefill", "settle_admission"]


def acknowledge_running(server, job_ids: list) -> int:
    """The nodes' clients report every pending allocation of these jobs
    ``running``, one ``update_allocs_from_client`` batch a job."""
    n = 0
    for job_id in job_ids:
        updates = []
        for a in server.store.allocs_by_job("default", job_id):
            if a.client_status == "pending" and not a.terminal_status():
                u = copy.copy(a)
                u.client_status = "running"
                updates.append(u)
        if updates:
            server.update_allocs_from_client(updates)
            n += len(updates)
    return n


def prefill(server, config: dict, traffic: dict, specs, make_job,
            seed: int, log) -> tuple:
    """Returns what the window's driver starts from (the drains set-up
    sent, the live allocations), every request sent, and the number of
    live jobs."""
    live, requests, n_jobs = base.prefill(
        server, config, traffic, specs, make_job, seed, log
    )
    acked = acknowledge_running(server, [job_id for job_id, _c in live])
    log(f"pre-fill: {acked} allocations acknowledged running")
    cycles = int(traffic["warm_cycles"])
    rate = float(traffic["arrivals"]["rate_per_s"])
    due = arrival_times(traffic, seed, 3.0 * cycles / rate + 60.0)[:cycles]
    start = {"drains_sent": 0, "live_allocs": acked}
    driver = Driver(
        server, iter(()), make_job, start, n_jobs, patient=True,
        traffic=traffic, seed=seed,
    )
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
    driver.drain(60.0)
    bad = [r for r in driver.requests if r.ok is not True]
    if bad:
        raise SetupFailure(
            f"warm-up drains: {len(bad)} of {cycles} did not end "
            f"({bad[0].job_id}: {bad[0].note})"
        )
    moved = sum(len(r.stops) for r in driver.requests)
    log(f"warm-up: {cycles} drains ended, {moved} allocations migrated, "
        f"every node eligible again")
    # the window goes on in the order where these drains left it
    start = {"drains_sent": len(driver.requests),
             "live_allocs": driver._live_allocs}
    return start, requests + driver.requests, n_jobs
