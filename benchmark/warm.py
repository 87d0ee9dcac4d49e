"""Set-up through the served path: warm the cell's shapes, fill the fleet.

Copied in method from ``chip_smoke.run_registration_phase``: a pass is
shaped by pausing the workers until its evals are enqueued, so warm-up
reaches each compiled G bucket on purpose and not by luck. Only the cell's
own shapes are warmed (``warm_shapes`` lists them). G = 32 exists only
behind the brownout lever, which the cells' load never pulls
(``admission_level_changes.lat`` reads 0 in every run).
"""

from __future__ import annotations

import time

from benchmark.driver import Driver, Request, clock
from benchmark.gen.jobs import plain_spec

SETUP_PATIENCE_S = 600.0  # a first run's pass may hold a cold compile


class SetupFailure(Exception):
    """Set-up could not bring the server to the cell's steady state."""


def _drain(driver: Driver, what: str) -> None:
    n_before = len(driver.requests)
    driver.drain(SETUP_PATIENCE_S)
    bad = [r for r in driver.requests[:n_before] if r.ok is False]
    if bad:
        raise SetupFailure(
            f"{what}: {len(bad)} requests failed, first: {bad[0].kind} "
            f"{bad[0].job_id}: {bad[0].note}"
        )


def _shaped_pass(server, driver: Driver, specs: list, make_job,
                 deregister: int = 0) -> None:
    """One pass holding exactly ``specs`` and ``deregister``
    deregistrations of earlier warm-up jobs (workers held until all are
    enqueued), then drained."""
    for w in server.workers:
        w.pause()
    time.sleep(0.5)  # an idle worker's 0.2 s dequeue returns empty
    try:
        for spec in specs:
            req = Request("register", spec["id"], spec["count"], clock())
            driver._send(req, make_job(spec))
        for _ in range(deregister):
            driver.send_deregister(clock())
    finally:
        for w in server.workers:
            w.resume()
    _drain(driver, f"warm-up pass of {len(specs)}+{deregister}")


def warm_shapes(server, traffic: dict, make_job, log) -> list:
    """Warm every shape the cell's traffic reaches, once per ask size of
    the cycle (a pass that holds only the larger ask gets the smaller J
    bucket), then the deregistration path, and leave the fleet empty
    again. Returns the requests sent (the accounting of ``correct`` covers
    set-up too). The shapes:

    - one registration alone: the solo path, G = 1, no tie-break jitter;
    - ``EVAL_BATCH_SIZE`` registrations: the batched pass, G = 16;
    - one registration beside a deregistration: a batched pass whose only
      ask is that registration, G = 1 *with* jitter, which is what an
      open-loop arrival (register + deregister the oldest) produces.
    """
    from nomad_tpu.server.worker import EVAL_BATCH_SIZE

    shape = traffic["job"]
    by_cpu: dict = {}
    for entry in traffic["cycle"]:
        by_cpu.setdefault(int(entry["cpu"]), entry)
    driver = Driver(server, iter(()), make_job, [], 0, patient=True)
    n = 0
    for g, deregister in ((1, 0), (EVAL_BATCH_SIZE, 0), (1, 1)):
        for cpu, entry in sorted(by_cpu.items()):
            specs = [
                plain_spec(
                    f"warm-{g}-{deregister}-{cpu}-{n + k:04d}", entry, shape
                )
                for k in range(g)
            ]
            n += g
            _shaped_pass(server, driver, specs, make_job, deregister)
            log(f"warm-up: G={g}+{deregister} cpu={cpu} drained")
    while driver.live:
        driver.send_deregister(0.0)
    _drain(driver, "warm-up deregistration")
    log("warm-up: deregistered")
    return driver.requests


def prefill(server, config: dict, traffic: dict, specs, make_job,
            seed: int, log) -> tuple:
    """Register the configuration's live allocations in jobs of the cell's
    own mix (``specs``, the window's stream: it needs no ``seed`` of its
    own) through the served path, ``prefill_in_flight`` at a time; returns
    the live FIFO ``[(job_id, count)]``, the requests sent and the number
    of jobs the driver then keeps live."""
    n_jobs = int(config["live_allocs"]) // int(traffic["job"]["count"])
    in_flight = int(traffic.get("prefill_in_flight", 32))
    driver = Driver(server, specs, make_job, [], 0, patient=True)
    store = server.store
    sent = 0
    deadline = time.monotonic() + SETUP_PATIENCE_S
    while len(driver.live) < n_jobs:
        if time.monotonic() > deadline:
            raise SetupFailure(
                f"pre-fill stalled at {len(driver.live)} of {n_jobs} jobs"
            )
        seen = store.latest_index
        for r in driver.collect():
            if not r.ok:
                raise SetupFailure(f"pre-fill: {r.job_id}: {r.note}")
        while sent < n_jobs and len(driver.pending) < in_flight:
            driver.send_register(0.0)
            sent += 1
        driver._wait(seen, 0.25)
    log(f"pre-fill: {n_jobs} jobs live")
    return list(driver.live), driver.requests, n_jobs


def settle_admission(server, log) -> None:
    """Wait until the admission controller reads NORMAL: a cold compile
    inside a warm-up pass leaves the 5 s latency window above the
    brownout threshold for a few seconds after it."""
    deadline = time.monotonic() + 60.0
    while server.admission.level(force=True) != "normal":
        if time.monotonic() > deadline:
            raise SetupFailure("admission controller did not settle")
        time.sleep(0.25)
    log("admission: normal")
