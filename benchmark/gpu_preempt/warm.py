"""Set-up through the served path: fill the fleet, then run the window's
own cycle until every shape it reaches is warm.

Nothing of this deployment's measured path exists on an empty fleet: the
victim tensors, the ranking kernel, a plan that evicts, the victims'
follow-up evals and the unblocking deregistration all need a full one. So
``warm_shapes`` has nothing to do and ``prefill`` does both jobs, in the
order a deployment comes to be:

1. the fill (``jobs.fill_specs``: GPU holders on every instance, then CPU
   fill until no node has room for one more), ``prefill_in_flight`` jobs at
   a time through batched passes (or the kind's own ``in_flight``), the
   last ``fill.tail_jobs`` of each kind one at a time (a lone pass sees the
   whole fleet; a lane of a batched pass sees a stripe, and the last free
   slots lie scattered). The GPU holders go one at a time throughout: the
   stripes of a batched pass are cut by free cpu and memory, not by free
   instances, and a lane whose stripe has run out of instances ends in the
   lone path beside the next pass, where two plans made on one snapshot
   can lose to each other twice (a batch eval gives up after two tries);
2. the configuration's ``steady_jobs`` services of the window's mix, one
   at a time, each placed by evicting, each followed until the victims'
   evals have run (the lone pass with a plan that evicts; the passes of
   follow-up evals that find no room and block);
3. ``warm_cycles`` arrivals as the window has them (register, deregister
   the oldest), by turns with the workers held until both evals are
   enqueued (one pass holds both) and not: the batched pass that holds a
   preempting registration, the deregistration that unblocks, the victims
   taking the room back.
"""

from __future__ import annotations

import time

from benchmark.gpu_preempt.driver import Driver, live_allocations, settle
from benchmark.gpu_preempt.fleet import fleet_spec
from benchmark.gpu_preempt.jobs import fill_specs
from benchmark.warm import (
    SETUP_PATIENCE_S,
    SetupFailure,
    _shaped_pass,
    settle_admission,
)

__all__ = ["warm_shapes", "prefill", "settle_admission"]


def warm_shapes(server, traffic: dict, make_job, log) -> list:
    log("warm-up: after the fill (the cell's shapes need a full fleet)")
    return []


def _register_all(driver: Driver, n_jobs: int, in_flight: int,
                  what: str) -> None:
    """Send ``n_jobs`` registrations, ``in_flight`` at a time; every one
    has to end complete with all its allocations live."""
    store = driver.server.store
    sent = finished = 0
    deadline = time.monotonic() + SETUP_PATIENCE_S
    while finished < n_jobs:
        if time.monotonic() > deadline:
            raise SetupFailure(f"{what} stalled at {finished} of {n_jobs}")
        seen = store.latest_index
        for r in driver.collect():
            if not r.ok:
                raise SetupFailure(f"{what}: {r.job_id}: {r.note}")
            finished += 1
        while sent < n_jobs and len(driver.pending) < in_flight:
            driver.send_register(0.0)
            sent += 1
        driver._wait(seen, 0.25)


def _warm_kernel_variants(server, config: dict, traffic: dict, log) -> None:
    """Compile every variant of the placement kernel the window can reach,
    on the full fleet and on purpose. The kernel's inputs collapse to
    ``[G, 1]`` where they say nothing (no allocation of the job anywhere,
    no device ask in the pass), its candidate width follows the largest
    count of the pass and the path's overflow, and a batched pass adds a
    tie-break input: which of these a pass gets depends on which victims'
    evals share it, so cycles of real traffic reach them by luck. The asks
    are flattened from jobs nobody registers and the placements are thrown
    away; nothing is planned or committed."""
    from nomad_tpu.device import flatten_group_ask
    from nomad_tpu.scheduler.algorithms import make_kernel
    from nomad_tpu.server.worker import EVAL_BATCH_SIZE

    from benchmark.gpu_preempt.jobs import fill_specs, make_job, service_spec

    snap = server.store.snapshot()
    ct = server.device_cache.tensors(snap)
    cfg = snap.scheduler_config()
    kernel = make_kernel(cfg.scheduler_algorithm)
    explain = bool(getattr(cfg, "placement_explanations", True))
    fleet = fleet_spec(config["fleet"])
    holder, cpu_fill = (
        next(s for s in fill_specs(config["fill"], fleet, 0)
             if s["kind"] == kind)
        for kind in ("gpu_holder", "cpu_fill")
    )
    per_job = int(config["fill"]["job_count"])
    service = service_spec("warm-service", traffic["cycle"][0], traffic["job"])
    n = 0

    def asks(spec, held, count, lanes):
        job = make_job({**spec, "id": f"warm-shape-{n}"})
        tg = job.task_groups[0]
        out = []
        for _ in range(lanes):
            ga = flatten_group_ask(
                ct, snap, job, tg, count, nodes_sorted=ct.nodes
            )
            if held:  # a job that has allocations: a victim's eval
                ga.job_counts[0] = 1
            out.append(ga)
        return out

    def lone(spec, held, count):
        nonlocal n
        kernel.place(ct, asks(spec, held, count, 1), explain=explain)
        n += 1

    def batched(spec, held, count, lanes):
        nonlocal n
        kernel.place(
            ct, asks(spec, held, count, lanes), decorrelate=True,
            decorrelate_salt=0, overflow=32, explain=explain,
        )
        n += 1

    # a registration: alone, beside its deregistration, beside another
    lone(service, False, service["count"])
    for lanes in (1, 2):
        batched(service, False, service["count"], lanes)
    # a victim's eval asks for what its job is short of: the candidate
    # width doubles from 32 (lone) or 64 (batched) with the count. Victims
    # come from few jobs (binpack lays a job's instances side by side and
    # a service takes neighbouring nodes), so one job can be short of
    # most of what the live services hold
    most = min(per_job, int(config["steady_jobs"]) * service["count"])
    for spec in (holder, cpu_fill):
        count = 8
        while True:
            lone(spec, True, min(count, most))
            # one lane that places and a full pass. The double pass the
            # admission controller's brownout lever makes of a deep queue
            # is not warmed: no pass of three traced windows held more
            # than EVAL_BATCH_SIZE lanes (``compiles_in_window.lat`` is
            # the alarm)
            for lanes in (1, EVAL_BATCH_SIZE):
                batched(spec, True, min(count, most), lanes)
            if count >= most:
                break
            count *= 3
    log(f"warm-up: {n} shaped calls of the placement kernel")


def prefill(server, config: dict, traffic: dict, specs, make_job,
            seed: int, log) -> tuple:
    """Returns the live services ``[(job_id, count)]``, every request
    sent, and the number of services the driver keeps live."""
    fill_block = config["fill"]
    fill = fill_specs(fill_block, fleet_spec(config["fleet"]), seed)
    tail = int(fill_block["tail_jobs"])
    filler = Driver(server, iter(fill), make_job, [], 0, patient=True)
    for kind in ("gpu_holder", "cpu_fill"):
        n = sum(1 for s in fill if s["kind"] == kind)
        in_flight = int(
            fill_block[kind].get("in_flight", traffic["prefill_in_flight"])
        )
        _register_all(filler, max(n - tail, 0), in_flight, f"fill {kind}")
        _register_all(filler, min(tail, n), 1, f"fill {kind} (tail)")
        log(f"pre-fill: {n} {kind} jobs live")

    _warm_kernel_variants(server, config, traffic, log)

    steady = int(config["steady_jobs"])
    services = Driver(server, specs, make_job, [], steady, patient=True)

    def one(deregister: bool, what: str) -> None:
        services.send_register(0.0)
        if deregister:
            services.send_deregister(0.0)
        n_before = len(services.requests)
        services.drain(SETUP_PATIENCE_S)
        bad = [r for r in services.requests[:n_before] if r.ok is False]
        if bad:
            raise SetupFailure(
                f"{what}: {bad[0].kind} {bad[0].job_id}: {bad[0].note}"
            )
        if not settle(server):
            raise SetupFailure(f"{what}: the victims' evals did not settle")

    for _ in range(steady):
        one(False, "pre-fill service")
    log(f"pre-fill: {steady} services placed by evicting")
    for i in range(int(traffic["warm_cycles"])):
        # an arrival's two evals reach the worker in one dequeue or in
        # two: both, for each ask of the cycle (pairs of cycles)
        if i // 2 % 2:
            one(True, "warm-up cycle")
        else:
            _shaped_pass(
                server, services, [next(services.specs)], make_job, 1
            )
            if not settle(server):
                raise SetupFailure("warm-up cycle: evals did not settle")
    log(
        f"warm-up: {traffic['warm_cycles']} cycles; "
        f"{live_allocations(server.store)} allocations live"
    )
    return (
        list(services.live), filler.requests + services.requests, steady,
    )
