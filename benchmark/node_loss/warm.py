"""Set-up through the served path: the window's placement shapes, c2m-10k's
pre-fill (``benchmark/warm.py``: 200 jobs of 250, ``prefill_in_flight`` at a
time), then ``warm_failures`` failures as the window has them, at the
window's spacing, each recovered and its rack returned before the window
opens. The failures reach the batched pass whose members stop the lost
allocations and place their replacements, and the solo pass of an eval that
finds nothing to do; which of the scan's compiled forms a storm reaches
follows how its evals meet in the passes, so ``warm_shapes`` reaches each
of them on purpose.

The expiry of a rack's timers in one sweep is ``NodeHeartbeater.expire``. A
program from before that call cannot run the deployment: importing this
part then fails, and ``run.py`` ends the run there, before it takes the
device."""

from __future__ import annotations

import time

from benchmark import driver as plain_driver
from benchmark import warm as base
from benchmark.gen.arrivals import arrival_times
from benchmark.gen.jobs import plain_spec
from benchmark.node_loss.driver import Driver, clock
from benchmark.warm import SETUP_PATIENCE_S, SetupFailure, settle_admission
from nomad_tpu.server.heartbeat import NodeHeartbeater

if not hasattr(NodeHeartbeater, "expire"):
    raise ImportError(
        "the program has no NodeHeartbeater.expire: a rack's timers cannot "
        "run out in one sweep"
    )

__all__ = ["warm_shapes", "prefill", "settle_admission"]


WARM_COUNT = 8  # a warm job's allocations before it is scaled up
# instances a scale-up places at once: the replacements a storm's eval
# places, a rack's share of a job (9 to 22) or, where the next failure came
# before the job's eval ran, two racks' (up to 32: the exact scan's
# bound): 32 and 64 steps of the scan on the solo path, J 16 and, for the
# 250 MHz ask, 24 and 32
WARM_PLACED = (10, 20, 30)


def _scaled(server, evals: list) -> None:
    """Wait for ``evals`` (scale-ups) to complete with every placement."""
    deadline = time.monotonic() + SETUP_PATIENCE_S
    for ev in evals:
        while True:
            got = server.store.eval_by_id(ev.id)
            if got is not None and got.status in ("complete", "failed",
                                                  "canceled"):
                break
            if time.monotonic() > deadline:
                raise SetupFailure(f"warm-up scale {ev.job_id} never ended")
            time.sleep(0.02)
        job = server.store.job_by_id("default", ev.job_id)
        live = sum(1 for a in server.store.allocs_by_job("default", job.id)
                   if not a.terminal_status())
        want = sum(tg.count for tg in job.task_groups)
        if got.status != "complete" or live != want:
            raise SetupFailure(
                f"warm-up scale {ev.job_id}: eval {got.status}, {live} of "
                f"{want} allocations")


def warm_shapes(server, traffic: dict, make_job, log) -> list:
    """The exact scan's compiled forms a storm's placing evals reach, each
    once per ask of the cycle: a job's new instances placed beside its own
    live allocations (Job.Scale up by ``WARM_PLACED``), alone on the solo
    path (G = 1), alone in a batched pass beside an eval with nothing to
    place (G = 1 with the tie-break jitter) and two in a batched pass
    (G = 16). Two warm jobs an ask are registered first and deregistered
    after; returns their requests."""
    shape = dict(traffic["job"], count=WARM_COUNT)
    by_cpu: dict = {}
    for entry in traffic["cycle"]:
        by_cpu.setdefault(int(entry["cpu"]), entry)
    driver = plain_driver.Driver(server, iter(()), make_job, [], 0,
                                 patient=True)
    pairs = []
    for cpu, entry in sorted(by_cpu.items()):
        pair = []
        for k in range(2):
            spec = plain_spec(f"warm-loss-{cpu}-{k}", entry, shape)
            driver._send(plain_driver.Request(
                "register", spec["id"], spec["count"], clock()),
                make_job(spec))
            base._drain(driver, "warm-up registration")
            pair.append([spec["id"], WARM_COUNT])
        pairs.append((cpu, pair))

    def scale(members: list, more: int) -> list:
        evals = []
        for m in members:
            job = server.store.job_by_id("default", m[0])
            m[1] += more
            evals.append(server.scale_job(
                "default", m[0], job.task_groups[0].name, m[1]))
        return evals

    for more in WARM_PLACED:
        for cpu, (a, b) in pairs:
            _scaled(server, scale([a], more))
            for beside in (0, more):  # one lane, then two, in one pass
                for w in server.workers:
                    w.pause()
                time.sleep(0.5)  # an idle worker's 0.2 s dequeue returns
                try:
                    evals = scale([a], more) + scale([b], beside)
                finally:
                    for w in server.workers:
                        w.resume()
                _scaled(server, evals)
            log(f"warm-up: {more} placed at once, cpu={cpu}")
    while driver.live:
        driver.send_deregister(0.0)
    base._drain(driver, "warm-up deregistration")
    _warm_double_pass(server, traffic, log)
    return driver.requests


def _warm_double_pass(server, traffic: dict, log) -> None:
    """A storm's backlog sends the admission controller into brownout,
    whose lever doubles the worker's dequeue: a batched pass of up to
    ``EVAL_BATCH_SIZE`` x ``brownout_batch_factor`` members, G = 32. Which
    J bucket such a pass reaches follows which jobs' evals meet in it (a
    job's share of the rack: 9 to 26), so the warm failures reach them by
    luck; two chip runs of six compiled G = 32 at J 24 or 32 in the window.
    This compiles the exact scan at G = 32 for each count of
    ``WARM_PLACED``, with the forms a storm's member has: the job holds
    allocations elsewhere, affinity scores, the tie-break jitter. The asks
    are flattened from a job nobody registers; nothing is planned or
    committed (``gpu_preempt/warm.py`` warms its variants the same way)."""
    from benchmark.gen.jobs import make_job
    from nomad_tpu.device import flatten_group_ask
    from nomad_tpu.scheduler.algorithms import make_kernel
    from nomad_tpu.server.worker import EVAL_BATCH_SIZE

    snap = server.store.snapshot()
    ct = server.device_cache.tensors(snap)
    kernel = make_kernel(snap.scheduler_config().scheduler_algorithm)
    lanes = EVAL_BATCH_SIZE * int(server.admission.brownout_batch_factor)
    # the smaller ask: J follows the count to the exact scan's bound
    entry = min(traffic["cycle"], key=lambda e: int(e["cpu"]))
    job = make_job(plain_spec("warm-loss-double", entry, traffic["job"]))
    tg = job.task_groups[0]
    for count in WARM_PLACED:
        asks = []
        for _ in range(lanes):
            ga = flatten_group_ask(ct, snap, job, tg, count,
                                   nodes_sorted=ct.nodes)
            ga.job_counts[0] = 1
            asks.append(ga)
        kernel.place(ct, asks, decorrelate=True, decorrelate_salt=0,
                     overflow=32, explain=False)
    log(f"warm-up: {lanes} lanes at once, {len(WARM_PLACED)} counts")


def prefill(server, config: dict, traffic: dict, specs, make_job,
            seed: int, log) -> tuple:
    """Returns what the window's driver starts from (the failures set-up
    sent, the racks, the live allocations), every request sent, and the
    number of live jobs."""
    live, requests, n_jobs = base.prefill(
        server, config, traffic, specs, make_job, seed, log
    )
    cycles = int(traffic["warm_failures"])
    rate = float(traffic["arrivals"]["rate_per_s"])
    due = arrival_times(traffic, seed, 3.0 * cycles / rate + 60.0)[:cycles]
    start = {
        "failures_sent": 0,
        "racks": int(config["fleet"]["racks"]),
        "live_allocs": sum(count for _job, count in live),
    }
    driver = Driver(
        server, iter(()), make_job, start, n_jobs, patient=True,
        traffic=traffic, seed=seed,
    )
    # the window's arrivals and their recovery, as ``Driver.run_open``
    # runs them, without a window to open
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
    driver.drain(120.0)
    bad = [r for r in driver.requests if r.ok is not True]
    if bad:
        raise SetupFailure(
            f"warm-up failures: {len(bad)} of {len(driver.requests)} jobs "
            f"did not recover ({bad[0].job_id}: {bad[0].note})"
        )
    lost = sum(r.count for r in driver.requests)
    log(f"warm-up: {cycles} racks down, {lost} allocations lost and "
        f"replaced, every rack back")
    # the window goes on in the order where these failures left it
    start["failures_sent"] = len(driver.failures)
    return start, requests + driver.requests, n_jobs
