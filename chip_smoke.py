"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one pass through the served scheduling path at the size a
deployment would run (10,000 nodes, 25 racks, 3 resource classes), through
the entry points the HTTP/RPC handlers call: ``Server(ServerConfig())``,
``establish_leadership()``, ``register_job`` / ``scale_job`` /
``deregister_job`` / ``update_node_status`` → eval broker → worker → device
pass → plan queue → applier → allocs in the store.

- Phase A: binpack-only service/batch jobs → ``place_closed_form_kernel``.
- Phase B: BASELINE config 3 (rack spread + ssd affinity) → spread kernels.
- Phase C: a scale-up, a deregistration and node failures → the solo path.
- Reference: ``device.parity.run_parity_suite()`` (configs 2/3/4 against
  the stepwise NumPy oracle, bar ≤ 0.5 %), one direct ``PlacementKernel``
  call at the 100 jobs × 1000 allocs shape with the process mesh and with
  the mesh off (rows must be identical), and every registered kernel
  compiled and executed (those the phases did not reach run once on
  ``analysis/jaxlint/exercise.py``'s fleet: "toy shape only").

Any failed check exits non-zero with the reason. Standard output is two
lines: ``{"report": {...}}`` (what was established: kernels, compiles per
phase, memory, cache, parity), then last the result line
``{"ok": true, "device": {"platform", "kind", "count"}}`` with exactly
those keys. It prints no rate and no latency: the deadline block only sets
the longest compile / pass beside the deadline it must stay under. ``__main__`` always demands a TPU; the
functions take sizes so tier-1 can drive them at toy size on the CPU.
"""

import json
import sys
import time

FLEET_NODES = 10_000
PARITY_BAR_PCT = 0.5  # BASELINE's own bar
EVAL_WAIT_S = 600.0  # a phase's first pass may hold a cold compile
ADMISSION_PATIENCE_S = 120.0  # how long a client keeps re-sending
SPREAD_KERNELS = ("place_spread_opv_kernel", "place_spread_chunked_kernel")


_T0 = time.monotonic()


class SmokeFailure(Exception):
    """A check of the smoke failed; the message is the reason."""


def _progress(what: str) -> None:
    """Progress on stderr, stamped with seconds since start: where the
    1200 s budget went, not a result."""
    print(
        f"chip_smoke [{time.monotonic() - _T0:7.1f}s] {what}",
        file=sys.stderr, flush=True,
    )


def check(cond, reason: str) -> None:
    if not cond:
        raise SmokeFailure(reason)


# -- counters the checks read ------------------------------------------------


def _counters() -> dict:
    from nomad_tpu.utils.metrics import global_metrics

    return global_metrics.snapshot()["counters"]


def _traces() -> dict:
    """kernel short name -> {shape that compiled: times}."""
    from nomad_tpu.utils.backend import kernel_profile

    out = {}
    for name, prof in kernel_profile().items():
        shapes: dict = {}
        for ev in prof["recent_traces"]:
            shapes[ev["shape"]] = shapes.get(ev["shape"], 0) + 1
        out[name.rsplit(".", 1)[-1]] = shapes
    return out


def _kernel_of(name: str) -> str:
    """Short name of the kernel a program runs: on one device a
    placement kernel is dispatched inside its outer program
    ``<kernel>_packed`` (device/score.py), which is the kernel as far
    as routing and coverage go."""
    return name.rsplit(".", 1)[-1].removesuffix("_packed")


def _calls() -> dict:
    from nomad_tpu.utils.backend import kernel_profile

    out: dict = {}
    for name, prof in kernel_profile().items():
        short = _kernel_of(name)
        out[short] = out.get(short, 0) + prof["calls"]
    return out


def _since(before: dict, after: dict) -> dict:
    """Non-zero counter deltas between two ``_calls()`` snapshots."""
    return {
        k: n - before.get(k, 0) for k, n in after.items()
        if n > before.get(k, 0)
    }


def _new_traces(before: dict, after: dict) -> dict:
    """Compiles between two ``_traces()`` snapshots, kernel -> shapes."""
    out = {}
    for kernel, shapes in after.items():
        new = {
            shape: n - before.get(kernel, {}).get(shape, 0)
            for shape, n in shapes.items()
            if n > before.get(kernel, {}).get(shape, 0)
        }
        if new:
            out[kernel] = new
    return out


def device_path_failures() -> list[str]:
    """Reasons this run did NOT stay on the device path: a kernel
    breaker tripped or is not closed (its calls finished on the eager
    reference path), or a scoring pass ran while degraded. Read from the
    breaker registry as well as the counters: a ``global_metrics.reset()``
    does not clear a trip."""
    from nomad_tpu.resilience.breaker import snapshot_all
    from nomad_tpu.utils.metrics import global_metrics

    out = []
    for name, b in sorted(snapshot_all().items()):
        if b["trips"] or b["state"] != "closed":
            out.append(
                f"breaker {name}: state={b['state']} trips={b['trips']} "
                f"last_error={b['last_error']}"
            )
    counters = global_metrics.snapshot()["counters"]
    for key in ("fallback_calls", "fallback_passes"):
        n = int(counters.get(f"nomad.resilience.{key}", 0))
        if n:
            out.append(f"nomad.resilience.{key}={n}")
    return out


def check_device_path(server) -> None:
    """The checks that hold after every phase: nothing fell off the
    device path, nothing was swallowed, nothing was nacked or failed."""
    failures = device_path_failures()
    check(not failures, "; ".join(failures))
    c = _counters()
    for key in (
        "worker.swallowed_errors",
        "nomad.worker.batch_kernel_errors",
        "nomad.resilience.eval.deadline_nacks",
    ):
        check(not c.get(key), f"{key} = {c.get(key)} (must be 0)")
    nacked = sum(w.stats["nacked"] for w in server.workers)
    check(nacked == 0, f"{nacked} worker nacks")
    broker = server.eval_broker.counters
    for key in ("nacks", "unack_timeouts"):
        check(broker[key] == 0, f"eval broker {key} = {broker[key]}")
    failed = [e.id for e in server.store.evals() if e.status == "failed"]
    check(not failed, f"{len(failed)} evals ended failed: {failed[:3]}")


def alloc_accounting(server, expected: dict) -> dict:
    """Full alloc accounting for the jobs in ``expected`` (job id ->
    desired count): live allocs + allocs queued on blocked evals (with
    their per-TG failure reasons) must equal the total asked;
    ``unaccounted_allocs`` > 0 is a bug surface, not fine print."""
    placed = sum(
        1
        for a in server.store.allocs()
        if a.job_id in expected and not a.terminal_status()
    )
    blocked = [
        bev
        for bev in server.blocked_evals.captured()
        if bev.job_id in expected
    ]
    blocked_queued = 0
    failed_reasons: dict = {}
    for bev in blocked:
        blocked_queued += sum(bev.queued_allocations.values())
        for metric in bev.failed_tg_allocs.values():
            m = getattr(metric, "metric", metric)
            for reason, cnt in (m.dimension_exhausted or {}).items():
                failed_reasons[f"exhausted:{reason}"] = (
                    failed_reasons.get(f"exhausted:{reason}", 0) + cnt
                )
            for reason, cnt in (m.constraint_filtered or {}).items():
                failed_reasons[f"filtered:{reason}"] = (
                    failed_reasons.get(f"filtered:{reason}", 0) + cnt
                )
    total = sum(expected.values())
    return {
        "placed": placed,
        "total": total,
        "blocked_evals": len(blocked),
        "blocked_queued_allocs": blocked_queued,
        "unaccounted_allocs": total - placed - blocked_queued,
        "failed_tg_reasons": failed_reasons,
    }


def check_store(server, expected: dict) -> dict:
    """From the store: every alloc asked for is placed or queued on a
    blocked eval, and no node's summed alloc resources exceed its
    capacity (the host AllocsFit reference)."""
    from nomad_tpu.structs.resources import allocs_fit

    acct = alloc_accounting(server, expected)
    check(acct["unaccounted_allocs"] == 0, f"unaccounted allocs: {acct}")
    by_node: dict = {}
    for a in server.store.allocs():
        if not a.terminal_status():
            by_node.setdefault(a.node_id, []).append(a)
    for node_id, allocs in by_node.items():
        node = server.store.node_by_id(node_id)
        fits, dim, _used = allocs_fit(node, allocs)
        check(fits, f"node {node_id} over capacity on {dim}")
        check(node.ready(), f"live allocs on node {node_id}, not ready")
    acct["nodes_with_allocs"] = len(by_node)
    return acct


# -- the served path ---------------------------------------------------------


def send(request):
    """One client request (a thunk calling a server entry point). A
    register or scale the admission controller defers or sheds (HTTP:
    429 + Retry-After) is re-sent after the delay it names, as a client
    would — a cold compile inside a pass is a latency spike the
    controller answers with exactly that."""
    from nomad_tpu.server.admission import AdmissionRejected

    deadline = time.monotonic() + ADMISSION_PATIENCE_S
    while True:
        try:
            return request()
        except AdmissionRejected as e:
            if time.monotonic() >= deadline:
                raise
            time.sleep(e.retry_after)


def _submit(server, requests, batch=None) -> None:
    """Send ``requests`` (thunks calling a server entry point) and wait
    for the broker to drain. With ``batch`` the workers are held until
    every request is enqueued, so the pass that follows carries exactly
    that many evals — the way warm-up reaches each compiled G bucket."""
    from nomad_tpu.server.admission import AdmissionRejected
    from nomad_tpu.server.worker import EVAL_BATCH_SIZE

    if batch is not None:
        for w in server.workers:
            w.pause()
        time.sleep(0.5)  # an idle worker's 0.2 s dequeue returns empty
        if batch > EVAL_BATCH_SIZE:
            # the brownout lever is what widens a pass past the base
            # batch size under load; pin it for this one pass
            server.admission.force_level("brownout", duration_s=5.0)
    for request in requests:
        try:
            send(request)  # re-sends a deferred request, as a client would
        except AdmissionRejected as e:
            raise SmokeFailure(f"request still rejected: {e}") from e
    if batch is not None:
        for w in server.workers:
            w.resume()
    check(
        server.wait_for_evals(timeout=EVAL_WAIT_S),
        "wait_for_evals timed out: broker not drained",
    )


def _warm_batches(server) -> tuple:
    """The G buckets a burst can reach: a straggler alone, the base
    batch, and the brownout-widened batch."""
    from nomad_tpu.server.worker import EVAL_BATCH_SIZE

    wide = EVAL_BATCH_SIZE * int(server.admission.brownout_batch_factor)
    return (1, EVAL_BATCH_SIZE, wide)


def _warm_jobs(tag: str, g: int, per_job: int, spread_affinity: bool):
    """Throw-away jobs for one warm-up pass of ``g`` evals. A straggler
    alone in a pass (g = 1) gets the J bucket of its own ask, so that
    bucket is warmed once per ask class; in a wider pass the largest
    ask sets J for everyone."""
    from nomad_tpu.mock import JOB_CPU_CHOICES, make_job

    seeds = iter(range(10_000_000 + 1000 * g, 10_000_000 + 1000 * (g + 1)))
    if g > 1:
        return [[
            make_job(f"warm-{tag}-{g}-{i}", next(seeds), per_job,
                     spread_affinity)
            for i in range(g)
        ]]
    by_cpu: dict = {}
    while len(by_cpu) < len(JOB_CPU_CHOICES):
        job = make_job(
            f"warm-{tag}-1-{len(by_cpu)}", next(seeds), per_job,
            spread_affinity,
        )
        by_cpu.setdefault(job.task_groups[0].tasks[0].resources.cpu, job)
    return [[job] for job in by_cpu.values()]


def run_registration_phase(
    server, tag: str, n_jobs: int, per_job: int, spread_affinity: bool,
    expected: dict,
) -> dict:
    """Warm every G bucket with throw-away jobs of the phase's own
    shape, drain them, then register the phase's jobs in one burst."""
    from nomad_tpu.mock import make_job

    warm_ids = []
    for g in _warm_batches(server):
        for jobs in _warm_jobs(tag, g, per_job, spread_affinity):
            warm_ids += [j.id for j in jobs]
            _submit(
                server,
                [lambda j=j: server.register_job(j) for j in jobs],
                batch=g,
            )
    _submit(
        server,
        [lambda i=i: server.deregister_job("default", i) for i in warm_ids],
    )
    warm_traces, warm_calls = _traces(), _calls()
    _progress(f"phase {tag}: warm-up drained")
    jobs = [
        make_job(f"{tag}-{j}", j, per_job, spread_affinity)
        for j in range(n_jobs)
    ]
    expected.update({j.id: per_job for j in jobs})
    _submit(server, [lambda j=j: server.register_job(j) for j in jobs])
    _progress(f"phase {tag}: {n_jobs} jobs x {per_job} allocs drained")
    check_device_path(server)
    return {
        "jobs": n_jobs,
        "allocs_per_job": per_job,
        "kernel_calls": _since(warm_calls, _calls()),
        "compiles_after_warmup": _new_traces(warm_traces, _traces()),
        "accounting_all_jobs_so_far": check_store(server, expected),
    }


def run_phase_a(server, n_jobs: int, per_job: int, expected: dict) -> dict:
    """Binpack only (BASELINE config-2 semantics on the config-3 fleet)."""
    out = run_registration_phase(server, "a", n_jobs, per_job, False, expected)
    calls = out["kernel_calls"]
    check(
        calls.get("place_closed_form_kernel", 0) > 0
        and not any(calls.get(k) for k in SPREAD_KERNELS),
        f"phase A must route to place_closed_form_kernel only: {calls}",
    )
    return out


def run_phase_b(server, n_jobs: int, per_job: int, expected: dict) -> dict:
    """BASELINE config 3: rack spread weight 50 + ssd affinity weight 50."""
    out = run_registration_phase(server, "b", n_jobs, per_job, True, expected)
    calls = out["kernel_calls"]
    check(
        any(calls.get(k) for k in SPREAD_KERNELS),
        f"phase B must route to the spread kernels: {calls}",
    )
    return out


def run_phase_c(server, down_nodes: int, expected: dict) -> dict:
    """A few requests off the fresh-registration path: one job scaled
    up, one deregistered, ``down_nodes`` nodes marked down so their
    allocs are replaced. Each kind is sent once as warm-up first."""
    jobs = sorted(j for j in expected if j.startswith("b-"))
    check(len(jobs) >= 4, "phase C needs four phase-B jobs")

    def scale(job_id):
        job = server.store.job_by_id("default", job_id)
        group = job.task_groups[0]
        expected[job_id] = group.count + 10
        server.scale_job("default", job_id, group.name, group.count + 10)

    def deregister(job_id):
        expected[job_id] = 0
        server.deregister_job("default", job_id)

    def busiest_nodes(n):
        load: dict = {}
        for a in server.store.allocs():
            if not a.terminal_status():
                load[a.node_id] = load.get(a.node_id, 0) + 1
        return sorted(load, key=lambda nid: (-load[nid], nid))[:n]

    def fail(node_ids):
        for nid in node_ids:
            server.update_node_status(nid, "down")

    _submit(server, [lambda: scale(jobs[0])])
    _submit(server, [lambda: deregister(jobs[1])])
    _submit(server, [lambda: fail(busiest_nodes(1))])

    def solo_evals():  # evals that ran alone, dequeued so or dropped to it
        c = _counters()
        return int(
            c.get("nomad.worker.solo_evals", 0)
            + c.get("nomad.worker.batch_single_fallbacks", 0)
        )

    solo0 = solo_evals()
    warm_traces, warm_calls = _traces(), _calls()
    _submit(server, [lambda: scale(jobs[2])])
    _submit(server, [lambda: deregister(jobs[3])])
    down = busiest_nodes(down_nodes)
    lost = sum(
        1
        for nid in down
        for a in server.store.allocs_by_node(nid)
        if not a.terminal_status()
    )
    _submit(server, [lambda: fail(down)])
    _progress("phase c: scale, deregister and node failures drained")
    check_device_path(server)
    return {
        "scaled": jobs[2],
        "deregistered": jobs[3],
        "nodes_down": len(down),
        "allocs_lost_on_down_nodes": lost,
        "solo_path_evals": solo_evals() - solo0,
        "kernel_calls": _since(warm_calls, _calls()),
        "compiles_after_warmup": _new_traces(warm_traces, _traces()),
        "accounting_all_jobs_so_far": check_store(server, expected),
    }


def run_phases(
    n_nodes: int, jobs_a: int, jobs_b: int, per_job: int, down_nodes: int
) -> dict:
    """Phases A–C against one live server on a seeded ``n_nodes`` fleet."""
    from nomad_tpu.mock import seed_fleet
    from nomad_tpu.server import Server, ServerConfig

    server = Server(ServerConfig(num_workers=1, num_batch_workers=1))
    server.establish_leadership()
    try:
        seed_fleet(server, n_nodes)
        _progress(f"fleet of {n_nodes} nodes seeded")
        expected: dict = {}
        report = {
            "fleet": {"nodes": n_nodes, "racks": 25, "resource_classes": 3},
            "phase_a": run_phase_a(server, jobs_a, per_job, expected),
            "phase_b": run_phase_b(server, jobs_b, per_job, expected),
            "phase_c": run_phase_c(server, down_nodes, expected),
            "mesh": mesh_report(server),
        }
    finally:
        server.shutdown()
    c = _counters()
    report["batch"] = {
        key: int(c.get(f"nomad.worker.{key}", 0))
        for key in (
            "batch_evals_completed", "batch_conflict_fallbacks",
            "batch_repair_fallbacks", "batch_commit_fallbacks",
            "batch_single_fallbacks", "solo_evals",
        )
    }
    return report


# -- mesh, memory, deadlines -------------------------------------------------


def mesh_report(server) -> dict:
    """Mesh shape and source, and which devices hold the shards of the
    two node-axis buffers every pass reads (``capacity``, ``used``)."""
    from nomad_tpu.device.score import PlacementKernel, used_device
    from nomad_tpu.utils.backend import get_mesh, shard_drops

    cfg = get_mesh()
    ct = server.device_cache.tensors(server.store.snapshot())
    out = dict(cfg.describe())
    for key, arr in (
        ("capacity", PlacementKernel._capacity_dev(ct, cfg)),
        ("used", used_device(ct, ct.used, cfg)),
    ):
        devices = sorted(sh.device.id for sh in arr.addressable_shards)
        out[f"{key}_shard_devices"] = devices
        out[f"{key}_shard_rows"] = sorted(
            {int(sh.data.shape[0]) for sh in arr.addressable_shards}
        )
        if cfg.active:
            check(
                len(devices) == cfg.dp * cfg.mp,
                f"{key} has shards on devices {devices}, mesh is "
                f"{cfg.dp}x{cfg.mp}",
            )
    # axes shard_put replicated because the mesh does not divide them
    out["replicated_axes"] = shard_drops()
    return out


def memory_report() -> dict:
    """Per-device ``memory_stats()``. On an accelerator a device whose
    limit cannot be read, or that never held a byte, is an error (the
    CPU backend reports no stats at all)."""
    import jax

    out = {}
    for d in jax.devices():
        stats = d.memory_stats()
        if d.platform != "cpu":
            check(
                stats is not None and stats.get("bytes_limit"),
                f"device {d.id}: memory_stats() has no bytes_limit",
            )
            check(
                stats.get("peak_bytes_in_use", 0) > 0,
                f"device {d.id}: peak_bytes_in_use is 0 — nothing ran there",
            )
        if stats is not None:
            out[str(d.id)] = {
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            }
    return out


def kernel_report() -> tuple:
    """Per kernel: calls, traces and the longest single trace+compile,
    set beside the deadlines it ran under."""
    from nomad_tpu.resilience.breaker import snapshot_all
    from nomad_tpu.server.server import ServerConfig
    from nomad_tpu.utils.backend import kernel_profile
    from nomad_tpu.utils.metrics import global_metrics

    breakers = snapshot_all()
    samples = global_metrics.snapshot()["samples"]
    kernels = {}
    for name, prof in sorted(kernel_profile().items()):
        short = name.rsplit(".", 1)[-1]
        kernels[short] = {
            "calls": prof["calls"],
            "traces": prof["traces"],
            "longest_compile_s": max(
                (ev["wall_s"] for ev in prof["recent_traces"]), default=0.0
            ),
            "compile_deadline_s": breakers[name]["compile_deadline_s"],
            "longest_warm_dispatch_s": round(
                samples.get(f"nomad.kernel.{short}.dispatch", {}).get(
                    "max_ms", 0.0
                ) / 1000.0, 4,
            ),
            "execute_deadline_s": breakers[name]["execute_deadline_s"],
        }
    deadlines = {
        "longest_pass_s": round(
            samples.get("nomad.worker.invoke_scheduler", {}).get(
                "max_ms", 0.0
            ) / 1000.0, 3,
        ),
        "eval_deadline_s": ServerConfig().eval_deadline,
    }
    return kernels, deadlines


# -- reference checks --------------------------------------------------------


def run_parity(small: bool) -> dict:
    from nomad_tpu.device.parity import run_parity_suite

    suite = run_parity_suite(small=small)
    for name, row in suite.items():
        check(
            abs(row["score_delta_pct"]) <= PARITY_BAR_PCT,
            f"parity {name}: |score delta| {row['score_delta_pct']} % "
            f"> {PARITY_BAR_PCT} %",
        )
    return suite


def run_direct_place(n_nodes: int, n_jobs: int, count: int) -> dict:
    """One direct ``PlacementKernel.place`` on a seeded input under the
    process mesh, and again with the mesh off. The node rows must be
    identical (trivially so on one device, where both are degenerate);
    ``run_smoke`` judges that last, after everything else is reported."""
    import numpy as np

    from nomad_tpu.device.score import PlacementKernel
    from nomad_tpu.mock import build_asks, build_cluster
    from nomad_tpu.utils.backend import MeshConfig, get_mesh

    ct = build_cluster(n_nodes)
    asks = build_asks(ct, n_jobs, count)
    on = PlacementKernel("binpack").place(ct, asks)
    off = PlacementKernel(
        "binpack", mesh=MeshConfig(None, 1, 1, "chip_smoke:off")
    ).place(ct, asks)
    placed = sum(int((r.node_rows >= 0).sum()) for r in on)
    check(placed > 0, "direct place call placed nothing")
    out = {
        "shape": f"{n_jobs} jobs x {count} allocs vs {n_nodes} nodes",
        "mesh_on": get_mesh().active,
        "placed": placed,
        "asked": n_jobs * count,
        "lanes": len(asks),
        "lanes_differing": 0,
    }
    # how far apart the two placements are, lane by lane: positions
    # whose node differs, lanes that still chose the same multiset of
    # nodes (order only), and the largest score difference anywhere
    positions = same_nodes = 0
    score_gap = 0.0
    for a, b in zip(on, off):
        diff = int((a.node_rows != b.node_rows).sum())
        if diff:
            out["lanes_differing"] += 1
            positions += diff
            same_nodes += bool(
                np.array_equal(np.sort(a.node_rows), np.sort(b.node_rows))
            )
        both = (a.node_rows >= 0) & (b.node_rows >= 0)
        if both.any():
            score_gap = max(
                score_gap,
                float(np.abs(a.scores[both] - b.scores[both]).max()),
            )
    out["positions_differing"] = positions
    out["differing_lanes_with_same_node_multiset"] = same_nodes
    out["max_abs_score_difference"] = score_gap
    return out


def run_compile_coverage() -> dict:
    """Every production kernel compiles and executes; those nothing
    above reached run once on the jaxlint exercise fleet."""
    from nomad_tpu.analysis.jaxlint.exercise import exercise_fleet
    from nomad_tpu.analysis.jaxlint.retracer import production_kernels

    reached = {k for k, n in _calls().items() if n}
    registry = production_kernels(exercise_fleet())
    shorts = sorted({_kernel_of(e.short) for e in registry.values()})
    calls = _calls()
    missing = [k for k in shorts if not calls.get(k)]
    check(not missing, f"kernels never executed: {missing}")
    return {
        "kernels": len(shorts),
        "real_shape": sorted(set(shorts) & reached),
        "toy_shape_only": sorted(set(shorts) - reached),
    }


# -- entry -------------------------------------------------------------------


def run_smoke(
    n_nodes: int = FLEET_NODES, *, jobs_a: int = 32, jobs_b: int = 100,
    per_job: int = 250, down_nodes: int = 8, parity_small: bool = False,
    direct: tuple = (100, 1000), report: dict | None = None,
) -> dict:
    """The whole smoke on whatever backend jax initialised; raises
    ``SmokeFailure`` on the first failed check. A caller that wants what
    was established before a failure passes the ``report`` to fill."""
    from nomad_tpu.utils.backend import compile_cache_stats

    report = {} if report is None else report
    report.update(run_phases(n_nodes, jobs_a, jobs_b, per_job, down_nodes))
    report["parity"] = run_parity(parity_small)
    _progress("parity suite done")
    report["coverage"] = run_compile_coverage()
    _progress("compile coverage done")
    direct_place = report["direct_place"] = run_direct_place(n_nodes, *direct)
    _progress("direct place (mesh on / off) done")
    report["kernels"], report["deadlines"] = kernel_report()
    report["memory"] = memory_report()
    report["compile_cache"] = compile_cache_stats()
    c = _counters()
    report["resilience"] = {
        key: int(c.get(f"nomad.resilience.{key}", 0))
        for key in ("trips_total", "fallback_calls", "fallback_passes")
    }
    report["swallowed_errors"] = int(c.get("worker.swallowed_errors", 0))
    # requests the admission controller deferred or shed (the smoke
    # re-sent them after Retry-After) while a cold compile held a pass
    report["admission"] = {
        key: int(c.get(f"nomad.admission.{key}_total", 0))
        for key in ("deferred", "shed")
    }
    # the one check only a mesh can fail is judged last, so a four-chip
    # run has reported everything else by then
    check(
        direct_place["lanes_differing"] == 0,
        "mesh-on rows differ from mesh-off rows in {lanes_differing} of "
        "{lanes} lanes ({positions_differing} of {asked} positions; "
        "{differing_lanes_with_same_node_multiset} of those lanes chose "
        "the same nodes in another order; max |score difference| "
        "{max_abs_score_difference:.3g})".format(**direct_place),
    )
    return report


def result_lines(ok: bool, device: dict, report: dict, failed=None) -> list:
    """What ``main`` writes to stdout: the report on one line, then the
    result line, whose keys are exactly ``ok`` and ``device``."""
    detail = {"report": report} if failed is None else {
        "failed": failed, "report": report,
    }
    return [json.dumps(detail), json.dumps({"ok": ok, "device": device})]


def main() -> int:
    import jax

    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; jax initialised {len(dev)} "
            f"{dev[0].platform} device(s)",
            file=sys.stderr,
        )
        return 2
    device = {
        "platform": dev[0].platform,
        "kind": dev[0].device_kind,
        "count": len(dev),
    }
    report: dict = {}
    failed = None
    try:
        run_smoke(report=report)
    except SmokeFailure as e:
        failed = str(e)
        print(f"chip_smoke: FAILED: {failed}", file=sys.stderr)
    print("\n".join(result_lines(failed is None, device, report, failed)))
    return 0 if failed is None else 1


if __name__ == "__main__":
    sys.exit(main())
