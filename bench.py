"""Benchmark: the BASELINE.md metric set, on one device.

Two measurements, both against a 10k-node synthetic cluster:

1. **Kernel**: the batched greedy placement kernel planning 100 jobs ×
   1000 instances = 100,000 allocations in one resident-tensor pass —
   the north star (BASELINE.md: 100k allocs vs 10k nodes < 1 s on a
   v5e-8 ⇒ 12.5k allocs/s per-chip share; ``vs_baseline`` is measured ÷
   12,500, ≥ 1.0 beats the target).

2. **End-to-end** (BASELINE config-3 shape): mixed service/batch jobs
   with spread + affinity driven through the real control plane —
   register_job → eval broker → workers → resident device cache →
   placement kernel → plan queue → serialized applier → FSM — reporting
   evaluations/sec and the plan-apply p99 read from the metrics registry
   (the ``nomad.plan.*`` timers, plan_apply.go:185,370).

Reference comparison: the Go scheduler walks O(allocs × log₂ nodes ×
iterator stages) sequentially per worker (scheduler/stack.go:83-90,
rank.go:193-527); its micro-bench grid is scheduler/benchmarks/
benchmarks_test.go:71-124.

Prints exactly ONE JSON line.
"""

import json
import os
import sys
import time

import numpy as np


def device_block() -> dict:
    """The device every number in this run came from, as jax reports it
    (the run uses whatever backend jax initialises; nothing here falls
    back to another one)."""
    import jax

    dev = jax.devices()
    return {
        "platform": dev[0].platform,
        "device_kind": dev[0].device_kind,
        "device_count": len(dev),
    }


def device_path_failures() -> list[str]:
    """Reasons this run's numbers are NOT device numbers: a kernel
    breaker tripped or is not closed (its calls finished on the eager
    reference path), or a scoring pass ran while degraded. Read from the
    breaker registry as well as the counters — ``global_metrics.reset()``
    between warm-up and the timed window does not clear a trip."""
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


def e2e_failures(e2e: dict) -> list[str]:
    out = []
    if not e2e["drained"]:
        out.append("end_to_end: broker not drained")
    if e2e["unaccounted_allocs"] > 0:
        out.append(
            f"end_to_end: {e2e['unaccounted_allocs']} unaccounted allocs"
        )
    return out


def build_cluster(n_nodes: int, seed: int = 42):
    """Synthetic heterogeneous cluster as resident device tensors
    (4/8/16-core classes, 3 datacenters), bypassing the Python struct
    walk — mirrors the design's steady state where device arrays are a
    derived cache refreshed incrementally (SURVEY.md §7 'latency floor')."""
    from nomad_tpu.device.flatten import ClusterTensors, node_bucket

    rng = np.random.default_rng(seed)
    pn = node_bucket(n_nodes)
    classes = rng.integers(0, 3, size=n_nodes)
    cpu = np.choose(classes, [4000, 8000, 16000]).astype(np.float32)
    mem = np.choose(classes, [8192, 16384, 32768]).astype(np.float32)
    capacity = np.zeros((pn, 4), dtype=np.float32)
    capacity[:n_nodes, 0] = cpu
    capacity[:n_nodes, 1] = mem
    capacity[:n_nodes, 2] = 100 * 1024
    capacity[:n_nodes, 3] = 1000
    used = np.zeros_like(capacity)
    # pre-existing load: 0-40% of cpu/mem
    load = rng.uniform(0.0, 0.4, size=(n_nodes, 1)).astype(np.float32)
    used[:n_nodes, :2] = capacity[:n_nodes, :2] * load
    ready = np.zeros(pn, dtype=bool)
    ready[:n_nodes] = True
    return ClusterTensors(
        node_ids=[f"node-{i}" for i in range(n_nodes)],
        index=1,
        num_nodes=n_nodes,
        capacity=capacity,
        used=used,
        ready=ready,
        dc_ids=np.pad(rng.integers(0, 3, n_nodes).astype(np.int32), (0, pn - n_nodes)),
        class_ids=np.pad(classes.astype(np.int32), (0, pn - n_nodes)),
        dc_vocab={"dc1": 0, "dc2": 1, "dc3": 2},
        class_vocab={"small": 0, "medium": 1, "large": 2},
        class_rep=[0, 1, 2],
        node_row={f"node-{i}": i for i in range(n_nodes)},
    )


def build_asks(ct, n_jobs: int, count_per_job: int, seed: int = 7):
    from nomad_tpu.device.flatten import GroupAsk

    rng = np.random.default_rng(seed)
    pn = ct.padded_n
    asks = []
    for j in range(n_jobs):
        cpu = float(rng.choice([250, 500, 1000]))
        mem = float(rng.choice([256, 512, 1024]))
        asks.append(
            GroupAsk(
                job_id=f"job-{j}",
                tg_name="web",
                count=count_per_job,
                desired_total=count_per_job,
                ask=np.array([cpu, mem, 300.0, 0.0], dtype=np.float32),
                eligible=ct.ready.copy(),
                job_counts=np.zeros(pn, dtype=np.int32),
                penalty_nodes=np.zeros(pn, dtype=bool),
                affinity_scores=np.zeros(pn, dtype=np.float32),
                has_affinities=False,
                distinct_hosts=False,
            )
        )
    return asks


def bench_kernel(n_nodes: int, n_jobs: int, count: int) -> dict:
    from nomad_tpu.device.score import PlacementKernel

    ct = build_cluster(n_nodes)
    asks = build_asks(ct, n_jobs, count)
    kernel = PlacementKernel("binpack")

    # warmup: compile the shape bucket
    kernel.place(ct, asks)

    t0 = time.perf_counter()
    results = kernel.place(ct, asks)
    elapsed = time.perf_counter() - t0

    placed = sum(int((r.node_rows >= 0).sum()) for r in results)
    return {
        "placed": placed,
        "total": n_jobs * count,
        "elapsed_s": round(elapsed, 4),
        "allocs_per_sec": round(placed / elapsed, 1) if elapsed > 0 else 0.0,
    }


def bench_degraded(n_nodes: int = 1_000, n_jobs: int = 8, count: int = 250) -> dict:
    """Kernel throughput with every breaker forced open: the whole pass
    routes through the eager CPU/reference scoring path (what the cluster
    sustains while a tripped kernel waits out its probe backoff). The
    delta vs the jitted headline is the cost of degraded mode, measured
    on a deliberately small shape so it doesn't dominate bench runtime."""
    from nomad_tpu.device.score import PlacementKernel
    from nomad_tpu.resilience.breaker import set_forced_open
    from nomad_tpu.utils.metrics import global_metrics

    ct = build_cluster(n_nodes)
    asks = build_asks(ct, n_jobs, count)
    kernel = PlacementKernel("binpack")
    kernel.place(ct, asks)  # warm the jitted path first (fair baseline)
    set_forced_open(True)
    try:
        t0 = time.perf_counter()
        results = kernel.place(ct, asks)
        elapsed = time.perf_counter() - t0
    finally:
        set_forced_open(False)
    placed = sum(int((r.node_rows >= 0).sum()) for r in results)
    snap = global_metrics.snapshot()["counters"]
    return {
        "mode": "breakers forced open -> eager reference path",
        "placed": placed,
        "total": n_jobs * count,
        "elapsed_s": round(elapsed, 4),
        "allocs_per_sec": round(placed / elapsed, 1) if elapsed > 0 else 0.0,
        "fallback_calls": int(snap.get("nomad.resilience.fallback_calls", 0)),
        "fallback_passes": int(snap.get("nomad.resilience.fallback_passes", 0)),
    }


def bench_explain(
    n_nodes: int = 5_000, n_lanes: int = 16, count: int = 250,
    repeats: int = 3,
) -> dict:
    """Explain-seam overhead gate: the config-3 inner shape (n_lanes
    concurrent evals x ``count`` allocs) with score provenance on vs
    off, through the same place → repair → finalize sequence the worker
    batch path runs. Explanations are host-side NumPy reconstruction
    (obs/explain.py) — no new jitted program exists in either mode — so
    the budget is the host-side bookkeeping only; gated at <=5%."""
    from nomad_tpu.device.score import PlacementKernel, repair_batch_conflicts
    from nomad_tpu.obs.explain import finalize_explanations

    kernel = PlacementKernel("binpack")

    def one_pass(explain: bool) -> float:
        ct = build_cluster(n_nodes)
        asks = build_asks(ct, n_lanes, count)
        t0 = time.perf_counter()
        results = kernel.place(ct, asks, explain=explain)
        repair_batch_conflicts(
            ct, asks, results, algorithm_spread=False
        )
        if explain:
            finalize_explanations(ct, asks, results)
        return time.perf_counter() - t0

    one_pass(False)  # warmup: compile the shape bucket
    off = min(one_pass(False) for _ in range(repeats))
    on = min(one_pass(True) for _ in range(repeats))
    overhead = (on - off) / off if off > 0 else 0.0
    return {
        "nodes": n_nodes,
        "lanes": n_lanes,
        "count": count,
        "explain_off_s": round(off, 4),
        "explain_on_s": round(on, 4),
        "overhead_frac": round(overhead, 4),
        "budget_frac": 0.05,
        "ok": overhead <= 0.05,
    }


def bench_kernel_spread(
    n_nodes: int, n_lanes: int = 16, count: int = 250, racks: int = 25
) -> dict:
    """Kernel-only headline for the spread-coupled path (the config-3
    inner shape): n_lanes concurrent evals, each placing ``count``
    instances under an even-mode rack spread, through the one-per-value
    chunked kernel + host conflict repair."""
    from nomad_tpu.device.flatten import ValueBlocks
    from nomad_tpu.device.score import (
        BLOCK_EVEN_SPREAD,
        PlacementKernel,
        repair_batch_conflicts,
    )

    ct = build_cluster(n_nodes)
    pn = ct.padded_n
    rack_ids = np.pad(
        (np.arange(n_nodes) % racks).astype(np.int32),
        (0, pn - n_nodes),
        constant_values=-1,
    )
    asks = build_asks(ct, n_lanes, count)
    for a in asks:
        a.blocks = ValueBlocks(
            value_ids=rack_ids[None, :],
            counts0=np.zeros((1, racks), dtype=np.float32),
            desired=np.full((1, racks), -1.0, dtype=np.float32),
            caps=np.full((1, racks), np.inf, dtype=np.float32),
            weights=np.ones(1, dtype=np.float32),
            kinds=np.array([BLOCK_EVEN_SPREAD], dtype=np.int32),
        )
    kernel = PlacementKernel("binpack")
    kernel.place(ct, asks, decorrelate=True, overflow=32)  # warmup

    t0 = time.perf_counter()
    results = kernel.place(ct, asks, decorrelate=True, overflow=32)
    ok = repair_batch_conflicts(ct, asks, results)
    elapsed = time.perf_counter() - t0
    placed = sum(int((r.node_rows >= 0).sum()) for r in results)
    return {
        "placed": placed,
        "total": n_lanes * count,
        "lanes_ok": sum(ok),
        "elapsed_s": round(elapsed, 4),
        "allocs_per_sec": round(placed / elapsed, 1) if elapsed > 0 else 0.0,
    }


ADMISSION_PATIENCE_S = 120.0  # how long a client keeps re-sending


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


def seed_fleet(server, n_nodes: int, racks: int = 25) -> None:
    """The config-3 fleet, upserted straight into state (set-up, not the
    measured path): ``racks`` racks round-robin, ssd on every 4th node,
    every 3rd node the double-size resource class."""
    from nomad_tpu import mock

    for i in range(n_nodes):
        node = mock.node()
        node.datacenter = "dc1"
        node.attributes["platform.rack"] = f"r{i % racks}"
        node.attributes["storage.type"] = "ssd" if i % 4 == 0 else "hdd"
        if i % 3 == 1:
            node.node_resources.cpu = 8000
            node.node_resources.memory_mb = 16384
        node.compute_class()
        server.store.upsert_node(i + 1, node)


JOB_CPU_CHOICES = (250, 500)  # MHz per alloc, drawn per job from its seed


def make_job(job_id: str, seed: int, per_job: int, spread_affinity=True):
    """One seeded mixed service/batch job of ``per_job`` allocs. With
    ``spread_affinity`` it is the config-3 job (rack spread weight 50 +
    ssd affinity weight 50 → the spread kernels); without, plain binpack
    (config-2 semantics → the closed-form kernel)."""
    from nomad_tpu import mock
    from nomad_tpu.structs import Affinity, Spread

    job = mock.batch_job() if seed % 3 == 2 else mock.job()
    job.id = job_id
    tg = job.task_groups[0]
    tg.count = per_job
    tg.tasks[0].resources.cpu = int(
        np.random.default_rng(seed).choice(JOB_CPU_CHOICES)
    )
    if spread_affinity:
        job.spreads = [Spread(attribute="${attr.platform.rack}", weight=50)]
        job.affinities = [
            Affinity(
                l_target="${attr.storage.type}",
                r_target="ssd",
                operand="=",
                weight=50,
            )
        ]
    return job


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


def bench_end_to_end(
    n_nodes: int, n_jobs: int, per_job: int, racks: int = 25,
    num_batch_workers: int = 1,
) -> dict:
    """BASELINE config-3 shape: mixed service/batch with spread+affinity
    through the full server pipeline."""
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.utils.metrics import global_metrics

    # num_batch_workers > 1 turns on deterministic lane ownership
    # (server/lanes.py): each batching worker owns a disjoint lane set,
    # dequeues lane-affine, and hands cross-lane placements through the
    # reserve→confirm claim protocol — commit conflicts are impossible
    # by construction. bench_multi_worker measures the scaling and
    # asserts the conflict rate is 0.0.
    server = Server(ServerConfig(
        num_workers=num_batch_workers, num_batch_workers=num_batch_workers
    ))
    server.establish_leadership()
    try:
        seed_fleet(server, n_nodes, racks)

        # warmup: compile the G buckets the measured run will hit (1 for
        # stragglers and the full EVAL_BATCH_SIZE-deep batched pass) for
        # this cluster size before the clock starts
        from nomad_tpu.server.worker import EVAL_BATCH_SIZE

        warm_ids = []
        for w in range(EVAL_BATCH_SIZE + 1):
            warm = make_job(f"warmup-{w}", 10_000_000 + w, per_job)
            warm_ids.append(warm.id)
            send(lambda: server.register_job(warm))
        server.wait_for_evals(timeout=600)
        # fixture drift guard: warm jobs left running hold cluster CPU
        # during the timed run. Stop and drain them so the measured run
        # starts against the SAME empty cluster every time.
        for wid in warm_ids:
            server.deregister_job("default", wid)
        server.wait_for_evals(timeout=600)
        warm_live = sum(
            1
            for a in server.store.allocs()
            if a.job_id.startswith("warmup-") and not a.terminal_status()
        )
        # the warm-up's cold compiles are a latency spike the admission
        # controller answers by shedding registrations; like the warm
        # jobs themselves, that must be gone before the clock starts
        settle_by = time.monotonic() + ADMISSION_PATIENCE_S
        while (
            server.admission.level(force=True) != "normal"
            and time.monotonic() < settle_by
        ):
            time.sleep(0.25)
        global_metrics.reset()
        from nomad_tpu.obs import flight_recorder, phase_breakdown

        flight_recorder.clear()

        t0 = time.perf_counter()
        for j in range(n_jobs):
            job = make_job(f"bench-{j}", j, per_job)
            send(lambda: server.register_job(job))
        ok = server.wait_for_evals(timeout=600)
        elapsed = time.perf_counter() - t0

        accounting = alloc_accounting(
            server, {f"bench-{j}": per_job for j in range(n_jobs)}
        )
        placed = accounting["placed"]
        snap = global_metrics.snapshot()
        plan = snap["samples"].get("nomad.plan.apply", {})
        invoke = snap["samples"].get("nomad.worker.invoke_scheduler", {})
        verify_batch = snap["samples"].get("nomad.plan.verify_batch", {})
        counters = snap["counters"]
        # commit-train coalescing: how many member plans each applier
        # commit carried (plans_per_commit ≈ batch depth means the whole
        # pass landed as ONE verify/apply instead of a per-eval train)
        plan_commits = int(counters.get("nomad.plan.commits", 0))
        committed_plans = int(counters.get("nomad.plan.committed_plans", 0))
        merged_commits = int(counters.get("nomad.plan.merged_commits", 0))
        merged_members = int(counters.get("nomad.plan.merged_members", 0))
        # per-eval counter, NOT the invoke_scheduler sample count: the
        # batched pass emits ONE timer sample per multi-eval batch
        evals = int(counters.get("nomad.worker.evals_processed", n_jobs))
        batch_completed = int(
            counters.get("nomad.worker.batch_evals_completed", 0)
        )
        batch_conflicts = int(
            counters.get("nomad.worker.batch_conflict_fallbacks", 0)
        )
        batch_singles = int(
            counters.get("nomad.worker.batch_single_fallbacks", 0)
        )
        batch_total = batch_completed + batch_conflicts
        solo_evals = int(counters.get("nomad.worker.solo_evals", 0))
        return {
            "config": f"{n_nodes} nodes, {n_jobs} jobs x {per_job} allocs, "
            f"spread+affinity, mixed service/batch",
            "batch_workers": num_batch_workers,
            # 0 ⇒ the warmup load was fully drained before the clock
            # started (comparable-by-construction across runs)
            "warm_allocs_live_at_start": warm_live,
            "drained": ok,
            # registrations the admission controller turned away inside
            # the timed window (re-sent after Retry-After: their sleeps
            # are in elapsed_s)
            "admission_deferred_or_shed": int(
                counters.get("nomad.admission.deferred_total", 0)
                + counters.get("nomad.admission.shed_total", 0)
            ),
            **accounting,
            "elapsed_s": round(elapsed, 3),
            "evals_per_sec": round(evals / elapsed, 1),
            "allocs_per_sec": round(placed / elapsed, 1),
            "plan_apply_p99_ms": round(plan.get("p99_ms", 0.0), 2),
            "plan_apply_mean_ms": round(plan.get("mean_ms", 0.0), 2),
            "invoke_scheduler_p99_ms": round(invoke.get("p99_ms", 0.0), 2),
            # does batching help or double work?
            "batch": {
                "evals_completed_in_batch": batch_completed,
                "conflict_fallbacks": batch_conflicts,
                "single_path_evals": batch_singles,
                # evals dequeued alone never see a batch: completed +
                # conflicts + solo reconciles to the eval total
                "solo_evals": solo_evals,
                "conflict_rate": round(batch_conflicts / batch_total, 3)
                if batch_total
                else 0.0,
            },
            # lane-partitioned commit path accounting (all zero at one
            # worker; at >1 the conflict counter is the law-9 invariant)
            "lanes": {
                "lane_conflicts": int(
                    counters.get("nomad.plan.lane_conflicts", 0)
                ),
                "cross_lane_handoffs": int(
                    counters.get("nomad.plan.cross_lane_handoffs", 0)
                ),
                "handoff_fallbacks": int(
                    counters.get("nomad.worker.lane_handoff_fallbacks", 0)
                ),
                "stale_token_drops": int(
                    counters.get("nomad.worker.stale_token_drops", 0)
                ),
            },
            # the coalesced commit train (one merged verify/apply per
            # batched pass): plans landed per applier commit, the merged
            # applier's batch width, and the vectorized verify tail
            "commit_train": {
                "plan_commits": plan_commits,
                "plans_per_commit": round(committed_plans / plan_commits, 2)
                if plan_commits
                else 0.0,
                "merged_commits": merged_commits,
                "applier_batch_size": round(
                    merged_members / merged_commits, 2
                )
                if merged_commits
                else 0.0,
                "verify_batch_p95_ms": round(
                    verify_batch.get("p95_ms", 0.0), 2
                ),
            },
            # mesh runs: full_uploads must stay at the initial build —
            # steady-state node updates refresh per shard, never the
            # whole tensor (all-zero when the mesh is off)
            "device_cache": {
                "full_flattens": server.device_cache.full_flattens,
                "incremental_refreshes": server.device_cache.incremental_refreshes,
                **server.device_cache.device_counters(),
            },
            # where the eval pipeline spends its time, from the span
            # traces of the measured run (flight recorder cleared at t0)
            "phase_breakdown_ms": phase_breakdown(flight_recorder.traces()),
        }
    finally:
        server.shutdown()


def auto_batch_workers() -> int:
    """Default worker count for the multi-worker block: one batching
    worker per host core, capped at 8 (past that the serialized applier,
    not the workers, is the bottleneck at bench shapes)."""
    return max(1, min(os.cpu_count() or 1, 8))


def bench_multi_worker(
    n_nodes: int,
    n_jobs: int,
    per_job: int,
    workers: int,
    single: dict,
) -> dict:
    """Single-vs-multi batching-worker comparison on the config-3 shape.

    ``single`` is the already-measured 1-worker run (the headline e2e);
    the multi run reuses the same shape at ``workers`` lane-partitioned
    batching workers. The lane contract is ASSERTED, not observed: a
    nonzero lane-conflict count or commit-conflict rate is a bug in the
    lane machinery and fails the bench loudly."""
    if workers <= 1:
        return {
            "workers": 1,
            "note": "single-core host: multi-worker run skipped "
            "(pass --batch-workers N to force)",
        }
    multi = bench_end_to_end(
        n_nodes, n_jobs, per_job, num_batch_workers=workers
    )
    conflict_rate = multi["batch"]["conflict_rate"]
    lane_conflicts = multi["lanes"]["lane_conflicts"]
    assert lane_conflicts == 0, (
        f"lane isolation violated: {lane_conflicts} lane conflicts at "
        f"{workers} workers (must be impossible by construction)"
    )
    assert conflict_rate == 0.0, (
        f"commit conflict rate {conflict_rate} at {workers} workers "
        f"(lane ownership must make pipelined commits conflict-free)"
    )
    return {
        "workers": workers,
        "evals_per_sec_single": single["evals_per_sec"],
        "evals_per_sec_multi": multi["evals_per_sec"],
        "scaling": round(
            multi["evals_per_sec"] / single["evals_per_sec"], 2
        )
        if single["evals_per_sec"]
        else 0.0,
        "allocs_per_sec_single": single["allocs_per_sec"],
        "allocs_per_sec_multi": multi["allocs_per_sec"],
        "conflict_rate": conflict_rate,
        "lanes": multi["lanes"],
        "detail": multi,
    }


def bench_grid() -> dict:
    """The BenchmarkServiceScheduler grid (scheduler/benchmarks/
    benchmarks_test.go:71-124): {1k, 5k, 10k} nodes × {10, 25, 50, 75}
    racks × {300, 600, 900, 1200} allocs, with and without spread —
    kernel-path timings per cell (one warm pass each; the e2e pipeline's
    per-cell cost is covered by the headline config-3 run)."""
    cells = []
    for n_nodes in (1_000, 5_000, 10_000):
        for racks in (10, 25, 50, 75):
            for count in (300, 600, 900, 1200):
                for spread in (False, True):
                    if spread:
                        r = bench_kernel_spread(
                            n_nodes, n_lanes=4, count=count, racks=racks
                        )
                    else:
                        r = bench_kernel(n_nodes, 4, count)
                    cells.append(
                        {
                            "nodes": n_nodes,
                            "racks": racks,
                            "allocs_per_job": count,
                            "spread": spread,
                            "allocs_per_sec": r["allocs_per_sec"],
                            "elapsed_s": r["elapsed_s"],
                        }
                    )
    return {"cells": cells}


def bench_replay(snapshot_path: str, n_jobs: int = 50, per_job: int = 100):
    """Real-state replay (benchmarks_test.go:19-36
    NOMAD_BENCHMARK_SNAPSHOT analog): bootstrap the server from a saved
    raft snapshot and drive the standard job workload against whatever
    nodes/allocs it contains."""
    from nomad_tpu import mock
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.state.snapshot import restore_snapshot

    server = Server(ServerConfig(num_workers=1))
    server._install_store(restore_snapshot(snapshot_path))
    server.establish_leadership()
    try:
        snap = server.store.snapshot()
        n_nodes = len(list(snap.nodes()))
        t0 = time.perf_counter()
        for j in range(n_jobs):
            job = mock.job()
            job.id = f"replay-{j}"
            job.task_groups[0].count = per_job
            server.register_job(job)
        ok = server.wait_for_evals(timeout=600)
        elapsed = time.perf_counter() - t0
        placed = sum(
            1
            for a in server.store.allocs()
            if a.job_id.startswith("replay-") and not a.terminal_status()
        )
        return {
            "snapshot": snapshot_path,
            "nodes_in_snapshot": n_nodes,
            "drained": ok,
            "placed": placed,
            "total": n_jobs * per_job,
            "elapsed_s": round(elapsed, 3),
            "evals_per_sec": round(n_jobs / elapsed, 1),
        }
    finally:
        server.shutdown()


def _pop_batch_workers_arg(argv: list) -> int:
    """Strip ``--batch-workers N`` / ``--batch-workers=N`` from argv
    (the rest of the CLI stays positional) and return the worker count:
    the explicit override, else one per host core (auto_batch_workers)."""
    for i, arg in enumerate(argv):
        if arg == "--batch-workers" and i + 1 < len(argv):
            n = int(argv[i + 1])
            del argv[i:i + 2]
            return max(1, n)
        if arg.startswith("--batch-workers="):
            n = int(arg.split("=", 1)[1])
            del argv[i]
            return max(1, n)
    return auto_batch_workers()


def _pop_mesh_arg(argv: list):
    """Strip ``--mesh SPEC`` / ``--mesh=SPEC`` from argv (every mode
    accepts it) and activate the mesh by seeding ``NOMAD_TPU_MESH``
    before the first ``get_mesh()`` resolution. Returns the spec or
    None. SPEC follows the env grammar: ``dp,mp``, ``auto``, ``off``."""
    spec = None
    for i, arg in enumerate(argv):
        if arg == "--mesh" and i + 1 < len(argv):
            spec = argv[i + 1]
            del argv[i:i + 2]
            break
        if arg.startswith("--mesh="):
            spec = arg.split("=", 1)[1]
            del argv[i]
            break
    if spec is not None:
        from nomad_tpu.utils.backend import parse_mesh_spec, reset_mesh

        parse_mesh_spec(spec)  # fail fast on junk, before any JSON line
        os.environ["NOMAD_TPU_MESH"] = spec
        reset_mesh()
    return spec


def mesh_block(n_nodes: int = 0) -> dict:
    """Self-describing mesh provenance for every bench JSON line: shape,
    axis names, per-shard node counts, and the measured cost of the
    per-step hierarchical reduction (per-shard local top-k + cross-shard
    merge) at this run's padded node bucket — so a mesh run says what
    the cross-shard merge cost, not just that a mesh was on."""
    from nomad_tpu.utils.backend import get_mesh

    cfg = get_mesh()
    out = dict(cfg.describe())
    if not cfg.active or not n_nodes:
        return out
    import jax
    import jax.numpy as jnp

    from nomad_tpu.device.flatten import node_bucket
    from nomad_tpu.device.score import _topk_nodes

    pn = node_bucket(n_nodes)
    mp = cfg.n_node_shards
    out["padded_nodes"] = pn
    out["nodes_per_shard"] = pn // mp if pn % mp == 0 else None
    n_shards = mp if pn % mp == 0 else 1
    flat = jnp.asarray(
        np.random.default_rng(0).random(pn, dtype=np.float32)
    )
    merge = jax.jit(lambda x: _topk_nodes(x, 16, n_shards))
    jax.block_until_ready(merge(flat))  # compile outside the clock
    t0 = time.perf_counter()
    reps = 10
    for _ in range(reps):
        jax.block_until_ready(merge(flat))
    out["topk_merge_us"] = round(
        (time.perf_counter() - t0) / reps * 1e6, 1
    )
    return out


def kernel_fingerprints_block() -> dict:
    """Canonical jaxpr fingerprints (jaxlint JXL006) for every traced_jit
    kernel this bench process actually traced, keyed kernel -> config
    label -> hash. Embedded in every mode's detail block so cross-run
    records prove "same program, different wall-clock" (or expose that a
    perf delta came with a jaxpr change) without re-running anything.
    An analyzer failure fails the run: a record whose fingerprints are
    silently empty proves nothing."""
    from nomad_tpu.analysis.jaxlint import fingerprint_table

    return fingerprint_table()


def bench_soak(argv: list, batch_workers: int) -> dict:
    """`bench.py soak` — steady-state SLO soak: seeded Poisson arrivals
    + node churn against a live cluster, reported as the canonical SLO
    block (see nomad_tpu/obs/loadgen.py). The canonical part of the
    emitted JSON (config, schedule, targets, slo_schema) is
    bit-reproducible for a given seed; measured latencies are
    timing-dependent diagnostics, like chaos-report diagnostics."""
    import argparse

    from nomad_tpu.obs.loadgen import run_soak

    p = argparse.ArgumentParser(prog="bench.py soak")
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--rate", type=float, default=25.0)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--nodes", type=int, default=10_000)
    p.add_argument(
        "--saturation", action="store_true",
        help="after the soak, binary-search the saturation arrival rate "
        "with short reduced-scale probes",
    )
    p.add_argument("--sat-probe-seconds", type=float, default=2.0)
    p.add_argument("--sat-nodes", type=int, default=200)
    p.add_argument(
        "--calib-artifact", type=str, default="CALIB_r01.json",
        help="where --saturation writes the calibration probe artifact "
        "(loaded by ServerConfig(calibration_artifact=...) to derive "
        "admission thresholds from the measured rate; '' disables)",
    )
    p.add_argument(
        "--calib-from", type=str, default=None,
        help="load a previously written probe artifact so this soak "
        "admits under the probe-derived thresholds (source: probe)",
    )
    p.add_argument(
        "--overload", action="store_true",
        help="admission-control acceptance run: find the saturation "
        "rate, then replay a burst soak spiking past it and demand the "
        "high-priority SLO holds while lower tiers are deferred/shed",
    )
    p.add_argument(
        "--overload-factor", type=float, default=2.0,
        help="spike arrival rate as a multiple of the measured "
        "saturation rate (default 2.0)",
    )
    p.add_argument("--spike-rate", type=float, default=0.0)
    p.add_argument("--spike-start", type=float, default=0.0)
    p.add_argument("--spike-seconds", type=float, default=0.0)
    p.add_argument(
        "--priority-mix", type=str, default=None,
        help="arrival priority weights as prio:weight pairs, e.g. "
        "'30:0.3,50:0.4,70:0.3' (default: uniform 30/50/70)",
    )
    p.add_argument(
        "--high-p99-ms", type=float, default=5000.0,
        help="high-tier p99 eval-latency bound enforced in --overload "
        "mode (the SLO the admission plane defends)",
    )
    p.add_argument(
        "--incremental", choices=("on", "off", "ab"), default="off",
        help="incremental score-state cache (device/cache.py): pin it "
        "on or off for the soak, or 'ab' to run both arms back to back "
        "and emit a per-arm comparison (steady-state p99, saturation "
        "rate, rescore accounting)",
    )
    args = p.parse_args(argv)
    mix = None
    if args.priority_mix:
        mix = {
            int(pair.split(":")[0]): float(pair.split(":")[1])
            for pair in args.priority_mix.split(",")
        }
    if args.overload:
        return _bench_soak_overload(args, batch_workers, mix)
    soak_kwargs = dict(
        seed=args.seed,
        seconds=args.seconds,
        rate=args.rate,
        nodes=args.nodes,
        batch_workers=batch_workers,
        saturation=args.saturation,
        saturation_kwargs={
            "probe_seconds": args.sat_probe_seconds,
            "nodes": args.sat_nodes,
        },
        spike_rate=args.spike_rate,
        spike_start=args.spike_start,
        spike_seconds=args.spike_seconds,
        priority_mix=mix,
        calibration_artifact=args.calib_from,
    )
    if args.incremental == "ab":
        return _bench_soak_incremental_ab(soak_kwargs)
    run = _soak_incremental_arm(args.incremental == "on", soak_kwargs)
    d = run.to_dict()
    if run.saturation_rate is not None and args.calib_artifact:
        from nomad_tpu.obs.calibrate import write_probe_artifact

        write_probe_artifact(
            args.calib_artifact,
            rate_per_s=run.saturation_rate,
            seed=args.seed,
            nodes=args.sat_nodes,
            probe_seconds=args.sat_probe_seconds,
        )
        d["calib_artifact"] = args.calib_artifact
    return d


def _soak_incremental_arm(on: bool, soak_kwargs: dict):
    """Run one soak with the incremental score cache pinned on/off via
    NOMAD_TPU_INCREMENTAL, restoring the ambient resolution after."""
    from nomad_tpu.obs.loadgen import run_soak
    from nomad_tpu.utils import backend

    prev = os.environ.get("NOMAD_TPU_INCREMENTAL")
    os.environ["NOMAD_TPU_INCREMENTAL"] = "on" if on else "off"
    backend.reset_incremental()
    try:
        return run_soak(**soak_kwargs)
    finally:
        if prev is None:
            os.environ.pop("NOMAD_TPU_INCREMENTAL", None)
        else:
            os.environ["NOMAD_TPU_INCREMENTAL"] = prev
        backend.reset_incremental()


def _bench_soak_incremental_ab(soak_kwargs: dict) -> dict:
    """`bench.py soak --incremental ab` — back-to-back off/on arms over
    the SAME seeded schedule (identical canonical blocks except the
    ``incremental`` flag), compared on steady-state p99, saturation
    rate, and the rescore accounting. Gates are honest measurements,
    not assertions: both arms must hold the invariants; the latency
    deltas are reported for the operator to judge at their scale."""
    # discarded warmup: the first soak in a process pays every one-time
    # jit trace/compile; without this the off arm (run first) would eat
    # that cost and the A/B would flatter the on arm dishonestly
    warm_kwargs = dict(
        soak_kwargs,
        seconds=min(4.0, float(soak_kwargs.get("seconds") or 4.0)),
        saturation=False,
    )
    _soak_incremental_arm(False, warm_kwargs)
    runs = {
        arm: _soak_incremental_arm(arm == "on", soak_kwargs)
        for arm in ("off", "on")
    }

    def _arm_stats(run) -> dict:
        dc = run.slo.get("device_cache", {})
        return {
            "p99_ms": run.slo["eval_latency_ms"]["p99_ms"],
            "p95_ms": run.slo["eval_latency_ms"]["p95_ms"],
            "saturation_rate": run.saturation_rate,
            "score_rows_rescored": dc.get("score_rows_rescored", 0),
            "score_rows_reused": dc.get("score_rows_reused", 0),
            "pipeline_overlap_ms": dc.get("pipeline_overlap_ms", 0.0),
            "invariants_ok": run.ok,
        }

    off, on = _arm_stats(runs["off"]), _arm_stats(runs["on"])
    sat_ratio = None
    if off["saturation_rate"] and on["saturation_rate"]:
        sat_ratio = round(on["saturation_rate"] / off["saturation_rate"], 3)
    comparison = {
        "off": off,
        "on": on,
        "p99_delta_ms": round(on["p99_ms"] - off["p99_ms"], 3),
        "p99_improved": on["p99_ms"] <= off["p99_ms"],
        "saturation_ratio": sat_ratio,
        "saturation_not_worse": (
            sat_ratio is None or sat_ratio >= 1.0
        ),
        "both_invariants_ok": off["invariants_ok"] and on["invariants_ok"],
    }
    # soak-shaped like the overload gate: the on arm is the headline
    # run main() reports, the off arm rides along in full for the A/B
    d = runs["on"].to_dict()
    d["incremental_ab"] = comparison
    d["arm_off"] = runs["off"].to_dict()
    d["ok"] = bool(d["ok"]) and comparison["both_invariants_ok"]
    return d


def _bench_soak_overload(args, batch_workers: int, mix) -> dict:
    """`bench.py soak --overload` — the overload acceptance gate.

    Measures the sustainable arrival rate first (same binary search as
    --saturation), then runs a soak whose middle third spikes to
    ``--overload-factor``× that rate with tightened admission
    thresholds so the controller must engage. The verdict is the
    admission plane's contract, not raw throughput: high-tier p99
    within --high-p99-ms, shedding confined to the lowest priority
    tier present, the per-tier conservation law intact, and the
    controller back at NORMAL once the spike drains.
    """
    from nomad_tpu.obs.loadgen import run_soak, saturation_search
    from nomad_tpu.obs.slo import SloTargets

    sat = saturation_search(
        seed=args.seed,
        nodes=args.sat_nodes,
        batch_workers=batch_workers,
        probe_seconds=args.sat_probe_seconds,
    )
    spike_rate = args.overload_factor * sat
    run = run_soak(
        seed=args.seed,
        seconds=args.seconds,
        # base load just under saturation; the spike stream carries the
        # overload so the pre/post-spike phases exercise recovery
        rate=0.9 * sat,
        nodes=args.sat_nodes,
        batch_workers=batch_workers,
        # only the high-tier bound: general latency/queue targets are
        # expected casualties of a deliberate 2x-saturation spike
        targets=SloTargets(
            eval_p99_ms=None,
            high_eval_p99_ms=args.high_p99_ms,
            placement_p99_ms=None,
            queue_depth_max=None,
            max_breaker_trips=None,
            max_fallback_activations=None,
            max_lane_conflicts=None,
        ),
        spike_rate=spike_rate,
        spike_start=args.seconds / 3.0,
        spike_seconds=args.seconds / 3.0,
        priority_mix=mix or {30: 0.3, 50: 0.4, 70: 0.3},
        # thresholds sized to the probe-scale cluster so the controller
        # engages within the spike window instead of at datacenter scale
        admission_overrides={
            "brownout_backlog": 32,
            "shed_backlog": 128,
            "brownout_p99_ms": 1000.0,
            "shed_p99_ms": 4000.0,
            "min_p99_samples": 8,
            "reeval_interval_s": 0.1,
            "dwell_s": 1.0,
            "defer_delay_s": 0.5,
        },
    )
    d = run.to_dict()
    adm = run.admission or {}
    counters = adm.get("counters", {})
    present = [
        t for t in ("low", "normal", "high")
        if counters.get(t, {}).get("submitted")
    ]
    lowest = present[0] if present else None
    shed_confined = all(
        c["shed"] == 0 for t, c in counters.items() if t != lowest
    )
    verdict_failures = run.slo["verdict"]["failures"]
    high_ok = not any(
        f.startswith("high_eval_p99_ms") for f in verdict_failures
    )
    d["overload"] = {
        "saturation_rate": sat,
        "spike_rate": spike_rate,
        "factor": args.overload_factor,
        "engaged": bool(adm.get("level_changes")),
        "high_slo_ok": high_ok,
        "shed_confined_to_lowest": shed_confined,
        "lowest_tier_present": lowest,
        "conserved": bool(adm.get("conserved")),
        "recovered": bool(adm.get("recovered")),
    }
    o = d["overload"]
    d["overload"]["ok"] = (
        o["engaged"] and o["high_slo_ok"] and o["shed_confined_to_lowest"]
        and o["conserved"] and o["recovered"]
    )
    return d


def main():
    batch_workers = _pop_batch_workers_arg(sys.argv)
    mesh_spec = _pop_mesh_arg(sys.argv)
    if len(sys.argv) > 1 and sys.argv[1] == "kernel":
        # kernel-only mode: the multi-chip scaling headline (ROADMAP
        # item 1's 100k-node / 1M-pending-alloc config runs here:
        # `bench.py kernel 100000 100 10000 --mesh 2,4`) without paying
        # for the e2e/degraded cells of the default mode
        n_nodes = int(sys.argv[2]) if len(sys.argv) > 2 else 10_000
        n_jobs = int(sys.argv[3]) if len(sys.argv) > 3 else 100
        count = int(sys.argv[4]) if len(sys.argv) > 4 else 1_000
        k = bench_kernel(n_nodes, n_jobs, count)
        failures = device_path_failures()
        per_chip_target = 100_000 / 8.0
        print(
            json.dumps(
                {
                    "metric": (
                        f"allocs planned/sec ({n_jobs} jobs x {count} "
                        f"allocs vs {n_nodes} nodes, binpack, "
                        f"mesh={mesh_spec or 'off'})"
                    ),
                    "value": k["allocs_per_sec"],
                    "unit": "allocs/s",
                    "vs_baseline": round(
                        k["allocs_per_sec"] / per_chip_target, 3
                    ),
                    **device_block(),
                    "failures": failures,
                    "detail": {
                        "kernel": k,
                        "mesh": mesh_block(n_nodes),
                        "kernel_fingerprints": kernel_fingerprints_block(),
                    },
                }
            )
        )
        if failures:
            sys.exit(1)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "soak":
        d = bench_soak(sys.argv[2:], batch_workers)
        d["mesh"] = mesh_block(d["nodes"])
        d["kernel_fingerprints"] = kernel_fingerprints_block()
        ev = d["slo"]["eval_latency_ms"]
        print(
            json.dumps(
                {
                    "metric": "steady-state p99 eval latency "
                    f"({d['rate']:g}/s arrivals, {d['nodes']} nodes, "
                    f"{d['batch_workers']} workers)",
                    "value": ev["p99_ms"],
                    "unit": "ms",
                    "vs_baseline": 0.0,
                    **device_block(),
                    "detail": d,
                }
            )
        )
        if not d["ok"] or not d.get("overload", {"ok": True})["ok"]:
            sys.exit(1)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "hetero":
        # heterogeneity A/B: binpack vs the hetero-* policies on one
        # seeded mixed fleet (≥3 device classes). Canonical, seeded,
        # byte-reproducible JSON; gates (exit 1) on maxmin improving the
        # worst-class normalized throughput share, makespan reducing the
        # modeled batch makespan, and every policy's device pass being
        # byte-identical to its host oracle (scheduler/hetero.py).
        from nomad_tpu.scheduler.hetero import run_hetero_ab

        n_nodes = int(sys.argv[2]) if len(sys.argv) > 2 else 1000
        n_jobs = int(sys.argv[3]) if len(sys.argv) > 3 else 12
        count = int(sys.argv[4]) if len(sys.argv) > 4 else 25
        d = run_hetero_ab(
            n_nodes=n_nodes, n_jobs=n_jobs, count_per_job=count, seed=42
        )
        d["mesh"] = mesh_block(n_nodes)
        d["kernel_fingerprints"] = kernel_fingerprints_block()
        print(
            json.dumps(
                {
                    "metric": "hetero maxmin worst-share gain vs binpack "
                    f"({n_nodes} nodes, {n_jobs} jobs x {count})",
                    "value": d["ab"]["maxmin_worst_share_delta"],
                    "unit": "share",
                    "vs_baseline": 0.0,
                    **device_block(),
                    "detail": d,
                },
                sort_keys=True,
            )
        )
        if not d["ok"]:
            sys.exit(1)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "cp":
        # constraint-programming dispatcher A/B: greedy binpack vs the
        # cp-pack joint relaxation on one seeded contended mixed fleet.
        # Canonical, seeded, byte-reproducible JSON; gates (exit 1) on
        # cp-pack beating binpack on aggregate placement score OR
        # preemptions avoided without regressing the other, and on the
        # device kernel being byte-identical to its NumPy host oracle
        # across two seeds (scheduler/cp.py).
        from nomad_tpu.scheduler.cp import run_cp_ab

        n_nodes = int(sys.argv[2]) if len(sys.argv) > 2 else 1000
        n_jobs = int(sys.argv[3]) if len(sys.argv) > 3 else 12
        count = int(sys.argv[4]) if len(sys.argv) > 4 else 40
        d = run_cp_ab(
            n_nodes=n_nodes, n_jobs=n_jobs, count_per_job=count, seed=42
        )
        d["mesh"] = mesh_block(n_nodes)
        d["kernel_fingerprints"] = kernel_fingerprints_block()
        print(
            json.dumps(
                {
                    "metric": "cp-pack aggregate score delta vs binpack "
                    f"({n_nodes} nodes, {n_jobs} jobs x {count})",
                    "value": d["ab"]["score_delta"],
                    "unit": "score",
                    "vs_baseline": 0.0,
                    **device_block(),
                    "detail": d,
                },
                sort_keys=True,
            )
        )
        if not d["ok"]:
            sys.exit(1)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "gang":
        # gang scheduling A/B: greedy binpack (gang-blind, fragments
        # multi-group jobs across racks) vs cp-gang (topology-priced
        # all-or-nothing placement) on one seeded topology fleet.
        # Canonical, seeded, byte-reproducible JSON; gates (exit 1) on
        # binpack fragmenting at least one gang, cp-gang placing every
        # gang all-or-nothing with its topology constraint satisfied at
        # no aggregate-objective loss, and the gang kernel being
        # byte-identical to its NumPy host oracle across two seeds
        # (scheduler/cp.py run_gang_ab).
        from nomad_tpu.scheduler.cp import run_gang_ab

        n_nodes = int(sys.argv[2]) if len(sys.argv) > 2 else 64
        n_jobs = int(sys.argv[3]) if len(sys.argv) > 3 else 8
        groups = int(sys.argv[4]) if len(sys.argv) > 4 else 3
        d = run_gang_ab(
            n_nodes=n_nodes, n_jobs=n_jobs, groups=groups, seed=42
        )
        d["mesh"] = mesh_block(n_nodes)
        d["kernel_fingerprints"] = kernel_fingerprints_block()
        print(
            json.dumps(
                {
                    "metric": "cp-gang aggregate objective delta vs "
                    f"binpack ({n_nodes} nodes, {n_jobs} jobs x "
                    f"{groups} groups)",
                    "value": d["ab"]["objective_delta"],
                    "unit": "score",
                    "vs_baseline": 0.0,
                    **device_block(),
                    "detail": d,
                },
                sort_keys=True,
            )
        )
        if not d["ok"]:
            sys.exit(1)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "calib":
        # calibration A/B: declared vs learned throughputs on one seeded
        # mixed fleet. The estimator learns per-(device class × job
        # profile) rates from synthetic execute traces fed through the
        # real flight-recorder fan-out, then places *blind* asks (no
        # declared throughputs). Canonical, seeded, byte-reproducible
        # JSON; gates (exit 1) on learned-mode quality landing within
        # tolerance of declared-mode, declared mode staying
        # byte-identical with an estimator attached, and zero added
        # jaxpr retraces (obs/calibrate.py).
        from nomad_tpu.obs.calibrate import run_calib_ab

        n_nodes = int(sys.argv[2]) if len(sys.argv) > 2 else 1000
        n_jobs = int(sys.argv[3]) if len(sys.argv) > 3 else 12
        count = int(sys.argv[4]) if len(sys.argv) > 4 else 25
        d = run_calib_ab(
            n_nodes=n_nodes, n_jobs=n_jobs, count_per_job=count, seed=42
        )
        d["mesh"] = mesh_block(n_nodes)
        d["kernel_fingerprints"] = kernel_fingerprints_block()
        print(
            json.dumps(
                {
                    "metric": "learned-throughput maxmin worst-share gain "
                    f"({n_nodes} nodes, {n_jobs} jobs x {count})",
                    "value": d["ab"]["learned"]["maxmin_worst_share_delta"],
                    "unit": "share",
                    "vs_baseline": 0.0,
                    **device_block(),
                    "detail": d,
                },
                sort_keys=True,
            )
        )
        if not d["ok"]:
            sys.exit(1)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "defrag":
        # defrag A/B: a seeded churned fleet left fragmented (load
        # smeared thinly across most nodes), then bounded-budget
        # migrate_plan_kernel cycles repack it with capacity conserved
        # mid-flight (the two-phase protocol's pricing model). Canonical,
        # seeded, byte-reproducible JSON; gates (exit 1) on the kernel
        # staying byte-identical to its NumPy oracle across two seeds,
        # zero mid-move capacity violations, every cycle within budget,
        # and at least half the packing-efficiency gap recovered
        # (scheduler/migrate.py run_defrag_ab).
        from nomad_tpu.scheduler.migrate import run_defrag_ab

        n_nodes = int(sys.argv[2]) if len(sys.argv) > 2 else 48
        n_allocs = int(sys.argv[3]) if len(sys.argv) > 3 else 96
        budget = int(sys.argv[4]) if len(sys.argv) > 4 else 8
        d = run_defrag_ab(
            n_nodes=n_nodes, n_allocs=n_allocs, budget=budget, seed=42
        )
        d["mesh"] = mesh_block(n_nodes)
        d["kernel_fingerprints"] = kernel_fingerprints_block()
        print(
            json.dumps(
                {
                    "metric": "defrag packing-efficiency recovered "
                    f"({n_nodes} nodes, {n_allocs} allocs, "
                    f"budget {budget}/cycle)",
                    "value": d["recovered_fraction"],
                    "unit": "fraction of gap (gate 0.5)",
                    "vs_baseline": 0.0,
                    **device_block(),
                    "detail": d,
                },
                sort_keys=True,
            )
        )
        if not d["ok"]:
            sys.exit(1)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "explain":
        # explain-seam overhead block: provenance-on must stay within
        # 5% of provenance-off at the config-3 inner shape (exit 1 on
        # breach) — the "always-on observability" budget
        n_nodes = int(sys.argv[2]) if len(sys.argv) > 2 else 5_000
        n_lanes = int(sys.argv[3]) if len(sys.argv) > 3 else 16
        count = int(sys.argv[4]) if len(sys.argv) > 4 else 250
        d = bench_explain(n_nodes=n_nodes, n_lanes=n_lanes, count=count)
        d["mesh"] = mesh_block(n_nodes)
        d["kernel_fingerprints"] = kernel_fingerprints_block()
        print(
            json.dumps(
                {
                    "metric": "explain-on overhead vs explain-off "
                    f"({n_nodes} nodes, {n_lanes} lanes x {count})",
                    "value": d["overhead_frac"],
                    "unit": "fraction (budget 0.05)",
                    "vs_baseline": 0.0,
                    **device_block(),
                    "detail": d,
                },
                sort_keys=True,
            )
        )
        if not d["ok"]:
            sys.exit(1)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "grid":
        grid = bench_grid()
        grid["mesh"] = mesh_block(10_000)  # largest grid cell's bucket
        grid["kernel_fingerprints"] = kernel_fingerprints_block()
        best = max(c["allocs_per_sec"] for c in grid["cells"])
        print(
            json.dumps(
                {
                    "metric": "benchmark grid (benchmarks_test.go:71-124 shape)",
                    "value": best,
                    "unit": "allocs/s (best cell)",
                    "vs_baseline": round(best / (100_000 / 8.0), 3),
                    **device_block(),
                    "detail": grid,
                }
            )
        )
        return
    if len(sys.argv) > 1 and sys.argv[1] == "parity":
        # the BASELINE <=0.5% placement-score clause: device kernels vs
        # the reference-faithful stepwise host oracle over seeded
        # graded-config streams (device/parity.py)
        from nomad_tpu.device.parity import run_parity_suite

        suite = run_parity_suite(small=False)
        worst = max(abs(c["score_delta_pct"]) for c in suite.values())
        print(
            json.dumps(
                {
                    "metric": "placement-score delta vs host oracle "
                    "(worst graded config)",
                    "value": worst,
                    "unit": "%",
                    # bar is <=0.5%: vs_baseline >= 1 means within bar
                    "vs_baseline": round(0.5 / max(worst, 1e-9), 3)
                    if worst > 0
                    else 1.0,
                    **device_block(),
                    "detail": {
                        "mesh": mesh_block(),
                        "kernel_fingerprints": kernel_fingerprints_block(),
                        **suite,
                    },
                }
            )
        )
        return
    if len(sys.argv) > 1 and sys.argv[1] == "replay":
        path = sys.argv[2] if len(sys.argv) > 2 else os.environ.get(
            "NOMAD_TPU_BENCH_SNAPSHOT", ""
        )
        r = bench_replay(path)
        r["mesh"] = mesh_block()
        r["kernel_fingerprints"] = kernel_fingerprints_block()
        print(
            json.dumps(
                {
                    "metric": f"replay of {path}",
                    "value": r["evals_per_sec"],
                    "unit": "evals/s",
                    "vs_baseline": 0.0,
                    **device_block(),
                    "detail": r,
                }
            )
        )
        return

    n_nodes = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000
    n_jobs = int(sys.argv[2]) if len(sys.argv) > 2 else 100
    count = int(sys.argv[3]) if len(sys.argv) > 3 else 1_000

    kernel = bench_kernel(n_nodes, n_jobs, count)
    e2e = bench_end_to_end(
        n_nodes, n_jobs, max(count // 4, 10)
    )
    multi_worker = bench_multi_worker(
        n_nodes, n_jobs, max(count // 4, 10), batch_workers, e2e
    )
    # judged BEFORE the degraded block forces every breaker open on
    # purpose: up to here a trip or a reference-path pass means the
    # numbers above are not the device's
    failures = device_path_failures() + e2e_failures(e2e)
    if "detail" in multi_worker:
        failures += e2e_failures(multi_worker["detail"])
    degraded = bench_degraded()

    per_chip_target = 100_000 / 8.0  # north-star share for one v5e chip
    allocs_per_sec = kernel["allocs_per_sec"]
    device = device_block()

    print(
        json.dumps(
            {
                "metric": (
                    f"allocs planned/sec ({n_jobs} jobs x {count} allocs vs "
                    f"{n_nodes} nodes, binpack, {device['platform']})"
                ),
                "value": allocs_per_sec,
                "unit": "allocs/s",
                "vs_baseline": round(allocs_per_sec / per_chip_target, 3),
                **device,
                "failures": failures,
                "detail": {
                    "mesh": mesh_block(n_nodes),
                    "kernel_fingerprints": kernel_fingerprints_block(),
                    "kernel": kernel,
                    "end_to_end": e2e,
                    # lane-partitioned multi-worker scaling: workers,
                    # evals/s single vs multi, conflict rate (asserted
                    # 0.0 — lane ownership makes conflicts structural
                    # impossibilities, not probabilities)
                    "multi_worker": multi_worker,
                    # allocs/s with every breaker forced open (the
                    # reference-path floor a tripped cluster degrades to)
                    "degraded_mode": degraded,
                },
            }
        )
    )
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
