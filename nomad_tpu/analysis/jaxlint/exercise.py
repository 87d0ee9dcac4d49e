"""Drive the production kernel fleet once with a tiny synthetic workload.

The analyzer re-traces kernels from their *recorded* call specs
(utils/backend kernel registry); a kernel that has never been called in
this process has no spec. This module is the standalone driver: a
16-node cluster and a handful of asks routed so every production kernel
traces exactly once — the four PlacementKernel families through the
real dispatch (closed-form, exact scan, chunked, one-per-value), the
score-matrix kernel in both its class-less and throughput configs, the
two preemption kernels, the hetero joint kernel, and the cp auction.

Shapes are deliberately minimal: the analyzer checks program structure,
not numerics, and a full fleet exercise compiles in seconds on CPU.
Everything is seeded/deterministic so the recorded specs — and
therefore the fingerprint table — are a pure function of this file.
"""

from __future__ import annotations

N_NODES = 16
D = 4


def _cluster():
    import numpy as np

    from ...device.flatten import ClusterTensors, node_bucket

    pn = node_bucket(N_NODES)
    capacity = np.zeros((pn, D), dtype=np.float32)
    capacity[:N_NODES, 0] = 16000.0
    capacity[:N_NODES, 1] = 32768.0
    capacity[:N_NODES, 2] = 100 * 1024.0
    capacity[:N_NODES, 3] = 1000.0
    used = np.zeros_like(capacity)
    used[:N_NODES, :2] = capacity[:N_NODES, :2] * 0.1
    ready = np.zeros(pn, dtype=bool)
    ready[:N_NODES] = True
    return ClusterTensors(
        node_ids=[f"jxl-node-{i}" for i in range(N_NODES)],
        index=1,
        num_nodes=N_NODES,
        capacity=capacity,
        used=used,
        ready=ready,
        dc_ids=np.zeros(pn, dtype=np.int32),
        class_ids=np.zeros(pn, dtype=np.int32),
        dc_vocab={"dc1": 0},
        class_vocab={"small": 0},
        class_rep=[0],
        node_row={f"jxl-node-{i}": i for i in range(N_NODES)},
    )


def _ask(ct, job, count, blocks=None):
    import numpy as np

    from ...device.flatten import GroupAsk

    pn = ct.padded_n
    return GroupAsk(
        job_id=f"jxl-{job}",
        tg_name="web",
        count=count,
        desired_total=count,
        ask=np.array([250.0, 512.0, 300.0, 0.0], dtype=np.float32),
        eligible=ct.ready.copy(),
        job_counts=np.zeros(pn, dtype=np.int32),
        penalty_nodes=np.zeros(pn, dtype=bool),
        affinity_scores=np.zeros(pn, dtype=np.float32),
        has_affinities=False,
        distinct_hosts=False,
        blocks=blocks,
    )


def _blocks(ct, kind, values=4):
    """One spread/cap accounting block over a synthetic rack attribute."""
    import numpy as np

    from ...device.flatten import ValueBlocks

    pn = ct.padded_n
    value_ids = np.full((1, pn), -1, dtype=np.int32)
    value_ids[0, :N_NODES] = np.arange(N_NODES) % values
    return ValueBlocks(
        value_ids=value_ids,
        counts0=np.zeros((1, values), dtype=np.float32),
        desired=np.full((1, values), -1.0, dtype=np.float32),
        caps=np.full((1, values), np.inf, dtype=np.float32),
        weights=np.ones(1, dtype=np.float32),
        kinds=np.array([kind], dtype=np.int32),
    )


def run_placement_paths(
    explain: bool = False, incremental: bool = False
) -> int:
    """Route one tiny batch through each PlacementKernel family.
    Returns the number of placement results produced.

    With ``incremental`` a DeviceStateCache rides along as the cluster's
    score cache and the batch runs TWICE — full rebuild, then one
    churned row through the dirty-patch path with a generation swap
    between — so the differ observes every incremental code path while
    proving none of them traced a new program."""
    from ...device.score import (
        BLOCK_EVEN_SPREAD,
        BLOCK_TARGET_SPREAD,
        PlacementKernel,
    )

    ct = _cluster()
    cache = None
    if incremental:
        from ...device.cache import DeviceStateCache

        cache = DeviceStateCache()
        ct.score_cache = cache
    asks = [
        _ask(ct, "fast-a", 3),  # closed-form top-k
        _ask(ct, "fast-b", 2),
        _ask(ct, "scan", 3, blocks=_blocks(ct, BLOCK_TARGET_SPREAD)),
        _ask(ct, "chunked", 40, blocks=_blocks(ct, BLOCK_TARGET_SPREAD)),
        _ask(ct, "opv", 40, blocks=_blocks(ct, BLOCK_EVEN_SPREAD)),
    ]
    kernel = PlacementKernel("binpack")
    results = kernel.place(ct, asks, explain=explain)
    if cache is not None:
        cache.score_commit()
        ct.used[0, 0] += 128.0  # one dirty row → per-shard patch pass
        results = kernel.place(ct, asks, explain=explain)
        cache.score_commit()
    return sum(1 for r in results if r is not None)


def run_score_matrix():
    """score_matrix_kernel in both configs: class-less (throughputs
    None — the Python gate) and with the throughput axis. Like every
    ``run_*`` below, returns the kernels' (possibly still in-flight)
    outputs so the caller can wait for the device to finish them."""
    import numpy as np

    from ...device.score import score_matrix_kernel

    g, n = 2, N_NODES
    capacity = np.full((n, D), 16000.0, dtype=np.float32)
    used = capacity * 0.1
    asks = np.full((g, D), 250.0, dtype=np.float32)
    eligible = np.ones((g, n), dtype=bool)
    job_counts = np.zeros((g, n), dtype=np.int32)
    desired_totals = np.full(g, 3.0, dtype=np.float32)
    penalty = np.zeros((g, n), dtype=bool)
    affinity = np.zeros((g, n), dtype=np.float32)
    has_aff = np.zeros(g, dtype=bool)
    distinct = np.zeros(g, dtype=bool)
    spread = np.asarray(False)
    plain = score_matrix_kernel(
        capacity, used, asks, eligible, job_counts, desired_totals,
        penalty, affinity, has_aff, distinct, spread,
    )
    tp = np.ones((g, n), dtype=np.float32)
    return plain, score_matrix_kernel(
        capacity, used, asks, eligible, job_counts, desired_totals,
        penalty, affinity, has_aff, distinct, spread, tp,
    )


def run_preemption():
    import numpy as np

    from ...device.preempt import (
        choose_preemption_node_kernel,
        find_preemption_kernel,
    )

    n, v = N_NODES, 3
    capacity = np.full((n, D), 16000.0, dtype=np.float32)
    used = capacity * 0.9
    ask = np.array([4000.0, 8000.0, 100.0, 0.0], dtype=np.float32)
    eligible = np.ones(n, dtype=bool)
    rng = np.random.default_rng(11)
    victim_res = rng.uniform(
        100.0, 4000.0, size=(n, v, D)
    ).astype(np.float32)
    victim_prio = np.full((n, v), 20, dtype=np.int32)
    victim_mask = np.ones((n, v), dtype=bool)
    found = find_preemption_kernel(
        capacity, used, ask, eligible, victim_res, victim_prio,
        victim_mask,
    )
    return found, choose_preemption_node_kernel(
        capacity, used, ask, eligible, victim_res, victim_prio,
        victim_mask, np.zeros((n, v), dtype=np.int32),
        np.zeros(n, dtype=np.int32),
    )


def run_hetero(policy: int = 0):
    import numpy as np

    from ...scheduler.hetero import hetero_place_kernel

    g, n = 2, N_NODES
    capacity = np.full((n, D), 16000.0, dtype=np.float32)
    used0 = capacity * 0.1
    asks = np.full((g, D), 250.0, dtype=np.float32)
    counts = np.full(g, 2, dtype=np.int32)
    eligible = np.ones((g, n), dtype=bool)
    tp = np.ones((g, n), dtype=np.float32)
    tpmax = np.ones(g, dtype=np.float32)
    cost = np.ones(n, dtype=np.float32)
    return hetero_place_kernel(
        capacity, used0, asks, counts, eligible, tp, tpmax, cost,
        policy=policy, steps=8, max_c=4,
    )


def run_cp():
    import numpy as np

    from ...device.cp import cp_place_kernel

    g, n = 2, N_NODES
    capacity = np.full((n, D), 16000.0, dtype=np.float32)
    used0 = capacity * 0.1
    asks = np.full((g, D), 250.0, dtype=np.float32)
    counts = np.full(g, 2, dtype=np.int32)
    eligible = np.ones((g, n), dtype=bool)
    scores = np.linspace(
        0.1, 0.9, g * n, dtype=np.float32
    ).reshape(g, n)
    prio = np.full(g, 50.0, dtype=np.float32)
    job_counts = np.zeros((g, n), dtype=np.int32)
    distinct = np.zeros(g, dtype=bool)
    jobgrp = np.arange(g, dtype=np.int32)
    lam0 = np.zeros(n, dtype=np.float32)
    return cp_place_kernel(
        capacity, used0, asks, counts, eligible, scores, prio,
        job_counts, distinct, jobgrp, lam0, steps=8, max_c=4,
    )


def run_cp_gang():
    import numpy as np

    from ...device.cp import cp_gang_place_kernel

    g, n, levels = 2, N_NODES, 4
    capacity = np.full((n, D), 16000.0, dtype=np.float32)
    used0 = capacity * 0.1
    asks = np.full((g, D), 250.0, dtype=np.float32)
    counts = np.full(g, 2, dtype=np.int32)
    eligible = np.ones((g, n), dtype=bool)
    scores = np.linspace(
        0.1, 0.9, g * n, dtype=np.float32
    ).reshape(g, n)
    prio = np.full(g, 50.0, dtype=np.float32)
    job_counts = np.zeros((g, n), dtype=np.int32)
    distinct = np.zeros(g, dtype=bool)
    jobgrp = np.zeros(g, dtype=np.int32)
    gang = np.ones(g, dtype=np.int32)  # both groups in gang 1
    w_rack = np.full(g, 1.0, dtype=np.float32)
    w_pod = np.zeros(g, dtype=np.float32)
    w_ici = np.full(g, 0.5, dtype=np.float32)
    rack_oh = np.zeros((n, levels), dtype=np.int32)
    rack_oh[np.arange(n), 1 + np.arange(n) % (levels - 1)] = 1
    pod_oh = np.zeros((n, 2), dtype=np.int32)
    pod_oh[:, 1] = 1
    ici_oh = np.zeros((n, levels * 2), dtype=np.int32)
    ici_oh[np.arange(n), 1 + np.arange(n) % (levels * 2 - 1)] = 1
    lam0 = np.zeros(n, dtype=np.float32)
    return cp_gang_place_kernel(
        capacity, used0, asks, counts, eligible, scores, prio,
        job_counts, distinct, jobgrp, gang, w_rack, w_pod, w_ici,
        rack_oh, pod_oh, ici_oh, lam0, steps=8, max_c=4,
    )


def run_migrate():
    """migrate_plan_kernel: the defrag plane's bounded-budget move
    selection over a small fragmented fleet."""
    import numpy as np

    from ...device.migrate import migrate_plan_kernel

    a, n = 4, N_NODES
    capacity = np.full((n, D), 16000.0, dtype=np.float32)
    used0 = capacity * 0.2
    sizes = np.full((a, D), 500.0, dtype=np.float32)
    cur = (np.arange(a) % n).astype(np.int32)
    eligible = np.ones((a, n), dtype=bool)
    scores = np.linspace(
        0.1, 0.9, a * n, dtype=np.float32
    ).reshape(a, n)
    cur_scores = scores[np.arange(a), cur]
    move_cost = np.full(a, 0.05, dtype=np.float32)
    lam0 = np.zeros(n, dtype=np.float32)
    return migrate_plan_kernel(
        capacity, used0, sizes, cur, eligible, scores, cur_scores,
        move_cost, np.int32(2), lam0, steps=8,
    )


def exercise_fleet(explain: bool = False) -> dict:
    """Run the whole fleet exercise to completion on the device;
    returns the kernel registry afterwards (every production kernel now
    has a recorded spec and has executed once)."""
    import jax

    from ...utils import backend
    from .retracer import import_fleet

    import_fleet()
    run_placement_paths(explain=explain)
    jax.block_until_ready([
        run_score_matrix(),
        run_preemption(),
        run_hetero(),
        run_cp(),
        run_cp_gang(),
        run_migrate(),
    ])
    return backend.kernel_registry()
