"""Heterogeneity-aware placement policies over the dense score matrix.

Gavel (PAPERS.md, arxiv 2008.09213) observes that once jobs carry
per-accelerator-class throughput coefficients, heterogeneity-aware
policies — max-min fairness, makespan minimization, cost-aware packing —
all become optimization passes over one (jobs × nodes) effective-rate
matrix. This module is that substrate for nomad-tpu: nodes declare a
``device_class`` (structs/node.py, folded into the computed class),
jobs declare ``throughputs`` (structs/job.py), the flattener gathers
them into per-node coefficient vectors (device/flatten.py
``job_throughput_vector``), and the policies here run a joint greedy
pass over the whole batch.

Three policies, all the same slot-at-a-time greedy skeleton with a
different (job-pick, node-pick) key pair:

``hetero-maxmin``
    each step gives the next slot to the job with the LOWEST normalized
    throughput share (accumulated rate ÷ ideal rate), on its fastest
    feasible node — discrete water-filling of Gavel's max-min objective.
``hetero-makespan``
    each step gives the next slot to the job with the LARGEST modeled
    completion time (remaining work ÷ accumulated rate), on its fastest
    feasible node — the LPT rule specialized to rate accumulation.
``hetero-cost``
    slots go to jobs most-remaining-first, each on the feasible node
    maximizing throughput-per-cost (per-class costs from
    ``DEVICE_CLASS_COSTS``; unknown classes cost 1.0).

Every policy has TWO implementations sharing one step definition: a
jitted device kernel (``lax.fori_loop``) and a pure-NumPy host oracle
(``oracle_hetero_place``). The pass is pinned BYTE-identical between
them the way device/parity.py pins binpack/spread: every carried value
is f32, every step does the same multiplies/divides/adds in the same
order, and ties break on the first index (both ``jnp.argmax`` and
``np.argmax`` take the first maximum).

Class-less batches never reach this module: ``HeteroPlacementKernel``
delegates to the base ``PlacementKernel`` whenever no ask carries a
throughput vector, so pre-heterogeneity clusters place bit-identically
to the binpack/spread kernels.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from ..utils.backend import traced_jit

import jax
import jax.numpy as jnp

# Policy ids (the step kernels branch on these as static ints).
POLICY_MAXMIN = 0
POLICY_MAKESPAN = 1
POLICY_COST = 2

POLICY_IDS = {
    "maxmin": POLICY_MAXMIN,
    "makespan": POLICY_MAKESPAN,
    "cost": POLICY_COST,
}

# Canonical per-device-class relative cost (hetero-cost's denominator).
# Operators override per deployment; unknown classes cost 1.0 so a fleet
# without declared costs degrades to pure throughput maximization.
DEVICE_CLASS_COSTS: dict[str, float] = {
    "": 1.0,
    "cpu": 1.0,
    "tpu-v4": 2.5,
    "tpu-v5e": 2.0,
    "tpu-v5p": 4.0,
    "gpu-a100": 3.0,
    "gpu-h100": 5.0,
}

_EPS = np.float32(1e-9)

# Where the policies' throughput matrix comes from (SchedulerConfiguration
# knob; obs/calibrate.py owns "learned"). Declared is the PR-9 behavior.
THROUGHPUT_DECLARED = "declared"
THROUGHPUT_LEARNED = "learned"
THROUGHPUT_SOURCES = (THROUGHPUT_DECLARED, THROUGHPUT_LEARNED)


def class_cost_vector(ct, costs: dict | None = None) -> np.ndarray:
    """Per-node cost f32[N] from the fleet's device-class column."""
    ids, vocab = ct.device_class_column()
    table = DEVICE_CLASS_COSTS if costs is None else costs
    per_class = np.ones(len(vocab), dtype=np.float32)
    for name, cid in vocab.items():
        per_class[cid] = np.float32(table.get(name, 1.0))
    return per_class[ids]


def _steps_bucket(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


# -- the shared greedy step --------------------------------------------------
#
# Carry: used f32[N, D], placed i32[G], accum f32[G] (Σ tp of assigned
# nodes), choices i32[G, C], choice_tp f32[G, C]. One step = pick a job
# by the policy's fairness key, pick its node by the policy's node key,
# commit. Infeasible/done lanes key to ±inf and the step masks to a
# no-op when nothing is placeable, so padded steps are exact no-ops —
# the property that lets the device loop run a bucketed step count
# while the host oracle runs exactly as many steps as it needs.


def _job_keys(policy, placed, accum, counts, tpmax, placeable):
    """f32[G] selection key, argmin semantics; +inf = not selectable."""
    countsf = counts.astype(np.float32) if isinstance(counts, np.ndarray) \
        else counts.astype(jnp.float32)
    xp = np if isinstance(placed, np.ndarray) else jnp
    placedf = placed.astype(xp.float32)
    if policy == POLICY_MAXMIN:
        ideal = countsf * tpmax  # rate if every slot ran on the best class
        key = accum / xp.maximum(ideal, _EPS)  # share in [0, 1]
    elif policy == POLICY_MAKESPAN:
        # modeled completion time = total work / accumulated rate; jobs
        # with no rate yet sort first (longest possible time)
        key = -(countsf / xp.maximum(accum, _EPS))
    else:  # POLICY_COST — most remaining work first
        key = -(countsf - placedf)
    big = xp.float32(np.inf)
    return xp.where(placeable, key, big)


def _node_keys(policy, tp_row, cost, feasible):
    """f32[N] node key, argmax semantics; -inf = infeasible."""
    xp = np if isinstance(tp_row, np.ndarray) else jnp
    if policy == POLICY_COST:
        key = tp_row / xp.maximum(cost, _EPS)
    else:
        key = tp_row
    return xp.where(feasible, key, -xp.float32(np.inf))


def _feasible_matrix(capacity, used, asks, eligible, tp):
    """bool[G, N]: room for one more instance ∧ eligible ∧ tp > 0."""
    xp = np if isinstance(capacity, np.ndarray) else jnp
    proposed = used[None, :, :] + asks[:, None, :]  # [G, N, D]
    fits = xp.all(proposed <= capacity[None, :, :], axis=-1)
    return fits & eligible & (tp > 0.0)


@functools.partial(
    traced_jit, retrace_budget=16, static_argnames=("policy", "steps", "max_c")
)
def hetero_place_kernel(
    capacity,  # f32[N, D]
    used0,  # f32[N, D]
    asks,  # f32[G, D]
    counts,  # i32[G]
    eligible,  # bool[G, N]
    tp,  # f32[G, N] per-node throughput coefficients
    tpmax,  # f32[G] max coefficient over each job's eligible nodes
    cost,  # f32[N]
    policy: int,
    steps: int,
    max_c: int,
):
    """Joint greedy hetero pass on device. Returns (choices i32[G, C],
    choice_tp f32[G, C], used f32[N, D]) — C = max_c, -1 = unfilled."""
    g, n = tp.shape

    def step(_, carry):
        used, placed, accum, choices, choice_tp = carry
        feas = _feasible_matrix(capacity, used, asks, eligible, tp)
        active = placed < counts
        placeable = active & jnp.any(feas, axis=1)
        jkey = _job_keys(policy, placed, accum, counts, tpmax, placeable)
        j = jnp.argmin(jkey)
        any_placeable = jnp.any(placeable)
        nkey = _node_keys(policy, tp[j], cost, feas[j])
        node = jnp.argmax(nkey)
        do = any_placeable
        slot = placed[j]
        used = jnp.where(
            do,
            used.at[node].add(asks[j]),
            used,
        )
        choices = jnp.where(
            do, choices.at[j, slot].set(node.astype(jnp.int32)), choices
        )
        choice_tp = jnp.where(
            do, choice_tp.at[j, slot].set(tp[j, node]), choice_tp
        )
        placed = jnp.where(do, placed.at[j].add(1), placed)
        accum = jnp.where(do, accum.at[j].add(tp[j, node]), accum)
        return used, placed, accum, choices, choice_tp

    carry = (
        used0,
        jnp.zeros(g, dtype=jnp.int32),
        jnp.zeros(g, dtype=jnp.float32),
        jnp.full((g, max_c), -1, dtype=jnp.int32),
        jnp.zeros((g, max_c), dtype=jnp.float32),
    )
    used, placed, accum, choices, choice_tp = jax.lax.fori_loop(
        0, steps, step, carry
    )
    return choices, choice_tp, used


def oracle_hetero_place(
    capacity: np.ndarray,
    used0: np.ndarray,
    asks: np.ndarray,
    counts: np.ndarray,
    eligible: np.ndarray,
    tp: np.ndarray,
    tpmax: np.ndarray,
    cost: np.ndarray,
    policy: int,
    steps: int,
    max_c: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pure-NumPy host oracle: the same step math as the device kernel,
    executed stepwise. Byte-identical output is the contract (pinned in
    tests/test_hetero.py the way device/parity.py pins binpack)."""
    g = tp.shape[0]
    used = used0.astype(np.float32).copy()
    placed = np.zeros(g, dtype=np.int32)
    accum = np.zeros(g, dtype=np.float32)
    choices = np.full((g, max_c), -1, dtype=np.int32)
    choice_tp = np.zeros((g, max_c), dtype=np.float32)
    counts = counts.astype(np.int32)
    for _ in range(steps):
        feas = _feasible_matrix(capacity, used, asks, eligible, tp)
        active = placed < counts
        placeable = active & feas.any(axis=1)
        if not placeable.any():
            continue  # exact no-op, like the device loop's masked step
        jkey = _job_keys(policy, placed, accum, counts, tpmax, placeable)
        j = int(np.argmin(jkey))
        nkey = _node_keys(policy, tp[j], cost, feas[j])
        node = int(np.argmax(nkey))
        slot = int(placed[j])
        used[node] = used[node] + asks[j]
        choices[j, slot] = node
        choice_tp[j, slot] = tp[j, node]
        placed[j] += 1
        accum[j] = accum[j] + tp[j, node]
    return choices, choice_tp, used


# -- PlacementKernel-compatible wrapper --------------------------------------


@dataclass
class HeteroBatch:
    """Assembled dense inputs for one joint hetero pass."""

    capacity: np.ndarray
    used: np.ndarray
    asks: np.ndarray
    counts: np.ndarray
    eligible: np.ndarray
    tp: np.ndarray
    tpmax: np.ndarray
    cost: np.ndarray
    steps: int
    max_c: int


def build_hetero_batch(cluster, asks: list, used_override=None) -> HeteroBatch:
    pn = cluster.padded_n
    g = len(asks)
    ask_m = np.stack([a.ask for a in asks]).astype(np.float32)
    counts = np.array([a.count for a in asks], dtype=np.int32)
    eligible = np.stack([a.eligible for a in asks])
    tp = np.ones((g, pn), dtype=np.float32)
    for i, a in enumerate(asks):
        if a.throughputs is not None:
            tp[i] = a.throughputs
    elig_tp = np.where(eligible, tp, np.float32(0.0))
    tpmax = elig_tp.max(axis=1).astype(np.float32)
    used = (
        used_override if used_override is not None else cluster.used
    ).astype(np.float32)
    total = int(counts.sum())
    return HeteroBatch(
        capacity=cluster.capacity.astype(np.float32),
        used=used,
        asks=ask_m,
        counts=counts,
        eligible=eligible,
        tp=tp,
        tpmax=tpmax,
        cost=class_cost_vector(cluster),
        steps=_steps_bucket(max(total, 1)),
        max_c=_steps_bucket(max(int(counts.max(initial=1)), 1)),
    )


class HeteroPlacementKernel:
    """Drop-in for device/score.py's PlacementKernel behind the algorithm
    registry: hetero batches run the joint policy pass; anything the
    policy doesn't model (class-less batches, spread/distinct coupling,
    device-slot caps) delegates to the base binpack kernel so behavior
    degrades to exactly the pre-heterogeneity placement."""

    def __init__(
        self,
        policy: str,
        force_scan: bool = False,
        mesh=None,
        throughput_source: str = "declared",
        estimator=None,
    ):
        from ..device.score import PlacementKernel

        if policy not in POLICY_IDS:
            raise ValueError(f"unknown hetero policy {policy!r}")
        if throughput_source not in THROUGHPUT_SOURCES:
            raise ValueError(
                f"unknown throughput source {throughput_source!r}"
            )
        self.policy = policy
        self.policy_id = POLICY_IDS[policy]
        self.algorithm_spread = False
        self.force_scan = force_scan
        self._mesh = mesh
        # calibration seam (obs/calibrate.py): in learned mode the batch's
        # declared tp matrix is substituted — same shape and dtype, pure
        # Python, so the jitted kernel never retraces. Declared mode never
        # consults the estimator at all (bit-identity gate).
        self.throughput_source = throughput_source
        self.estimator = estimator
        self._base = PlacementKernel("binpack", force_scan, mesh=mesh)

    def mesh_cfg(self):
        from ..utils.backend import get_mesh

        return self._mesh if self._mesh is not None else get_mesh()

    def _learned(self) -> bool:
        return (
            self.throughput_source == THROUGHPUT_LEARNED
            and self.estimator is not None
        )

    def _hetero_eligible(self, cluster, asks: list) -> bool:
        if not getattr(cluster, "has_device_classes", False):
            return False
        # learned mode qualifies on profile keys alone: the whole point
        # is running the policies on jobs whose declared coefficients are
        # absent (or hidden), estimated from telemetry instead
        if not any(a.has_throughputs for a in asks) and not (
            self._learned()
            and any(getattr(a, "profile", "") for a in asks)
        ):
            return False
        # coupled features stay on the battle-tested base scan
        return not any(
            a.blocks is not None or a.slot_caps is not None
            or a.distinct_hosts
            for a in asks
        )

    def place(self, cluster, asks: list, **kwargs):
        from ..device.score import PlacementResult

        if not asks:
            return []
        if not self._hetero_eligible(cluster, asks):
            return self._base.place(cluster, asks, **kwargs)
        batch = build_hetero_batch(
            cluster, asks, used_override=kwargs.get("used_override")
        )
        if self._learned():
            # Python-level substitution before device upload: learned
            # per-(class × profile) values replace the declared matrix
            # cell-wise (declared anchors stay the fallback below the
            # sample floor), shapes/dtypes unchanged — zero new traces.
            from ..obs.calibrate import learned_tp_matrix

            batch.tp = learned_tp_matrix(
                self.estimator, cluster, asks, batch.tp
            )
            elig_tp = np.where(batch.eligible, batch.tp, np.float32(0.0))
            batch.tpmax = elig_tp.max(axis=1).astype(np.float32)
        from ..device.score import used_device
        from ..utils.backend import shard_put

        cfg = self.mesh_cfg()
        choices, choice_tp, _ = hetero_place_kernel(
            shard_put(batch.capacity, ("nodes",), cfg),
            used_device(cluster, batch.used, cfg),
            shard_put(batch.asks, ("groups",), cfg),
            shard_put(batch.counts, ("groups",), cfg),
            shard_put(batch.eligible, ("groups", "nodes"), cfg),
            shard_put(batch.tp, ("groups", "nodes"), cfg),
            shard_put(batch.tpmax, ("groups",), cfg),
            batch.cost,
            policy=self.policy_id,
            steps=batch.steps,
            max_c=batch.max_c,
        )
        choices = np.asarray(choices)
        choice_tp = np.asarray(choice_tp)
        explain = bool(kwargs.get("explain", False))
        results = []
        for i, a in enumerate(asks):
            rows = choices[i, : a.count].astype(np.int32)
            # score = throughput share of the job's best class, in [0, 1]
            denom = max(float(batch.tpmax[i]), float(_EPS))
            scores = np.where(
                rows >= 0,
                choice_tp[i, : a.count] / np.float32(denom),
                np.float32(-np.inf),
            ).astype(np.float32)
            res = PlacementResult(node_rows=rows, scores=scores)
            if explain:
                # same Python-level gate as the base kernel: explain-off
                # traces and places exactly as before; explanations rank
                # by this policy's node key so the top candidate is the
                # node the joint greedy takes first for this lane
                from ..obs.explain import explain_hetero_group

                res.explanation = explain_hetero_group(
                    cluster, a, batch.used,
                    policy=self.policy,
                    tp_row=batch.tp[i],
                    tpmax=float(batch.tpmax[i]),
                    cost=batch.cost,
                )
            results.append(res)
        return results


# -- seeded mixed-fleet A/B harness (run_hetero_ab) --------------------------


def build_mixed_fleet(
    n_nodes: int, seed: int = 42, classes: tuple[str, ...] = (
        "tpu-v5e", "tpu-v4", "gpu-a100", "cpu"
    )
):
    """Seeded synthetic mixed fleet as ClusterTensors (≥3 device
    classes), mirroring ``mock.build_cluster`` but with a populated
    device-class column."""
    from ..device.flatten import ClusterTensors, node_bucket

    rng = np.random.default_rng(seed)
    pn = node_bucket(n_nodes)
    kind = rng.integers(0, len(classes), size=n_nodes)
    cpu = np.choose(kind % 3, [4000, 8000, 16000]).astype(np.float32)
    mem = np.choose(kind % 3, [8192, 16384, 32768]).astype(np.float32)
    capacity = np.zeros((pn, 4), dtype=np.float32)
    capacity[:n_nodes, 0] = cpu
    capacity[:n_nodes, 1] = mem
    capacity[:n_nodes, 2] = 100 * 1024
    capacity[:n_nodes, 3] = 1000
    used = np.zeros_like(capacity)
    load = rng.uniform(0.0, 0.3, size=(n_nodes, 1)).astype(np.float32)
    used[:n_nodes, :2] = capacity[:n_nodes, :2] * load
    ready = np.zeros(pn, dtype=bool)
    ready[:n_nodes] = True
    device_class_vocab = {"": 0}
    for c in classes:
        device_class_vocab[c] = len(device_class_vocab)
    device_class_ids = np.zeros(pn, dtype=np.int32)
    device_class_ids[:n_nodes] = kind.astype(np.int32) + 1
    return ClusterTensors(
        node_ids=[f"node-{i}" for i in range(n_nodes)],
        index=1,
        num_nodes=n_nodes,
        capacity=capacity,
        used=used,
        ready=ready,
        dc_ids=np.zeros(pn, dtype=np.int32),
        class_ids=np.pad(kind.astype(np.int32), (0, pn - n_nodes)),
        dc_vocab={"dc1": 0},
        class_vocab={c: i for i, c in enumerate(classes)},
        class_rep=list(range(min(len(classes), n_nodes))),
        node_row={f"node-{i}": i for i in range(n_nodes)},
        device_class_ids=device_class_ids,
        device_class_vocab=device_class_vocab,
    )


def build_mixed_asks(ct, n_jobs: int, count_per_job: int, seed: int = 7):
    """Seeded GroupAsks with per-class throughput maps: some jobs are
    TPU-hungry, some GPU-leaning, some indifferent — the mixed workload
    Gavel's policies differentiate on."""
    from ..device.flatten import GroupAsk

    rng = np.random.default_rng(seed)
    ids, vocab = ct.device_class_column()
    names = [n for n in vocab if n]
    pn = ct.padded_n
    profiles = []
    for j in range(n_jobs):
        kindj = j % 3
        m: dict[str, float] = {}
        for c in names:
            if kindj == 0:  # accelerator-hungry: fast on TPUs
                m[c] = 4.0 if c.startswith("tpu") else (
                    2.0 if c.startswith("gpu") else 0.5
                )
            elif kindj == 1:  # GPU-leaning
                m[c] = 3.5 if c.startswith("gpu") else (
                    1.5 if c.startswith("tpu") else 0.75
                )
            else:  # CPU-leaning batch (accelerators waste on it)
                m[c] = 1.0 if c == "cpu" else (
                    0.9 if c.startswith("tpu") else 0.6
                )
        profiles.append(m)
    asks = []
    for j, m in enumerate(profiles):
        per_class = np.ones(len(vocab), dtype=np.float32)
        for name, cid in vocab.items():
            if name:
                per_class[cid] = np.float32(m.get(name, 1.0))
        vec = per_class[ids]
        has_tp = not bool(np.all(vec == np.float32(1.0)))
        cpu = float(rng.choice([500, 1000, 2000]))
        memv = float(rng.choice([512, 1024, 2048]))
        asks.append(
            GroupAsk(
                job_id=f"job-{j}",
                tg_name="web",
                count=count_per_job,
                desired_total=count_per_job,
                ask=np.array([cpu, memv, 300.0, 0.0], dtype=np.float32),
                eligible=ct.ready.copy(),
                job_counts=np.zeros(pn, dtype=np.int32),
                penalty_nodes=np.zeros(pn, dtype=bool),
                affinity_scores=np.zeros(pn, dtype=np.float32),
                has_affinities=False,
                distinct_hosts=False,
                throughputs=vec if has_tp else None,
                has_throughputs=has_tp,
            )
        )
    return asks


def _quality_metrics(ct, asks, results) -> dict:
    """Canonical placement-quality block for one algorithm's output."""
    ids, vocab = ct.device_class_column()
    names = {cid: name for name, cid in vocab.items()}
    per_class_alloc: dict[str, int] = {}
    per_class_cpu_used: dict[str, float] = {}
    cost_vec = class_cost_vector(ct)
    shares = []
    makespans = []
    total_cost = 0.0
    total_rate = 0.0
    placed = 0
    for a, r in zip(asks, results):
        tp_vec = (
            a.throughputs
            if a.throughputs is not None
            else np.ones(ct.padded_n, dtype=np.float32)
        )
        rows = r.node_rows[r.node_rows >= 0]
        placed += int(rows.size)
        rate = float(tp_vec[rows].sum(dtype=np.float32))
        elig_tp = np.where(a.eligible, tp_vec, 0.0)
        ideal = float(elig_tp.max()) * a.count
        shares.append(rate / ideal if ideal > 0 else 0.0)
        makespans.append(a.count / rate if rate > 0 else float("inf"))
        total_cost += float(cost_vec[rows].sum(dtype=np.float32))
        total_rate += rate
        for row in rows:
            name = names.get(int(ids[row]), "")
            per_class_alloc[name] = per_class_alloc.get(name, 0) + 1
            per_class_cpu_used[name] = per_class_cpu_used.get(name, 0.0) + float(
                a.ask[0]
            )
    class_cap: dict[str, float] = {}
    for i in range(ct.num_nodes):
        name = names.get(int(ids[i]), "")
        class_cap[name] = class_cap.get(name, 0.0) + float(ct.capacity[i, 0])
    utilization = {
        name: round(per_class_cpu_used.get(name, 0.0) / cap, 4)
        for name, cap in sorted(class_cap.items())
        if cap > 0
    }
    return {
        "placed": placed,
        "worst_share": round(min(shares), 4) if shares else 0.0,
        "mean_share": round(float(np.mean(shares)), 4) if shares else 0.0,
        "makespan": round(max(makespans), 4) if makespans else 0.0,
        "throughput_per_cost": round(total_rate / total_cost, 4)
        if total_cost > 0
        else 0.0,
        "per_class_allocs": dict(sorted(per_class_alloc.items())),
        "per_class_cpu_utilization": utilization,
    }


def run_hetero_ab(
    n_nodes: int = 1000,
    n_jobs: int = 12,
    count_per_job: int = 25,
    seed: int = 42,
) -> dict:
    """The hetero A/B report (tier-1: ``tests/test_hetero.py``
    ``TestMixedFleetAB``): binpack vs each hetero policy on
    one seeded mixed fleet. Placements are deterministic for a seed, so
    the whole report is byte-reproducible (chaos/soak-report style).
    Also cross-checks each policy's device pass against its host oracle
    and reports the mismatch count (must be 0)."""
    from ..device.score import PlacementKernel

    ct = build_mixed_fleet(n_nodes, seed=seed)
    asks = build_mixed_asks(ct, n_jobs, count_per_job, seed=seed + 1)

    base = PlacementKernel("binpack")
    base_results = base.place(ct, asks)
    report: dict = {
        "config": {
            "nodes": n_nodes,
            "jobs": n_jobs,
            "count_per_job": count_per_job,
            "seed": seed,
            "device_classes": sorted(
                k for k in ct.device_class_vocab if k
            ),
        },
        "binpack": _quality_metrics(ct, asks, base_results),
        "policies": {},
        "oracle_mismatches": 0,
    }
    for policy in ("maxmin", "makespan", "cost"):
        kern = HeteroPlacementKernel(policy)
        results = kern.place(ct, asks)
        metrics = _quality_metrics(ct, asks, results)
        batch = build_hetero_batch(ct, asks)
        o_choices, o_tp, _ = oracle_hetero_place(
            batch.capacity, batch.used, batch.asks, batch.counts,
            batch.eligible, batch.tp, batch.tpmax, batch.cost,
            POLICY_IDS[policy], batch.steps, batch.max_c,
        )
        d_choices, d_tp, _ = hetero_place_kernel(
            batch.capacity, batch.used, batch.asks, batch.counts,
            batch.eligible, batch.tp, batch.tpmax, batch.cost,
            policy=POLICY_IDS[policy], steps=batch.steps,
            max_c=batch.max_c,
        )
        mism = int(
            (np.asarray(d_choices) != o_choices).sum()
            + (np.asarray(d_tp).view(np.uint32) != o_tp.view(np.uint32)).sum()
        )
        metrics["oracle_identical"] = mism == 0
        report["oracle_mismatches"] += mism
        report["policies"][f"hetero-{policy}"] = metrics

    b = report["binpack"]
    mm = report["policies"]["hetero-maxmin"]
    ms = report["policies"]["hetero-makespan"]
    report["ab"] = {
        "maxmin_worst_share_delta": round(
            mm["worst_share"] - b["worst_share"], 4
        ),
        "makespan_delta": round(b["makespan"] - ms["makespan"], 4),
        "maxmin_improves_worst_share": mm["worst_share"] > b["worst_share"],
        "makespan_reduced": ms["makespan"] < b["makespan"],
    }
    report["ok"] = (
        report["ab"]["maxmin_improves_worst_share"]
        and report["ab"]["makespan_reduced"]
        and report["oracle_mismatches"] == 0
    )
    return report
