"""Placement explainability — score provenance from the dense kernels.

The reference answers "why did alloc X land on node Y" with the
per-node iterator chain's AllocMetric/ScoreMetaData trail (structs.go
:10034-10079): every node the stack walked leaves a score row the CLI
renders. Our batched kernels (device/score.py) collapse that walk into
one dense pass and return only the winning rows, so the trail has to be
*reconstructed* from the same component math instead of recorded along
the way.

This module is that reconstruction — the one seam raw score data may
cross on its way to an operator (lint rule NTA014 polices the
scheduler/server side). Three pieces:

- ``PlacementExplanation``: per-group top-k candidate nodes with the
  per-component score breakdown (fit, anti-affinity, reschedule
  penalty, affinity, spread boost, throughput), a feasibility-rejection
  histogram bucketed by structured reason, and the committed placement
  rows.
- ``explain_group`` / ``explain_hetero_group``: host-side NumPy mirrors
  of the kernels' component semantics (the same math as
  ``device.score._rescore_pick``, which the conflict-repair walk
  already trusts as the exact oracle). Explanations are *observational*:
  they never feed back into placement, so explain-on and explain-off
  place bit-identically, and no new jitted program exists in either
  mode (zero extra retraces by construction).
- ``finalize_explanations``: post-repair pass that stamps the
  *committed* rows (conflict repair may move placements after the
  kernel returns) and derives per-instance score breakdowns by
  replaying the lane's placements against a lane-local overlay. The
  overlay is computed, not stepped: once repair is done the placement
  order is fixed, so the usage, collision count and per-value spread
  counts instance i was scored against are the base snapshot plus
  running sums over instances 0..i-1, formed for the whole lane in one
  array pass. Addition commutes, so those sums are the state a
  sequential walk would hold at i.

Candidate ranking is computed against the same base usage snapshot the
kernel pass scored against, so on an uncontended pass the top-1
candidate is exactly the node greedy placement committed first — the
provenance property the parity tests pin across seeds and algorithms.
Decorrelated batch passes add per-lane tie-break jitter (~1e-5) the
explanation deliberately omits: the ranking shown is the jitter-free
score, while ``placed_nodes`` always reflects what actually committed.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..structs.alloc import NodeScoreMeta
from ..structs.resources import BINPACK_MAX_SCORE, RESOURCE_DIMS

EXPLAIN_SCHEMA_VERSION = 1
DEFAULT_TOP_K = 5

# structured feasibility-rejection reasons (the histogram keys). A node
# lands in exactly one of ineligible/class-infeasible/distinct-hosts,
# or in one-or-more exhausted:* axis buckets (a node short on cpu AND
# memory counts in both, matching AllocMetric.dimension_exhausted).
REJECT_INELIGIBLE = "ineligible"
REJECT_CLASS_INFEASIBLE = "class-infeasible"
REJECT_DISTINCT_HOSTS = "distinct-hosts"
REJECT_PENALTY = "penalty-excluded"


def _exhausted_key(dim: str) -> str:
    return f"exhausted:{dim}"


@dataclass
class CandidateExplanation:
    """One candidate node's first-instance score breakdown."""

    node_id: str = ""
    node_row: int = -1
    final_score: float = 0.0
    components: dict[str, float] = field(default_factory=dict)
    # committed instances of this group on this node (filled post-repair)
    placed: int = 0


@dataclass
class PlacementExplanation:
    """Why one task group's placements landed where they did.

    Threaded onto ``PlacementResult.explanation`` by the placement
    kernels when explain is on, stamped into ``failed_tg_allocs`` for
    unplaced groups and the flight recorder's explanation ring for
    placed ones (scheduler/generic.py, scheduler/system.py)."""

    schema_version: int = EXPLAIN_SCHEMA_VERSION
    job_id: str = ""
    tg_name: str = ""
    algorithm: str = ""
    policy: str = ""  # hetero policy name when the joint pass scored
    nodes_evaluated: int = 0
    feasible_nodes: int = 0
    top_candidates: list[CandidateExplanation] = field(default_factory=list)
    rejections: dict[str, int] = field(default_factory=dict)
    # committed node ids in placement order (post conflict repair)
    placed_nodes: list[str] = field(default_factory=list)
    # CP solver provenance when the cp-pack joint pass scored
    # (scheduler/cp.py): {"iterations", "gap", "agreement"}. None for
    # every other algorithm — the JSON shape only grows a "cp" block
    # when the solver ran, so existing schema pins are untouched.
    cp: dict | None = None
    # gang provenance when the cp-gang pass scored a gang member
    # (scheduler/cp.py): {"gang_id", "members", "topology_score",
    # "release_rounds"}. None otherwise — same only-grows contract.
    gang: dict | None = None


def _feasibility(capacity, used, a, n: int, throughputs=None):
    """Shared feasibility split: returns (fits bool[n], rejections dict).

    Bucketing mirrors the kernels' gates in order: eligibility, the
    hetero class gate (tp==0 ⇒ the job cannot progress on that class),
    distinct_hosts, then per-resource-axis capacity — a node is counted
    under the FIRST gate that rejects it, except the axis buckets which
    count every short dimension (AllocMetric.dimension_exhausted
    semantics, rank.go:483)."""
    elig = np.asarray(a.eligible[:n], dtype=bool)
    rejections: dict[str, int] = {}
    n_inelig = int(n - elig.sum())
    if n_inelig:
        rejections[REJECT_INELIGIBLE] = n_inelig

    alive = elig.copy()
    if throughputs is not None:
        class_dead = alive & (np.asarray(throughputs[:n]) <= 0.0)
        k = int(class_dead.sum())
        if k:
            rejections[REJECT_CLASS_INFEASIBLE] = k
        alive &= ~class_dead
    if a.distinct_hosts:
        dh_dead = alive & (np.asarray(a.job_counts[:n]) > 0)
        k = int(dh_dead.sum())
        if k:
            rejections[REJECT_DISTINCT_HOSTS] = k
        alive &= ~dh_dead

    prop = used[:n] + a.ask[None, :]
    short = prop > capacity[:n]  # [n, D]
    for d, dim in enumerate(RESOURCE_DIMS):
        k = int((alive & short[:, d]).sum())
        if k:
            rejections[_exhausted_key(dim)] = k
    fits_cap = ~short.any(axis=1)
    if a.slot_caps is not None:
        dev_dead = alive & fits_cap & (np.asarray(a.slot_caps[:n]) < 1)
        k = int(dev_dead.sum())
        if k:
            rejections[_exhausted_key("devices")] = k
        alive &= ~dev_dead
    fits = alive & fits_cap
    # reschedule-penalized nodes are feasible but score -1 on that
    # component; surfaced in the histogram because in practice they are
    # excluded from winning whenever any unpenalized node fits
    if fits.any():
        k = int((fits & np.asarray(a.penalty_nodes[:n], dtype=bool)).sum())
        if k:
            rejections[REJECT_PENALTY] = k
    return fits, rejections


def _final_vector(
    capacity, used, a, n: int, fits, counts, algorithm_spread,
    throughputs=None, desired_total=None, rows=None,
):
    """Vectorized first-instance final score f32[n] (-inf infeasible) —
    the ranking pass. Same formulation as device.score._rescore_pick
    (the host oracle conflict repair already trusts) so the candidate
    order agrees with what greedy placement picks.

    ``rows`` (i64[m], ascending) restricts the pass to a candidate
    subset — the sharded-node-axis path, where pulling full score rows
    back to host would defeat the mesh; the returned vector is then
    length m, aligned with ``rows``, and ``fits`` must already be
    row-aligned."""
    from ..device.score import (
        BLOCK_DISTINCT_CAP,
        _host_block_tables,
    )

    idx = slice(None, n) if rows is None else rows
    m = n if rows is None else len(rows)
    prop = used[idx] + a.ask[None, :]
    free = np.where(
        capacity[idx] > 0,
        (capacity[idx] - prop) / np.maximum(capacity[idx], 1e-9),
        1.0,
    )
    pow_sum = 10.0 ** free[:, 0] + 10.0 ** free[:, 1]
    binpack = np.clip(20.0 - pow_sum, 0.0, BINPACK_MAX_SCORE)
    spread_fit = np.clip(pow_sum - 2.0, 0.0, BINPACK_MAX_SCORE)
    fit = (spread_fit if algorithm_spread else binpack) / BINPACK_MAX_SCORE
    jc = np.asarray(a.job_counts)[idx]
    coll = jc.astype(np.float32)
    dt = a.desired_total if desired_total is None else desired_total
    anti = np.where(jc > 0, -(coll + 1.0) / max(dt, 1.0), 0.0)
    pen = np.asarray(a.penalty_nodes, dtype=bool)[idx]
    resched = np.where(pen, -1.0, 0.0)
    aff = a.affinity_scores[idx] if a.has_affinities else 0.0
    boost = np.zeros(m, dtype=np.float32)
    has_spread_any = False
    if a.blocks is not None and counts is not None:
        tbl_boost, _allow = _host_block_tables(counts, a.blocks)
        for b in range(a.blocks.num_blocks):
            if a.blocks.kinds[b] == BLOCK_DISTINCT_CAP:
                continue
            has_spread_any = True
            vids = a.blocks.value_ids[b][idx]
            safe = np.maximum(vids, 0)
            boost += np.where(vids >= 0, tbl_boost[b][safe], -1.0)
    spread_on = has_spread_any & (boost != 0.0)
    num = fit + anti + resched + aff + np.where(spread_on, boost, 0.0)
    den = (
        1.0
        + (jc > 0)
        + pen
        + (1.0 if a.has_affinities else 0.0)
        + spread_on
    )
    if throughputs is not None:
        tp = np.asarray(throughputs)[idx]
        num = num + tp
        den = den + 1.0
    return np.where(fits, num / den, -np.inf)


def _components_at(
    capacity, used, a, rows, placed_on_rows, counts, algorithm_spread,
    throughputs=None, desired_total=None,
):
    """Per-component breakdown for ``rows`` (same math and component
    join rules as device.score._rescore_pick / component_scores).
    ``placed_on_rows`` is this lane's prior instance count per row (0
    for the first-instance candidate view). Returns a list of
    (components dict, final) aligned with rows."""
    from ..device.score import (
        BLOCK_DISTINCT_CAP,
        _host_block_tables,
    )

    fit_name = "spread-fit" if algorithm_spread else "binpack"
    blocks = a.blocks
    boost_tbl = None
    if blocks is not None and counts is not None:
        boost_tbl, _allow = _host_block_tables(counts, blocks)
    out = []
    for row, mine in zip(rows, placed_on_rows):
        prop = used[row] + a.ask
        free = np.where(
            capacity[row] > 0,
            (capacity[row] - prop) / np.maximum(capacity[row], 1e-9),
            1.0,
        )
        pow_sum = 10.0 ** float(free[0]) + 10.0 ** float(free[1])
        binpack = float(np.clip(20.0 - pow_sum, 0.0, BINPACK_MAX_SCORE))
        spread_fit = float(np.clip(pow_sum - 2.0, 0.0, BINPACK_MAX_SCORE))
        fit = (spread_fit if algorithm_spread else binpack) / BINPACK_MAX_SCORE
        comps = {fit_name: fit}
        num, den = fit, 1.0
        jc = int(a.job_counts[row]) + int(mine)
        if jc > 0:
            dt = a.desired_total if desired_total is None else desired_total
            anti = -(jc + 1.0) / max(dt, 1.0)
            comps["job-anti-affinity"] = anti
            num, den = num + anti, den + 1.0
        if a.penalty_nodes[row]:
            comps["node-reschedule-penalty"] = -1.0
            num, den = num - 1.0, den + 1.0
        if a.has_affinities:
            aff = float(a.affinity_scores[row])
            comps["node-affinity"] = aff
            num, den = num + aff, den + 1.0
        if blocks is not None and boost_tbl is not None:
            boost = 0.0
            spread_any = False
            for b in range(blocks.num_blocks):
                if blocks.kinds[b] == BLOCK_DISTINCT_CAP:
                    continue
                spread_any = True
                v = blocks.value_ids[b, row]
                boost += float(boost_tbl[b][v]) if v >= 0 else -1.0
            if spread_any and boost != 0.0:
                comps["allocation-spread"] = boost
                num, den = num + boost, den + 1.0
        if throughputs is not None:
            tp = float(throughputs[row])
            comps["throughput"] = tp
            num, den = num + tp, den + 1.0
        out.append((comps, num / den))
    return out


def explain_group(
    cluster,
    a,
    used0,
    *,
    algorithm: str = "binpack",
    algorithm_spread: bool = False,
    throughputs=None,
    top_k: int = DEFAULT_TOP_K,
    desired_total=None,
    candidate_rows=None,
) -> PlacementExplanation:
    """Build the candidate/rejection explanation for one group ask
    against the usage snapshot the kernel pass scored with.

    ``throughputs`` is the pre-normalized [0, 1] heterogeneity axis when
    the *scoring* path consumed one (score_group); the base placement
    kernels ignore the axis, so their explanations do too.

    ``candidate_rows`` (ascending node rows) restricts the RANKING pass
    to the columns the kernel's hierarchical top-k already surfaced —
    the node-axis-sharded path, where the per-shard top-k union provably
    contains every global winner, so ranking the union ranks the same
    top candidates without gathering full score rows to host. The
    rejection histogram stays a full host-side pass either way (it reads
    the flattened ask masks, not device score rows)."""
    n = cluster.num_nodes
    capacity = np.asarray(cluster.capacity)
    used = np.asarray(used0)
    fits, rejections = _feasibility(capacity, used, a, n, throughputs)
    ex = PlacementExplanation(
        job_id=a.job_id,
        tg_name=a.tg_name,
        algorithm=algorithm,
        nodes_evaluated=n,
        feasible_nodes=int(fits.sum()),
        rejections=rejections,
    )
    if not fits.any() or a.count <= 0:
        return ex
    counts = a.blocks.counts0 if a.blocks is not None else None
    if candidate_rows is not None:
        rows = np.asarray(candidate_rows, dtype=np.int64)
        rows = np.unique(rows[(rows >= 0) & (rows < n)])
        if rows.size == 0:
            return ex
        finals = _final_vector(
            capacity, used, a, n, fits[rows], counts, algorithm_spread,
            throughputs, desired_total, rows=rows,
        )
        # stable sort over ascending rows: ties keep row order, matching
        # argmax's first-index win (the subset inherits the full
        # ranking's tie-break because rows are ascending)
        pick = np.argsort(-finals, kind="stable")[: max(top_k, 1)]
        pick = pick[finals[pick] > -np.inf]
        order = rows[pick]
        finals_by_row = {int(r): finals[i] for i, r in enumerate(rows)}
        finals = np.full(n, -np.inf, dtype=np.float32)
        for r, f in finals_by_row.items():
            finals[r] = f
    else:
        finals = _final_vector(
            capacity, used, a, n, fits, counts, algorithm_spread,
            throughputs, desired_total,
        )
        # stable sort: ties keep row order, matching argmax's
        # first-index win
        order = np.argsort(-finals, kind="stable")[: max(top_k, 1)]
        order = order[finals[order] > -np.inf]
    breakdown = _components_at(
        capacity, used, a, order, np.zeros(len(order)), counts,
        algorithm_spread, throughputs, desired_total,
    )
    ex.top_candidates = [
        CandidateExplanation(
            node_id=cluster.node_ids[int(r)],
            node_row=int(r),
            final_score=float(f),
            components={k: float(v) for k, v in comps.items()},
        )
        for r, (comps, f) in zip(order, breakdown)
    ]
    return ex


def explain_hetero_group(
    cluster,
    a,
    used0,
    *,
    policy: str,
    tp_row,
    tpmax: float,
    cost,
    top_k: int = DEFAULT_TOP_K,
) -> PlacementExplanation:
    """Explanation for one lane of the joint hetero pass. Candidates
    rank by the policy's node key (throughput for maxmin/makespan,
    throughput-per-cost for cost — scheduler/hetero.py _node_keys) so
    the top candidate is the node the joint greedy takes first; the
    reported score stays the tp-share in [0, 1] like PlacementResult."""
    n = cluster.num_nodes
    capacity = np.asarray(cluster.capacity)
    used = np.asarray(used0)
    fits, rejections = _feasibility(capacity, used, a, n, tp_row)
    ex = PlacementExplanation(
        job_id=a.job_id,
        tg_name=a.tg_name,
        algorithm=f"hetero-{policy}",
        policy=policy,
        nodes_evaluated=n,
        feasible_nodes=int(fits.sum()),
        rejections=rejections,
    )
    if not fits.any() or a.count <= 0:
        return ex
    tp = np.asarray(tp_row[:n], dtype=np.float64)
    cost_n = np.asarray(cost[:n], dtype=np.float64)
    key = tp / np.maximum(cost_n, 1e-9) if policy == "cost" else tp
    key = np.where(fits, key, -np.inf)
    order = np.argsort(-key, kind="stable")[: max(top_k, 1)]
    order = order[key[order] > -np.inf]
    denom = max(float(tpmax), 1e-9)
    for r in order:
        comps = {"throughput": float(tp[r] / denom)}
        if policy == "cost":
            comps["cost"] = float(cost_n[r])
            comps["throughput-per-cost"] = float(key[r])
        ex.top_candidates.append(
            CandidateExplanation(
                node_id=cluster.node_ids[int(r)],
                node_row=int(r),
                final_score=float(tp[r] / denom),
                components=comps,
            )
        )
    return ex


def explain_cp_group(
    cluster,
    a,
    used0,
    *,
    scores_row,
    cp: dict | None = None,
    top_k: int = DEFAULT_TOP_K,
) -> PlacementExplanation:
    """Explanation for one group of the joint CP pass (scheduler/cp.py).
    Candidates rank by the group's dense score row — the relaxation's
    objective coefficients, i.e. the node the fractional assignment
    weights highest comes first — and the solver-level provenance
    (iterations, duality-gap proxy, rounded-vs-fractional agreement)
    rides in the ``cp`` block. Stays on the non-hetero finalize path
    (``policy`` empty): per-instance breakdowns replay the same binpack
    component math the score row was built from."""
    n = cluster.num_nodes
    capacity = np.asarray(cluster.capacity)
    used = np.asarray(used0)
    fits, rejections = _feasibility(capacity, used, a, n)
    ex = PlacementExplanation(
        job_id=a.job_id,
        tg_name=a.tg_name,
        algorithm="cp-pack",
        nodes_evaluated=n,
        feasible_nodes=int(fits.sum()),
        rejections=rejections,
        cp=dict(cp) if cp is not None else None,
    )
    if not fits.any() or a.count <= 0:
        return ex
    key = np.where(fits, np.asarray(scores_row[:n], dtype=np.float64),
                   -np.inf)
    order = np.argsort(-key, kind="stable")[: max(top_k, 1)]
    order = order[key[order] > -np.inf]
    for r in order:
        ex.top_candidates.append(
            CandidateExplanation(
                node_id=cluster.node_ids[int(r)],
                node_row=int(r),
                final_score=float(key[r]),
                components={"score-matrix": float(key[r])},
            )
        )
    return ex


def explain_cp_gang(
    cluster,
    a,
    used0,
    *,
    scores_row,
    cp: dict | None = None,
    gang_info: dict | None = None,
    top_k: int = DEFAULT_TOP_K,
) -> PlacementExplanation:
    """Explanation for one group of the cp-gang joint pass: the
    cp-pack explanation plus gang provenance — which gang the group
    belongs to, its member set, the signed topology score its final
    placement achieved, and how many auction rounds the all-or-nothing
    gate held its wins back (release_rounds)."""
    ex = explain_cp_group(
        cluster, a, used0, scores_row=scores_row, cp=cp, top_k=top_k
    )
    ex.algorithm = "cp-gang"
    if gang_info is not None:
        ex.gang = dict(gang_info)
    return ex


# elements of one [instances, values] count array in the finalize replay;
# a longer lane is replayed in chunks that carry the counts over
_REPLAY_CHUNK_ELEMS = 1 << 20


def _instance_block_boost(blocks, rows):
    """Summed spread boost each of one lane's committed instances was
    scored with, f64[M] aligned with ``rows`` (placement order), and
    whether the lane carries a spread block at all.

    The counts instance i saw are ``counts0`` plus one per earlier
    instance on a node of the same value (value id -1 adds nothing): the
    exclusive running sum of the rows' one-hot values, which is the state
    a sequential overlay holds after i increments. Boosts follow
    ``device.score._host_block_tables`` rule for rule, read at the
    instance's own value only. Blocks are independent, so the counts of a
    BLOCK_DISTINCT_CAP block (no boost) are never formed."""
    from ..device.score import (
        BLOCK_DISTINCT_CAP,
        BLOCK_EVEN_SPREAD,
        BLOCK_TARGET_SPREAD,
        even_boost,
    )

    m = len(rows)
    boost = np.zeros(m, dtype=np.float64)
    spread_any = False
    values = np.arange(blocks.num_values)
    step = max(1, _REPLAY_CHUNK_ELEMS // max(blocks.num_values, 1))
    for b in range(blocks.num_blocks):
        kind = blocks.kinds[b]
        if kind == BLOCK_DISTINCT_CAP:
            continue
        spread_any = True
        vids = blocks.value_ids[b][rows]
        base = blocks.counts0[b]
        for s in range(0, m, step):
            v = vids[s:s + step]
            at = (np.arange(len(v)), np.maximum(v, 0))
            onehot = (v[:, None] == values[None, :]).astype(base.dtype)
            after = base + np.cumsum(onehot, axis=0)
            c = after - onehot  # [chunk, V]: the counts before each instance
            base = after[-1]
            c_at = c[at]
            if kind == BLOCK_TARGET_SPREAD:
                d = blocks.desired[b][at[1]]
                val = np.where(
                    d > 0,
                    (d - (c_at + 1.0)) / np.maximum(d, 1e-9)
                    * blocks.weights[b],
                    -1.0,
                )
            elif kind == BLOCK_EVEN_SPREAD:
                val = even_boost(c, blocks.held_at_zero[b][None, :])[at]
            else:
                val = 0.0
            boost[s:s + step] += np.where(v >= 0, val, -1.0)
    return boost, spread_any


def _instance_components_vec(capacity, used0, a, rows, mine, algorithm_spread):
    """Per-instance breakdowns for one lane's committed rows, the whole
    lane in one array pass. Instance i on row r is scored against the
    state a sequential overlay would hold when it reached i, and that
    state is a running sum over the placement order: usage
    ``used0[r] + mine[i] * ask`` (``mine`` = the lane's earlier instances
    on r), collisions ``job_counts[r] + mine[i]``, and for a lane with
    value blocks the per-value counts of ``_instance_block_boost``. Same
    component keys, insertion order and join rules as ``_components_at``.
    Returns (components, final) pairs aligned with ``rows``."""
    fit_name = "spread-fit" if algorithm_spread else "binpack"
    rows = np.asarray(rows, dtype=np.int64)
    mine_i = np.asarray(mine, dtype=np.int64)
    cap = capacity[rows]
    prop = used0[rows] + (mine_i + 1).astype(np.float32)[:, None] * a.ask[None, :]
    free = np.where(
        cap > 0, (cap - prop) / np.maximum(cap, 1e-9), 1.0
    ).astype(np.float64)
    pow_sum = 10.0 ** free[:, 0] + 10.0 ** free[:, 1]
    binpack = np.clip(20.0 - pow_sum, 0.0, BINPACK_MAX_SCORE)
    spread_fit = np.clip(pow_sum - 2.0, 0.0, BINPACK_MAX_SCORE)
    fit = (spread_fit if algorithm_spread else binpack) / BINPACK_MAX_SCORE
    jc = np.asarray(a.job_counts)[rows] + mine_i
    collided = jc > 0
    anti = np.where(collided, -(jc + 1.0) / max(a.desired_total, 1.0), 0.0)
    pen = np.asarray(a.penalty_nodes, dtype=bool)[rows]
    num = fit + anti + np.where(pen, -1.0, 0.0)
    den = 1.0 + collided + pen
    aff = None
    if a.has_affinities:
        aff = np.asarray(a.affinity_scores, dtype=np.float64)[rows]
        num = num + aff
        den = den + 1.0
    boost, spread_any = (
        _instance_block_boost(a.blocks, rows)
        if a.blocks is not None
        else (np.zeros(len(rows)), False)
    )
    spread_on = spread_any & (boost != 0.0)
    num = num + np.where(spread_on, boost, 0.0)
    den = den + spread_on
    finals = (num / den).tolist()
    # plain Python values from here on: the dicts are built per instance
    fit, anti, collided, pen, boost, spread_on = (
        x.tolist() for x in (fit, anti, collided, pen, boost, spread_on)
    )
    if aff is not None:
        aff = aff.tolist()
    out = []
    for i, final in enumerate(finals):
        comps = {fit_name: fit[i]}
        if collided[i]:
            comps["job-anti-affinity"] = anti[i]
        if pen[i]:
            comps["node-reschedule-penalty"] = -1.0
        if aff is not None:
            comps["node-affinity"] = aff[i]
        if spread_on[i]:
            comps["allocation-spread"] = boost[i]
        out.append((comps, final))
    return out


def finalize_explanations(cluster, asks, results, used_override=None) -> dict:
    """Post-repair pass: stamp committed rows into each lane's
    explanation and derive per-instance score breakdowns by replaying
    the lane's placements against a lane-local overlay (the same
    evolution the greedy scan applied). The overlay is not stepped
    through: the placement order is fixed once repair is done, so what
    instance i was scored against (usage on its row, collisions, the
    per-value spread counts) is the base snapshot plus a running sum
    over the instances before it, and ``_instance_components_vec``
    forms those sums for the whole lane at once. Hetero lanes carry
    their per-instance score from the joint pass and are stamped one by
    one. Conflict repair mutates ``node_rows`` in place after the kernel
    returned, so this runs AFTER ``repair_batch_conflicts`` —
    ``placed_nodes`` reflects what will actually commit.

    Returns the tags of the ``explain`` span's final step:
    ``instances`` (rows stamped) and ``sequential_lanes`` (lanes stamped
    instance by instance, outside the array replay)."""
    used0 = np.asarray(
        cluster.used if used_override is None else used_override
    )
    capacity = np.asarray(cluster.capacity)
    stats = {"instances": 0, "sequential_lanes": 0}
    for a, res in zip(asks, results):
        ex = getattr(res, "explanation", None)
        if ex is None:
            continue
        rows_list = np.asarray(res.node_rows).tolist()
        placed_idx = [i for i, r in enumerate(rows_list) if r >= 0]
        prows = [rows_list[i] for i in placed_idx]
        placed_on: dict[int, int] = {}
        mine = []
        for r in prows:
            k = placed_on.get(r, 0)
            mine.append(k)
            placed_on[r] = k + 1
        ex.placed_nodes = [cluster.node_ids[r] for r in prows]
        stats["instances"] += len(prows)
        if ex.policy:
            stats["sequential_lanes"] += 1
            breakdown = [
                ({"throughput": float(res.scores[i])}, float(res.scores[i]))
                for i in placed_idx
            ]
        else:
            breakdown = _instance_components_vec(
                capacity, used0, a, prows, mine, ex.algorithm == "spread"
            )
        # per-instance metas ride as a plain attribute (not a dataclass
        # field) so API encodings of the explanation stay bounded
        ex.instance_meta = instance_meta = [None] * len(rows_list)
        first_meta: dict[int, NodeScoreMeta] = {}
        for i, r, node_id, (comps, final) in zip(
            placed_idx, prows, ex.placed_nodes, breakdown
        ):
            instance_meta[i] = meta = NodeScoreMeta(
                node_id=node_id, scores=comps, norm_score=final
            )
            first_meta.setdefault(r, meta)
        by_row = {c.node_row: c for c in ex.top_candidates}
        for row, k in placed_on.items():
            cand = by_row.get(row)
            if cand is not None:
                cand.placed = k
            else:
                # repair (or a later greedy step) committed a node
                # outside the first-instance top-k: append it so
                # `alloc why` always finds its breakdown
                meta = first_meta[row]
                ex.top_candidates.append(
                    CandidateExplanation(
                        node_id=meta.node_id,
                        node_row=int(row),
                        final_score=meta.norm_score,
                        components=dict(meta.scores),
                        placed=k,
                    )
                )
    return stats


def score_meta_for_row(
    cluster, a, used0, row: int, *, algorithm_spread: bool = False,
    desired_total=None,
) -> NodeScoreMeta:
    """First-instance breakdown for one committed row — the system
    scheduler's per-alloc ScoreMetaData (a system job places at most one
    alloc per node, so the first-instance view IS the instance view).
    Normalizes the heterogeneity axis exactly like score_group so the
    throughput component matches the recorded final."""
    throughputs = None
    if a.has_throughputs and a.throughputs is not None:
        tp = np.asarray(a.throughputs, dtype=np.float32)
        best = float(np.max(np.where(a.eligible, tp, 0.0)))
        if best > 0.0:
            throughputs = tp / np.float32(best)
    counts = a.blocks.counts0 if a.blocks is not None else None
    ((comps, final),) = _components_at(
        np.asarray(cluster.capacity),
        np.asarray(used0),
        a,
        [int(row)],
        [0],
        counts,
        algorithm_spread,
        throughputs,
        desired_total,
    )
    return NodeScoreMeta(
        node_id=cluster.node_ids[int(row)],
        scores={k: float(v) for k, v in comps.items()},
        norm_score=float(final),
    )


def candidates_as_score_meta(ex: PlacementExplanation) -> list[NodeScoreMeta]:
    """Top-k candidates as AllocMetric.score_meta rows (the reference's
    ScoreMetaData shape) — stamped onto failed placements so blocked
    evals carry the near-miss table."""
    return [
        NodeScoreMeta(
            node_id=c.node_id,
            scores=dict(c.components),
            norm_score=c.final_score,
        )
        for c in ex.top_candidates
    ]


def explanation_to_dict(ex: PlacementExplanation) -> dict:
    """JSON shape for the API/CLI surfaces (schema pinned by the tier-1
    smoke test)."""
    return {
        "schema_version": ex.schema_version,
        "job_id": ex.job_id,
        "tg_name": ex.tg_name,
        "algorithm": ex.algorithm,
        "policy": ex.policy,
        "nodes_evaluated": ex.nodes_evaluated,
        "feasible_nodes": ex.feasible_nodes,
        "top_candidates": [
            {
                "node_id": c.node_id,
                "rank": i + 1,
                "final_score": c.final_score,
                "components": dict(c.components),
                "placed": c.placed,
            }
            for i, c in enumerate(ex.top_candidates)
        ],
        "rejections": dict(ex.rejections),
        "placed_nodes": list(ex.placed_nodes),
        **({"cp": dict(ex.cp)} if ex.cp is not None else {}),
        **({"gang": dict(ex.gang)} if ex.gang is not None else {}),
    }
