"""The plain reference: what a correct scheduler's answers must say.

Plain numpy on plain tables; it imports nothing of the program and takes
nothing the program has made. Its inputs are the fleet table from
``gen/fleet.py`` (the configuration's, not the program's flattened
tensors), the job specs the generator sent, and the *answers* read back
from the store once the window has closed: for every allocation of the run
its job, node row, resources, the commit index that created it and the one
that stopped it.

Three judgements, each by what the answer says, not by when it came:

- ``accounting``: every acknowledged registration holds exactly its count
  of live allocations, every acknowledged deregistration none, and no
  allocation sits on a node outside the fleet.
- ``capacity_replay``: the commit log is replayed in index order (stops
  before placements at one index) and no node ever holds more than its
  capacity after the reserve, in any dimension, at any index: not only at
  the end, when the evidence of a transient overfill would be gone.
- ``greedy_walk``: a stepwise greedy scheduler written out in the open
  replays a sampled job on the cluster as the scheduler
  can have seen it (``usage_before``: every placement before the job's
  commit, and the stops up to a snapshot index at or before it); every
  served placement is scored by the reference's own formula at its own
  step and set against the best score any feasible node offered at that
  step.
"""

from __future__ import annotations

import numpy as np

DIMS = ("cpu", "memory_mb", "disk_mb")
BINPACK_MAX = 18.0  # nomad/structs/funcs.go ScoreFit: 20 - (10^a + 10^b)


def accounting(expected_live: dict, live_by_job: dict, total_by_job: dict,
               asked_by_job: dict) -> dict:
    """``misplaced_allocs``: over all jobs, how far the live count is
    from the count the acknowledged requests imply, plus allocations a
    job holds beyond what it ever asked for."""
    misplaced = 0
    for job, want in expected_live.items():
        misplaced += abs(live_by_job.get(job, 0) - want)
        misplaced += max(0, total_by_job.get(job, 0) - asked_by_job[job])
    return {"misplaced_allocs": misplaced}


def capacity_replay(fleet: dict, node: np.ndarray, create_idx: np.ndarray,
                    stop_idx: np.ndarray, res: dict) -> dict:
    """Replay placements (+) and stops (-) per node in commit order.

    ``stop_idx`` is 0 for an allocation still live. Returns the number of
    nodes that at any index held more than their capacity and the worst
    overfill as a share of capacity."""
    n_alloc = node.shape[0]
    off_fleet = int(((node < 0) | (node >= fleet["n"])).sum())
    keep = (node >= 0) & (node < fleet["n"])
    stopped = keep & (stop_idx > 0)
    ev_node = np.concatenate([node[keep], node[stopped]])
    ev_idx = np.concatenate([create_idx[keep], stop_idx[stopped]])
    # at one index a plan's stops free capacity before its placements
    ev_sign = np.concatenate(
        [np.ones(int(keep.sum()), np.int64),
         -np.ones(int(stopped.sum()), np.int64)]
    )
    order = np.lexsort((ev_sign, ev_idx, ev_node))
    ev_node, ev_sign = ev_node[order], ev_sign[order]
    over = np.zeros(fleet["n"], dtype=bool)
    worst = 0.0
    if ev_node.size:
        starts = np.flatnonzero(np.r_[True, ev_node[1:] != ev_node[:-1]])
        for dim in DIMS:
            amount = np.concatenate([res[dim][keep], res[dim][stopped]])
            delta = amount[order] * ev_sign
            running = np.cumsum(delta)
            base = np.repeat(
                running[starts] - delta[starts],
                np.diff(np.r_[starts, ev_node.size]),
            )
            peak = np.maximum.reduceat(running - base, starts)
            cap = fleet[dim][ev_node[starts]]
            over[ev_node[starts]] |= peak > cap
            worst = max(worst, float(((peak - cap) / cap).max()))
    return {
        "allocs_off_fleet": off_fleet,
        "nodes_over_capacity": int(over.sum()),
        "worst_overfill_share": max(worst, 0.0),
        "allocs_replayed": int(n_alloc),
    }


def usage_before(fleet: dict, node, create_idx, stop_idx, res: dict,
                 index: int, stops_before: int | None = None) -> dict:
    """Per-node usage from every allocation committed before ``index``
    and not stopped before ``stops_before`` (``index`` itself when not
    given). A scheduler works on a snapshot taken some commits before its
    own plan lands: it has reserved every placement up to its own, but a
    stop that landed after the snapshot it cannot have seen. A smaller
    ``stops_before`` is that older view."""
    horizon = index if stops_before is None else stops_before
    live = (create_idx < index) & ((stop_idx == 0) | (stop_idx >= horizon))
    live &= (node >= 0) & (node < fleet["n"])
    return {
        dim: np.bincount(
            node[live], weights=res[dim][live], minlength=fleet["n"]
        )
        for dim in DIMS
    }


def _fit(fleet: dict, used: dict, ask: dict, dtype=np.float64):
    """ScoreFit (funcs.go:236-274), normalised to [0, 1] (rank.go:513).
    ``dtype`` is float64 for the reference; the precision control computes
    the same arithmetic in the type below the program's float32."""
    total = dtype(0.0)
    for dim in ("cpu", "memory_mb"):
        cap = fleet[dim].astype(dtype)
        proposed = used[dim].astype(dtype) + dtype(ask[dim])
        total = total + np.power(dtype(10.0), (cap - proposed) / cap)
    score = dtype(20.0) - total
    score = np.clip(score, dtype(0.0), dtype(BINPACK_MAX)) / dtype(BINPACK_MAX)
    return score.astype(np.float64)


def _even_spread_boost(rack_counts: np.ndarray) -> np.ndarray:
    """evenSpreadScoreBoost (scheduler/spread.go:178-228) per rack value,
    given the job's allocations per rack so far."""
    lo, hi = rack_counts.min(), rack_counts.max()
    if hi == 0:
        return np.zeros(rack_counts.shape)
    if lo == hi:
        at_min = -1.0
    elif lo == 0:
        at_min = 1.0
    else:
        at_min = (hi - lo) / lo
    if lo == 0:
        off_min = -1.0
        return np.where(rack_counts == lo, at_min, off_min)
    return np.where(
        rack_counts == lo, at_min, (lo - rack_counts) / lo
    )


def _scores(fleet, used, ask, job, mine, rack_counts, respect_capacity,
            dtype=np.float64):
    """Every node's score for one more instance of ``job`` (see
    ``greedy_walk``); infeasible nodes read -inf."""
    n = fleet["n"]
    fits = np.ones(n, dtype=bool)
    if respect_capacity:
        for d in DIMS:
            fits &= used[d] + ask[d] <= fleet[d]
    total = _fit(fleet, used, ask, dtype)
    coll = mine > 0
    total = total + np.where(coll, -(mine + 1.0) / job["count"], 0.0)
    parts = 1.0 + coll
    if job.get("affinity"):
        total = total + fleet["ssd"]
        parts = parts + 1.0
    if job.get("spread"):
        boost = _even_spread_boost(rack_counts)[fleet["rack"]]
        total = total + boost
        parts = parts + (boost != 0.0)
    final = (total / parts).astype(dtype).astype(np.float64)
    return np.where(fits, final, -np.inf)


def pick_best(score: np.ndarray, _rng) -> int:
    return int(np.argmax(score))


def pick_sampled(score: np.ndarray, rng, handful: int = 8) -> int:
    """The selection control: the best of a handful of feasible nodes
    drawn at random, its score honest. What stock samplers and
    approximate top-k do; faster, and not the configuration's answer."""
    feasible = np.flatnonzero(np.isfinite(score))
    if feasible.size == 0:
        return int(np.argmax(score))
    few = rng.choice(feasible, size=min(handful, feasible.size), replace=False)
    return int(few[np.argmax(score[few])])


def greedy_walk(fleet: dict, used: dict, job: dict, served_rows,
                respect_capacity: bool = True, dtype=np.float64,
                pick=pick_best, rng=None) -> dict:
    """Walk one job's placements step by step on ``used`` (copied).

    At each step every node gets the reference's score for "one more
    instance of this job here": fit, job anti-affinity
    (rank.go:536-604), node affinity (rank.go:650-737), even spread over
    racks; the mean over the components that contribute (rank.go:740-767).
    ``served_rows`` are the nodes the program chose, in the order of the
    allocations' name index; the walk follows *them* and records, per step,
    the score of the served node and the best score any feasible node
    offered. With ``served_rows=None`` the walk follows the node ``pick``
    chooses, by default its own best: that is the reference scheduler
    itself, and ``rows`` holds its choices.
    """
    used = {d: used[d].astype(np.float64).copy() for d in DIMS}
    ask = {d: float(job[d]) for d in DIMS}
    mine = np.zeros(fleet["n"], dtype=np.int64)  # this job's allocs per node
    rack_counts = np.zeros(int(fleet["rack"].max()) + 1, dtype=np.int64)
    served, best, rows = [], [], []
    steps = range(job["count"]) if served_rows is None else served_rows
    for step in steps:
        score = _scores(fleet, used, ask, job, mine, rack_counts,
                        respect_capacity, dtype)
        row = pick(score, rng) if served_rows is None else int(step)
        served.append(float(score[row]))
        best.append(float(score.max()))
        rows.append(row)
        for d in DIMS:
            used[d][row] += ask[d]
        mine[row] += 1
        rack_counts[fleet["rack"][row]] += 1
    return {"served": np.array(served), "best": np.array(best),
            "rows": np.array(rows, dtype=np.int64)}


def reference_answers(fleet: dict, requests: list, specs_by_job: dict,
                      respect_capacity: bool = True,
                      dtype=np.float64, pick=pick_best, seed: int = 0) -> dict:
    """The reference put in the program's place: serve ``requests``
    (``(kind, job ordinal)`` in order, one commit index each) with the
    stepwise greedy scheduler and return the answers in the form
    ``check.extract_answers`` reads from the store.

    Three controls, each of which the comparison must refuse:
    ``respect_capacity=False`` breaks the capacity guarantee (a node's fit
    is no longer checked); ``dtype=ml_dtypes.bfloat16`` computes the scores
    in the precision below the program's float32; ``pick=pick_sampled``
    places each instance on the best of a handful of nodes, not of all."""
    rng = np.random.default_rng(seed)
    used = {d: np.zeros(fleet["n"]) for d in DIMS}
    cols = {k: [] for k in ("node", "job", "create", "stop", "name_idx",
                            "score", *DIMS)}
    placed: dict = {}  # job ordinal -> (first answer, rows)
    for index, (kind, j) in enumerate(requests, start=1):
        job = specs_by_job[j]
        if kind == "deregister":
            start, rows = placed.pop(j)
            for k, row in enumerate(rows):
                cols["stop"][start + k] = index
                for d in DIMS:
                    used[d][row] -= job[d]
            continue
        walk = greedy_walk(fleet, used, job, None,
                           respect_capacity=respect_capacity, dtype=dtype,
                           pick=pick, rng=rng)
        ok = np.isfinite(walk["served"])
        rows = walk["rows"][ok]
        placed[j] = (len(cols["node"]), rows)
        for k, (row, score) in enumerate(zip(rows, walk["served"][ok])):
            cols["node"].append(int(row))
            cols["job"].append(j)
            cols["create"].append(index)
            cols["stop"].append(0)
            cols["name_idx"].append(k)
            cols["score"].append(float(score))
            for d in DIMS:
                cols[d].append(job[d])
                used[d][row] += job[d]
    as_i = lambda x: np.asarray(x, dtype=np.int64)  # noqa: E731
    return {
        "node": as_i(cols["node"]), "job": as_i(cols["job"]),
        "create": as_i(cols["create"]), "stop": as_i(cols["stop"]),
        "name_idx": as_i(cols["name_idx"]),
        "score": np.asarray(cols["score"], dtype=np.float64),
        "res": {d: as_i(cols[d]) for d in DIMS},
    }
