"""The plain reference for the loss of nodes: which allocations are lost,
which evals the loss makes, what each eval stops and places, and what the
cluster looks like to the replacements.

Plain numpy on plain tables, float64; it imports nothing of the program.
Written from the description of Nomad's ``nomad/node_endpoint.go``
``createNodeEvals`` (one eval per job with an allocation on the node that
went down), ``scheduler/reconcile_util.go`` ``filterByTainted`` (an
allocation on a down node is lost: stopped with client status ``lost`` and
replaced under its name), ``scheduler/generic_sched.go``
``computePlacements`` and ``scheduler/spread.go`` ``evenSpreadScoreBoost``
over ``propertyset.go``'s combined-use map. The fit is
``reference/placement.py``'s ScoreFit; the score is its mean of the
components that contribute (fit, job anti-affinity, node affinity, spread).

``node_evals``: one eval a job that has a live allocation on the node.

``eval_plan``: the eval stops every live allocation of its job that sits on
a node down at its snapshot, and places one under each name it stops (and
under each name the job is short of besides), in the same plan.

``even_spread_boost``: the boost of each rack for one more instance, over
the racks of the job's combined-use map: those where the job has a live
allocation (the lost ones included, until a plan stops them) or a proposed
one. A rack whose allocations the plan stops stays in the map at a count
of 0; a rack the job never used is not in it. The min and max run over the
map; a min of 0 takes the source's branches for it: -1 at every rack off
the min, +1 at a rack on it. (Go's loop lets a later value overwrite a
zero min, so the source's own result there follows the map's iteration
order; its branches written for a zero min are the reading taken.) The
rack that went down is that zero: every rack that can still take a
replacement reads -1 until a placement lands on a rack the job does not
use. A map whose every count is 0 (a plan that stops all of the job's
allocations) scores 0, as an empty one does: Go's returns -1 there, and
this is ``reference/placement.py``'s reading, which ``reference/rollout.py``
holds a batch job replaced whole to.

``freed_view`` / ``walk``: a plan's placements see the cluster with the
plan's stops freed (usage, the job's allocations per node, the racks'
counts), every node down at the commit masked out. Departures, noted here:
the stops free their racks' counts once, before the first placement (the
source recounts before every placement, to the same counts: no placement
lands on a down node); one group a job; no reschedule policy (a lost
allocation is replaced at once, as the source does).
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import placement as plain

DIMS = plain.DIMS
# the reference in the program's place with one rule broken (the controls)
FAULTS = (
    "down_nodes_feasible",  # a node that went down stays open to placement
    "lost_counted_in_spread",  # the racks' counts still hold the lost ones
    "bfloat16_scores",  # scores in the type below the program's float32
    "later_eval_replaces_again",  # a job's later eval places once more
    "lost_left_running",  # the lost allocation is not stopped
)


def node_evals(job_of_live_alloc_on_node) -> list:
    """The jobs one node's loss makes an eval for, in order of first
    appearance: one a job with a live allocation on the node."""
    return list(dict.fromkeys(job_of_live_alloc_on_node))


def eval_plan(count: int, name_idx, on_down) -> tuple:
    """``(stop, place)``: sorted name indices one eval stops and places,
    given the job's live allocations by name and whether their node is
    down at the eval's snapshot."""
    name_idx = np.asarray(name_idx, dtype=np.int64)
    lost = np.sort(name_idx[np.asarray(on_down, dtype=bool)])
    missing = max(int(count) - name_idx.size, 0)
    free = np.setdiff1d(np.arange(count + missing), name_idx)[:missing]
    return lost, np.sort(np.r_[lost, free])


def even_spread_boost(counts, held) -> np.ndarray:
    """``evenSpreadScoreBoost`` per rack for one more instance, given the
    job's combined counts per rack and which racks its map holds."""
    counts = np.asarray(counts, dtype=np.float64)
    held = np.asarray(held, dtype=bool) | (counts > 0)
    if not (counts > 0).any():
        return np.zeros(counts.shape)  # as an empty map: nothing placed
    lo, hi = counts[held].min(), counts[held].max()
    if lo == hi:
        at_min = -1.0
    elif lo == 0:
        at_min = 1.0
    else:
        at_min = (hi - lo) / lo
    off_min = (lo - counts) / lo if lo > 0 else np.full(counts.shape, -1.0)
    return np.where(counts == lo, at_min, off_min)


def scores(fleet: dict, used: dict, spec: dict, mine, rack_counts,
           rack_held, eligible, dtype=np.float64) -> np.ndarray:
    """Every node's score for one more instance of ``spec`` (-inf where
    it does not fit or is not ``eligible``): ScoreFit, job anti-affinity
    (rank.go:536-604), node affinity, even spread; the mean over the
    components that contribute (rank.go:740-767)."""
    ask = {d: float(spec[d]) for d in DIMS}
    fits = np.asarray(eligible, dtype=bool).copy()
    for d in DIMS:
        fits &= used[d] + ask[d] <= fleet[d]
    total = plain._fit(fleet, used, ask, dtype)
    coll = mine > 0
    total = total + np.where(coll, -(mine + 1.0) / spec["count"], 0.0)
    parts = 1.0 + coll
    if spec.get("affinity"):
        total = total + fleet["ssd"]
        parts = parts + 1.0
    if spec.get("spread"):
        boost = even_spread_boost(rack_counts, rack_held)[fleet["rack"]]
        total = total + boost
        parts = parts + (boost != 0.0)
    final = (total / parts).astype(dtype).astype(np.float64)
    return np.where(fits, final, -np.inf)


def freed_view(fleet: dict, used: dict, spec: dict, job_rows,
               stopped_rows, fault=None) -> tuple:
    """``(used, mine, rack_counts, rack_held)`` as a plan's placements see
    them: ``used`` without the plan's stops; the job's live allocations per
    node and per rack (``job_rows``: their node rows, the stopped ones
    among them) less the stops; the racks of the job's map."""
    n = fleet["n"]
    n_racks = int(fleet["rack"].max()) + 1
    job_rows = np.asarray(job_rows, dtype=np.int64)
    stopped_rows = np.asarray(stopped_rows, dtype=np.int64)
    gone = np.bincount(stopped_rows, minlength=n)
    out = {d: used[d].astype(np.float64) - gone * float(spec[d]) for d in DIMS}
    mine = np.bincount(job_rows, minlength=n) - gone
    racks = np.bincount(fleet["rack"][job_rows], minlength=n_racks)
    held = racks > 0
    if fault != "lost_counted_in_spread":
        racks = racks - np.bincount(
            fleet["rack"][stopped_rows], minlength=n_racks)
    return out, mine.astype(np.int64), racks.astype(np.int64), held


def walk(fleet: dict, used: dict, spec: dict, served_rows, mine, rack_counts,
         rack_held, eligible, steps: int = 0, dtype=np.float64) -> dict:
    """A greedy walk from a given state over the nodes that are
    ``eligible``. With ``served_rows`` the walk follows the program's
    nodes in their order and records, per step, the served node's score
    and the best on offer; with None it takes ``steps`` steps on its own
    best: the reference scheduler itself."""
    used = {d: used[d].astype(np.float64).copy() for d in DIMS}
    mine = np.asarray(mine, dtype=np.int64).copy()
    rack_counts = np.asarray(rack_counts, dtype=np.int64).copy()
    rack_held = np.asarray(rack_held, dtype=bool).copy()
    served, best, rows = [], [], []
    for step in (range(steps) if served_rows is None else served_rows):
        score = scores(fleet, used, spec, mine, rack_counts, rack_held,
                       eligible, dtype)
        row = int(np.argmax(score)) if served_rows is None else int(step)
        served.append(float(score[row]))
        best.append(float(score.max()))
        rows.append(row)
        for d in DIMS:
            used[d][row] += float(spec[d])
        mine[row] += 1
        rack = fleet["rack"][row]
        rack_counts[rack] += 1
        rack_held[rack] = True
    return {"served": np.array(served), "best": np.array(best),
            "rows": np.array(rows, dtype=np.int64)}


def serve_eval(fleet: dict, used: dict, spec: dict, rows_by_name: dict,
               down, fault=None) -> list:
    """The reference in the program's place for one eval of a job whose
    live allocations sit on ``rows_by_name`` (name index -> node row),
    ``down`` (bool per node) the nodes down at its snapshot: stops the
    lost ones and places their replacements, in ``used`` and the table,
    in place. Returns ``[(name index, lost row or -1, new row or -1,
    score)]``; a lost allocation left running (``lost_left_running``)
    reads ``-2`` as its lost row. ``fault`` breaks one rule
    (``FAULTS``)."""
    down = np.asarray(down, dtype=bool)
    names = sorted(rows_by_name)
    stop, place = eval_plan(
        spec["count"], names, [down[rows_by_name[k]] for k in names])
    if fault == "later_eval_replaces_again" and not stop.size and names:
        # one more instance, under a name past the count
        place = np.asarray([max(names) + 1], dtype=np.int64)
    stopped_rows = np.asarray(
        [rows_by_name[int(k)] for k in stop], dtype=np.int64)
    job_rows = [rows_by_name[k] for k in names]
    view, mine, racks, held = freed_view(
        fleet, used, spec, job_rows, stopped_rows, fault)
    eligible = np.ones(fleet["n"], dtype=bool)
    if fault != "down_nodes_feasible":
        eligible = ~down
    dtype = np.float64
    if fault == "bfloat16_scores":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    w = walk(fleet, view, spec, None, mine, racks, held, eligible,
             steps=len(place), dtype=dtype)
    kept = fault == "lost_left_running"
    if not kept:
        for d in DIMS:
            used[d] -= np.bincount(
                stopped_rows, minlength=fleet["n"]) * float(spec[d])
    out = []
    for i, k in enumerate(int(k) for k in place):
        if kept and k in rows_by_name:
            old = -2
        else:
            old = rows_by_name.pop(k, -1)
        if not np.isfinite(w["served"][i]):
            out.append((k, old, -1, np.nan))
            continue
        row = int(w["rows"][i])
        out.append((k, old, row, float(w["served"][i])))
        if old != -2:
            rows_by_name[k] = row
        for d in DIMS:
            used[d][row] += float(spec[d])
    return out
