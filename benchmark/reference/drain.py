"""The plain reference for a node drain: which allocations of a draining node
may be marked, what the eval behind a mark stops and places, and what the
cluster looks like to the replacement.

Plain numpy on plain tables, float64; it imports nothing of the program.
Written from the description of Nomad's ``nomad/drainer/watch_jobs.go``
``handleTaskGroup``, ``scheduler/reconcile_util.go`` ``filterByTainted`` and
``scheduler/generic_sched.go`` ``computePlacements``; the choice of node is
``reference/placement.py``'s score (ScoreFit, job anti-affinity, node
affinity, even spread).

``may_mark``: a group of ``count`` with ``migrate.max_parallel`` keeps
``count - max_parallel`` allocations serving. Serving are the live
allocations that carry no mark and are healthy (a replacement counts once
its client reports it running); drainable are the unmarked ones on a
draining node. One wave marks ``min(drainable, serving - (count -
max_parallel))`` of them (``numToDrain``), so a group never has more than
``max_parallel`` marked allocations whose replacement is not yet healthy.
Which of the drainable ones go first the source leaves to the order of its
iteration; the reference says how many.

``eval_plan``: only marked allocations leave a draining node
(``filterByTainted``: ``DesiredTransition.ShouldMigrate``); the eval stops
every marked allocation of the job that sits on a tainted node and places
one under each name it stops, in the same plan (``AppendStoppedAlloc`` +
``computePlacements``), and fills the names the job is short of besides.

``freed_view`` / ``walk``: the view of the plan's placements is the cluster
with the plan's own stops freed first (usage, the job's allocations per
node, the per-rack spread counts: existing + proposed - cleared,
``propertyset.go``), and **with every node masked out that drains or is
ineligible** at that index: the replacement prefers no node, and the node
it leaves is not feasible. Departures, noted here: as in the program a stop
frees its rack's count once, before the first placement (the source
recounts before every placement); health is the client's ``running`` (no
Consul checks: ``health_check = "task_states"``, ``min_healthy_time`` 0);
no deadline, no system jobs, no batch jobs, one group a job.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import placement as plain

DIMS = plain.DIMS
# the reference in the program's place with one rule broken (the controls)
FAULTS = (
    "draining_node_not_masked",  # the node being drained stays feasible
    "stops_not_freed",  # scored on a view that still holds the plan's stop
    "spread_counts_the_stop",  # spread counts still count the stopped one
    "max_parallel_ignored",  # every allocation of the node marked at once
    "bfloat16_scores",  # scores in the type below the program's float32
    "replacement_never_placed",  # the stop commits, nothing takes its name
)


def may_mark(count: int, max_parallel: int, marked, healthy,
             on_draining) -> int:
    """How many more allocations of a group one wave may mark. The three
    arrays describe the group's live allocations."""
    marked = np.asarray(marked, dtype=bool)
    healthy = np.asarray(healthy, dtype=bool)
    on_draining = np.asarray(on_draining, dtype=bool)
    serving = int((~marked & healthy).sum())
    drainable = int((~marked & on_draining).sum())
    return max(0, min(drainable, serving - (int(count) - int(max_parallel))))


def eval_plan(count: int, name_idx, marked, on_tainted) -> tuple:
    """``(stop, place)``: sorted name indices one eval stops and places,
    given the job's live allocations by name, mark and whether their node
    is tainted (drains)."""
    name_idx = np.asarray(name_idx, dtype=np.int64)
    leave = np.asarray(marked, dtype=bool) & np.asarray(on_tainted, dtype=bool)
    stop = np.sort(name_idx[leave])
    missing = max(int(count) - name_idx.size, 0)
    free = np.setdiff1d(np.arange(count + missing), name_idx)[:missing]
    return stop, np.sort(np.r_[stop, free])


def freed_view(fleet: dict, used: dict, spec: dict, job_rows,
               stopped_rows) -> tuple:
    """``(used, mine, rack_counts)`` as a plan's placements see them:
    ``used`` without the plan's stops, the job's live allocations per node
    and per rack (``job_rows``: node rows of its live allocations, the
    stopped ones among them) less the stops."""
    n = fleet["n"]
    n_racks = int(fleet["rack"].max()) + 1
    job_rows = np.asarray(job_rows, dtype=np.int64)
    stopped_rows = np.asarray(stopped_rows, dtype=np.int64)
    gone = np.bincount(stopped_rows, minlength=n)
    out = {
        d: used[d].astype(np.float64) - gone * float(spec[d]) for d in DIMS
    }
    mine = np.bincount(job_rows, minlength=n) - gone
    racks = np.bincount(fleet["rack"][job_rows], minlength=n_racks) - (
        np.bincount(fleet["rack"][stopped_rows], minlength=n_racks))
    return out, mine.astype(np.int64), racks.astype(np.int64)


def walk(fleet: dict, used: dict, spec: dict, served_rows, mine, rack_counts,
         eligible, steps: int = 0, dtype=np.float64) -> dict:
    """``placement.greedy_walk`` from a given state over the nodes that
    are ``eligible`` (bool per node; the others score -inf). With
    ``served_rows`` the walk follows the program's nodes in their order
    and records, per step, the served node's score and the best on offer;
    with None it takes ``steps`` steps on its own best: the reference
    scheduler itself."""
    used = {d: used[d].astype(np.float64).copy() for d in DIMS}
    ask = {d: float(spec[d]) for d in DIMS}
    mine = np.asarray(mine, dtype=np.int64).copy()
    rack_counts = np.asarray(rack_counts, dtype=np.int64).copy()
    eligible = np.asarray(eligible, dtype=bool)
    served, best, rows = [], [], []
    for step in (range(steps) if served_rows is None else served_rows):
        score = np.where(
            eligible,
            plain._scores(fleet, used, ask, spec, mine, rack_counts, True,
                          dtype),
            -np.inf,
        )
        row = int(np.argmax(score)) if served_rows is None else int(step)
        served.append(float(score[row]))
        best.append(float(score.max()))
        rows.append(row)
        for d in DIMS:
            used[d][row] += ask[d]
        mine[row] += 1
        rack_counts[fleet["rack"][row]] += 1
    return {"served": np.array(served), "best": np.array(best),
            "rows": np.array(rows, dtype=np.int64)}


def serve_eval(fleet: dict, used: dict, spec: dict, rows_by_name: dict,
               marked_by_name: dict, closed, fault=None) -> list:
    """The reference in the program's place for one eval of a job whose
    live allocations sit on ``rows_by_name`` (name index -> node row):
    stops the marked ones on a node of ``closed`` (bool per node: drains or
    is ineligible) and places their replacements, in ``used`` and the two
    tables, in place. Returns ``[(name index, stopped row, new row or -1,
    score)]``. ``fault`` breaks one rule (``FAULTS``)."""
    closed = np.asarray(closed, dtype=bool)
    names = sorted(rows_by_name)
    stop, place = eval_plan(
        spec["count"], names, [marked_by_name[k] for k in names],
        [closed[rows_by_name[k]] for k in names],
    )
    stopped_rows = np.asarray(
        [rows_by_name[int(k)] for k in stop], dtype=np.int64)
    job_rows = [rows_by_name[k] for k in names]
    view, mine, racks = freed_view(fleet, used, spec, job_rows, stopped_rows)
    if fault == "stops_not_freed":
        view, mine, racks = freed_view(fleet, used, spec, job_rows, [])
    if fault == "spread_counts_the_stop":
        racks = freed_view(fleet, used, spec, job_rows, [])[2]
    eligible = ~closed
    if fault == "draining_node_not_masked":
        eligible = eligible.copy()
        eligible[stopped_rows] = True
    dtype = np.float64
    if fault == "bfloat16_scores":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    steps = 0 if fault == "replacement_never_placed" else len(place)
    w = walk(fleet, view, spec, None, mine, racks, eligible, steps=steps,
             dtype=dtype)
    for d in DIMS:
        used[d] -= np.bincount(
            stopped_rows, minlength=fleet["n"]) * float(spec[d])
    out = []
    for i, k in enumerate(int(k) for k in place):
        old = rows_by_name.pop(k, -1)  # -1: a name the job was short of
        marked_by_name.pop(k, None)
        if i >= steps or not np.isfinite(w["served"][i]):
            out.append((k, old, -1, np.nan))
            continue
        row = int(w["rows"][i])
        out.append((k, old, row, float(w["served"][i])))
        rows_by_name[k] = row
        marked_by_name[k] = False
        for d in DIMS:
            used[d][row] += float(spec[d])
    return out
