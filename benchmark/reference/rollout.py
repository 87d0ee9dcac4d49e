"""The plain reference for a rollout: which allocations an eval may stop and
place, and what the cluster looks like to the placements it makes.

Plain numpy on plain tables, float64; it imports nothing of the program.
Written from the description of Nomad's ``scheduler/reconcile.go``
(``computeGroup``, ``computeUpdates``, ``computeLimit``),
``scheduler/util.go`` ``tasksUpdated``, ``scheduler/generic_sched.go``
``computePlacements`` and ``scheduler/propertyset.go``; the choice of node
is ``reference/placement.py``'s score (ScoreFit, job anti-affinity, node
affinity, even spread).

``round_plan``: given a job's live allocations by name, version and health,
the version registered and ``max_parallel``, the names one eval stops and
places:

- every live allocation of an older version needs a destructive update (the
  version differs in a task ``env`` value: ``tasksUpdated``);
- a group without an update strategy (every batch group) is replaced all at
  once; a rolling group at most ``max_parallel`` less the new-version
  allocations that are not yet healthy (``computeLimit``), lowest name
  index first (``destructive.nameOrder()[:min]``);
- a destructive update places under the name it stops (the old allocation
  is stopped in the plan that places its replacement:
  ``plan.AppendStoppedAlloc(prev, allocUpdating)``), and names the job is
  short of are filled from the lowest free index, all of them, each
  counting against the limit before the destructive updates do.

Departures from the source, by design of the deployment: no canaries, no
``auto_revert``, no in-place updates, no paused or failed deployment, no
tainted nodes, no reschedules, one group a job.

``walk``: ``placement.greedy_walk`` started from a state that is not empty.
The view of a plan's placements is the cluster with the plan's own stops
freed first: usage without the stopped allocations, the job's allocations
per node without them (``JobAntiAffinityIterator`` counts the proposed
set), and per-rack spread counts = the job's live allocations of every
version, less those the plan stops, plus those it has placed
(``propertyset.go``: existing + proposed - cleared). Departure: the source
recounts ``proposed`` and ``cleared`` before every placement and stops
discounting a cleared value once a proposed allocation re-uses it
(``propertyset.go:199-208``); here, as in the program, a stop frees its
rack's count once, before the first placement, and every placement adds
one.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import placement as plain

DIMS = plain.DIMS
# the reference in the program's place with one rule broken (the controls)
FAULTS = (
    "stops_not_freed",  # placements scored on usage that still holds the stops
    "spread_forgets_old_version",  # spread counts of the new version only
    "bfloat16_scores",  # scores in the type below the program's float32
    "max_parallel_ignored",  # a rolling group replaced all at once
    "name_twice",  # a replacement named after a neighbour that stays
)


def round_plan(count: int, name_idx, version, healthy, current: int,
               max_parallel) -> tuple:
    """``(stop, place)``: sorted name indices one eval stops and places.
    The three arrays describe the job's live allocations; ``current`` is
    the version registered; ``max_parallel`` None means no update
    strategy."""
    name_idx = np.asarray(name_idx, dtype=np.int64)
    version = np.asarray(version, dtype=np.int64)
    healthy = np.asarray(healthy, dtype=bool)
    old = np.sort(name_idx[version < current])
    missing = max(count - name_idx.size, 0)
    if max_parallel is None:
        limit = old.size
    else:
        # placements count against the limit before the destructive
        # updates do (``limit -= min(len(place), limit)``)
        in_flight = int(((version == current) & ~healthy).sum())
        limit = max(int(max_parallel) - in_flight - missing, 0)
    stop = old[:limit]
    free = np.setdiff1d(np.arange(count + missing), name_idx)[:missing]
    return stop, np.sort(np.r_[stop, free])


def freed_view(fleet: dict, used: dict, spec: dict, job_rows,
               stopped_rows) -> tuple:
    """``(used, mine, rack_counts)`` as a plan's placements see them:
    ``used`` without the plan's stops, the job's live allocations per node
    and per rack (``job_rows``: node rows of its live allocations of every
    version, the stopped ones among them) less the stops."""
    n = fleet["n"]
    n_racks = int(fleet["rack"].max()) + 1
    job_rows = np.asarray(job_rows, dtype=np.int64)
    stopped_rows = np.asarray(stopped_rows, dtype=np.int64)
    out = {d: used[d].astype(np.float64).copy() for d in DIMS}
    for d in DIMS:
        out[d] -= np.bincount(stopped_rows, minlength=n) * float(spec[d])
    mine = np.bincount(job_rows, minlength=n) - np.bincount(
        stopped_rows, minlength=n)
    racks = np.bincount(fleet["rack"][job_rows], minlength=n_racks) - (
        np.bincount(fleet["rack"][stopped_rows], minlength=n_racks))
    return out, mine.astype(np.int64), racks.astype(np.int64)


def walk(fleet: dict, used: dict, spec: dict, served_rows, mine, rack_counts,
         steps: int = 0, dtype=np.float64, pick=plain.pick_best,
         rng=None) -> dict:
    """``placement.greedy_walk`` from a given state. With ``served_rows``
    the walk follows the program's nodes in their order and records, per
    step, the served node's score and the best on offer; with None it
    takes ``steps`` steps on the node ``pick`` chooses: the reference
    scheduler itself."""
    used = {d: used[d].astype(np.float64).copy() for d in DIMS}
    ask = {d: float(spec[d]) for d in DIMS}
    mine = np.asarray(mine, dtype=np.int64).copy()
    rack_counts = np.asarray(rack_counts, dtype=np.int64).copy()
    served, best, rows = [], [], []
    for step in (range(steps) if served_rows is None else served_rows):
        score = plain._scores(
            fleet, used, ask, spec, mine, rack_counts, True, dtype
        )
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


def serve_round(fleet: dict, used: dict, spec: dict, rows_by_name: dict,
                version_by_name: dict, healthy_by_name: dict, current: int,
                fault=None) -> list:
    """The reference in the program's place for one eval of a job whose
    live allocations sit on ``rows_by_name`` (name index -> node row):
    stops and places in ``used`` and the three tables, in place, and
    returns ``[(name index, stopped row or -1, new row, score, name index
    the new allocation is given)]``. ``fault`` breaks one rule
    (``FAULTS``)."""
    names = sorted(rows_by_name)
    update = spec.get("update")
    max_parallel = int(update["max_parallel"]) if update else None
    if fault == "max_parallel_ignored":
        max_parallel = None
    stop, place = round_plan(
        spec["count"], names, [version_by_name[k] for k in names],
        [healthy_by_name[k] for k in names], current, max_parallel,
    )
    stopped_rows = [rows_by_name[int(k)] for k in stop]
    view, mine, racks = freed_view(
        fleet, used, spec, [rows_by_name[k] for k in names], stopped_rows)
    if fault == "spread_forgets_old_version":
        racks = np.bincount(
            fleet["rack"][np.asarray(
                [rows_by_name[k] for k in names
                 if version_by_name[k] == current], dtype=np.int64)],
            minlength=racks.size,
        )
    if fault == "stops_not_freed":
        view = {d: used[d].astype(np.float64).copy() for d in DIMS}
    dtype = np.float64
    if fault == "bfloat16_scores":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    w = walk(fleet, view, spec, None, mine, racks, steps=len(place),
             dtype=dtype)
    out = []
    for d in DIMS:
        used[d] -= np.bincount(
            np.asarray(stopped_rows, dtype=np.int64), minlength=fleet["n"]
        ) * float(spec[d])
    stopped = {int(k) for k in stop}
    for k, row, score in zip(place, w["rows"], w["served"]):
        k = int(k)
        if not np.isfinite(score):
            continue
        old = rows_by_name.get(k, -1) if k in stopped else -1
        if fault == "name_twice" and k + 1 in rows_by_name and (
            k + 1 not in stopped
        ):
            k_new = k + 1  # the name of a neighbour that stays
        else:
            k_new = k
        out.append((k, old, int(row), float(score), k_new))
        rows_by_name[k] = int(row)
        version_by_name[k] = current
        healthy_by_name[k] = False
        for d in DIMS:
            used[d][row] += float(spec[d])
    return out
