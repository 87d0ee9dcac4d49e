"""The plain reference for a system job: which nodes an eval places on,
which allocations it stops, and what each placement scores.

Plain numpy on plain tables, float64; it imports nothing of the program.
Written from the description of Nomad's ``scheduler/util.go``
``diffSystemAllocs`` / ``diffSystemAllocsForNode``, ``evictAndPlace``,
``inplaceUpdate`` and ``tasksUpdated``, and of the system scheduler's stack
(``scheduler/stack.go`` ``SystemStack``: feasibility, then the binpack
iterator alone, then score normalisation — no job anti-affinity, no
spread).

``diff``: for one group of one job, given the eligible node rows, the rows
its live allocations stand on with their versions, the version registered,
whether the new version changes the tasks and the update's
``max_parallel``:

- an eligible node without an allocation of the group gets one (*place*);
- an allocation of an older version is updated: in place where the tasks
  did not change (*inplace*, same node, same allocation), else it is
  stopped and a new one placed on its node in the same plan (*replace*),
  at most ``max_parallel`` of them an eval when the update is rolling, by
  node (``evictAndPlace``; *limit_reached* then asks for a follow-up);
- an allocation on a node that is no longer eligible is stopped (*stop*);
- a current one is left alone (*ignore*).

``scores``: the binpack score (``placement._fit``: ScoreFit normalised) of
one more allocation of the ask on each row, on the usage with the
allocation the plan stops there taken off, and whether it fits in every
dimension. ``serve_update``: the two as a scheduler, applied to the usage.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.placement import DIMS, _fit

# the ways a scheduler can get an update wrong, each failing its own limit
FAULTS = (
    "stops_not_freed",  # the old allocation still on its node when scored
    "every_97th_skipped",  # every 97th node of the fleet left without one
    "bfloat16_scores",  # scores in the precision below the program's
    "places_nothing",  # the parent's: an update that places nothing
)


def diff(eligible, held_node, held_version, version: int,
         destructive: bool, max_parallel=None) -> dict:
    """diffSystemAllocs + evictAndPlace for one group. Rows are sorted;
    ``held_*`` are the group's live allocations."""
    eligible = np.unique(np.asarray(eligible, dtype=np.int64))
    held_node = np.asarray(held_node, dtype=np.int64)
    held_version = np.asarray(held_version, dtype=np.int64)
    on_target = np.isin(held_node, eligible)
    stop = np.sort(held_node[~on_target])
    current = on_target & (held_version == version)
    older = on_target & (held_version != version)
    ignore = held_node[current]
    if destructive:
        candidates = np.sort(held_node[older])
        limit = candidates.size if max_parallel is None else int(max_parallel)
        replace, waiting = candidates[:limit], candidates[limit:]
        inplace = np.zeros(0, dtype=np.int64)
    else:
        replace = waiting = np.zeros(0, dtype=np.int64)
        inplace = np.sort(held_node[older])
    return {
        "place": np.setdiff1d(eligible, held_node),
        "replace": replace,
        "inplace": inplace,
        "ignore": np.sort(np.r_[ignore, waiting]),
        "stop": stop,
        "limit_reached": bool(waiting.size),
    }


def scores(fleet: dict, used: dict, ask: dict, rows, freed=None,
           dtype=np.float64) -> tuple:
    """``(score, fits)`` of one more allocation of ``ask`` on each of
    ``rows``, on ``used`` less ``freed`` (per-node usage the plan stops)."""
    rows = np.asarray(rows, dtype=np.int64)
    view = {
        d: used[d][rows] - (freed[d][rows] if freed is not None else 0.0)
        for d in DIMS
    }
    fits = np.ones(rows.size, dtype=bool)
    for d in DIMS:
        fits &= view[d] + ask[d] <= fleet[d][rows]
    sub = {d: fleet[d][rows] for d in DIMS}
    return _fit(sub, view, ask, dtype), fits


def serve_update(fleet: dict, used: dict, ask: dict, held_node, held_version,
                 version: int, destructive: bool = True, eligible=None,
                 max_parallel=None, fault=None, old_ask=None) -> dict:
    """One eval of the reference scheduler: the diff, the old allocations
    (of ``old_ask``, by default the ask) freed, every row to place scored
    and placed where it fits. Takes the stops off ``used`` and adds the
    placements to it. Returns the diff with ``placed`` (rows), ``score``
    (each placed row's), ``unplaced`` and ``stopped`` (rows)."""
    if eligible is None:
        eligible = np.arange(fleet["n"])
    if fault == "every_97th_skipped":
        eligible = np.setdiff1d(eligible, np.arange(0, fleet["n"], 97))
    d = diff(eligible, held_node, held_version, version, destructive,
             max_parallel)
    stopped = np.r_[d["replace"], d["stop"]].astype(np.int64)
    if fault == "places_nothing":
        d.update(placed=np.zeros(0, np.int64), score=np.zeros(0),
                 unplaced=0, stopped=np.zeros(0, np.int64))
        return d
    freed = {dim: np.zeros(fleet["n"]) for dim in DIMS}
    for dim in DIMS:
        np.add.at(freed[dim], stopped, (old_ask or ask)[dim])
    rows = np.sort(np.r_[d["place"], d["replace"]]).astype(np.int64)
    score, fits = scores(
        fleet, used, ask, rows,
        None if fault == "stops_not_freed" else freed,
        dtype=_bfloat16() if fault == "bfloat16_scores" else np.float64,
    )
    for dim in DIMS:
        used[dim] -= freed[dim]
        np.add.at(used[dim], rows[fits], ask[dim])
    d.update(placed=rows[fits], score=score[fits],
             unplaced=int((~fits).sum()), stopped=stopped)
    return d


def _bfloat16():
    import ml_dtypes

    return ml_dtypes.bfloat16
