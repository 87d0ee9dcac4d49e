"""The comparison that decides ``correct`` for ``system-10k``.

Read from what the timed path left in the store (``extract_answers``):
every allocation the run's jobs ever held, agents and services, with its
node, resources, job version, the eval that placed it, create and stop
index and recorded score. ``judge`` holds the run to the configuration's
guarantees, exactly, by replaying the commit log (at one index a plan's
stops come before its placements), and every replacement of the window's
updates to the plain reference (``reference/system.py``): its score on the
cluster at the plan's commit with the plan's own stops freed, as a share.

Every update is judged at the index its client saw it done
(``Update.done_index``): the reference's node set for it (every node of
the fleet: all are eligible in this deployment) against the nodes that
then held a live allocation of the new version, and the allocations of an
older version still live beside one.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import placement as plain
from benchmark.reference import system as ref

SCORE_MATCH = 1e-4  # as c2m-10k


def extract_answers(store, job_ids: dict) -> dict:
    """Arrays over every allocation of the run's jobs (``job_ids``: job id
    -> ordinal of the last spec sent under it)."""
    cols: dict = {k: [] for k in (
        "node", "job", "create", "stop", "version", "system", "score",
        *plain.DIMS,
    )}
    evals: list = []
    eval_col: dict = {}  # eval id -> ordinal in ``evals``
    for a in store.allocs():
        j = job_ids.get(a.job_id)
        if j is None:
            continue
        nid = a.node_id
        try:
            row = int(nid[-12:]) if nid.startswith("00000000-0000-4000") else -1
        except ValueError:
            row = -1
        cols["node"].append(row)
        cols["job"].append(j)
        cols["create"].append(a.create_index)
        cols["stop"].append(a.modify_index if a.terminal_status() else 0)
        cols["version"].append(a.job_version)
        cols["system"].append(a.job is not None and a.job.type == "system")
        if a.eval_id not in eval_col:
            eval_col[a.eval_id] = len(evals)
            evals.append(a.eval_id)
        cols.setdefault("eval", []).append(eval_col[a.eval_id])
        served = a.metrics.scores.get(f"{nid}.score") if a.metrics else None
        cols["score"].append(np.nan if served is None else served)
        for d in plain.DIMS:
            cols[d].append(getattr(a.resources, d))
    kind = {"score": np.float64, "system": bool}
    out = {
        k: np.asarray(v, dtype=kind.get(k, np.int64)) for k, v in cols.items()
    }
    out.setdefault("eval", np.zeros(0, dtype=np.int64))
    out["res"] = {d: out[d] for d in plain.DIMS}
    out["eval_col"] = eval_col
    return out


def duplicated(a: dict) -> int:
    """(agent, node) pairs that at some commit index held two live
    allocations at once (stops before placements at one index)."""
    rows = np.flatnonzero(a["system"] & (a["node"] >= 0))
    if not rows.size:
        return 0
    stopped = rows[a["stop"][rows] > 0]
    who = np.r_[rows, stopped]
    idx = np.r_[a["create"][rows], a["stop"][stopped]]
    sign = np.r_[np.ones(rows.size, np.int64),
                 -np.ones(stopped.size, np.int64)]
    keys = a["job"][who] * (int(a["node"].max()) + 1) + a["node"][who]
    order = np.lexsort((sign, idx, keys))
    keys, sign = keys[order], sign[order]
    running = np.cumsum(sign)
    starts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    base = np.repeat(running[starts] - sign[starts],
                     np.diff(np.r_[starts, keys.size]))
    peak = np.maximum.reduceat(running - base, starts)
    return int((peak > 1).sum())


def _update(fleet: dict, a: dict, j: int, spec: dict, r) -> dict:
    """One update, at the index its client saw it done: the reference's
    nodes that lack the new version, the older versions still live beside
    it, and its placements' recorded scores against the reference's."""
    mine = a["job"] == j
    at = int(r.done_index)
    live = mine & (a["create"] <= at) & ((a["stop"] == 0) | (a["stop"] > at))
    new = live & (a["version"] == r.version)
    old = live & (a["version"] < r.version)
    out = {"missing": 0, "old_left": 0, "errors": np.zeros(0)}
    placed = np.flatnonzero(
        mine & (a["version"] == r.version)
        & (a["eval"] == a["eval_col"].get(r.eval_id, -1))
    )
    if placed.size:
        commit = int(a["create"][placed].min())
        placed = placed[a["create"][placed] == commit]
        before = mine & (a["create"] < commit) & (
            (a["stop"] == 0) | (a["stop"] >= commit))
        want = ref.diff(np.arange(fleet["n"]), a["node"][before],
                        a["version"][before], r.version, destructive=True)
        expected = np.r_[want["place"], want["replace"]]
        used = plain.usage_before(
            fleet, a["node"], a["create"], a["stop"], a["res"], commit)
        gone = np.flatnonzero(mine & (a["stop"] == commit))
        freed = {
            d: np.bincount(a["node"][gone], weights=a["res"][d][gone],
                           minlength=fleet["n"]).astype(np.float64)
            for d in plain.DIMS
        }
        ask = {d: float(spec[d]) for d in plain.DIMS}
        score, _fits = ref.scores(fleet, used, ask, a["node"][placed], freed)
        err = np.abs(score - a["score"][placed])
        out["errors"] = np.where(np.isfinite(err), err, 1.0)
    else:
        # nothing placed: every node of the fleet was owed one
        expected = np.arange(fleet["n"])
    out["missing"] = int(np.setdiff1d(expected, a["node"][new]).size)
    out["old_left"] = int(np.isin(a["node"][old], a["node"][new]).sum())
    return out


def judge(fleet: dict, specs_by_job: dict, requests: list, answers: dict,
          window: tuple, seed: int) -> dict:
    t_open, t_close = window
    a = answers
    ordinal = {s["id"]: j for j, s in specs_by_job.items()}
    out = {
        "unfinished_requests": sum(1 for r in requests if r.ok is not True),
    }
    replay = plain.capacity_replay(
        fleet, a["node"], a["create"], a["stop"], a["res"])
    out["nodes_over_capacity"] = replay["nodes_over_capacity"]
    out["allocs_off_fleet"] = replay["allocs_off_fleet"]
    out["system_allocs_duplicated"] = duplicated(a)
    out["unrelated_allocs_stopped"] = int(
        ((~a["system"]) & (a["stop"] > 0)).sum())
    missing = old_left = judged = 0
    errors = []
    for r in requests:
        if getattr(r, "done_index", None) is None:
            continue  # a service's registration, or never done
        j = ordinal[r.job_id]
        spec = next(
            s for s in specs_by_job.values()
            if s["id"] == r.job_id and s.get("version") == r.version
        )
        u = _update(fleet, a, j, spec, r)
        missing += u["missing"]
        old_left += u["old_left"]
        judged += 1
        if r.ok and t_open < r.done <= t_close:
            errors.append(u["errors"])
    out["system_nodes_missing"] = missing
    out["old_version_left"] = old_left
    out["updates_judged"] = judged
    if errors:
        e = np.concatenate(errors)
        out["replacements_scored"] = int(e.size)
        out["score_mismatch_share"] = (
            float((e > SCORE_MATCH).mean()) if e.size else 1.0)
        out["score_error_median"] = float(np.median(e)) if e.size else None
    return out
