"""The comparison that decides ``correct`` for ``rollout-10k``.

Read from what the timed path left in the store (``extract_answers``):
every allocation the run's jobs ever held, with its node, resources, name
index, job version, the eval that placed it, its deployment, its health
verdict, create and stop index and recorded score; every eval and every
deployment of those jobs; the jobs' final version and ``stable`` flag.
``judge`` holds the run to the configuration's guarantees, exactly, by
replaying the commit log (at one index a plan's stops come before its
placements), and a seeded sample of the evals that placed something — the
window's registrations and the deployment watcher's later rounds alike — to
the plain reference (``reference/rollout.py``), as shares.

A plan was made on a snapshot the store does not record. As ``check.py``
does, a sampled eval is judged on *views*: the cluster at the plan's own
commit or at one of the few stop commits before it; the view that explains
most of the recorded scores is taken. Whatever the view, the plan's own
stops are freed first.
"""

from __future__ import annotations

import random

import numpy as np

from benchmark.reference import placement as plain
from benchmark.reference import rollout as ref

SAMPLE_EVALS = 24
SCORE_MATCH = 1e-4  # as c2m-10k
OLDER_VIEWS = 16
JOB_OFF_BEST = 0.05
JOB_UNEXPLAINED = 0.1
SETTLE_S = 5.0  # a rollout due this long before the close has to have ended
_TRIGGER_WATCHER = "deployment-watcher"


def extract_answers(store, job_ids: dict) -> dict:
    """Arrays over every allocation of the run's jobs (``job_ids``: job id
    -> ordinal of the last spec sent under it), over their evals and their
    deployments."""
    evals, ev_row = {k: [] for k in ("job", "create", "watcher", "ok")}, {}
    for e in store.evals():
        j = job_ids.get(e.job_id)
        if j is None:
            continue
        ev_row[e.id] = len(evals["job"])
        evals["job"].append(j)
        evals["create"].append(e.create_index)
        evals["watcher"].append(e.triggered_by == _TRIGGER_WATCHER)
        evals["ok"].append(e.status == "complete")
    deployments = {k: [] for k in ("job", "version", "status")}
    for d in store.deployments():
        j = job_ids.get(d.job_id)
        if j is not None:
            deployments["job"].append(j)
            deployments["version"].append(d.job_version)
            deployments["status"].append(d.status)
    cols: dict = {k: [] for k in (
        "node", "job", "create", "stop", "modify", "name_idx", "version",
        "eval", "in_deployment", "healthy", "score", *plain.DIMS,
    )}
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
        cols["modify"].append(a.modify_index)
        cols["name_idx"].append(a.index())
        cols["version"].append(a.job_version)
        cols["eval"].append(ev_row.get(a.eval_id, -1))
        cols["in_deployment"].append(bool(a.deployment_id))
        cols["healthy"].append(
            a.deployment_status is not None
            and a.deployment_status.healthy is True
        )
        served = a.metrics.scores.get(f"{nid}.score") if a.metrics else None
        cols["score"].append(np.nan if served is None else served)
        for d in plain.DIMS:
            cols[d].append(getattr(a.resources, d))
    kind = {"score": np.float64, "in_deployment": bool, "healthy": bool}
    out = {
        k: np.asarray(v, dtype=kind.get(k, np.int64)) for k, v in cols.items()
    }
    out["res"] = {d: out[d] for d in plain.DIMS}
    out["evals"] = {
        k: np.asarray(v, dtype=np.int64 if k in ("job", "create") else bool)
        for k, v in evals.items()
    }
    out["eval_row"] = ev_row  # eval id -> row of ``evals``
    out["deployments"] = {
        "job": np.asarray(deployments["job"], dtype=np.int64),
        "version": np.asarray(deployments["version"], dtype=np.int64),
        "status": list(deployments["status"]),
    }
    out["jobs"] = {}
    for job_id, j in job_ids.items():
        job = store.job_by_id("default", job_id)
        if job is not None:
            out["jobs"][j] = (int(job.version), bool(job.stable))
    return out


def _peaks(keys, idx, sign) -> tuple:
    """Per distinct key the running sums of ``sign`` in order of ``idx``
    (at one index the negative ones first): ``(keys, order, running)``
    with ``running`` counted from 0 inside each key."""
    order = np.lexsort((sign, idx, keys))
    keys, sign = keys[order], sign[order]
    running = np.cumsum(sign)
    starts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    base = np.repeat(running[starts] - sign[starts],
                     np.diff(np.r_[starts, keys.size]))
    return keys, order, running - base


def _events(a: dict, rows) -> tuple:
    """+1 at its create index, -1 at its stop index, per allocation of
    ``rows``: ``(row of the allocation, index, sign)``."""
    stopped = rows[a["stop"][rows] > 0]
    return (
        np.r_[rows, stopped],
        np.r_[a["create"][rows], a["stop"][stopped]],
        np.r_[np.ones(rows.size, np.int64), -np.ones(stopped.size, np.int64)],
    )


def job_count_off(a: dict, counts: dict) -> int:
    """Commit indices, from a job's first full count on, after which its
    live allocations were not its count (``counts``: job -> count, the
    jobs that were never deregistered)."""
    rows = np.flatnonzero(np.isin(a["job"], list(counts)))
    if not rows.size:
        return 0
    who, idx, sign = _events(a, rows)
    jobs, order, running = _peaks(a["job"][who], idx, sign)
    idx = idx[order]
    last = np.r_[(jobs[1:] != jobs[:-1]) | (idx[1:] != idx[:-1]), True]
    want = np.asarray([counts[int(j)] for j in jobs], dtype=np.int64)
    off = 0
    for j in np.unique(jobs):
        at = np.flatnonzero((jobs == j) & last)
        full = np.flatnonzero(running[at] == want[at])
        if not full.size:
            off += 1
            continue
        off += int((running[at[full[0]:]] != want[at[full[0]:]]).sum())
    return off


def max_parallel_exceeded(a: dict, limits: dict) -> int:
    """Commit indices at which a rolling job (``limits``: job ->
    ``max_parallel``) held more new-version allocations that were not yet
    healthy than its ``max_parallel``. An allocation of a deployment
    counts from its create index to the index of its health verdict (the
    clients' sync is the last commit to touch a live one)."""
    rows = np.flatnonzero(
        np.isin(a["job"], list(limits)) & a["in_deployment"]
    )
    if not rows.size:
        return 0
    well = rows[a["healthy"][rows]]
    gone = rows[~a["healthy"][rows] & (a["stop"][rows] > 0)]
    who = np.r_[rows, well, gone]
    idx = np.r_[a["create"][rows], a["modify"][well], a["stop"][gone]]
    sign = np.r_[np.ones(rows.size, np.int64),
                 -np.ones(well.size + gone.size, np.int64)]
    jobs, _order, running = _peaks(a["job"][who], idx, sign)
    cap = np.asarray([limits[int(j)] for j in jobs], dtype=np.int64)
    return int((running > cap).sum())


def names_duplicated(a: dict) -> int:
    """(job, name index) pairs that two live allocations held at once."""
    rows = np.flatnonzero(a["name_idx"] >= 0)
    if not rows.size:
        return 0
    who, idx, sign = _events(a, rows)
    width = int(a["name_idx"].max()) + 1
    keys = a["job"][who] * width + a["name_idx"][who]
    keys, _order, running = _peaks(keys, idx, sign)
    starts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    return int((np.maximum.reduceat(running, starts) > 1).sum())


def _judge_eval(fleet: dict, a: dict, spec: dict, e: int,
                stop_commits) -> dict:
    """One sampled eval: whether the names it stopped and placed are the
    reference's, and its placements' recorded scores and choice of nodes
    on the view that explains most."""
    placed = np.flatnonzero(a["eval"] == e)
    commit = int(a["create"][placed].min())
    placed = placed[a["create"][placed] == commit]
    placed = placed[np.argsort(a["name_idx"][placed], kind="stable")]
    j = int(a["job"][placed[0]])
    mine = np.flatnonzero(a["job"] == j)
    before = mine[(a["create"][mine] < commit) & (
        (a["stop"][mine] == 0) | (a["stop"][mine] >= commit))]
    stopped = before[a["stop"][before] == commit]
    current = int(a["version"][placed].max())
    update = spec.get("update")
    # healthy by the plan's commit: the verdict landed before it
    well = a["healthy"][before] & (a["modify"][before] < commit)
    want_stop, want_place = ref.round_plan(
        spec["count"], a["name_idx"][before], a["version"][before], well,
        current, int(update["max_parallel"]) if update else None,
    )
    names_ok = (
        np.array_equal(np.sort(a["name_idx"][stopped]), want_stop)
        and np.array_equal(a["name_idx"][placed], want_place)
    )
    rows, said = a["node"][placed], a["score"][placed]
    horizons = [commit] + [
        int(s) for s in stop_commits[stop_commits < commit][::-1][:OLDER_VIEWS]
    ]
    seen = None
    for horizon in horizons:
        used = plain.usage_before(
            fleet, a["node"], a["create"], a["stop"], a["res"], commit,
            horizon,
        )
        view, on_node, racks = ref.freed_view(
            fleet, used, spec, a["node"][before], a["node"][stopped]
        )
        w = ref.walk(fleet, view, spec, rows, on_node, racks)
        err = np.abs(w["served"] - said)
        err = np.where(np.isfinite(err), err, 1.0)
        best = np.where(np.isfinite(w["best"]), w["best"], 1.0)
        gap = best - np.where(np.isfinite(w["served"]), w["served"], 0.0)
        key = (float((err > SCORE_MATCH).mean()),
               float(gap.sum() / best.sum()))
        if seen is None or key < seen[0]:
            seen = (key, err, horizon)
        if key[0] == 0.0 and key[1] <= JOB_OFF_BEST:
            break
    key, err, horizon = seen
    return {
        "names_ok": names_ok, "errors": err, "gap": key[1],
        "off": key[0] > JOB_UNEXPLAINED or key[1] > JOB_OFF_BEST,
        "older_view": horizon != commit,
    }


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

    gone = {ordinal[r.job_id] for r in requests if r.kind == "deregister"}
    kept = {j: s for j, s in specs_by_job.items()
            if ordinal[s["id"]] == j and j not in gone}
    out["job_count_off"] = job_count_off(
        a, {j: s["count"] for j, s in kept.items()})
    out["max_parallel_exceeded"] = max_parallel_exceeded(a, {
        j: int(s["update"]["max_parallel"])
        for j, s in kept.items() if s.get("update")
    })
    out["alloc_names_duplicated"] = names_duplicated(a)
    out["deployments_failed"] = sum(
        s == "failed" for s in a["deployments"]["status"])

    # the version a placement has to be on: the registrations of its job
    # committed before it, less one (the first is version 0)
    registered: dict = {}  # job -> commit indices of its registrations
    rollouts = []  # (job, version, request) of every later registration
    for r in requests:
        if r.kind != "register" or r.eval_id not in a["eval_row"]:
            continue
        j = ordinal[r.job_id]
        at = registered.setdefault(j, [])
        if at:
            rollouts.append((j, len(at), r))
        at.append(int(a["evals"]["create"][a["eval_row"][r.eval_id]]))
    old = 0
    for j, at in registered.items():
        rows = np.flatnonzero(a["job"] == j)
        due = np.searchsorted(np.asarray(at), a["create"][rows]) - 1
        old += int((a["version"][rows] < due).sum())
    out["old_version_placed"] = old

    live = a["stop"] == 0
    deployed = {
        (int(j), int(v)): s for j, v, s in zip(
            a["deployments"]["job"], a["deployments"]["version"],
            a["deployments"]["status"])
    }
    unfinished = 0
    for j, version, r in rollouts:
        if r.due > t_close - SETTLE_S or len(registered[j]) - 1 != version:
            continue  # still rolling at the close, or rolled again since
        rows = np.flatnonzero((a["job"] == j) & live)
        spec = specs_by_job[j]
        ended = rows.size == spec["count"] and bool(
            (a["version"][rows] == version).all())
        if spec.get("update"):
            ended = (
                ended and deployed.get((j, version)) == "successful"
                and a["jobs"].get(j) == (version, True)
            )
        unfinished += not ended
    out["rollouts_judged"] = len(rollouts)
    out["rollouts_unfinished"] = unfinished

    # the sample: evals of the window's rollouts that placed something,
    # the registrations' own and the watcher's rounds
    in_window = {
        ordinal[r.job_id] for _j, _v, r in rollouts
        if r.ok and t_open < r.done <= t_close
    }
    placing = np.unique(a["eval"][(a["eval"] >= 0) & np.isin(
        a["job"], list(in_window))])
    placing = [int(e) for e in placing
               if a["evals"]["create"][e] >= min(
                   registered[int(a["evals"]["job"][e])][1:] or [0])]
    rng = random.Random(f"{seed}:check")
    sample = rng.sample(placing, min(SAMPLE_EVALS, len(placing)))
    stop_commits = np.unique(a["stop"][a["stop"] > 0])
    judged = [
        _judge_eval(fleet, a, specs_by_job[int(a["evals"]["job"][e])], e,
                    stop_commits)
        for e in sample
    ]
    out["evals_judged"] = len(judged)
    out["watcher_evals_judged"] = int(
        sum(a["evals"]["watcher"][e] for e in sample))
    if judged:
        errors = np.concatenate([b["errors"] for b in judged])
        out["placements_scored"] = int(errors.size)
        out["evals_judged_on_an_older_view"] = sum(
            b["older_view"] for b in judged)
        out["stop_set_mismatch_share"] = sum(
            not b["names_ok"] for b in judged) / len(judged)
        out["score_mismatch_share"] = float((errors > SCORE_MATCH).mean())
        out["score_error_median"] = float(np.median(errors))
        out["jobs_off_best_share"] = sum(
            b["off"] for b in judged) / len(judged)
        out["worst_gap_to_best"] = max(b["gap"] for b in judged)
    return out
