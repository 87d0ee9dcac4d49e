"""The comparison that decides ``correct`` for ``drain-10k``.

Read from what the timed path left in the store (``extract_answers``):
every allocation the run's jobs ever held, with its node, resources, name
index, the eval that placed it, its migrate mark, the allocation it
replaces and the one that replaced it, its create and modify index and its
recorded score; every eval of those jobs with its trigger and node; the
nodes' last drain strategy and eligibility; the drainer's force-stop
counter. The store keeps a node's and a stopped allocation's *last* write
only, so the requests bring what their driver read from the store when it
happened (``drain/driver.py``): the index at which each node was set
draining, seen cleared and set eligible again, at which each held
allocation was stopped and each replacement acknowledged.

``judge`` holds the run to the configuration's guarantees, exactly, by
replaying the commit log (at one index a plan's stops come before its
placements), and a seeded sample of the drainer's evals that placed
something to the plain reference (``reference/drain.py``), as shares.

A plan was made on a snapshot the store does not record. As ``check.py``
does, a sampled eval is judged on *views*: the cluster at the plan's own
commit or at one of the few stop commits before it; the view that explains
most of the recorded scores is taken. Whatever the view, the plan's own
stops are freed first and every node that drains or is ineligible at the
plan's commit (or at the view's) is masked out.
"""

from __future__ import annotations

import random

import numpy as np

from benchmark.reference import drain as ref
from benchmark.reference import placement as plain
from benchmark.rollout.judge import (
    _peaks,
    job_count_off,
    names_duplicated,
)

SAMPLE_EVALS = 24
SCORE_MATCH = 1e-4  # as c2m-10k
OLDER_VIEWS = 16
JOB_OFF_BEST = 0.05
JOB_UNEXPLAINED = 0.1
SETTLE_S = 5.0  # a drain due this long before the close has to have ended
_TRIGGER_DRAIN = "node-drain"
_FOREVER = np.iinfo(np.int64).max


def _node_row(node_id: str) -> int:
    try:
        return (
            int(node_id[-12:]) if node_id.startswith("00000000-0000-4000")
            else -1
        )
    except ValueError:
        return -1


def extract_answers(store, job_ids: dict) -> dict:
    """Arrays over every allocation of the run's jobs (``job_ids``: job id
    -> ordinal of the last spec sent under it) and over their evals; the
    nodes' last state; the drainer's alarm counter."""
    from nomad_tpu.utils.metrics import global_metrics

    evals, ev_row = {k: [] for k in (
        "job", "create", "node", "drain", "ok", "blocked")}, {}
    for e in store.evals():
        j = job_ids.get(e.job_id)
        if j is None:
            continue
        ev_row[e.id] = len(evals["job"])
        evals["job"].append(j)
        evals["create"].append(e.create_index)
        evals["node"].append(_node_row(e.node_id or ""))
        evals["drain"].append(e.triggered_by == _TRIGGER_DRAIN)
        evals["ok"].append(e.status == "complete")
        evals["blocked"].append(e.status == "blocked")
    cols: dict = {k: [] for k in (
        "node", "job", "create", "stop", "name_idx", "eval", "marked",
        "score", *plain.DIMS,
    )}
    ids, prev_id, next_id = [], [], []
    for a in store.allocs():
        j = job_ids.get(a.job_id)
        if j is None:
            continue
        ids.append(a.id)
        prev_id.append(a.previous_allocation)
        next_id.append(a.next_allocation)
        cols["node"].append(_node_row(a.node_id))
        cols["job"].append(j)
        cols["create"].append(a.create_index)
        # a stopped allocation's last write is its client's ``complete``:
        # ``judge`` puts the plan's own index here from the requests
        cols["stop"].append(a.modify_index if a.terminal_status() else 0)
        cols["name_idx"].append(a.index())
        cols["eval"].append(ev_row.get(a.eval_id, -1))
        cols["marked"].append(bool(a.desired_transition.migrate))
        served = (
            a.metrics.scores.get(f"{a.node_id}.score") if a.metrics else None
        )
        cols["score"].append(np.nan if served is None else served)
        for d in plain.DIMS:
            cols[d].append(getattr(a.resources, d))
    kind = {"score": np.float64, "marked": bool}
    out = {
        k: np.asarray(v, dtype=kind.get(k, np.int64)) for k, v in cols.items()
    }
    row_of = {alloc_id: i for i, alloc_id in enumerate(ids)}
    out["ids"] = row_of  # allocation id -> row
    out["prev"] = np.asarray(
        [row_of.get(p, -1) for p in prev_id], dtype=np.int64)
    out["next"] = np.asarray(
        [row_of.get(p, -1) for p in next_id], dtype=np.int64)
    out["res"] = {d: out[d] for d in plain.DIMS}
    out["evals"] = {
        k: np.asarray(v, dtype=np.int64 if k in ("job", "create", "node")
                      else bool)
        for k, v in evals.items()
    }
    out["eval_row"] = ev_row
    draining, ineligible = [], []
    for n in store.nodes():
        row = _node_row(n.id)
        if row < 0:
            continue
        if n.drain is not None:
            draining.append(row)
        if n.scheduling_eligibility != "eligible":
            ineligible.append(row)
    out["nodes_draining"] = np.asarray(draining, dtype=np.int64)
    out["nodes_ineligible"] = np.asarray(ineligible, dtype=np.int64)
    out["drain_force_stops"] = int(
        global_metrics.snapshot()["counters"].get(
            "nomad.drain.force_stops", 0)
    )
    return out


# -- what the requests know ---------------------------------------------------
def drains_of(requests: list) -> list:
    return [r for r in requests if hasattr(r, "drain_index")]


def with_the_drivers_indices(a: dict, drains: list) -> tuple:
    """``(stop, acked)``: the allocations' stop index with the plan's own
    index where the driver read it, and the index at which a replacement
    was acknowledged running (0: never, or an allocation of the fill,
    acknowledged before the first drain)."""
    stop = a["stop"].copy()
    acked = np.zeros(stop.size, dtype=np.int64)
    for r in drains:
        for alloc_id, index in r.stops.items():
            stop[a["ids"][alloc_id]] = index
        for alloc_id, index in r.acks.items():
            acked[a["ids"][alloc_id]] = index
    return stop, acked


class Closed:
    """The intervals in which a node drained or was ineligible:
    ``(drain set, set eligible again)``, both exclusive."""

    def __init__(self, n: int, drains: list):
        self.n = n
        self.rows = np.asarray([r.node_row for r in drains], dtype=np.int64)
        self.since = np.asarray(
            [r.drain_index for r in drains], dtype=np.int64)
        self.until = np.asarray(
            [r.eligible_index or _FOREVER for r in drains], dtype=np.int64)
        # the strategy itself: set until the drainer cleared it
        self.cleared = np.asarray(
            [r.clear_index or _FOREVER for r in drains], dtype=np.int64)

    def at(self, index: int, draining_only: bool = False) -> np.ndarray:
        until = self.cleared if draining_only else self.until
        hit = (self.since < index) & (index < until)
        out = np.zeros(self.n, dtype=bool)
        out[self.rows[hit]] = True
        return out


def placed_on_ineligible(a: dict, closed: Closed) -> int:
    """Allocations created on a node inside one of its intervals."""
    n = 0
    for row, since, until in zip(closed.rows, closed.since, closed.until):
        there = a["create"][a["node"] == row]
        n += int(((since < there) & (there < until)).sum())
    return n


def mark_index(a: dict) -> np.ndarray:
    """Per allocation the index of the commit that marked it, -1 without a
    mark. The drainer's transition message carries a wave's marks and its
    evals, one eval a job with the draining node on it, so a marked
    allocation's wave is the newest drainer eval of its job and its node
    from before the plan that stopped it (with ``max_parallel`` 1 a group's
    next wave waits for that plan's replacement; over 1 this reads a wave
    too late, never too early); never stopped, the newest of all. Which
    eval placed the replacement does not say: any eval of the job migrates
    every allocation marked by then, so a wave's eval that waited in the
    broker places the replacement of a later wave's mark, on another node
    too."""
    ev = a["evals"]
    waves = np.flatnonzero(ev["drain"])
    out = np.full(a["node"].size, -1, dtype=np.int64)
    for i in np.flatnonzero(a["marked"]):
        mine = ev["create"][waves[
            (ev["job"][waves] == a["job"][i])
            & (ev["node"][waves] == a["node"][i])
        ]]
        if a["stop"][i] > 0 and (mine < a["stop"][i]).any():
            mine = mine[mine < a["stop"][i]]
        out[i] = mine.max() if mine.size else a["create"][i]
    return out


def migrate_parallel_exceeded(a: dict, mark: np.ndarray, acked: np.ndarray,
                              limits: dict) -> int:
    """Commit indices at which a job (``limits``: job -> ``max_parallel``)
    held more marked allocations whose replacement was not yet healthy
    than its ``max_parallel``: one counts from its mark to the index at
    which its replacement was acknowledged running."""
    rows = np.flatnonzero((mark >= 0) & np.isin(a["job"], list(limits)))
    if not rows.size:
        return 0
    nxt = a["next"][rows]
    done = np.where(nxt >= 0, acked[np.maximum(nxt, 0)], 0)
    well = rows[done > 0]
    who = np.r_[rows, well]
    idx = np.r_[mark[rows], done[done > 0]]
    sign = np.r_[np.ones(rows.size, np.int64), -np.ones(well.size, np.int64)]
    jobs, _order, running = _peaks(a["job"][who], idx, sign)
    cap = np.asarray([limits[int(j)] for j in jobs], dtype=np.int64)
    return int((running > cap).sum())


def _judge_eval(fleet: dict, a: dict, spec: dict, e: int, mark, acked,
                closed: Closed, stop_commits) -> dict:
    """One sampled eval of the drainer: whether the marks behind it kept to
    the reference's budget and the names it stopped and placed are the
    reference's, and its placements' recorded scores and choice of nodes
    on the view that explains most."""
    placed = np.flatnonzero(a["eval"] == e)
    commit = int(a["create"][placed].min())
    placed = placed[a["create"][placed] == commit]
    placed = placed[np.argsort(a["name_idx"][placed], kind="stable")]
    j = int(a["job"][placed[0]])
    mine = np.flatnonzero(a["job"] == j)

    def live_at(index):
        return mine[(a["create"][mine] < index) & (
            (a["stop"][mine] == 0) | (a["stop"][mine] >= index))]

    before = live_at(commit)
    stopped = before[a["stop"][before] == commit]
    shut = closed.at(commit)
    want_stop, want_place = ref.eval_plan(
        spec["count"], a["name_idx"][before],
        (mark[before] >= 0) & (mark[before] < commit),
        shut[a["node"][before]],
    )
    names_ok = (
        np.array_equal(np.sort(a["name_idx"][stopped]), want_stop)
        and np.array_equal(a["name_idx"][placed], want_place)
    )
    # the wave behind the eval: the marks its commit made, beside the budget
    wave = int(a["evals"]["create"][e])
    then = live_at(wave)
    replacement = a["prev"][then] >= 0
    budget = ref.may_mark(
        spec["count"], int(spec["migrate"]["max_parallel"]),
        (mark[then] >= 0) & (mark[then] < wave),
        np.where(replacement, (acked[then] > 0) & (acked[then] < wave), True),
        closed.at(wave, draining_only=True)[a["node"][then]],
    )
    names_ok = names_ok and int((mark[then] == wave).sum()) <= budget

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
        open_nodes = ~(shut | closed.at(horizon))
        w = ref.walk(fleet, view, spec, rows, on_node, racks, open_nodes)
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
    drains = drains_of(requests)
    stop, acked = with_the_drivers_indices(answers, drains)
    a = {**answers, "stop": stop}
    closed = Closed(fleet["n"], drains)
    ordinal = {s["id"]: j for j, s in specs_by_job.items()}
    out = {
        "unfinished_requests": sum(1 for r in requests if r.ok is not True),
    }
    replay = plain.capacity_replay(
        fleet, a["node"], a["create"], a["stop"], a["res"])
    out["nodes_over_capacity"] = replay["nodes_over_capacity"]
    out["allocs_off_fleet"] = replay["allocs_off_fleet"]
    out["placed_on_ineligible"] = placed_on_ineligible(a, closed)

    gone = {ordinal[r.job_id] for r in requests if r.kind == "deregister"}
    kept = {j: s for j, s in specs_by_job.items()
            if ordinal[s["id"]] == j and j not in gone}
    out["job_count_off"] = job_count_off(
        a, {j: s["count"] for j, s in kept.items()})
    mark = mark_index(a)
    out["migrate_parallel_exceeded"] = migrate_parallel_exceeded(
        a, mark, acked,
        {j: int(s["migrate"]["max_parallel"])
         for j, s in kept.items() if s.get("migrate")},
    )
    stayed = np.isin(a["job"], list(kept))
    out["unmarked_alloc_stopped"] = int(
        (stayed & (a["stop"] > 0) & ~a["marked"]).sum())
    out["alloc_names_duplicated"] = names_duplicated(a)
    out["blocked_evals_left"] = int(a["evals"]["blocked"].sum())
    out["drain_force_stops"] = int(a["drain_force_stops"])

    not_empty = unfinished = 0
    for r in drains:
        there = a["node"] == r.node_row
        if r.ok and r.clear_index:
            not_empty += bool((there & (a["create"] < r.clear_index) & (
                (a["stop"] == 0) | (a["stop"] > r.clear_index))).any())
        if r.due <= t_close - SETTLE_S:
            unfinished += (
                r.ok is not True or r.node_row in a["nodes_draining"])
    out["drains_judged"] = len(drains)
    out["drained_node_not_empty"] = not_empty
    out["drains_unfinished"] = unfinished
    held = sorted(r.count for r in drains if t_open <= r.due < t_close)
    if held:
        out["allocs_held_min_median_max"] = [
            held[0], held[len(held) // 2], held[-1]]

    # the sample: the drainer's evals of the window's drains that placed
    # something
    first = min(
        (r.drain_index for r in drains if r.due >= t_open), default=_FOREVER)
    ev = a["evals"]
    placing = [
        int(e) for e in np.unique(a["eval"][a["eval"] >= 0])
        if ev["drain"][e] and ev["create"][e] > first
    ]
    rng = random.Random(f"{seed}:check")
    sample = rng.sample(placing, min(SAMPLE_EVALS, len(placing)))
    stop_commits = np.unique(a["stop"][a["stop"] > 0])
    judged = [
        _judge_eval(fleet, a, specs_by_job[int(ev["job"][e])], e, mark,
                    acked, closed, stop_commits)
        for e in sample
    ]
    out["drain_evals_that_placed"] = len(placing)
    out["evals_judged"] = len(judged)
    if judged:
        errors = np.concatenate([b["errors"] for b in judged])
        out["placements_scored"] = int(errors.size)
        out["evals_judged_on_an_older_view"] = sum(
            b["older_view"] for b in judged)
        out["mark_set_mismatch_share"] = sum(
            not b["names_ok"] for b in judged) / len(judged)
        out["score_mismatch_share"] = float((errors > SCORE_MATCH).mean())
        out["score_error_median"] = float(np.median(errors))
        out["jobs_off_best_share"] = sum(
            b["off"] for b in judged) / len(judged)
        out["worst_gap_to_best"] = max(b["gap"] for b in judged)
    return out
