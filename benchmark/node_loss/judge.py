"""The comparison that decides ``correct`` for ``rackloss-10k``.

Read from what the timed path left in the store (``extract_answers``):
every allocation the run's jobs ever held, with its node, resources, name
index, the eval that placed it, whether its client status is ``lost``, the
allocation it replaces and the one that replaced it, its create and stop
index and its recorded score; every eval of those jobs with its trigger and
status; the counters ``nomad.heartbeat.expired`` and
``nomad.plan.allocs_lost``. The store keeps a node's last write only, so the
failures bring what their driver read when it happened
(``node_loss/driver.py``): the index at which each node was marked down and
ready again, and the index at which each request's client saw its job done.

``judge`` holds the run to the configuration's guarantees, exactly, by
replaying the commit log (at one index a plan's stops come before its
placements), and a seeded sample of the node evals that placed something to
the plain reference (``reference/node_loss.py``), as shares.

A plan's placements are judged on the cluster as its pass read it, which
the program writes down: the eval's ``snapshot_index`` (the nodes open to
it) and each placement's ``usage_read``, the ordinal of the placement
overlay's read it was scored on (``_read_view`` says what that read held).
The plan's own stops are freed first; an eval committed in part is judged on
its retry's placements, which were scored with the first part in the store.
"""

from __future__ import annotations

import random

import numpy as np

from benchmark.reference import node_loss as ref
from benchmark.reference import placement as plain
from benchmark.rollout.judge import _events, _peaks, names_duplicated

SAMPLE_EVALS = 24
SCORE_MATCH = 1e-4  # as c2m-10k
JOB_OFF_BEST = 0.05
JOB_UNEXPLAINED = 0.1
_TRIGGER_NODE = "node-update"
_FOREVER = np.iinfo(np.int64).max
COUNTERS = ("nomad.heartbeat.expired", "nomad.plan.allocs_lost")


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
    two counters the judge holds to the store."""
    from nomad_tpu.utils.metrics import global_metrics

    evals, ev_row = {k: [] for k in ("job", "create", "node", "snap",
                                     "modify", "ok", "blocked", "failed",
                                     "max_plans")}, {}
    failed_seen = []
    for e in store.evals():
        j = job_ids.get(e.job_id)
        if j is None:
            continue
        ev_row[e.id] = len(evals["job"])
        evals["job"].append(j)
        evals["create"].append(e.create_index)
        evals["snap"].append(e.snapshot_index)
        evals["modify"].append(e.modify_index)
        evals["node"].append(
            _node_row(e.node_id or "") if e.triggered_by == _TRIGGER_NODE
            else -1)
        evals["ok"].append(e.status == "complete")
        evals["blocked"].append(e.status == "blocked")
        evals["failed"].append(e.status == "failed")
        evals["max_plans"].append(
            e.status_description == "maximum attempts reached")
        if e.status == "failed" and len(failed_seen) < 5:
            failed_seen.append([e.triggered_by, e.type, e.status_description,
                                e.create_index, e.snapshot_index,
                                e.modify_index, _node_row(e.node_id or "")])
    cols: dict = {k: [] for k in (
        "node", "job", "create", "stop", "name_idx", "eval", "lost",
        "score", "read", *plain.DIMS,
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
        # stopped by a plan: the plan's own index, which the link to a
        # replacement placed later (a retry's) does not move
        cols["stop"].append(
            (a.alloc_modify_index or a.modify_index)
            if a.desired_status == "stop"
            else a.modify_index if a.terminal_status() else 0)
        cols["name_idx"].append(a.index())
        cols["eval"].append(ev_row.get(a.eval_id, -1))
        cols["lost"].append(a.client_status == "lost")
        served = (
            a.metrics.scores.get(f"{a.node_id}.score") if a.metrics else None
        )
        cols["score"].append(np.nan if served is None else served)
        cols["read"].append(a.metrics.usage_read if a.metrics else 0)
        for d in plain.DIMS:
            cols[d].append(getattr(a.resources, d))
    kind = {"score": np.float64, "lost": bool}
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
        k: np.asarray(v, dtype=np.int64 if k in ("job", "create", "node",
                                                 "snap", "modify") else bool)
        for k, v in evals.items()
    }
    out["eval_row"] = ev_row
    out["failed_seen"] = failed_seen
    counters = global_metrics.snapshot()["counters"]
    out["counters"] = {n: int(counters.get(n, 0)) for n in COUNTERS}
    return out


# -- what the failures know ---------------------------------------------------
def losses_of(requests: list) -> list:
    return [r for r in requests if hasattr(r, "failure")]


class Down:
    """The intervals in which a node was down: ``(marked down, marked
    ready again)``, both exclusive, each with the ordinal of its failure."""

    def __init__(self, n: int, failures: list):
        self.n = n
        rows, since, until, of = [], [], [], []
        for k, f in enumerate(failures):
            for row, index in f.down_index.items():
                rows.append(row)
                since.append(index)
                until.append(f.ready_index.get(row) or _FOREVER)
                of.append(k)
        self.rows = np.asarray(rows, dtype=np.int64)
        self.since = np.asarray(since, dtype=np.int64)
        self.until = np.asarray(until, dtype=np.int64)
        self.failure = np.asarray(of, dtype=np.int64)

    def at(self, index: int) -> np.ndarray:
        """Bool per node: down at ``index`` (its down commit included)."""
        hit = (self.since <= index) & (index < self.until)
        out = np.zeros(self.n, dtype=bool)
        out[self.rows[hit]] = True
        return out


def placed_on_down_node(a: dict, down: Down) -> int:
    """Allocations created on a node between its down commit and its
    ready commit."""
    n = 0
    for row, since, until in zip(down.rows, down.since, down.until):
        there = a["create"][a["node"] == row]
        n += int(((since < there) & (there < until)).sum())
    return n


def job_count_off(a: dict, counts: dict, done_at: dict) -> int:
    """Commit indices after a job's first full count at which it held more
    live allocations than its count, plus those at or after the index at
    which its last request was seen done at which it held fewer
    (``counts``: job -> count; ``done_at``: job -> that index)."""
    rows = np.flatnonzero(np.isin(a["job"], list(counts)))
    if not rows.size:
        return 0
    who, idx, sign = _events(a, rows)
    jobs, order, running = _peaks(a["job"][who], idx, sign)
    idx = idx[order]
    last = np.r_[(jobs[1:] != jobs[:-1]) | (idx[1:] != idx[:-1]), True]
    off = 0
    for j in np.unique(jobs):
        at = np.flatnonzero((jobs == j) & last)
        want = counts[int(j)]
        full = np.flatnonzero(running[at] == want)
        if not full.size:
            off += 1
            continue
        tail = at[full[0]:]
        off += int((running[tail] > want).sum())
        settled = idx[tail] >= done_at.get(int(j), _FOREVER)
        off += int((running[tail][settled] < want).sum())
    return off


def _read_view(a: dict, read: int, commit: int) -> tuple:
    """``create`` as a pass that scored on overlay read ``read`` and
    committed at ``commit`` saw the cluster. Reads are taken one at a time:
    the usage of read ``read`` held every placement of an earlier read,
    committed or still in flight (moved before ``commit``), and none of a
    later one, wherever it committed (a retry on the commit thread that
    read after it and committed before it: moved to ``commit``). A
    placement of the same read (another member of the pass) is in it where
    it committed earlier: a deferred member is scored on the usage that
    holds the pass's others. Returns ``(create, in flight, cut)``: the
    view, and how many placements it moved each way. Without stamps (a
    read of 0) the view is the cluster at the commit."""
    create, rd = a["create"], a["read"]
    if not read:
        return create, 0, 0
    flight = (rd > 0) & (rd < read) & (create >= commit)
    cut = (rd > read) & (create < commit)
    view = np.where(flight, commit - 1, create)
    view = np.where(rd > read, np.maximum(create, commit), view)
    return view, int(flight.sum()), int(cut.sum())


def _judge_eval(fleet: dict, a: dict, spec: dict, e: int, down: Down) -> dict:
    """One sampled node eval that placed: the recorded scores and choice of
    nodes of its last commit's placements (a retry's, where the applier
    refused part of its plan) on the cluster its pass read: the nodes open
    at its snapshot, the usage of its overlay read."""
    placed = np.flatnonzero(a["eval"] == e)
    commit = int(a["create"][placed].max())
    placed = placed[a["create"][placed] == commit]
    placed = placed[np.argsort(a["name_idx"][placed], kind="stable")]
    j = int(a["job"][placed[0]])
    mine = np.flatnonzero(a["job"] == j)
    before = mine[(a["create"][mine] < commit) & (
        (a["stop"][mine] == 0) | (a["stop"][mine] >= commit))]
    stopped = before[a["stop"][before] == commit]
    rows, said = a["node"][placed], a["score"][placed]
    snap = int(a["evals"]["snap"][e]) or commit
    create, flight, cut = _read_view(a, int(a["read"][placed[0]]), commit)
    used = plain.usage_before(
        fleet, a["node"], create, a["stop"], a["res"], commit)
    view, on_node, racks, held = ref.freed_view(
        fleet, used, spec, a["node"][before], a["node"][stopped])
    w = ref.walk(fleet, view, spec, rows, on_node, racks, held,
                 ~down.at(snap))
    err = np.abs(w["served"] - said)
    err = np.where(np.isfinite(err), err, 1.0)
    best = np.where(np.isfinite(w["best"]), w["best"], 1.0)
    gap = best - np.where(np.isfinite(w["served"]), w["served"], 0.0)
    mismatch = float((err > SCORE_MATCH).mean())
    gap = float(gap.sum() / np.abs(best).sum())
    return {
        "errors": err, "gap": gap,
        "off": mismatch > JOB_UNEXPLAINED or gap > JOB_OFF_BEST,
        "in_flight": flight > 0, "cut": cut > 0,
        "retried": bool((a["create"][a["eval"] == e] < commit).any()),
    }


def failed_evals(specs_by_job: dict, ev: dict, down: Down) -> tuple:
    """``(on a dying rack, other)`` by a rule of the job's type alone: a
    batch job's eval that ran out of plan attempts while any node went
    down, between its creation and the commit that failed it. The judge
    holds a run to ``failed_on_a_dying_rack``; tests/test_node_loss.py
    still holds this one to its rule."""
    dying = other = 0
    for e in np.flatnonzero(ev["failed"]):
        born, mod = int(ev["create"][e]), int(ev["modify"][e])
        went_down = bool(((down.since > born) & (down.since <= mod)).any())
        if (ev["max_plans"][e] and went_down
                and specs_by_job[int(ev["job"][e])]["type"] == "batch"):
            dying += 1
        else:
            other += 1
    return dying, other


def failed_on_a_dying_rack(specs_by_job: dict, ev: dict, down: Down) -> dict:
    """Evals that ended ``failed``: ``service`` and ``batch`` count the
    service and batch jobs' evals that ran out of plan attempts on a dying
    rack, ``unexplained`` every other. One ran out on a dying rack where it
    is a node-update eval whose own node (``ev["node"]``) went down in a
    failure of which a node went down between the eval's creation and the
    commit that failed it: the rack goes down over the 0.1-0.3 s of its
    node writes, the spread boost keeps sending the job's replacements to
    the rack's nodes still up, the applier refuses them as each goes down
    ("node is not allowed to receive allocations"), and generic_sched.go
    fails the eval after its attempts (a service's 5, a batch's 2) with a
    blocked eval behind it. Each attempt refreshes its snapshot, so the
    last one's is no witness."""
    out = {"service": 0, "batch": 0, "unexplained": 0}
    for e in np.flatnonzero(ev["failed"]):
        born, mod = int(ev["create"][e]), int(ev["modify"][e])
        mine = np.isin(down.failure, down.failure[down.rows == ev["node"][e]])
        went_down = bool(
            (mine & (down.since > born) & (down.since <= mod)).any())
        kind = specs_by_job[int(ev["job"][e])]["type"]
        if ev["max_plans"][e] and went_down and kind in ("service", "batch"):
            out[kind] += 1
        else:
            out["unexplained"] += 1
    return out


def judge(fleet: dict, specs_by_job: dict, requests: list, answers: dict,
          window: tuple, seed: int) -> dict:
    t_open, _t_close = window
    a = answers
    losses = losses_of(requests)
    failures = list({id(r.failure): r.failure for r in losses}.values())
    down = Down(fleet["n"], failures)
    ordinal = {s["id"]: j for j, s in specs_by_job.items()}
    out = {
        "unfinished_requests": sum(1 for r in requests if r.ok is not True),
    }
    replay = plain.capacity_replay(
        fleet, a["node"], a["create"], a["stop"], a["res"])
    out["nodes_over_capacity"] = replay["nodes_over_capacity"]
    out["allocs_off_fleet"] = replay["allocs_off_fleet"]
    out["placed_on_down_node"] = placed_on_down_node(a, down)

    # a held allocation: stopped lost by the index its request was done at
    row_of = a["ids"]
    not_marked = 0
    for r in losses:
        for alloc_id in r.held:
            i = row_of.get(alloc_id)
            not_marked += i is None or not (
                a["lost"][i] and 0 < a["stop"][i] <= (r.done_index or 0))
    out["lost_not_marked"] = not_marked

    gone = {ordinal[r.job_id] for r in requests if r.kind == "deregister"}
    kept = {j: s for j, s in specs_by_job.items()
            if ordinal[s["id"]] == j and j not in gone}
    done_at: dict = {}
    for r in losses:
        j = ordinal[r.job_id]
        done_at[j] = max(done_at.get(j, 0), r.done_index or _FOREVER)
    out["job_count_off"] = job_count_off(
        a, {j: s["count"] for j, s in kept.items()}, done_at)
    # every lost allocation names its replacement, which names it back
    lost = np.flatnonzero(a["lost"])
    nxt = a["next"][lost]
    linked = (nxt >= 0) & (a["prev"][np.maximum(nxt, 0)] == lost) & (
        a["name_idx"][np.maximum(nxt, 0)] == a["name_idx"][lost])
    out["replacement_unlinked"] = int((~linked).sum())
    stayed = np.isin(a["job"], list(kept))
    out["unrelated_allocs_stopped"] = int(
        (stayed & (a["stop"] > 0) & ~a["lost"]).sum())
    out["alloc_names_duplicated"] = names_duplicated(a)
    out["blocked_evals_left"] = int(a["evals"]["blocked"].sum())
    failed = failed_on_a_dying_rack(specs_by_job, a["evals"], down)
    out["failed_evals_unexplained"] = failed.pop("unexplained")
    out["failed_evals_on_a_dying_rack"] = failed
    if a.get("failed_seen"):
        # trigger, type, status description, create, snapshot and modify
        # index and node row of the first failed evals
        out["failed_evals_seen"] = a["failed_seen"]
    counters = a["counters"]
    out["lost_counter_off"] = abs(
        counters["nomad.plan.allocs_lost"] - int(a["lost"].sum()))
    out["expired_counter_off"] = abs(
        counters["nomad.heartbeat.expired"]
        - sum(len(f.down_index) for f in failures))
    window_failures = [f for f in failures if f.due >= t_open]
    held = sorted(r.count for r in losses if r.due >= t_open)
    out["failures_judged"] = len(failures)
    if window_failures:
        out["jobs_hit_per_failure"] = [
            len(f.requests) for f in window_failures]
    if held:
        out["allocs_held_min_median_max"] = [
            held[0], held[len(held) // 2], held[-1]]

    # the sample: node evals of the window's failures that placed
    first = min(
        (min(f.down_index.values()) for f in window_failures
         if f.down_index), default=_FOREVER)
    ev = a["evals"]
    placing = [
        int(e) for e in np.unique(a["eval"][a["eval"] >= 0])
        if ev["node"][e] >= 0 and ev["create"][e] >= first and ev["ok"][e]
    ]
    rng = random.Random(f"{seed}:check")
    sample = rng.sample(placing, min(SAMPLE_EVALS, len(placing)))
    judged = [
        _judge_eval(fleet, a, specs_by_job[int(ev["job"][e])], e, down)
        for e in sample
    ]
    out["node_evals_that_placed"] = len(placing)
    out["node_evals_committed_in_part"] = sum(
        np.unique(a["create"][a["eval"] == e]).size > 1 for e in placing)
    out["evals_judged"] = len(judged)
    if judged:
        errors = np.concatenate([b["errors"] for b in judged])
        out["placements_scored"] = int(errors.size)
        # the view's parts in play: placements of an earlier read still in
        # flight at the commit, of a later read committed before it, and
        # evals judged on a retry
        for part in ("in_flight", "cut", "retried"):
            out[f"evals_judged_{part}"] = sum(b[part] for b in judged)
        out["score_mismatch_share"] = float((errors > SCORE_MATCH).mean())
        out["score_error_median"] = float(np.median(errors))
        out["jobs_off_best_share"] = sum(
            b["off"] for b in judged) / len(judged)
        out["worst_gap_to_best"] = max(b["gap"] for b in judged)
    return out
