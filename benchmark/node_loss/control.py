"""The controls: the reference in the program's place, one rule broken.

    python benchmark/node_loss/control.py --seeds 1 2 3 [--rehearse]

The plain reference (``reference/placement.py``'s greedy for the fill, one
job at a time, ``reference/node_loss.py`` for the failures) fills the
cell's own fleet with the cell's own jobs and takes ``--failures`` racks
down one after the other in the traffic file's order: every node of the
rack marked down, one node eval a job with an allocation on the node, the
rack back after its jobs recovered. The evals run in the order they were
made, pipelined as the program runs them: each pass reads the usage (an
overlay read, stamped on its placements as the program stamps
``usage_read``) while the pass before it is in flight, and that one
commits next; every ``RETRY_EVERY``-th plan is refused in part and placed
again by a retry that reads after the next pass and commits before it. So
every part of the judge's view is in play: placements in flight at a read,
placements of a later read committed first, an eval judged on its retry.

Eight runs: soundly, and with one of ``FAULTS`` each: a node that went down
left open to placement, the racks' counts still holding the lost
allocations, scores in bfloat16 (the precision below the program's
float32), a job's later eval that places once more, a lost allocation left
running, every pass of a failure scored on the usage as the failure began
(a read older than its stamp says) while stamped as the sound run is, and
a service job's node eval failed after its plan attempts while the rack
went down, on a node that stayed up. The cell's own comparison
(``judge.judge`` + ``check.verdict``) judges all eight: the sound one must
come out correct, each control not, by its own number. No server, no
chip: numpy only; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.reference import node_loss as _ref  # noqa: E402

CELL = "rackloss-10k.arrivals-rack-down"
# the reference's faults, a pass that scored on an older read, and a node
# eval failed where no node of its own went down
FAULTS = _ref.FAULTS + ("stale_read", "failed_on_a_live_node")
# the number each fault has to push over its limit
FAILS = {
    "down_nodes_feasible": "placed_on_down_node",
    "lost_counted_in_spread": "score_mismatch_share",
    "bfloat16_scores": "score_mismatch_share",
    "later_eval_replaces_again": "job_count_off",
    "lost_left_running": "lost_not_marked",
    "stale_read": "score_mismatch_share",
    "failed_on_a_live_node": "failed_evals_unexplained",
}
# every this many-th eval's plan is refused in part and retried
RETRY_EVERY = 4


def filled(config: dict, traffic: dict, seed: int) -> dict:
    """The fleet with the configuration's jobs placed by the reference's
    greedy, one commit a job: what every run of a seed starts from."""
    from benchmark.gen.fleet import fleet_spec
    from benchmark.gen.jobs import job_specs
    from benchmark.reference import placement as plain

    fleet = fleet_spec(config["fleet"])
    n_jobs = int(config["live_allocs"]) // int(traffic["job"]["count"])
    stream = job_specs(traffic, seed, "c")
    specs = [next(stream) for _ in range(n_jobs)]
    used = {d: np.zeros(fleet["n"]) for d in plain.DIMS}
    allocs, evals = [], []
    for spec in specs:
        index = 10 + 2 * len(evals)  # the registration; its plan lands next
        evals.append({"job": spec["id"], "create": index, "node": -1})
        w = plain.greedy_walk(fleet, used, spec, None)
        for k, (row, score) in enumerate(zip(w["rows"], w["served"])):
            assert np.isfinite(score), "the reference found no room"
            allocs.append({
                "job": spec["id"], "node": int(row), "create": index + 1,
                "stop": 0, "name_idx": k, "eval": len(evals) - 1,
                "lost": False, "prev": -1, "next": -1,
                "score": float(score), "spec": spec,
            })
            for d in plain.DIMS:
                used[d][row] += spec[d]
    return {"fleet": fleet, "specs": specs, "used": used, "allocs": allocs,
            "evals": evals, "index": 10 + 2 * n_jobs}


def reference_run(start: dict, config: dict, traffic: dict, seed: int,
                  n_failures: int, fault=None) -> tuple:
    """``(fleet, specs_by_job, requests, answers, window)`` as ``run.py``
    hands them to the judge, made by the reference alone."""
    from benchmark.driver import Request
    from benchmark.node_loss.driver import Failure, LossRequest, rack_order
    from benchmark.reference import node_loss as ref
    from benchmark.reference import placement as plain

    DIMS = plain.DIMS
    fleet = start["fleet"]
    n, racks = fleet["n"], int(config["fleet"]["racks"])
    used = {d: v.copy() for d, v in start["used"].items()}
    allocs = [dict(a) for a in start["allocs"]]
    evals = [dict(e) for e in start["evals"]]
    specs = {s["id"]: s for s in start["specs"]}
    index = start["index"]
    requests = []
    for e, spec in enumerate(start["specs"]):
        r = Request("register", spec["id"], spec["count"], float(e))
        r.ok, r.done, r.eval_id = True, r.due + 0.5, f"e{e}"
        requests.append(r)
    t_open = float(len(requests)) - 0.25
    by_job: dict = {}  # job id -> its allocations
    by_node: dict = {}  # node row -> the allocations placed there

    def note(i: int) -> None:
        by_job.setdefault(allocs[i]["job"], []).append(i)
        by_node.setdefault(allocs[i]["node"], []).append(i)

    for i in range(len(allocs)):
        note(i)
    # the fill's one overlay read an eval, in order
    reads = len(evals)
    for a in allocs:
        a["read"] = a["eval"] + 1
    for e in evals:
        e["snap"] = e["create"]
    down = np.zeros(n, dtype=bool)
    stale = None  # the stale read's usage: the cluster as the failure began

    def live_rows(job_id) -> dict:
        return {allocs[i]["name_idx"]: allocs[i]["node"]
                for i in by_job[job_id] if not allocs[i]["stop"]}

    def taken(e: int, moved) -> list:
        """The placements the applier takes of a plan: all of them, or, on
        every ``RETRY_EVERY``-th eval that places two or more, the first
        half by name; a retry places the rest again."""
        placing = [m for m in moved if m[2] >= 0]
        if e % RETRY_EVERY == 0 and len(placing) >= 2:
            return placing[:(len(placing) + 1) // 2]
        return placing

    def read(job_id, in_flight) -> list:
        """One pass of ``job_id``'s eval on an overlay read: the usage
        committed, with the placements of ``in_flight`` (a pass that read
        before and has not committed) on it."""
        nonlocal reads
        reads += 1
        if stale is not None:
            seen = {d: v.copy() for d, v in stale.items()}
        else:
            seen = {d: v.copy() for d, v in used.items()}
            if in_flight is not None:
                spec = specs[in_flight[0]]
                for _name, _old, row, _score in taken(*in_flight[1:3]):
                    for d in DIMS:
                        seen[d][row] += float(spec[d])
        return ref.serve_eval(fleet, seen, specs[job_id], live_rows(job_id),
                              down, fault if fault in _ref.FAULTS else None)

    def apply(job_id, e, moved, placing, index) -> int:
        """One commit at ``index + 1``: ``moved``'s stops and the
        placements in ``placing``, stamped with the eval's last read. A
        placement under a name whose lost allocation a refused plan
        stopped replaces that allocation."""
        spec = specs[job_id]
        index += 1
        at = {allocs[i]["name_idx"]: i for i in by_job[job_id]
              if not allocs[i]["stop"]}
        orphans = {allocs[i]["name_idx"]: i for i in by_job[job_id]
                   if allocs[i]["lost"] and allocs[i]["next"] < 0}
        names = {m[0] for m in placing}
        for name, old_row, new_row, score in moved:
            old = at.get(name) if old_row not in (-1, -2) else None
            if old is not None:
                allocs[old]["stop"], allocs[old]["lost"] = index, True
                for d in DIMS:
                    used[d][old_row] -= float(spec[d])
            if name not in names or new_row < 0:
                continue
            if old is None and old_row != -2:
                old = orphans.get(name)
            if old is not None:
                allocs[old]["next"] = len(allocs)
            allocs.append({
                "job": job_id, "node": new_row, "create": index,
                "stop": 0, "name_idx": name, "eval": e, "lost": False,
                "prev": -1 if old is None else old, "next": -1,
                "score": score, "spec": spec, "read": reads_of[e],
            })
            note(len(allocs) - 1)
            for d in DIMS:
                used[d][new_row] += float(spec[d])
        return index

    reads_of: dict = {}  # eval -> the read its placements were scored on

    def commit(pending, behind, index: int, done_at: dict) -> int:
        """Commit the pass in flight, ``(job id, eval, moved)``. A plan
        refused in part is placed again by a retry on the commit thread:
        it reads after the pass ``behind`` it read (those placements are
        in flight on its read) and commits before that pass does."""
        job_id, e, moved = pending
        placing = taken(e, moved)
        index = apply(job_id, e, moved, placing, index)
        if len(placing) < sum(m[2] >= 0 for m in moved):
            evals[e]["snap"] = index
            retry = read(job_id, behind)
            reads_of[e] = reads
            index = apply(job_id, e, retry, [m for m in retry if m[2] >= 0],
                          index)
        done_at[job_id] = index
        return index

    order = rack_order(racks, traffic["failure"], seed)
    taken_racks = []
    for k in range(n_failures):
        rack = next(order)
        taken_racks.append(rack)
        rows = list(range(rack, n, racks))
        failure = Failure(k, rack, rows, [f"n{r}" for r in rows],
                          float(len(requests)))
        held: dict = {}
        for row in rows:
            for i in by_node.get(row, ()):
                if not allocs[i]["stop"]:
                    held.setdefault(allocs[i]["job"], []).append(i)
        for job_id, ids in held.items():
            r = LossRequest(job_id, failure, [f"a{i}" for i in ids],
                            failure.due)
            r.sent = r.due
            failure.requests.append(r)
            requests.append(r)
        # every node of the rack down, its node evals made with it
        queue = []
        for row in rows:
            index += 1
            failure.down_index[row] = index
            down[row] = True
            jobs = ref.node_evals(
                allocs[i]["job"] for i in by_node.get(row, ())
                if not allocs[i]["stop"])
            index += 1
            for job_id in jobs:
                evals.append({"job": job_id, "create": index, "node": row})
                queue.append((job_id, len(evals) - 1))
        # the evals in the order they were made, pipelined as the
        # program's pass and its commit thread are: each pass reads while
        # the one before it is in flight, which commits next
        done_at: dict = {}
        if fault == "stale_read":
            stale = {d: v.copy() for d, v in used.items()}
        pending = None
        for job_id, e in queue:
            if pending is not None and pending[0] == job_id:
                # the broker holds a job's next eval until its last is done
                index = commit(pending, None, index, done_at)
                pending = None
            evals[e]["snap"] = index
            this = (job_id, e, read(job_id, pending))
            reads_of[e] = reads
            if pending is not None:
                index = commit(pending, this, index, done_at)
            pending = this
        if pending is not None:
            index = commit(pending, None, index, done_at)
        for r in failure.requests:
            r.done, r.ok = r.due + 0.5, True
            r.done_index = done_at.get(r.job_id, index)
        for row in rows:
            index += 1
            failure.ready_index[row] = index
            down[row] = False
    if fault == "failed_on_a_live_node":
        # a service job's node eval made as the last rack began to go down
        # and failed after its attempts once the rack was down, on a node
        # of a rack that stayed up
        job_id = next(r.job_id for r in failure.requests
                      if specs[r.job_id]["type"] == "service")
        born = min(failure.down_index.values())
        evals.append({
            "job": job_id, "create": born, "snap": born,
            "node": next(r for r in range(n) if r % racks not in taken_racks),
            "modify": max(failure.down_index.values()) + 1, "failed": True,
        })
    specs_by_job = dict(enumerate(start["specs"]))
    ordinal = {s["id"]: j for j, s in specs_by_job.items()}
    as_i = lambda key: np.asarray(  # noqa: E731
        [a[key] for a in allocs], dtype=np.int64)
    answers = {k: as_i(k) for k in ("node", "create", "stop", "name_idx",
                                    "eval", "prev", "next")}
    answers["job"] = np.asarray(
        [ordinal[a["job"]] for a in allocs], dtype=np.int64)
    answers["lost"] = np.asarray([a["lost"] for a in allocs], dtype=bool)
    answers["score"] = np.asarray([a["score"] for a in allocs])
    answers["read"] = np.asarray([a["read"] for a in allocs], dtype=np.int64)
    for d in plain.DIMS:
        answers[d] = np.asarray([a["spec"][d] for a in allocs], dtype=np.int64)
    answers["res"] = {d: answers[d] for d in plain.DIMS}
    answers["ids"] = {f"a{i}": i for i in range(len(allocs))}
    failed = np.asarray([e.get("failed", False) for e in evals], dtype=bool)
    answers["evals"] = {
        "job": np.asarray([ordinal[e["job"]] for e in evals], dtype=np.int64),
        "create": np.asarray([e["create"] for e in evals], dtype=np.int64),
        "node": np.asarray([e["node"] for e in evals], dtype=np.int64),
        "snap": np.asarray([e["snap"] for e in evals], dtype=np.int64),
        "modify": np.asarray([e.get("modify", index) for e in evals],
                             dtype=np.int64),
        "ok": ~failed,
        "blocked": np.zeros(len(evals), dtype=bool),
        "failed": failed,
        "max_plans": failed,
    }
    answers["eval_row"] = {f"e{i}": i for i in range(len(evals))}
    answers["counters"] = {
        "nomad.heartbeat.expired": n_failures * len(rows),
        "nomad.plan.allocs_lost": int(answers["lost"].sum()),
    }
    # every failure is long due when the window closes
    window = (t_open, float(len(requests)) + 10.0)
    return fleet, specs_by_job, requests, answers, window


def judge_reference(config, traffic, start, seed, n_failures,
                    fault=None) -> tuple:
    from benchmark import check
    from benchmark.node_loss import judge

    fleet, specs, requests, answers, window = reference_run(
        start, config, traffic, seed, n_failures, fault)
    numbers = judge.judge(fleet, specs, requests, answers, window, seed)
    for name in ("breaker_trips", "reference_path_passes", "nacks",
                 "swallowed_errors", "live_allocs_out_of_band",
                 "window_stalled"):
        numbers[name] = 0  # the program's own counters: no program here
    return check.verdict(numbers, config["limits"]), numbers


def main(argv=None) -> int:
    from benchmark import run

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--failures", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    _cell, _bench, config, traffic = run.load_cell(CELL, args.rehearse)
    ok = True
    for seed in args.seeds:
        t0 = time.perf_counter()
        start = filled(config, traffic, seed)
        row = {"workload": CELL, "seed": seed,
               "fill_seconds": round(time.perf_counter() - t0, 1)}
        for fault in (None,) + FAULTS:
            t0 = time.perf_counter()
            (correct, compared), numbers = judge_reference(
                config, traffic, start, seed, args.failures, fault
            )
            failed = sorted(
                k for k, c in compared.items()
                if c["value"] is None or c["value"] > c["limit"]
            )
            row[fault or "sound"] = {
                "correct": correct, "failed": failed,
                "seconds": round(time.perf_counter() - t0, 1),
                **{k: numbers.get(k) for k in (
                    "evals_judged", "score_mismatch_share",
                    "jobs_off_best_share", "placed_on_down_node",
                    "lost_not_marked", "job_count_off",
                    "worst_gap_to_best", "evals_judged_in_flight",
                    "evals_judged_cut", "evals_judged_retried",
                    "failed_evals_unexplained",
                )},
            }
            ok = ok and (
                correct if fault is None
                else not correct and FAILS[fault] in failed
            )
        print(json.dumps(row), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
