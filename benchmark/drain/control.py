"""The controls: the reference in the program's place, one rule broken.

    python benchmark/drain/control.py --seeds 1 2 3 [--rehearse]

The plain reference (``reference/placement.py``'s greedy for the fill,
``reference/drain.py`` for the drains) fills the cell's own fleet with the
cell's own services and drains ``--drains`` nodes one after the other in
the traffic file's order, each wave acknowledged by the clients, each node
set eligible again, seven times: soundly, and with one of
``reference.FAULTS`` each: the draining node left feasible, placements
scored on a view that still holds the plan's stop, spread counts that still
count the stopped allocation, ``max_parallel`` ignored (every allocation of
the node marked at once), scores in bfloat16 (the precision below the
program's float32), a replacement never placed. The cell's own comparison
(``judge.judge`` + ``check.verdict``) judges all seven: the sound one must
come out correct, each control not, by its own number. No server, no chip:
numpy only; the benchmark's own runs never run it.

What the sixth control shows beside its number: a job left one short stands
still. ``may_mark`` keeps ``count - max_parallel`` allocations serving, so
with one gone for good no further allocation of that group is ever marked
and the drain waits for its deadline; the run gives it up, as the driver
does.
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

CELL = "drain-10k.arrivals-drain-node"
# the number each fault has to push over its limit
FAILS = {
    "draining_node_not_masked": "placed_on_ineligible",
    # the two read alike here: what they differ in (usage and the job's
    # own count on the node the stop leaves) lies on a node that is masked
    # out; what shows is the rack's spread count, on every other node.
    # Where a job has one allocation a rack the served node's score is the
    # same either way and only the better offer tells; with ten a rack,
    # the cell's shape, every score differs
    "stops_not_freed": "jobs_off_best_share",
    "spread_counts_the_stop": "jobs_off_best_share",
    "max_parallel_ignored": "migrate_parallel_exceeded",
    "bfloat16_scores": "score_mismatch_share",
    "replacement_never_placed": "job_count_off",
}


def filled(config: dict, traffic: dict, seed: int) -> dict:
    """The fleet with the configuration's services placed by the
    reference's greedy, one commit a job: what every run of a seed starts
    from."""
    from benchmark.drain.jobs import job_specs
    from benchmark.gen.fleet import fleet_spec
    from benchmark.reference import placement as plain

    fleet = fleet_spec(config["fleet"])
    n_jobs = int(config["live_allocs"]) // int(traffic["job"]["count"])
    stream = job_specs(traffic, seed, "c")
    specs = [next(stream) for _ in range(n_jobs)]
    used = {d: np.zeros(fleet["n"]) for d in plain.DIMS}
    allocs, evals = [], []
    for spec in specs:
        index = 10 + 2 * len(evals)  # the registration; its plan lands next
        evals.append({"job": spec["id"], "create": index, "node": -1,
                      "drain": False})
        w = plain.greedy_walk(fleet, used, spec, None)
        for k, (row, score) in enumerate(zip(w["rows"], w["served"])):
            assert np.isfinite(score), "the reference found no room"
            allocs.append({
                "job": spec["id"], "node": int(row), "create": index + 1,
                "stop": 0, "name_idx": k, "eval": len(evals) - 1,
                "marked": False, "prev": -1, "next": -1,
                "score": float(score), "spec": spec,
            })
            for d in plain.DIMS:
                used[d][row] += spec[d]
    return {"fleet": fleet, "specs": specs, "used": used, "allocs": allocs,
            "evals": evals, "index": 10 + 2 * n_jobs}


def reference_run(start: dict, traffic: dict, seed: int, n_drains: int,
                  fault=None) -> tuple:
    """``(fleet, specs_by_job, requests, answers, window)`` as ``run.py``
    hands them to the judge, made by the reference alone."""
    from benchmark.drain.driver import DrainRequest, node_order
    from benchmark.driver import Request
    from benchmark.reference import drain as ref
    from benchmark.reference import placement as plain

    fleet = start["fleet"]
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
    closed = np.zeros(fleet["n"], dtype=bool)
    still_draining = []
    order = node_order(fleet["n"], traffic["drain"], seed)
    for _ in range(n_drains):
        # under this fill, one job at a time over the whole fleet (as the
        # cell's own pre-fill places them: ``prefill_in_flight`` 1), only
        # a big node holds two allocations of one job, which is what
        # ``max_parallel`` ignored needs to show
        row = next(order)
        here = [i for i, a in enumerate(allocs)
                if a["node"] == row and not a["stop"]]
        req = DrainRequest(f"node-{row}", row, [f"a{i}" for i in here],
                           float(len(requests)))
        requests.append(req)
        index += 1
        req.drain_index, closed[row] = index, True
        # a replacement that lands on the draining node itself is marked
        # again, wave after wave: as the driver does, give the drain up
        for _wave in range(2 * len(here) + 4):
            here = [i for i, a in enumerate(allocs)
                    if a["node"] == row and not a["stop"]]
            if not here:
                break
            marked_now: dict = {}  # job id -> positions marked this wave
            for job_id in sorted({allocs[i]["job"] for i in here}):
                spec = specs[job_id]
                live = [i for i, a in enumerate(allocs)
                        if a["job"] == job_id and not a["stop"]]
                drainable = [i for i in live if allocs[i]["node"] == row
                             and not allocs[i]["marked"]]
                budget = len(drainable) if fault == "max_parallel_ignored" \
                    else ref.may_mark(
                        spec["count"], int(spec["migrate"]["max_parallel"]),
                        [allocs[i]["marked"] for i in live],
                        [allocs[i].get("acked", 1) > 0 for i in live],
                        [closed[allocs[i]["node"]] for i in live],
                    )
                if budget:
                    marked_now[job_id] = drainable[:budget]
            if not marked_now:
                break  # the drain stands (see the module's docstring)
            index += 1  # the drainer's transition message, evals with it
            wave = {}
            for job_id, marks in marked_now.items():
                for i in marks:
                    allocs[i]["marked"] = True
                evals.append({"job": job_id, "create": index, "node": row,
                              "drain": True})
                wave[job_id] = len(evals) - 1
            new = []
            for job_id, e in wave.items():
                index += 1  # the eval's plan
                live = [i for i, a in enumerate(allocs)
                        if a["job"] == job_id and not a["stop"]]
                at = {allocs[i]["name_idx"]: i for i in live}
                moved = ref.serve_eval(
                    fleet, used, specs[job_id],
                    {k: allocs[i]["node"] for k, i in at.items()},
                    {k: allocs[i]["marked"] for k, i in at.items()},
                    closed, fault,
                )
                for k, _old_row, new_row, score in moved:
                    gone = allocs[at[k]]
                    gone["stop"] = index
                    req.stops[f"a{at[k]}"] = index
                    if new_row < 0:
                        continue
                    gone["next"] = len(allocs)
                    new.append(len(allocs))
                    allocs.append({
                        "job": job_id, "node": new_row, "create": index,
                        "stop": 0, "name_idx": k, "eval": e,
                        "marked": False, "prev": at[k], "next": -1,
                        "score": score, "spec": specs[job_id], "acked": 0,
                    })
            index += 1  # the clients' sync: replacements running
            for i in new:
                allocs[i]["acked"] = index
                req.acks[f"a{i}"] = index
        req.sent = req.due
        req.done = req.due + 0.5
        if here:
            req.ok, req.note = False, "given up: the drain stands"
            still_draining.append(row)
            continue
        index += 1
        req.clear_index, req.ok = index, True
        index += 1
        req.eligible_index, closed[row] = index, False
    specs_by_job = dict(enumerate(start["specs"]))
    ordinal = {s["id"]: j for j, s in specs_by_job.items()}
    as_i = lambda key: np.asarray(  # noqa: E731
        [a[key] for a in allocs], dtype=np.int64)
    answers = {k: as_i(k) for k in ("node", "create", "name_idx", "eval",
                                    "prev", "next")}
    # as the store leaves it: a stopped allocation's last write is its
    # client's ``complete``, one commit after the plan
    answers["stop"] = np.asarray(
        [a["stop"] + 1 if a["stop"] else 0 for a in allocs], dtype=np.int64)
    answers["job"] = np.asarray(
        [ordinal[a["job"]] for a in allocs], dtype=np.int64)
    answers["marked"] = np.asarray([a["marked"] for a in allocs], dtype=bool)
    answers["score"] = np.asarray([a["score"] for a in allocs])
    for d in plain.DIMS:
        answers[d] = np.asarray([a["spec"][d] for a in allocs], dtype=np.int64)
    answers["res"] = {d: answers[d] for d in plain.DIMS}
    answers["ids"] = {f"a{i}": i for i in range(len(allocs))}
    answers["evals"] = {
        "job": np.asarray([ordinal[e["job"]] for e in evals], dtype=np.int64),
        "create": np.asarray([e["create"] for e in evals], dtype=np.int64),
        "node": np.asarray([e["node"] for e in evals], dtype=np.int64),
        "drain": np.asarray([e["drain"] for e in evals], dtype=bool),
        "ok": np.ones(len(evals), dtype=bool),
        "blocked": np.zeros(len(evals), dtype=bool),
    }
    answers["eval_row"] = {f"e{i}": i for i in range(len(evals))}
    answers["nodes_draining"] = np.asarray(still_draining, dtype=np.int64)
    answers["nodes_ineligible"] = np.flatnonzero(closed)
    answers["drain_force_stops"] = 0
    # every drain is long due when the window closes
    window = (t_open, float(len(requests)) + 10.0)
    return fleet, specs_by_job, requests, answers, window


def judge_reference(config, traffic, start, seed, n_drains,
                    fault=None) -> tuple:
    from benchmark import check
    from benchmark.drain import judge

    fleet, specs, requests, answers, window = reference_run(
        start, traffic, seed, n_drains, fault)
    numbers = judge.judge(fleet, specs, requests, answers, window, seed)
    for name in ("breaker_trips", "reference_path_passes", "nacks",
                 "swallowed_errors", "failed_evals",
                 "live_allocs_out_of_band", "window_stalled"):
        numbers[name] = 0  # the program's own counters: no program here
    return check.verdict(numbers, config["limits"]), numbers


def main(argv=None) -> int:
    from benchmark import run
    from benchmark.reference.drain import FAULTS

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--drains", type=int, default=12)
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
                config, traffic, start, seed, args.drains, fault
            )
            failed = sorted(
                k for k, c in compared.items()
                if c["value"] is None or c["value"] > c["limit"]
            )
            row[fault or "sound"] = {
                "correct": correct, "failed": failed,
                "seconds": round(time.perf_counter() - t0, 1),
                **{k: numbers.get(k) for k in (
                    "evals_judged", "mark_set_mismatch_share",
                    "score_mismatch_share", "jobs_off_best_share",
                    "placed_on_ineligible", "migrate_parallel_exceeded",
                    "job_count_off", "drains_unfinished",
                    "worst_gap_to_best",
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
