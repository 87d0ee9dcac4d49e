"""The controls: the reference in the program's place, one rule broken.

    python benchmark/rollout/control.py --seeds 1 2 3 [--rehearse]

The plain reference (``reference/placement.py``'s greedy for the fill,
``reference/rollout.py`` for the rollouts) fills the cell's own fleet with
the cell's own jobs at version 0 and rolls ``--rollouts`` of them to
version 1, health acknowledged after every round, six times: soundly, and
with one of ``reference.FAULTS`` each: placements scored on usage that
still holds the plan's stops, spread counts that forget the old version,
scores in bfloat16 (the precision below the program's float32),
``max_parallel`` ignored, a name handed out twice. The cell's own
comparison (``judge.judge`` + ``check.verdict``) judges all six: the sound
one must come out correct, each control not, by its own number. No server,
no chip: numpy only; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CELL = "rollout-10k.arrivals-update-250"
# the number each fault has to push over its limit
FAILS = {
    "stops_not_freed": "jobs_off_best_share",
    "spread_forgets_old_version": "score_mismatch_share",
    "bfloat16_scores": "score_mismatch_share",
    "max_parallel_ignored": "max_parallel_exceeded",
    "name_twice": "alloc_names_duplicated",
}


def filled(config: dict, traffic: dict, seed: int) -> dict:
    """The fleet with the configuration's jobs at version 0, placed by the
    reference's greedy, one commit a job: what every run of a seed starts
    from."""
    from benchmark.gen.fleet import fleet_spec
    from benchmark.reference import placement as plain
    from benchmark.rollout.jobs import job_specs

    fleet = fleet_spec(config["fleet"])
    n_jobs = int(config["live_allocs"]) // int(traffic["job"]["count"])
    stream = job_specs(traffic, seed, "c")
    specs = [next(stream) for _ in range(n_jobs)]
    used = {d: np.zeros(fleet["n"]) for d in plain.DIMS}
    allocs, evals = [], []
    for j, spec in enumerate(specs):
        index = 10 + 2 * j  # the registration; its plan lands one later
        evals.append({"job": spec["id"], "create": index, "watcher": False})
        w = plain.greedy_walk(fleet, used, spec, None)
        for k, (row, score) in enumerate(zip(w["rows"], w["served"])):
            assert np.isfinite(score), "the reference found no room"
            allocs.append({
                "job": spec["id"], "node": int(row), "create": index + 1,
                "stop": 0, "modify": index + 1, "name_idx": k, "version": 0,
                "eval": len(evals) - 1, "in_deployment": False,
                "healthy": False, "score": float(score), "spec": spec,
            })
            for d in plain.DIMS:
                used[d][row] += spec[d]
    return {"fleet": fleet, "specs": specs, "used": used, "allocs": allocs,
            "evals": evals, "index": 10 + 2 * n_jobs}


def reference_run(start: dict, n_rollouts: int, fault=None) -> tuple:
    """``(fleet, specs_by_job, requests, answers, window)`` as ``run.py``
    hands them to the judge, made by the reference alone."""
    from benchmark.driver import Request
    from benchmark.reference import placement as plain
    from benchmark.reference import rollout as ref
    from benchmark.rollout.jobs import versioned

    fleet = start["fleet"]
    used = {d: v.copy() for d, v in start["used"].items()}
    allocs = copy.deepcopy(start["allocs"])
    evals = copy.deepcopy(start["evals"])
    specs_sent = list(start["specs"])
    index = start["index"]
    requests, deployments = [], []

    def request(spec, eval_row):
        r = Request("register", spec["id"], spec["count"], float(len(requests)))
        r.ok, r.done, r.eval_id = True, r.due + 0.5, f"e{eval_row}"
        requests.append(r)

    for e, spec in enumerate(start["specs"]):
        request(spec, e)
    t_open = float(len(requests)) - 0.25
    live_of = {}  # job id -> {name index: position in ``allocs``}
    for i, a in enumerate(allocs):
        live_of.setdefault(a["job"], {})[a["name_idx"]] = i
    for spec0 in start["specs"][:n_rollouts]:
        spec = versioned(spec0, 1)
        specs_sent.append(spec)
        mine = live_of[spec["id"]]
        index += 1
        evals.append({"job": spec["id"], "create": index, "watcher": False})
        request(spec, len(evals) - 1)
        if spec.get("update"):
            deployments.append([spec["id"], 1, "running"])
        while any(allocs[i]["version"] < 1 for i in mine.values()):
            index += 1  # the round's plan
            moved = ref.serve_round(
                fleet, used, spec,
                {k: allocs[i]["node"] for k, i in mine.items()},
                {k: allocs[i]["version"] for k, i in mine.items()},
                {k: allocs[i]["healthy"] for k, i in mine.items()},
                1, fault,
            )
            assert moved, "the reference made no progress"
            for k, _old, row, score, k_new in moved:
                gone = allocs[mine[k]]
                gone["stop"] = gone["modify"] = index
                mine[k] = len(allocs)
                allocs.append({
                    "job": spec["id"], "node": row, "create": index,
                    "stop": 0, "modify": index, "name_idx": k_new,
                    "version": 1, "eval": len(evals) - 1,
                    "in_deployment": bool(spec.get("update")),
                    "healthy": False, "score": score, "spec": spec,
                })
            index += 1  # the clients' sync: every new allocation healthy
            for i in mine.values():
                a = allocs[i]
                if a["in_deployment"] and not a["healthy"]:
                    a["healthy"], a["modify"] = True, index
            if any(allocs[i]["version"] < 1 for i in mine.values()):
                index += 1  # the watcher's eval for the next round
                evals.append(
                    {"job": spec["id"], "create": index, "watcher": True})
        if spec.get("update"):
            deployments[-1][2] = "successful"
    specs_by_job = dict(enumerate(specs_sent))
    ordinal = {s["id"]: j for j, s in specs_by_job.items()}
    as_i = lambda key: np.asarray(  # noqa: E731
        [a[key] for a in allocs], dtype=np.int64)
    answers = {
        k: as_i(k) for k in ("node", "create", "stop", "modify", "name_idx",
                             "version", "eval")
    }
    answers["job"] = np.asarray(
        [ordinal[a["job"]] for a in allocs], dtype=np.int64)
    answers["in_deployment"] = np.asarray(
        [a["in_deployment"] for a in allocs], dtype=bool)
    answers["healthy"] = np.asarray([a["healthy"] for a in allocs], dtype=bool)
    answers["score"] = np.asarray([a["score"] for a in allocs])
    for d in plain.DIMS:
        answers[d] = np.asarray([a["spec"][d] for a in allocs], dtype=np.int64)
    answers["res"] = {d: answers[d] for d in plain.DIMS}
    answers["evals"] = {
        "job": np.asarray([ordinal[e["job"]] for e in evals], dtype=np.int64),
        "create": np.asarray([e["create"] for e in evals], dtype=np.int64),
        "watcher": np.asarray([e["watcher"] for e in evals], dtype=bool),
        "ok": np.ones(len(evals), dtype=bool),
    }
    answers["eval_row"] = {f"e{i}": i for i in range(len(evals))}
    answers["deployments"] = {
        "job": np.asarray([ordinal[d[0]] for d in deployments], np.int64),
        "version": np.asarray([d[1] for d in deployments], np.int64),
        "status": [d[2] for d in deployments],
    }
    answers["jobs"] = {
        ordinal[s["id"]]: (s["version"], bool(s.get("update")))
        for s in specs_sent
    }
    # every rollout is long due when the window closes
    window = (t_open, float(len(requests)) + 10.0)
    return fleet, specs_by_job, requests, answers, window


def judge_reference(config, start, seed, n_rollouts, fault=None) -> tuple:
    from benchmark import check
    from benchmark.rollout import judge

    fleet, specs, requests, answers, window = reference_run(
        start, n_rollouts, fault)
    numbers = judge.judge(fleet, specs, requests, answers, window, seed)
    for name in ("breaker_trips", "reference_path_passes", "nacks",
                 "swallowed_errors", "failed_evals",
                 "live_allocs_out_of_band", "window_stalled"):
        numbers[name] = 0  # the program's own counters: no program here
    return check.verdict(numbers, config["limits"]), numbers


def main(argv=None) -> int:
    from benchmark import run
    from benchmark.reference.rollout import FAULTS

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rollouts", type=int, default=6)
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
                config, start, seed, args.rollouts, fault
            )
            failed = sorted(
                k for k, c in compared.items()
                if c["value"] is None or c["value"] > c["limit"]
            )
            row[fault or "sound"] = {
                "correct": correct, "failed": failed,
                "seconds": round(time.perf_counter() - t0, 1),
                **{k: numbers.get(k) for k in (
                    "stop_set_mismatch_share", "score_mismatch_share",
                    "jobs_off_best_share", "max_parallel_exceeded",
                    "alloc_names_duplicated", "job_count_off",
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
