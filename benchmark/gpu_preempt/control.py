"""The controls: the reference in the program's place, one rule broken.

    python benchmark/gpu_preempt/control.py --seeds 1 2 3 [--rehearse]

The plain reference (``reference/preemption.py``) fills the cell's own
fleet and serves the cell's own services (``--services`` registrations,
the oldest deregistered once the configuration's ``steady_jobs`` are
live), six times: soundly, and with one of ``reference.FAULTS`` each: victims taken highest
priority first and, inside a priority, farthest first (the fill has one
priority level, so the order by priority alone decides nothing there), a
victim kept that the superset filter drops, both recorded scores in
bfloat16 (the precision below the program's float32), a GPU instance
handed out twice, the nodes ranked upside down. The cell's own comparison
(``judge.judge`` + ``check.verdict``) judges all six: the sound one must
come out correct, each control not, by its own number. No server, no chip:
numpy only, so it runs anywhere; the benchmark's own runs never run it.
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

CELL = "gpu-preempt-10k.arrivals-evict-32"
# the number each fault has to push over its limit
FAILS = {
    "highest_priority_first": "victim_set_mismatch_share",
    "redundant_victim": "redundant_victims_share",
    "bfloat16_scores": "rank_score_mismatch_share",
    "instance_twice": "gpu_instances_double_held",
    "worst_nodes_first": "jobs_off_best_share",
}


def reference_run(config: dict, traffic: dict, seed: int, n_services: int,
                  fault=None) -> tuple:
    """``(fleet, specs_by_job, requests, answers)`` as ``run.py`` hands
    them to the judge, made by the reference alone: one commit a job."""
    from benchmark.driver import Request
    from benchmark.gpu_preempt.fleet import fleet_spec
    from benchmark.gpu_preempt.jobs import fill_specs, job_specs
    from benchmark.reference import preemption as ref

    fleet = fleet_spec(config["fleet"])
    fill = fill_specs(config["fill"], fleet, seed)
    specs_by_job = dict(enumerate(fill))
    cluster, records = ref.filled_cluster(fleet, list(specs_by_job.items()))
    # [job, node, create, stop, spec, gpu slot, by, score, rank score]
    rows: list = []
    slots_taken = {}  # node -> next slot the fill hands out
    for j, node, spec in records:
        slot = -1
        if spec["gpus"]:
            slot = slots_taken.get(node, 0)
            slots_taken[node] = slot + 1
        rows.append([j, node, 10 + j, 0, spec, slot, -1, np.nan, np.nan])
    requests, evals, live = [], [], []
    index = 10 + len(fill)
    stream = job_specs(traffic, seed, "c")

    def request(kind, spec):
        r = Request(kind, spec["id"], spec["count"], 0.0)
        r.ok, r.done = True, float(len(requests))
        requests.append(r)

    for spec in fill:
        request("register", spec)
    t_open = float(len(requests)) - 0.5
    for _ in range(n_services):
        j, spec = len(specs_by_job), next(stream)
        specs_by_job[j] = spec
        index += 1
        placed = ref.place_by_evicting(cluster, j, spec, fault)
        assert len(placed) == spec["count"], "the reference found no room"
        for node, victim_keys, score in placed:
            me = len(rows)
            freed = []
            for _vj, v in victim_keys:
                rows[v][3], rows[v][6] = index, me
                evals.append((rows[v][0], index))
                if rows[v][5] >= 0:
                    freed.append(rows[v][5])
            taken = {r[5] for r in rows if r[1] == node and r[3] == 0}
            slot = freed[0] if freed else next(
                (k for k in range(int(fleet["gpus"][node])) if k not in taken),
                -1,
            )
            if fault == "instance_twice":
                held = [r[5] for r in rows
                        if r[1] == node and r[3] == 0 and r[5] >= 0]
                slot = held[0] if held else slot
            # the reference stands in for both of the program's scores
            rows.append([j, node, index, 0, spec, slot, -1, score, score])
        request("register", spec)
        live.append(j)
        if len(live) > int(config["steady_jobs"]):
            old = live.pop(0)
            index += 1
            left = []
            for r in rows:
                if r[0] == old and r[3] == 0:
                    r[3] = index
                    left.append(len(rows) - 1 - rows[::-1].index(r))
                    cluster.remove(r[1], next(
                        c[7] for c in cluster.live[r[1]] if c[7][0] == old))
            request("deregister", specs_by_job[old])
            # the victims' blocked evals take the room back: each on the
            # node it was evicted from, where it fits again (unless a
            # later service has taken the room meanwhile)
            index += 1
            for me in left:
                for v in [v for v, r in enumerate(rows) if r[6] == me]:
                    vj, node, _c, _s, spec, slot = rows[v][:6]
                    _cap, _used, free, free_gpus, _cands = cluster.state(node)
                    ask = (spec["cpu"], spec["memory_mb"], spec["disk_mb"])
                    if not ref.covers(free, ask) or free_gpus < spec["gpus"]:
                        continue
                    if spec["gpus"]:
                        taken = {r[5] for r in rows
                                 if r[1] == node and r[3] == 0}
                        slot = next(k for k in range(int(fleet["gpus"][node]))
                                    if k not in taken)
                    cluster.add(node, (
                        spec["priority"], spec["cpu"], spec["memory_mb"],
                        spec["disk_mb"], spec["gpus"], 0, 0, (vj, len(rows)),
                    ))
                    rows.append(
                        [vj, node, index, 0, spec, slot, -1, np.nan, np.nan])
    col = lambda f: np.asarray([f(r) for r in rows], dtype=np.int64)  # noqa: E731
    answers = {
        "node": col(lambda r: r[1]), "job": col(lambda r: r[0]),
        "create": col(lambda r: r[2]), "stop": col(lambda r: r[3]),
        "name_idx": col(lambda r: 0),
        "priority": col(lambda r: r[4]["priority"]),
        "cpu": col(lambda r: r[4]["cpu"]),
        "memory_mb": col(lambda r: r[4]["memory_mb"]),
        "disk_mb": col(lambda r: r[4]["disk_mb"]),
        "gpu_mask": col(lambda r: 1 << r[5] if r[5] >= 0 else 0),
        "gpu_count": col(lambda r: int(r[5] >= 0)),
        "preempted_by": col(lambda r: r[6]),
        "score": np.asarray([r[7] for r in rows], dtype=np.float64),
        "rank_score": np.asarray([r[8] for r in rows], dtype=np.float64),
        "evals": {
            "job": np.asarray([e[0] for e in evals], dtype=np.int64),
            "create": np.asarray([e[1] for e in evals], dtype=np.int64),
            "preemption": np.ones(len(evals), dtype=bool),
        },
        "preempt_unplaced": 0,
    }
    answers["res"] = {d: answers[d] for d in ref.DIMS}
    window = (t_open, float(len(requests)))
    return fleet, specs_by_job, requests, answers, window


def judge_reference(config, traffic, seed, n_services, fault=None) -> tuple:
    from benchmark import check
    from benchmark.gpu_preempt import judge

    fleet, specs, requests, answers, window = reference_run(
        config, traffic, seed, n_services, fault
    )
    numbers = judge.judge(fleet, specs, requests, answers, window, seed)
    for name in ("breaker_trips", "reference_path_passes", "nacks",
                 "swallowed_errors", "failed_evals",
                 "live_allocs_out_of_band", "window_stalled"):
        numbers[name] = 0  # the program's own counters: no program here
    return check.verdict(numbers, config["limits"]), numbers


def main(argv=None) -> int:
    from benchmark import run
    from benchmark.reference.preemption import FAULTS

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--services", type=int, default=12)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    _cell, _bench, config, traffic = run.load_cell(CELL, args.rehearse)
    ok = True
    for seed in args.seeds:
        row = {"workload": CELL, "seed": seed}
        for fault in (None,) + FAULTS:
            t0 = time.perf_counter()
            (correct, compared), numbers = judge_reference(
                config, traffic, seed, args.services, fault
            )
            failed = sorted(
                k for k, c in compared.items()
                if c["value"] is None or c["value"] > c["limit"]
            )
            row[fault or "sound"] = {
                "correct": correct, "failed": failed,
                "seconds": round(time.perf_counter() - t0, 1),
                **{k: numbers.get(k) for k in (
                    "victim_set_mismatch_share", "score_mismatch_share",
                    "rank_score_mismatch_share",
                    "redundant_victims_share", "jobs_off_best_share",
                    "gpu_instances_double_held", "worst_gap_to_best",
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
