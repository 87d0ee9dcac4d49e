"""The controls: the reference in the program's place, one rule broken.

    python benchmark/system/control.py --seeds 1 2 3 [--rehearse]

The plain reference (``reference/system.py`` for the agents,
``reference/placement.py``'s greedy for the services) sets the cell's own
fleet up as set-up does — the agents registered, the first updated, c2m's
services one job at a time, the second agent updated — and then serves
``--updates`` of the window's updates, five times: soundly, and with one of
``reference/system.FAULTS`` each: the old allocation not freed before
scoring, every 97th node skipped, scores in bfloat16 (the precision below
the program's float32), an update that places nothing (the parent's
behaviour). The cell's own comparison (``judge.judge`` + ``check.verdict``)
judges all five: the sound one must come out correct, each control not, by
its own number. No server, no chip: numpy only; the benchmark's own runs
never run it.
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

CELL = "system-10k.arrivals-agent-update"
# the number each fault has to push over its limit
FAILS = {
    "stops_not_freed": "score_mismatch_share",
    "every_97th_skipped": "system_nodes_missing",
    "bfloat16_scores": "score_mismatch_share",
    "places_nothing": "system_nodes_missing",
}


class _State:
    """What the store would hold: allocations as rows, the requests, the
    specs sent, the usage, the commit index."""

    def __init__(self, fleet: dict):
        from benchmark.reference.placement import DIMS

        self.fleet = fleet
        self.used = {d: np.zeros(fleet["n"]) for d in DIMS}
        self.allocs: list = []
        self.requests: list = []
        self.specs: list = []
        self.index = 10
        self.clock = 0.0
        self.fifo: list = []  # the agents' specs, updated longest ago first

    def update(self, spec: dict, fault=None):
        """One agent's registration at ``spec["version"]``, served by the
        reference: its eval, its plan at the next index."""
        from benchmark.reference import system as ref
        from benchmark.system.driver import Update

        self.specs.append(spec)
        mine = [
            i for i, a in enumerate(self.allocs)
            if a["job"] == spec["id"] and a["stop"] == 0
        ]
        ask = {d: float(spec[d]) for d in self.used}
        self.index += 2  # the registration, then its plan
        d = ref.serve_update(
            self.fleet, self.used, ask,
            [self.allocs[i]["node"] for i in mine],
            [self.allocs[i]["version"] for i in mine],
            spec["version"], fault=fault,
        )
        by_node = {self.allocs[i]["node"]: i for i in mine}
        for row in d["stopped"]:
            self.allocs[by_node[int(row)]]["stop"] = self.index
        eval_id = f"e{len(self.requests)}"
        for row, score in zip(d["placed"], d["score"]):
            self.allocs.append({
                "job": spec["id"], "node": int(row), "create": self.index,
                "stop": 0, "version": spec["version"], "system": True,
                "eval": eval_id, "score": float(score), "spec": spec,
            })
        self.clock += 1.0
        r = Update("register", spec["id"], self.fleet["n"], self.clock,
                   spec["version"])
        r.eval_id, r.done, r.done_index = eval_id, self.clock + 0.5, self.index
        r.placed = int(d["placed"].size)
        r.ok = r.placed == self.fleet["n"]
        self.requests.append(r)

    def service(self, spec: dict):
        from benchmark.driver import Request
        from benchmark.reference import placement as plain

        self.specs.append(spec)
        self.index += 2
        w = plain.greedy_walk(self.fleet, self.used, spec, None)
        assert np.isfinite(w["served"]).all(), "the reference found no room"
        for row, score in zip(w["rows"], w["served"]):
            self.allocs.append({
                "job": spec["id"], "node": int(row), "create": self.index,
                "stop": 0, "version": 0, "system": False,
                "eval": f"s{spec['id']}", "score": float(score),
                "spec": spec,
            })
            for d in self.used:
                self.used[d][row] += spec[d]
        r = Request("register", spec["id"], spec["count"], self.clock)
        r.ok, r.done, r.eval_id = True, self.clock, f"s{spec['id']}"
        self.requests.append(r)


def filled(config: dict, traffic: dict, seed: int) -> _State:
    """Set-up as ``system/warm.py`` runs it, served by the reference."""
    from benchmark.gen.fleet import fleet_spec
    from benchmark.system.jobs import agent_specs, job_specs, versioned

    s = _State(fleet_spec(config["fleet"]))
    agents = agent_specs(traffic, seed)
    for spec in agents:
        s.update(spec)
    s.update(versioned(agents[0], 1))
    n_jobs = (
        int(config["live_allocs"]) - len(agents) * s.fleet["n"]
    ) // int(traffic["job"]["count"])
    stream = job_specs(traffic, seed, "c")
    for _ in range(n_jobs):
        s.service(next(stream))
    s.update(versioned(agents[1], 1))
    s.fifo = agents[2:] + [versioned(a, 1) for a in agents[:2]]
    return s


def reference_run(start: _State, n_updates: int, fault=None) -> tuple:
    """``(fleet, specs_by_job, requests, answers, window)`` as ``run.py``
    hands them to the judge, made by the reference alone."""
    from benchmark.reference.placement import DIMS
    from benchmark.system.jobs import versioned

    s = copy.deepcopy(start)
    t_open = s.clock + 0.25
    fifo = list(s.fifo)
    for _ in range(n_updates):
        spec = fifo.pop(0)
        nxt = versioned(spec, spec["version"] + 1)
        s.update(nxt, fault)
        fifo.append(nxt)
    specs_by_job = dict(enumerate(s.specs))
    ordinal = {sp["id"]: j for j, sp in specs_by_job.items()}
    eval_col: dict = {}
    for a in s.allocs:
        eval_col.setdefault(a["eval"], len(eval_col))
    col = lambda key: np.asarray(  # noqa: E731
        [a[key] for a in s.allocs], dtype=np.int64)
    answers = {k: col(k) for k in ("node", "create", "stop", "version")}
    answers["job"] = np.asarray(
        [ordinal[a["job"]] for a in s.allocs], dtype=np.int64)
    answers["system"] = np.asarray([a["system"] for a in s.allocs], bool)
    answers["eval"] = np.asarray(
        [eval_col[a["eval"]] for a in s.allocs], dtype=np.int64)
    answers["score"] = np.asarray([a["score"] for a in s.allocs])
    answers["res"] = {
        d: np.asarray([a["spec"][d] for a in s.allocs], dtype=np.int64)
        for d in DIMS
    }
    answers["eval_col"] = eval_col
    window = (t_open, s.clock + 10.0)
    return s.fleet, specs_by_job, s.requests, answers, window


def judge_reference(config, start, seed, n_updates, fault=None) -> tuple:
    from benchmark import check
    from benchmark.system import judge

    fleet, specs, requests, answers, window = reference_run(
        start, n_updates, fault)
    numbers = judge.judge(fleet, specs, requests, answers, window, seed)
    for name in ("breaker_trips", "reference_path_passes", "nacks",
                 "swallowed_errors", "failed_evals",
                 "live_allocs_out_of_band", "window_stalled"):
        numbers[name] = 0  # the program's own counters: no program here
    return check.verdict(numbers, config["limits"]), numbers


def main(argv=None) -> int:
    from benchmark import run
    from benchmark.reference.system import FAULTS

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--updates", type=int, default=6)
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
                config, start, seed, args.updates, fault
            )
            failed = sorted(
                k for k, c in compared.items()
                if c["value"] is None or c["value"] > c["limit"]
            )
            row[fault or "sound"] = {
                "correct": correct, "failed": failed,
                "seconds": round(time.perf_counter() - t0, 1),
                **{k: numbers.get(k) for k in (
                    "score_mismatch_share", "system_nodes_missing",
                    "old_version_left", "system_allocs_duplicated",
                    "nodes_over_capacity", "unfinished_requests",
                    "score_error_median",
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
