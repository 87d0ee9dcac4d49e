"""The control: the reference in the program's place, one guarantee broken.

    python benchmark/tests/control.py --workload <cell> --seeds 1 2 3 [--rehearse]

The system runs no model and states no precision, so the control breaks a
guarantee the configuration states: the plain reference scheduler
(``reference.placement.reference_answers``) serves the cell's own request
stream (pre-fill, then the window's registrations and deregistrations at
the cell's own size) four times: soundly; with the capacity check switched
off (``capacity``); with its scores computed in bfloat16, the precision
below the program's float32 (``precision``); and placing each instance on
the best of a handful of nodes drawn at random, its score honest
(``selection``). The comparison that decides ``correct`` (``check.judge`` +
``check.verdict``) judges all four: the sound run must come out correct,
each control not. No server, no chip work: numpy only, so it runs anywhere;
the benchmark's own runs never run it.
"""

import argparse
import json
import os
import sys
import time

import ml_dtypes
import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def request_stream(config: dict, traffic: dict, seed: int, seconds: float):
    """The cell's requests in commit order, as the driver would send them:
    pre-fill registrations, then per arrival (or per closed-loop turn) one
    registration and one deregistration of the oldest live job."""
    from benchmark.gen.jobs import job_specs

    per_job = int(traffic["job"]["count"])
    steady = int(config["live_allocs"]) // per_job
    specs = job_specs(traffic, seed, "j")
    if traffic["loop"] == "open":
        n_window = int(traffic["arrivals"]["rate_per_s"] * seconds)
    else:
        n_window = int(traffic["in_flight"]) * 4
    specs_by_job, requests, live = {}, [], []
    for _ in range(steady + n_window):
        j = len(specs_by_job)
        specs_by_job[j] = next(specs)
        requests.append(("register", j))
        live.append(j)
        if len(live) > steady:
            requests.append(("deregister", live.pop(0)))
    return specs_by_job, requests


def judge_reference(config, traffic, seed, seconds, respect_capacity,
                    dtype, pick):
    from benchmark import check
    from benchmark.driver import Request
    from benchmark.gen.fleet import fleet_spec
    from benchmark.reference.placement import reference_answers

    fleet = fleet_spec(config["fleet"])
    specs_by_job, stream = request_stream(config, traffic, seed, seconds)
    answers = reference_answers(
        fleet, stream, specs_by_job, respect_capacity, dtype, pick, seed
    )
    requests = []
    for i, (kind, j) in enumerate(stream):
        r = Request(kind, specs_by_job[j]["id"], specs_by_job[j]["count"], 0.0)
        r.ok, r.done = True, float(i)
        requests.append(r)
    numbers = check.judge(
        fleet, specs_by_job, requests, answers, (-1.0, float(len(stream))),
        seed,
    )
    for name in ("breaker_trips", "reference_path_passes", "nacks",
                 "swallowed_errors", "failed_evals",
                 "live_allocs_out_of_band", "window_stalled"):
        numbers[name] = 0  # the program's own counters: no program here
    return check.verdict(numbers, config["limits"]), numbers


def main(argv=None) -> int:
    from benchmark import run
    from benchmark.reference.placement import pick_best, pick_sampled

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    _cell, _bench, config, traffic = run.load_cell(args.workload, args.rehearse)
    ok = True
    for seed in args.seeds:
        row = {"workload": args.workload, "seed": seed}
        for label, respect, dtype, pick in (
            ("sound", True, np.float64, pick_best),
            ("capacity", False, np.float64, pick_best),
            ("precision", True, ml_dtypes.bfloat16, pick_best),
            ("selection", True, np.float64, pick_sampled),
        ):
            t0 = time.perf_counter()
            (correct, compared), numbers = judge_reference(
                config, traffic, seed, args.seconds, respect, dtype, pick
            )
            row[label] = {
                "correct": correct,
                "seconds": round(time.perf_counter() - t0, 1),
                "nodes_over_capacity": numbers["nodes_over_capacity"],
                "worst_overfill_share": numbers["worst_overfill_share"],
                **{k: numbers.get(k) for k in (
                    "score_error_median", "score_mismatch_share",
                    "lone_jobs_off_best_share", "jobs_off_best_share",
                    "score_regression",
                )},
                "failed": sorted(
                    k for k, c in compared.items()
                    if c["value"] is None or c["value"] > c["limit"]
                ),
            }
        ok = ok and row["sound"]["correct"] and not (
            row["capacity"]["correct"] or row["precision"]["correct"]
            or row["selection"]["correct"]
        )
        print(json.dumps(row), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
