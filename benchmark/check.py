"""The comparison that decides ``correct``.

Runs once the window has closed, the peak memory has been read and the
server is shut down. It reads the *answers* of the timed path back from the
store (every allocation the run's jobs ever held) and hands them, as plain
arrays, to the plain reference (``reference/placement.py``), together with
the fleet table and job specs of the benchmark's own generators. Every
number compared is printed beside its limit; ``correct`` is true when none
exceeds its limit. The limits are the configuration's (``limits`` in its
file): the guarantees are exact (limit 0), the shares that judge the
recorded scores and the choice of node are set from readings (PERF.md
section 2).
"""

from __future__ import annotations

import random

import numpy as np

from benchmark.reference import placement as ref

SCORE_SAMPLE_JOBS = 24
SCORE_MATCH = 1e-4  # a recorded score this close to the reference's matches
OLDER_VIEWS = 16  # stop commits before the newest that a view may lack
JOB_OFF_BEST = 0.05  # a job's placements score this share under the best
JOB_UNEXPLAINED = 0.1  # or this share of its recorded scores match no view


def extract_answers(store, job_ids: dict) -> dict:
    """Arrays over every allocation of the run's jobs. ``job_ids`` maps a
    job id to its ordinal. Node rows are parsed from the fixed node ids of
    ``gen/fleet.py``; an id of another shape reads as row -1."""
    node, job, create, stop, name_idx, score = [], [], [], [], [], []
    res = {d: [] for d in ref.DIMS}
    for a in store.allocs():
        j = job_ids.get(a.job_id)
        if j is None:
            continue
        nid = a.node_id
        try:
            row = int(nid[-12:]) if nid.startswith("00000000-0000-4000") else -1
        except ValueError:
            row = -1
        node.append(row)
        job.append(j)
        create.append(a.create_index)
        stop.append(a.modify_index if a.terminal_status() else 0)
        name_idx.append(a.index())
        served = a.metrics.scores.get(f"{nid}.score") if a.metrics else None
        score.append(np.nan if served is None else served)
        r = a.resources
        res["cpu"].append(r.cpu)
        res["memory_mb"].append(r.memory_mb)
        res["disk_mb"].append(r.disk_mb)
    as_i = lambda x: np.asarray(x, dtype=np.int64)  # noqa: E731
    return {
        "node": as_i(node), "job": as_i(job), "create": as_i(create),
        "stop": as_i(stop), "name_idx": as_i(name_idx),
        "score": np.asarray(score, dtype=np.float64),
        "res": {d: as_i(v) for d, v in res.items()},
    }


def program_failures(server) -> dict:
    """What the program itself reports as having left the device path or
    lost work (copied in substance from ``bench.device_path_failures`` and
    ``chip_smoke.check_device_path``)."""
    from nomad_tpu.resilience.breaker import snapshot_all
    from nomad_tpu.utils.metrics import global_metrics

    c = global_metrics.snapshot()["counters"]
    breakers = snapshot_all()
    trips = sum(
        1 for b in breakers.values() if b["trips"] or b["state"] != "closed"
    )
    reference_path = int(
        c.get("nomad.resilience.fallback_calls", 0)
        + c.get("nomad.resilience.fallback_passes", 0)
    )
    nacks = int(
        sum(w.stats["nacked"] for w in server.workers)
        + server.eval_broker.counters["nacks"]
        + server.eval_broker.counters["unack_timeouts"]
        + c.get("nomad.resilience.eval.deadline_nacks", 0)
    )
    swallowed = int(
        c.get("worker.swallowed_errors", 0)
        + c.get("nomad.worker.batch_kernel_errors", 0)
    )
    failed_evals = sum(1 for e in server.store.evals() if e.status == "failed")
    return {
        "breaker_trips": trips,
        "reference_path_passes": reference_path,
        "nacks": nacks,
        "swallowed_errors": swallowed,
        "failed_evals": failed_evals,
    }


def judge(fleet: dict, specs_by_job: dict, requests: list, answers: dict,
          window: tuple, seed: int) -> dict:
    """All reference-side numbers for one run. ``specs_by_job`` maps the
    job ordinal to the plain spec the generator sent; ``requests`` are the
    driver's records (set-up's too); ``window`` is ``(t_open, t_close)``."""
    t_open, t_close = window
    asked = {j: s["count"] for j, s in specs_by_job.items()}
    ordinal = {s["id"]: j for j, s in specs_by_job.items()}
    expected_live = {j: 0 for j in specs_by_job}
    unfinished = 0
    for r in requests:
        j = ordinal[r.job_id]
        if r.ok is not True:
            unfinished += 1
            continue
        expected_live[j] = asked[j] if r.kind == "register" else 0
    live_mask = answers["stop"] == 0
    live_by_job = dict(zip(*np.unique(
        answers["job"][live_mask], return_counts=True)))
    total_by_job = dict(zip(*np.unique(answers["job"], return_counts=True)))
    out = {"unfinished_requests": unfinished}
    out.update(ref.accounting(expected_live, live_by_job, total_by_job, asked))
    out.update(ref.capacity_replay(
        fleet, answers["node"], answers["create"], answers["stop"],
        answers["res"],
    ))
    # score gap: a seeded sample of the registrations the window
    # finished, the last of them always in it
    in_window = [
        r for r in requests
        if r.kind == "register" and r.ok and t_open < r.done <= t_close
    ]
    rng = random.Random(f"{seed}:check")
    sample = rng.sample(in_window, min(SCORE_SAMPLE_JOBS, len(in_window)))
    if in_window and in_window[-1] not in sample:
        sample[-1] = in_window[-1]
    gaps, bests, errors, off, older_views = [], [], [], 0, 0
    lone = lone_off = 0
    stop_commits = np.unique(answers["stop"][answers["stop"] > 0])
    for r in sample:
        j = ordinal[r.job_id]
        rows_mask = answers["job"] == j
        order = np.argsort(answers["name_idx"][rows_mask], kind="stable")
        rows = answers["node"][rows_mask][order]
        said = answers["score"][rows_mask][order]
        commit = int(answers["create"][rows_mask].min())
        # the view the job was scored on: the newest snapshot (every stop
        # before the commit seen) or one of the few before it. The one that
        # explains most of the scores the program recorded is taken, and
        # of those the one kindest to its choice of nodes; a job that is
        # explained and close to the best needs no older view
        horizons = [commit] + [
            int(s) for s in stop_commits[stop_commits < commit][::-1][:OLDER_VIEWS]
        ]
        seen = None
        for horizon in horizons:
            used = ref.usage_before(
                fleet, answers["node"], answers["create"], answers["stop"],
                answers["res"], commit, horizon,
            )
            walk = ref.greedy_walk(fleet, used, specs_by_job[j], rows)
            err = np.abs(walk["served"] - said)
            # a placement with no recorded score, or on a node the replay
            # finds full, counts as wholly wrong
            err = np.where(np.isfinite(err), err, 1.0)
            best = np.where(np.isfinite(walk["best"]), walk["best"], 1.0)
            gap = best - np.where(
                np.isfinite(walk["served"]), walk["served"], 0.0)
            key = (float((err > SCORE_MATCH).mean()),
                   float(gap.sum() / best.sum()))
            if seen is None or key < seen[0]:
                seen = (key, gap, best, err, horizon)
            if key[0] == 0.0 and key[1] <= JOB_OFF_BEST:
                break
        key, gap, best, err, horizon = seen
        older_views += horizon != commit
        is_off = key[0] > JOB_UNEXPLAINED or key[1] > JOB_OFF_BEST
        off += is_off
        if np.unique(answers["job"][answers["create"] == commit]).size == 1:
            lone += 1
            lone_off += is_off
        gaps.extend(gap.tolist())
        bests.extend(best.tolist())
        errors.extend(err.tolist())
    out["placements_scored"] = len(errors)
    out["jobs_scored"] = len(sample)
    out["jobs_scored_on_an_older_view"] = int(older_views)
    if errors:
        e, g = np.asarray(errors), np.asarray(gaps)
        out["jobs_off_best_share"] = off / len(sample)
        out["lone_jobs"] = lone
        out["lone_jobs_off_best_share"] = lone_off / lone if lone else None
        out["score_mismatch_share"] = float((e > SCORE_MATCH).mean())
        out["score_error_median"] = float(np.median(e))
        out["score_error_p90"] = float(np.quantile(e, 0.9))
        # the source's own measure: how far the mean score of the served
        # placements lies under the mean of the best on offer
        out["score_regression"] = float(g.sum() / np.sum(bests))
        out["score_gap_max"] = float(g.max())
    return out


def verdict(numbers: dict, limits: dict) -> tuple:
    """``(correct, compared)``: every limited number beside its limit. A
    number the limits name and the run could not produce fails."""
    compared = {}
    correct = True
    for name, limit in limits.items():
        value = numbers.get(name)
        if isinstance(value, np.generic):
            value = value.item()
        ok = value is not None and value <= limit
        compared[name] = {"value": value, "limit": limit}
        correct = correct and ok
    return correct, compared
