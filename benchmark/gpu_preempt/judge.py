"""The comparison that decides ``correct`` for ``gpu-preempt-10k``.

Read from what the timed path left in the store (``extract_answers``):
every allocation the run's jobs ever held, with its node, resources,
priority, device instances, create and stop index, who evicted it
(``preempted_by_allocation``) and the two scores recorded at its placement
(the ranking kernel's for the node, and the host's for the victims chosen);
every eval of those jobs; the program's count of instances that found no
victim set. ``judge`` then holds the run to the configuration's guarantees,
exactly, and a seeded sample of the window's services to the plain
reference (``reference/preemption.py``), as shares.

A service's plan was made on a snapshot the store does not record, and
under load plans commit between a snapshot and the plan made on it. So the
sample is judged on *views*: the cluster as it stood at the job's own
commit and at the few commits before it; the view that explains most of
what the program did is taken (as ``check.py`` does for its scores).
"""

from __future__ import annotations

import random

import numpy as np

from benchmark.gpu_preempt.fleet import instance_slot
from benchmark.reference import placement as plain
from benchmark.reference import preemption as ref

SAMPLE_JOBS = 12
VIEWS = 6  # the job's own commit and the commits before it
SCORE_MATCH = 1e-4
JOB_OFF_BEST = 0.01  # a job whose nodes score this share under the best
_TRIGGER_PREEMPTION = "preemption"


def extract_answers(store, job_ids: dict) -> dict:
    """Arrays over every allocation of the run's jobs (``job_ids``: job id
    -> ordinal) and over their evals."""
    from nomad_tpu.utils.metrics import global_metrics

    cols: dict = {k: [] for k in (
        "node", "job", "create", "stop", "name_idx", "priority", "cpu",
        "memory_mb", "disk_mb", "gpu_mask", "gpu_count", "score",
        "rank_score",
    )}
    ids, by, row_of = [], [], {}
    for a in store.allocs():
        j = job_ids.get(a.job_id)
        if j is None:
            continue
        nid = a.node_id
        try:
            node = int(nid[-12:]) if nid.startswith("00000000-0000-4000") else -1
        except ValueError:
            node = -1
        mask = count = 0
        for dev in a.allocated_devices or ():
            for inst in dev.device_ids:
                mask |= 1 << instance_slot(inst)
                count += 1
        row_of[a.id] = len(ids)
        ids.append(a.id)
        by.append(a.preempted_by_allocation or "")
        cols["node"].append(node)
        cols["job"].append(j)
        cols["create"].append(a.create_index)
        cols["stop"].append(a.modify_index if a.terminal_status() else 0)
        cols["name_idx"].append(a.index())
        cols["priority"].append(a.job.priority if a.job is not None else -1)
        cols["cpu"].append(a.resources.cpu)
        cols["memory_mb"].append(a.resources.memory_mb)
        cols["disk_mb"].append(a.resources.disk_mb)
        cols["gpu_mask"].append(mask)
        cols["gpu_count"].append(count)
        scores = a.metrics.scores if a.metrics else {}
        cols["score"].append(scores.get(f"{nid}.score", np.nan))
        # what the chip's ranking computed for the node
        cols["rank_score"].append(scores.get(f"{nid}.preemption-rank", np.nan))
    out = {
        k: np.asarray(
            v, dtype=np.float64 if k.endswith("score") else np.int64
        )
        for k, v in cols.items()
    }
    # -1: not evicted; -2: evicted by an allocation the store does not hold
    out["preempted_by"] = np.asarray(
        [row_of.get(b, -2) if b else -1 for b in by], dtype=np.int64
    )
    out["res"] = {d: out[d] for d in ref.DIMS}
    ev_job, ev_create, ev_preempt = [], [], []
    for e in store.evals():
        j = job_ids.get(e.job_id)
        if j is not None:
            ev_job.append(j)
            ev_create.append(e.create_index)
            ev_preempt.append(e.triggered_by == _TRIGGER_PREEMPTION)
    out["evals"] = {
        "job": np.asarray(ev_job, dtype=np.int64),
        "create": np.asarray(ev_create, dtype=np.int64),
        "preemption": np.asarray(ev_preempt, dtype=bool),
    }
    counters = global_metrics.snapshot()["counters"]
    out["preempt_unplaced"] = int(counters.get("nomad.preempt.unplaced", 0))
    return out


def _double_held(answers: dict, fleet: dict) -> int:
    """(node, instance) pairs that two live allocations held at once, at
    any commit index (a plan's stops and evictions before its placements),
    plus instances named on a node that has no such slot."""
    mask = answers["gpu_mask"]
    rows = np.flatnonzero(mask > 0)
    keys, idx, sign = [], [], []
    off_node = 0
    for r in rows:
        node, m = int(answers["node"][r]), int(mask[r])
        for slot in range(m.bit_length()):
            if not m >> slot & 1:
                continue
            if node < 0 or slot >= int(fleet["gpus"][node]):
                off_node += 1
                continue
            keys.append(node * 64 + slot)
            idx.append(answers["create"][r])
            sign.append(1)
            if answers["stop"][r] > 0:
                keys.append(node * 64 + slot)
                idx.append(answers["stop"][r])
                sign.append(-1)
    if not keys:
        return off_node
    keys, idx, sign = (np.asarray(x, dtype=np.int64) for x in (keys, idx, sign))
    order = np.lexsort((sign, idx, keys))
    keys, sign = keys[order], sign[order]
    running = np.cumsum(sign)
    starts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    base = np.repeat(running[starts] - sign[starts],
                     np.diff(np.r_[starts, keys.size]))
    peak = np.maximum.reduceat(running - base, starts)
    return off_node + int((peak > 1).sum())


def _sharing_a_host(answers: dict, service_jobs: list) -> int:
    """Allocations of a ``distinct_hosts`` job that lived on a node while
    another of the same job did."""
    n = 0
    for j in service_jobs:
        rows = np.flatnonzero(answers["job"] == j)
        rows = rows[np.argsort(answers["node"][rows], kind="stable")]
        node, create = answers["node"][rows], answers["create"][rows]
        stop = np.where(answers["stop"][rows] > 0, answers["stop"][rows],
                        np.iinfo(np.int64).max)
        for a in range(len(rows)):
            b = a + 1
            while b < len(rows) and node[b] == node[a]:
                if create[a] < stop[b] and create[b] < stop[a]:
                    n += 1
                b += 1
    return n


def _eviction_guarantees(answers: dict) -> dict:
    victim = np.flatnonzero(answers["preempted_by"] != -1)
    by = answers["preempted_by"][victim]
    known = by >= 0
    above = placeless = 0
    if victim.size:
        p = np.where(known, by, 0)
        above = int((
            ~known
            | (answers["priority"][victim]
               > answers["priority"][p] - ref.PRIORITY_DELTA)
        ).sum())
        placeless = int((
            ~known
            | (answers["node"][p] != answers["node"][victim])
            | (answers["create"][p] != answers["stop"][victim])
        ).sum())
    ev = answers["evals"]
    followed = set(zip(ev["job"][ev["preemption"]].tolist(),
                       ev["create"][ev["preemption"]].tolist()))
    evictions = set(zip(answers["job"][victim].tolist(),
                        answers["stop"][victim].tolist()))
    return {
        "victims": int(victim.size),
        "victims_above_priority_delta": above,
        "evictions_without_placement": placeless,
        "victims_without_followup_eval": len(evictions - followed),
        "preemption_followup_evals": len(followed),
    }


class _Views:
    """The cluster at a commit index, GPU nodes only: per node the
    allocations alive there, as the reference's candidates."""

    def __init__(self, fleet: dict, answers: dict):
        self.fleet, self.a = fleet, answers
        on_gpu = np.flatnonzero(
            (answers["node"] >= 0) & (fleet["gpus"][answers["node"]] > 0)
        )
        order = on_gpu[np.argsort(answers["node"][on_gpu], kind="stable")]
        nodes = answers["node"][order]
        starts = np.flatnonzero(np.r_[True, nodes[1:] != nodes[:-1]])
        self.rows_of = {
            int(nodes[s]): order[s:e]
            for s, e in zip(starts, np.r_[starts[1:], order.size])
        }

    def cluster(self, at: int) -> ref.Cluster:
        """Alive at ``at``: committed at or before it and not stopped by
        then."""
        a = self.a
        c = ref.Cluster(self.fleet)
        for node, rows in self.rows_of.items():
            alive = rows[(a["create"][rows] <= at) & (
                (a["stop"][rows] == 0) | (a["stop"][rows] > at))]
            c.live[node] = [
                (int(a["priority"][r]), int(a["cpu"][r]),
                 int(a["memory_mb"][r]), int(a["disk_mb"][r]),
                 int(a["gpu_count"][r]), 0, 0, (int(a["job"][r]), int(r)))
                for r in alive
            ]
        return c


def _signature(cands: list, idxs) -> list:
    return sorted(cands[i][:5] for i in idxs)


def _judge_job(views: _Views, answers: dict, spec: dict, j: int) -> dict:
    """One sampled service on its best view: per placement whether the
    victims and the two recorded scores are the reference's for that node,
    how many victims could have stayed, and how far the nodes taken lie
    under the best on offer."""
    rows = np.flatnonzero(answers["job"] == j)
    commit = int(answers["create"][rows].min())
    ask = (spec["cpu"], spec["memory_mb"], spec["disk_mb"])
    evicted_by = {int(r): [] for r in rows}
    for v in np.flatnonzero(np.isin(answers["preempted_by"], rows)):
        evicted_by[int(answers["preempted_by"][v])].append(int(v))
    events = np.unique(np.r_[answers["create"], answers["stop"]])
    horizons = events[(events > 0) & (events < commit)][::-1][:VIEWS].tolist()
    best = None
    for at in horizons or [commit - 1]:
        cluster = views.cluster(at)
        offers = {}
        for node in views.rows_of:
            opt = cluster.option(
                node, ask, spec["gpus"], spec["priority"], j
            )
            if opt is not None:
                offers[node] = opt
        # the scheduler looks for room first and evicts only where it
        # finds none (generic_sched.go:773-792): a placement that evicted
        # nothing was not made by the code under test and is judged by its
        # count alone (``non_evicting_placements_share``); the others are
        # held to the best of the offers that evict
        top = sorted((o[1] for o in offers.values() if o[0]), reverse=True)
        wrong_victims = wrong_scores = wrong_ranks = 0
        spare = n_victims = n_evicting = 0
        served = 0.0
        for r in rows:
            if not evicted_by[int(r)]:
                continue
            n_evicting += 1
            node = int(answers["node"][r])
            cap, _used, free, free_gpus, cands = cluster.state(node)
            at_row = {c[7][1]: i for i, c in enumerate(cands)}
            mine = [at_row.get(v) for v in evicted_by[int(r)]]
            n_victims += len(mine)
            offer = offers.get(node)
            if offer is None or None in mine:
                # not on offer in this view, or a victim the view lacks
                wrong_victims += 1
                wrong_scores += 1
                wrong_ranks += 1
                continue
            if _signature(cands, mine) != _signature(cands, offer[0]):
                wrong_victims += 1
            said = answers["score"][r]
            if not np.isfinite(said) or abs(said - offer[1]) > SCORE_MATCH:
                wrong_scores += 1
            ranked = answers["rank_score"][r]
            if not np.isfinite(ranked) or not (
                abs(ranked - offer[1]) <= SCORE_MATCH
            ):
                wrong_ranks += 1
            spare += ref.redundant(
                ask, spec["gpus"], free, free_gpus, cands, mine
            )
            served += offer[1]
        on_offer = sum(top[:n_evicting])
        gap = (on_offer - served) / on_offer if on_offer else 0.0
        key = (wrong_victims + wrong_scores + wrong_ranks, gap)
        if best is None or key < best["key"]:
            best = {
                "key": key, "placements": n_evicting,
                "wrong_victims": wrong_victims, "wrong_scores": wrong_scores,
                "wrong_ranks": wrong_ranks,
                "redundant": spare, "victims": n_victims, "gap": gap,
                "older_view": at != horizons[0] if horizons else False,
            }
        if key[0] == 0 and gap <= JOB_OFF_BEST:
            break
    return best


def judge(fleet: dict, specs_by_job: dict, requests: list, answers: dict,
          window: tuple, seed: int) -> dict:
    t_open, t_close = window
    ordinal = {s["id"]: j for j, s in specs_by_job.items()}
    out = {
        "unfinished_requests": sum(1 for r in requests if r.ok is not True),
    }
    replay = plain.capacity_replay(
        fleet, answers["node"], answers["create"], answers["stop"],
        answers["res"],
    )
    out["nodes_over_capacity"] = replay["nodes_over_capacity"]
    out["allocs_off_fleet"] = replay["allocs_off_fleet"]
    out["gpu_instances_double_held"] = _double_held(answers, fleet)
    asked = np.asarray(
        [specs_by_job[int(j)]["gpus"] for j in answers["job"]], dtype=np.int64
    )
    out["allocs_missing_gpu_instances"] = int(
        (answers["gpu_count"] != asked).sum()
    )
    services = [j for j, s in specs_by_job.items() if s["kind"] == "service"]
    out["service_allocs_sharing_a_host"] = _sharing_a_host(answers, services)
    out.update(_eviction_guarantees(answers))
    out["preempt_unplaced"] = answers["preempt_unplaced"]

    in_window = [
        r for r in requests
        if r.kind == "register" and r.ok and t_open < r.done <= t_close
        and specs_by_job[ordinal[r.job_id]]["kind"] == "service"
    ]
    placed = np.isin(answers["job"], [ordinal[r.job_id] for r in in_window])
    evicting = np.zeros(answers["node"].shape[0], dtype=bool)
    by = answers["preempted_by"]
    evicting[by[by >= 0]] = True
    out["service_placements"] = int(placed.sum())
    out["non_evicting_placements_share"] = (
        float((placed & ~evicting).sum() / placed.sum())
        if placed.any() else None
    )
    rng = random.Random(f"{seed}:check")
    sample = rng.sample(in_window, min(SAMPLE_JOBS, len(in_window)))
    if in_window and in_window[-1] not in sample:
        sample[-1] = in_window[-1]
    views = _Views(fleet, answers)
    judged = [
        _judge_job(views, answers, specs_by_job[ordinal[r.job_id]],
                   ordinal[r.job_id])
        for r in sample
    ]
    out["jobs_judged"] = len(judged)
    n = sum(b["placements"] for b in judged)
    if n:
        victims = sum(b["victims"] for b in judged)
        out["evicting_placements_judged"] = n
        out["jobs_judged_on_an_older_view"] = sum(
            b["older_view"] for b in judged)
        out["victim_set_mismatch_share"] = (
            sum(b["wrong_victims"] for b in judged) / n)
        out["score_mismatch_share"] = (
            sum(b["wrong_scores"] for b in judged) / n)
        out["rank_score_mismatch_share"] = (
            sum(b["wrong_ranks"] for b in judged) / n)
        out["redundant_victims_share"] = (
            sum(b["redundant"] for b in judged) / victims if victims else 0.0)
        out["jobs_off_best_share"] = (
            sum(b["gap"] > JOB_OFF_BEST for b in judged) / len(judged))
        out["worst_gap_to_best"] = max(b["gap"] for b in judged)
    return out
