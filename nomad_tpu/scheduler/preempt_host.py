"""Exact per-node preemption victim selection — the reference-parity host
pass that finishes what the device kernel starts.

Split of labor: device/preempt.py ranks ALL nodes in one vectorized
[N, V] pass (feasibility of freeing room + a preemption-penalty-scaled
fit score); this module then selects the final victim set on a chosen
node with the reference's exact greedy semantics. The candidate sets per
node are tiny (a handful of allocs), so exactness is cheap here while the
10k-node search stays on device.

Reference semantics implemented (scheduler/preemption.go):
- eligibility: victim job priority ≤ job priority − 10
  (filterAndGroupPreemptibleAllocs :663-697), grouped by priority asc;
- victim choice: repeatedly take the candidate minimizing
  ``basicResourceDistance(remaining_need, victim) + maxParallel penalty``
  (PreemptForTaskGroup :198-265, scoreForTaskGroup :640-646,
  maxParallelPenalty = 50 :13, distance :608-624) until the freed +
  node-remaining resources form a superset of the ask;
- redundancy: filterSuperset (:702-733) — re-sort the chosen victims by
  distance to the *original* ask descending (no penalty) and keep the
  minimal prefix that meets requirements;
- reserved ports: allocations holding a reserved port the ask needs MUST
  be preempted; a non-preemptible (priority-delta < 10) holder makes the
  node infeasible (PreemptForNetwork :270-395's reserved-port phase).
  Deviation: the reference tracks bandwidth per NIC device and only
  preempts within one device; this build models one aggregate NIC per
  node (SURVEY §7 hard-parts: port bitmaps stay host-side), so bandwidth
  rides the resource vector's 4th dim through the same distance/superset
  math instead of a per-device phase;
- devices: victims holding matching device instances, taken in priority
  order until freed + free instances cover the ask, choosing the option
  with minimal net unique-priority sum (PreemptForDevice :472-555,
  selectBestAllocs :558-604).
"""

from __future__ import annotations

import math
from typing import Optional

import numpy as np

from ..device.preempt import PREEMPTION_PRIORITY_DELTA
from ..structs.resources import _dev_id_matches

MAX_PARALLEL_PENALTY = 50.0  # preemption.go:13


class Candidate:
    """One preemptible allocation on the node under consideration."""

    __slots__ = ("alloc", "priority", "res", "max_parallel", "job_key", "tg")

    def __init__(self, alloc):
        self.alloc = alloc
        self.priority = alloc.job.priority if alloc.job is not None else 50
        self.res = alloc.comparable_resources().to_vector().astype(np.float64)
        self.job_key = (alloc.namespace, alloc.job_id)
        self.tg = alloc.task_group
        mp = 0
        if alloc.job is not None:
            tg = alloc.job.lookup_task_group(alloc.task_group)
            if tg is not None and tg.migrate is not None:
                mp = tg.migrate.max_parallel
        self.max_parallel = mp


def collect_candidates(snap, node_id, job, exclude_ids=frozenset()):
    """Preemptible allocs on a node: non-terminal, not of the placing job
    (SetCandidates :146-163), not already evicted by the in-flight plan,
    and within the priority delta (:663-697)."""
    out = []
    max_prio = job.priority - PREEMPTION_PRIORITY_DELTA
    for a in snap.allocs_by_node(node_id):
        if a.terminal_status() or a.id in exclude_ids:
            continue
        if a.job_id == job.id and a.namespace == job.namespace:
            continue
        c = Candidate(a)
        if c.priority <= max_prio:
            out.append(c)
    return out


def basic_resource_distance(ask: np.ndarray, used: np.ndarray) -> float:
    """preemption.go:608-624 — relative per-dim deltas over cpu/mem/disk
    (dims 0..2; bandwidth is excluded from the basic distance just as the
    reference's basic distance ignores networks)."""
    total = 0.0
    for d in range(3):
        if ask[d] > 0:
            coord = (ask[d] - used[d]) / ask[d]
            total += coord * coord
    return math.sqrt(total)


def _superset(available: np.ndarray, ask: np.ndarray) -> bool:
    return bool(np.all(available + 1e-6 >= ask))


def _alloc_reserved_ports(alloc) -> set[int]:
    ports: set[int] = set()
    job = alloc.job
    if job is None:
        return ports
    tg = job.lookup_task_group(alloc.task_group)
    if tg is None:
        return ports
    for t in tg.tasks:
        for net in t.resources.networks:
            ports.update(net.reserved_ports)
    return ports


def preempt_for_ports(
    snap, node_id, job, ask_ports: set[int], exclude_ids=frozenset()
) -> Optional[list[Candidate]]:
    """Reserved-port phase (PreemptForNetwork :280-395): holders of needed
    ports must go; a high-priority holder makes the node infeasible
    (returns None). Empty list = no port conflicts."""
    if not ask_ports:
        return []
    victims: dict[str, Candidate] = {}
    max_prio = job.priority - PREEMPTION_PRIORITY_DELTA
    for a in snap.allocs_by_node(node_id):
        if a.terminal_status() or a.id in exclude_ids:
            continue
        if a.job_id == job.id and a.namespace == job.namespace:
            continue
        held = _alloc_reserved_ports(a)
        if not (held & ask_ports):
            continue
        c = Candidate(a)
        if c.priority > max_prio:
            return None  # un-preemptible holder (filteredReservedPorts)
        victims[a.id] = c
    return list(victims.values())


def preempt_for_task_group(
    capacity: np.ndarray,
    used: np.ndarray,
    ask: np.ndarray,
    candidates: list[Candidate],
    prior_counts: Optional[dict] = None,
    already_chosen: Optional[list[Candidate]] = None,
) -> Optional[list[Candidate]]:
    """PreemptForTaskGroup (:198-265) + filterSuperset (:702-733), exact.

    ``prior_counts`` maps (job_key, tg) → allocs of that group already
    preempted by the in-flight plan (SetPreemptions :166-183; the penalty
    is NOT updated for picks within this call, matching getNumPreemptions
    reading only the plan). ``already_chosen`` seeds the freed pool with
    victims selected by an earlier phase (ports)."""
    prior_counts = prior_counts or {}
    chosen: list[Candidate] = list(already_chosen or [])
    chosen_ids = {c.alloc.id for c in chosen}
    ask = ask.astype(np.float64)
    node_remaining = (capacity - used).astype(np.float64)

    available = node_remaining.copy()
    for c in chosen:
        available = available + c.res
    if _superset(available, ask):
        return _filter_superset(chosen, node_remaining, ask)

    needed = ask.copy()
    for c in chosen:
        needed = needed - c.res

    by_prio: dict[int, list[Candidate]] = {}
    for c in candidates:
        if c.alloc.id in chosen_ids:
            continue
        by_prio.setdefault(c.priority, []).append(c)

    met = False
    for prio in sorted(by_prio):
        grp = by_prio[prio]
        while grp and not met:
            best_i, best_score = -1, float("inf")
            for i, c in enumerate(grp):
                n_pre = prior_counts.get((c.job_key, c.tg), 0)
                penalty = 0.0
                if c.max_parallel > 0 and n_pre >= c.max_parallel:
                    penalty = ((n_pre + 1) - c.max_parallel) * MAX_PARALLEL_PENALTY
                score = basic_resource_distance(needed, c.res) + penalty
                if score < best_score:
                    best_score, best_i = score, i
            c = grp.pop(best_i)
            chosen.append(c)
            available = available + c.res
            needed = needed - c.res
            met = _superset(available, ask)
        if met:
            break
    if not met:
        return None
    return _filter_superset(chosen, node_remaining, ask)


def _filter_superset(
    chosen: list[Candidate], node_remaining: np.ndarray, ask: np.ndarray
) -> list[Candidate]:
    """filterSuperset (:702-733): distance-descending vs the ORIGINAL ask,
    keep the minimal prefix meeting requirements."""
    ordered = sorted(
        chosen,
        key=lambda c: basic_resource_distance(ask, c.res),
        reverse=True,
    )
    available = node_remaining.copy()
    out = []
    for c in ordered:
        out.append(c)
        available = available + c.res
        if _superset(available, ask):
            break
    return out


def preempt_for_devices(
    snap, node, job, tg, exclude_ids=frozenset(), plan=None
) -> Optional[list[Candidate]]:
    """PreemptForDevice (:472-555): per device ask, free held instances by
    preempting their holders in priority order; among sufficient options
    pick minimal net unique-priority (selectBestAllocs :558-604).
    Returns None when an ask can't be covered even with preemption.
    Instances the in-flight ``plan`` has already handed out on this node
    (an earlier instance of the same group took a victim's) count as held,
    by holders that are no candidates."""
    from .device import collect_in_use, device_group_matches, group_device_asks

    asks = group_device_asks(tg)
    if not asks:
        return []
    max_prio = job.priority - PREEMPTION_PRIORITY_DELTA
    live = [
        a
        for a in snap.allocs_by_node(node.id)
        if not a.terminal_status()
        and a.id not in exclude_ids
        and not (a.job_id == job.id and a.namespace == job.namespace)
    ]
    placed = plan.node_allocation.get(node.id, []) if plan is not None else []
    in_use = collect_in_use(live + list(placed))
    victims: dict[str, Candidate] = {}
    for ask in asks:
        # free instances per matching device group
        options = []
        for dev in node.node_resources.devices:
            if not device_group_matches(dev, ask):
                continue
            did = dev.id()
            held = in_use.get(did, set())
            free = sum(
                1 for i in dev.instances if i.healthy and i.id not in held
            )
            if free >= ask.count:
                options = []  # no preemption needed for this ask
                break
            # holders of this device's instances, priority-grouped
            holders: list[tuple[Candidate, int]] = []
            for a in live:
                ids = a.device_instance_ids().get(did)
                n = len(ids) if ids else a.device_asks().get(did, 0)
                if not n:
                    # partial-id asks (e.g. bare "gpu") also hold instances
                    for aid, cnt in a.device_asks().items():
                        if _dev_id_matches(did, aid):
                            n = cnt
                            break
                if n:
                    c = Candidate(a)
                    if c.priority <= max_prio:
                        holders.append((c, n))
            holders.sort(key=lambda h: h[0].priority)
            freed, option = 0, []
            for c, n in holders:
                freed += n
                option.append((c, n))
                if freed + free >= ask.count:
                    options.append((option, free))
                    break
        else:
            if not options:
                return None  # ask cannot be covered on this node
            # minimal net unique-priority option (selectBestAllocs).
            # Deviation: the reference filter counts preempted instances
            # against the FULL ask (selectBestAllocs :558-604), evicting
            # holders whose instances the device's already-free pool
            # could cover; we count against (ask − free), which frees the
            # same capacity with strictly fewer evictions.
            best, best_net = None, None
            for option, dev_free in options:
                option.sort(key=lambda h: -h[1])  # instance count desc
                taken, count, prios = [], 0, set()
                need = max(ask.count - dev_free, 0)
                for c, n in option:
                    if count >= need:
                        break
                    taken.append(c)
                    count += n
                    prios.add(c.priority)
                net = sum(prios)
                if best_net is None or net < best_net:
                    best_net, best = net, taken
            for c in best or []:
                victims[c.alloc.id] = c
    return list(victims.values())


def select_victims(
    ct,
    snap,
    job,
    tg,
    ask_vec: np.ndarray,
    row: int,
    plan=None,
    exclude_ids=frozenset(),
) -> Optional[list]:
    """Full exact victim selection on one node: port phase → device phase
    → resource phase, all sharing one freed pool. Returns alloc-id list
    or None when the node can't be made to fit."""
    node_id = ct.node_ids[row]
    node = snap.node_by_id(node_id)
    if node is None:
        return None

    ask_ports: set[int] = set()
    for t in tg.tasks:
        for net in t.resources.networks:
            ask_ports.update(net.reserved_ports)

    port_victims = preempt_for_ports(
        snap, node_id, job, ask_ports, exclude_ids
    )
    if port_victims is None:
        return None
    dev_victims = preempt_for_devices(
        snap, node, job, tg, exclude_ids, plan=plan
    )
    if dev_victims is None:
        return None
    seed = {c.alloc.id: c for c in port_victims}
    for c in dev_victims:
        seed.setdefault(c.alloc.id, c)

    prior_counts: dict = {}
    if plan is not None:
        for allocs in plan.node_preemptions.values():
            for a in allocs:
                victim = snap.alloc_by_id(a.id) or a
                key = ((victim.namespace, victim.job_id), victim.task_group)
                prior_counts[key] = prior_counts.get(key, 0) + 1

    candidates = collect_candidates(snap, node_id, job, exclude_ids)
    chosen = preempt_for_task_group(
        np.asarray(ct.capacity[row], dtype=np.float64),
        np.asarray(ct.used[row], dtype=np.float64),
        np.asarray(ask_vec, dtype=np.float64),
        candidates,
        prior_counts=prior_counts,
        already_chosen=list(seed.values()),
    )
    if chosen is None:
        return None
    # device/port victims are mandatory even if the resource pass's
    # superset filter would drop them
    ids = [c.alloc.id for c in chosen]
    for aid in seed:
        if aid not in ids:
            ids.append(aid)
    return ids
