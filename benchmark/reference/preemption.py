"""Plain reference for placement by eviction: numpy and the standard
library, float64, nothing of ``nomad_tpu``.

Written from the description of ``scheduler/preemption.go`` and
``scheduler/rank.go:775-844``; the same operations on the same data give
the same answers as the program, by another route (per node, in Python,
on plain tuples). A *candidate* is one live allocation on a node:
``(priority, cpu, memory_mb, disk_mb, gpus, max_parallel, evicted_before,
key)``; ``gpus`` the device instances it holds, ``evicted_before`` how many
allocations of its job and group the plan in flight has evicted already,
``key`` whatever names it to the caller. One node, one ask:

1. *Eligibility.* A candidate may go only if its priority is at most the
   placing job's less 10 (``filterAndGroupPreemptibleAllocs``).
2. *Device instances* (``PreemptForDevice``). Where the ask wants more
   instances than the node has free, holders are taken lowest priority
   first until freed + free covers the ask; of that option the holders of
   most instances are kept until the *shortfall* is covered. Departure
   from the source, as the program: the source counts against the whole
   ask (``selectBestAllocs``) and so evicts holders the free instances
   would have covered; with one device group per node there is one option,
   so its choice by the least net priority has nothing to choose from.
3. *Resources* (``PreemptForTaskGroup``). Starting from the device
   victims, candidates are taken priority group by priority group, lowest
   first; inside a group the one nearest to what is still needed, by
   ``basicResourceDistance`` (cpu, memory, disk; a dimension no longer
   needed drops out) plus the ``maxParallel`` penalty (50 for every
   eviction beyond the victim group's ``migrate.max_parallel``), until
   what is free and freed covers the ask.
4. *Superset filter* (``filterSuperset``). The chosen are sorted by their
   distance to the whole ask, farthest first, and the shortest prefix that
   covers the ask is kept; device victims stay whatever the filter says.
5. *Score* (``rank.go:775-844``): the binpack fit of the node after the
   victims are gone and the ask is placed, times ``1 / (1 + exp((net -
   2048) / 256))``. Departures from the source, as the program: ``net`` is
   the plain sum of the victims' priorities (the source: the largest plus
   sum / largest), the rate is 1/256 (the source: 0.0048), and the two are
   multiplied (the source averages them as two score components).

The program computes step 5 twice for a placement: on the chip for every
node of the fleet, to rank them (steps 2 to 4 over padded arrays), and on
the host for the victims it then chose on the node it took. Both numbers
are recorded and both are held to this one.

``fault`` breaks one of these on purpose; the controls of the benchmark
run the reference with a fault in the program's place.
"""

from __future__ import annotations

import math

import numpy as np

PRIORITY_DELTA = 10
MAX_PARALLEL_PENALTY = 50.0
INFLECTION = 2048.0
RATE = 1.0 / 256.0
DIMS = ("cpu", "memory_mb", "disk_mb")

FAULTS = (
    # steps 2 and 3 read their orders upside down: the highest priority
    # first and, inside a priority, the farthest from the need first
    "highest_priority_first",
    "redundant_victim",  # step 4 left out and one victim more than needed
    "bfloat16_scores",  # step 5 in the precision below the program's
    "instance_twice",  # the placed allocation is handed a held instance
    "worst_nodes_first",  # the ranking of the nodes read upside down
)


def distance(need, res) -> float:
    total = 0.0
    for d in range(3):
        if need[d] > 0:
            coord = (need[d] - res[d]) / need[d]
            total += coord * coord
    return math.sqrt(total)


def covers(available, ask) -> bool:
    return all(available[d] + 1e-6 >= ask[d] for d in range(3))


def fit_score(cap, used, dtype=np.float64) -> float:
    """Binpack fit: ``20 - 10^free_cpu - 10^free_mem`` over 18, clipped;
    free as shares of the node's capacity after its reserve."""
    t = dtype
    free_cpu = (t(cap[0]) - t(used[0])) / t(cap[0])
    free_mem = (t(cap[1]) - t(used[1])) / t(cap[1])
    total = t(20.0) - t(10.0) ** free_cpu - t(10.0) ** free_mem
    return float(min(max(total, t(0.0)), t(18.0)) / t(18.0))


def penalty(net: float, dtype=np.float64) -> float:
    t = dtype
    return float(
        t(1.0) / (t(1.0) + np.exp((t(net) - t(INFLECTION)) * t(RATE)))
    )


def select_victims(ask, ask_gpus, free, free_gpus, cands, job_priority,
                   fault=None):
    """Indices into ``cands`` of the victims on one node, or ``None``
    where no set of them makes the ask fit. ``free`` is capacity less
    usage in the three dimensions, ``free_gpus`` the instances nobody
    holds."""
    sign = -1 if fault == "highest_priority_first" else 1
    may_go = [
        i for i, c in enumerate(cands) if c[0] <= job_priority - PRIORITY_DELTA
    ]
    # 2. device instances
    device = []
    if ask_gpus > free_gpus:
        holders = sorted(
            (i for i in may_go if cands[i][4] > 0),
            key=lambda i: sign * cands[i][0],
        )
        option, freed = [], 0
        for i in holders:
            option.append(i)
            freed += cands[i][4]
            if freed + free_gpus >= ask_gpus:
                break
        else:
            return None
        option.sort(key=lambda i: -cands[i][4])
        need, count = ask_gpus - free_gpus, 0
        for i in option:
            if count >= need:
                break
            device.append(i)
            count += cands[i][4]
    # 3. resources
    chosen = list(device)
    available = [free[d] + sum(cands[i][1 + d] for i in chosen)
                 for d in range(3)]
    if not covers(available, ask):
        needed = [ask[d] - sum(cands[i][1 + d] for i in chosen)
                  for d in range(3)]
        groups: dict = {}
        for i in may_go:
            if i not in chosen:
                groups.setdefault(cands[i][0], []).append(i)
        met = False
        for prio in sorted(groups, key=lambda p: sign * p):
            group = groups[prio]
            while group and not met:
                best, best_score = None, math.inf
                for i in group:
                    c = cands[i]
                    over = 0.0
                    if c[5] > 0 and c[6] >= c[5]:
                        over = (c[6] + 1 - c[5]) * MAX_PARALLEL_PENALTY
                    score = sign * (distance(needed, c[1:4]) + over)
                    if score < best_score:
                        best, best_score = i, score
                group.remove(best)
                chosen.append(best)
                for d in range(3):
                    available[d] += cands[best][1 + d]
                    needed[d] -= cands[best][1 + d]
                met = covers(available, ask)
            if met:
                break
        if not met:
            return None
    if fault == "redundant_victim":
        spare = [i for i in may_go if i not in chosen]
        return chosen + spare[:1]
    # 4. superset filter
    ordered = sorted(chosen, key=lambda i: -distance(ask, cands[i][1:4]))
    available, kept = list(free), []
    for i in ordered:
        kept.append(i)
        for d in range(3):
            available[d] += cands[i][1 + d]
        if covers(available, ask):
            break
    return kept + [i for i in device if i not in kept]


def option_score(cap, used, ask, cands, victims, fault=None) -> float:
    """5. the node's score once ``victims`` are gone and the ask placed."""
    dtype = np.float64
    if fault == "bfloat16_scores":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    after = [
        used[d] - sum(cands[i][1 + d] for i in victims) + ask[d]
        for d in range(3)
    ]
    net = sum(cands[i][0] for i in victims)
    return fit_score(cap, after, dtype) * penalty(net, dtype)


def redundant(ask, ask_gpus, free, free_gpus, cands, victims) -> int:
    """How many of ``victims`` could each have stayed, the others gone."""
    n = 0
    for out in victims:
        rest = [i for i in victims if i != out]
        available = [free[d] + sum(cands[i][1 + d] for i in rest)
                     for d in range(3)]
        gpus = free_gpus + sum(cands[i][4] for i in rest)
        n += covers(available, ask) and gpus >= ask_gpus
    return n


class Cluster:
    """The fleet as the reference keeps it: per node the live allocations
    as candidates. ``fleet`` is the benchmark's plain table (capacity
    after the reserve per dimension, ``gpus`` instances per node)."""

    def __init__(self, fleet: dict):
        self.cap = np.stack([fleet[d] for d in DIMS], axis=1).astype(float)
        self.gpus = np.asarray(fleet["gpus"], dtype=np.int64)
        self.live = [[] for _ in range(int(fleet["n"]))]  # node -> [cand]
        self._memo: dict = {}

    def add(self, node: int, cand: tuple) -> None:
        self.live[node].append(cand)

    def remove(self, node: int, key) -> None:
        self.live[node] = [c for c in self.live[node] if c[7] != key]

    def state(self, node: int) -> tuple:
        """``(cap, used, free, free_gpus, cands)`` of one node."""
        cands = self.live[node]
        used = [sum(c[1 + d] for c in cands) for d in range(3)]
        cap = self.cap[node]
        free = [cap[d] - used[d] for d in range(3)]
        free_gpus = int(self.gpus[node]) - sum(c[4] for c in cands)
        return cap, used, free, free_gpus, cands

    def option(self, node: int, ask, ask_gpus, job_priority, job_key,
               fault=None):
        """``(victim indices, score)`` on ``node``, or ``None``: no
        hardware, the job is there already (``distinct_hosts``), or no
        victim set fits. Nodes in the same state share one computation:
        a fleet filled by a few job shapes has few distinct states."""
        if self.gpus[node] < ask_gpus:
            return None
        cap, used, free, free_gpus, cands = self.state(node)
        if any(c[7][0] == job_key for c in cands):
            return None
        memo_key = (
            tuple(cap), tuple(ask), ask_gpus, free_gpus, job_priority, fault,
            tuple(c[:7] for c in cands),
        )
        if memo_key not in self._memo:
            if covers(free, ask) and free_gpus >= ask_gpus:
                victims = []
            else:
                victims = select_victims(
                    ask, ask_gpus, free, free_gpus, cands, job_priority, fault
                )
            self._memo[memo_key] = None if victims is None else (
                victims, option_score(cap, used, ask, cands, victims, fault)
            )
        return self._memo[memo_key]


def filled_cluster(fleet: dict, fill: list) -> tuple:
    """The fleet full, without a scheduler: every fill job's instances
    dealt onto the nodes in row order while they fit (``fill``: specs in
    order, GPU holders first). Returns the cluster and the allocation
    records ``[job ordinal, node, spec]`` made."""
    cluster = Cluster(fleet)
    records = []
    node_of = 0
    for j, spec in fill:
        ask = (spec["cpu"], spec["memory_mb"], spec["disk_mb"])
        placed, node = 0, node_of
        while placed < spec["count"]:
            if node >= fleet["n"]:
                raise ValueError(f"fill job {spec['id']} does not fit")
            _cap, _used, free, free_gpus, _c = cluster.state(node)
            if covers(free, ask) and free_gpus >= spec["gpus"]:
                key = (j, len(records))
                cluster.add(node, (
                    spec["priority"], *ask, spec["gpus"], 0, 0, key,
                ))
                records.append([j, node, spec])
                placed += 1
            else:
                node += 1
        if spec["gpus"] == 0:
            node_of = node
    return cluster, records


def place_by_evicting(cluster: Cluster, j: int, spec: dict, fault=None,
                      rng=None) -> list:
    """One service job on the reference's cluster: every instance on the
    best node on offer, its victims evicted. Returns ``[(node, victim
    keys, score)]`` per instance; the cluster is left as after the
    commit."""
    ask = (spec["cpu"], spec["memory_mb"], spec["disk_mb"])
    options = {}
    for node in range(len(cluster.live)):
        opt = cluster.option(
            node, ask, spec["gpus"], spec["priority"], j, fault
        )
        if opt is not None:
            options[node] = opt
    # room first, eviction only where there is none (the scheduler's two
    # passes, generic_sched.go:773-792), each by score
    sign = 1 if fault == "worst_nodes_first" else -1
    ranked = sorted(
        options, key=lambda n: (bool(options[n][0]), sign * options[n][1], n)
    )
    out = []
    for node in ranked[: spec["count"]]:
        victims, score = options[node]
        cands = cluster.live[node]
        keys = [cands[i][7] for i in victims]
        for key in keys:
            cluster.remove(node, key)
        out.append((node, keys, score))
    for node, _keys, _score in out:
        cluster.add(node, (
            spec["priority"], *ask, spec["gpus"], 0, 0, (j, len(out), node),
        ))
    return out
