"""Vectorized preemption — the reference's greedy victim search, every
node at once.

Reference semantics (scheduler/preemption.go):
- Eligibility: victim priority ≤ job priority − 10
  (filterAndGroupPreemptibleAllocs :663-697).
- Victim choice per node: group by priority ascending, inside a group the
  nearest to what is still needed first (PreemptForTaskGroup :198-265,
  basicResourceDistance :608-624) — take victims until the ask fits.
- Redundancy: drop victims whose removal isn't needed (filterSuperset
  :702-733).
- Scoring: preempting options are down-ranked by a logistic of the summed
  victim priorities, inflection at net priority 2048
  (rank.go:775-844 PreemptionScoringIterator / preemptionScore).

TPU reformulation (SURVEY.md §7 step 6): all nodes evaluated at once.
Victims are padded to ``[N, V]``; one pass does

    must    = cheapest holders of the device instances the ask lacks
    taken   = must, then V steps: the nearest of the lowest priority
              group left, on every node that does not fit yet
    victims = filterSuperset(taken) ∪ must             # V x V compare + sum
    net[n]  = sum of the victims' priorities
    score   = fit(used − freed + ask) · logistic(net)  # preemption penalty

The greedy's loop runs over the victim axis (V steps of [N, V] array
work), not over nodes: a step's choice depends on what the steps before
it took, so a single sorted prefix is not the reference's set once a
priority group holds allocations of different sizes (it ranked nodes up
to 2 % under the reference's best on a fleet filled at one priority).
The host pass (scheduler/preempt_host.py) stays the authority on the
nodes a placement takes.

Where the reference sorts (the holders of a device by priority and
distance, the chosen by distance for the superset filter, the victims
ahead of the rest for the host), nothing here is sorted or permuted:
``_precedes`` compares every pair of a node's slots ([N, V, V]), a sum
over the slots that come before one is the prefix scan at that slot, and
their count is its place in the order. On the chip a permutation gather
walks its result element by element and cost more than everything else
in the kernel together; the dense planes are array work like the rest
(measured in PERF.md §6, PR 32; the same finding as PR 30's there).

The victim tensors are kept on the ``ClusterTensors`` generation
(``VictimTensors``): a ranking walks the nodes whose allocations changed
since the last one, not the fleet.
"""

from __future__ import annotations

import functools
import itertools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import global_tracer as _tracer
from ..utils.backend import traced_jit
from ..utils.metrics import global_metrics as _metrics

# Priority delta a preemptor must have over its victims
# (preemption.go:673: delta ≥ 10).
PREEMPTION_PRIORITY_DELTA = 10
# Logistic inflection point for the net-priority penalty (rank.go:842).
NET_PRIORITY_INFLECTION = 2048.0


def preemption_score(net_priority):
    """Down-weight for preempting options: ≈1 for cheap preemptions, →0 as
    summed victim priority passes the inflection (rank.go:834-844)."""
    return 1.0 / (1.0 + jnp.exp((net_priority - NET_PRIORITY_INFLECTION) / 256.0))


def preemption_option_score(capacity, proposed, net_priority) -> float:
    """The kernel's score for one node, on the host in float32: binpack
    fit of ``proposed`` (usage once the victims are gone and the ask is
    placed) times the preemption penalty. The scheduler records it on an
    allocation placed by evicting, for the victims it actually chose."""
    capacity = np.asarray(capacity, dtype=np.float32)
    proposed = np.asarray(proposed, dtype=np.float32)
    free_frac = np.where(
        capacity > 0,
        (capacity - proposed) / np.maximum(capacity, np.float32(1e-9)),
        np.float32(1.0),
    ).astype(np.float32)
    ten = np.float32(10.0)
    fit = np.clip(
        np.float32(20.0) - ten ** free_frac[0] - ten ** free_frac[1],
        np.float32(0.0),
        np.float32(18.0),
    ) / np.float32(18.0)
    penalty = np.float32(1.0) / (
        np.float32(1.0)
        + np.exp(
            (np.float32(net_priority) - np.float32(NET_PRIORITY_INFLECTION))
            / np.float32(256.0)
        )
    )
    return float(fit * penalty)


def resource_distance(need, victim):
    """basicResourceDistance (preemption.go:608-624): L2 over the relative
    per-dimension deltas of cpu, memory and disk — closer victims waste
    less. A dimension no longer needed drops out."""
    need, victim = need[..., :3], victim[..., :3]
    wanted = need > 0
    rel = jnp.where(wanted, (need - victim) / jnp.where(wanted, need, 1.0), 0.0)
    return jnp.sqrt(jnp.sum(rel * rel, axis=-1))


def _superset(available, ask):
    return jnp.all(available + 1e-6 >= ask, axis=-1)


def _precedes(*keys):
    """bool[N, V, V]: ``[n, i, j]`` says that slot ``i`` of node ``n`` comes
    before its slot ``j`` in a stable sort by ``keys`` (each ``[N, V]``, the
    first decides first): equal keys leave the lower slot first, and -0.0
    equals 0.0 as it does to the sort. The permutation as one compare over
    the victim axis; nothing is moved."""
    v = keys[0].shape[1]
    before = (jnp.arange(v)[:, None] < jnp.arange(v)[None, :])[None]
    for key in reversed(keys):
        k_i, k_j = key[:, :, None], key[:, None, :]
        before = (k_i < k_j) | ((k_i == k_j) & before)
    return before


def _victim_sets(
    capacity, used, ask, eligible, victim_res, victim_prio, victim_mask,
    victim_dev, dev_need,
):
    """The body both kernels share: per node the victim set the
    reference's greedy chooses, its net priority and what it frees
    (``freed`` f32[N, D]; zero where no set fits), every node at once.

    Device phase (PreemptForDevice): ``victim_dev`` (i32[N, V] device
    instances a victim holds) and ``dev_need`` (i32[N] instances the ask
    lacks on the node): the cheapest holders that cover the need go
    whatever their distance, and a node whose holders cannot cover it is
    infeasible. An ask without a device hands in zeros: one program for
    both. Resource phase (PreemptForTaskGroup): one victim a step, lowest
    priority group first, inside it the nearest to what is still needed,
    until free + freed covers the ask; V steps at most. Superset filter
    (filterSuperset): the chosen by distance to the whole ask, farthest
    first, the shortest prefix that covers; device victims stay. Left to
    the host pass (scheduler/preempt_host.py), which is exact on the
    nodes a placement takes: the maxParallel penalty, reserved ports, the
    match of device instances by vendor, type and name.

    Nothing is sorted and nothing gathered (``_precedes``): a prefix scan
    in sorted order is a sum over the slots that come before (``[N, V, V]``
    array work, ``[N, V, V, D]`` for the freed resources), and a slot's
    place in an order is the count of those slots."""
    big = jnp.float32(1e9)
    v = victim_mask.shape[1]
    slots = jnp.arange(v)[None, :]
    free = capacity - used
    res = jnp.where(victim_mask[:, :, None], victim_res, 0.0)
    prio = jnp.where(victim_mask, victim_prio, 0)
    dist_ask = resource_distance(ask[None, None, :], victim_res)  # [N, V]

    with jax.named_scope("victim_sort"):
        # holders of the lacking instances, cheapest first
        key = victim_prio.astype(jnp.float32) * 1e4 + jnp.minimum(dist_ask, 9e3)
        holder = victim_mask & (victim_dev > 0)
        held = jnp.where(holder, victim_dev, 0)
        cheaper = _precedes(jnp.where(holder, key, big))
        # freed by cheaper ones
        before = jnp.sum(jnp.where(cheaper, held[:, :, None], 0), axis=1)
        must = holder & (before < dev_need[:, None])
        eligible = eligible & (
            jnp.sum(jnp.where(must, held, 0), axis=1) >= dev_need
        )

    with jax.named_scope("freed_prefix"):
        seeded = jnp.sum(jnp.where(must[:, :, None], res, 0.0), axis=1)
        available = free + seeded
        carry = (
            must,
            jnp.where(must, 0, v + 1).astype(jnp.int32),  # step taken at
            available,
            ask[None, :] - seeded,
            _superset(available, ask[None, :]),
        )

        def take_nearest(i, carry):
            taken, step_of, available, needed, met = carry
            on_offer = victim_mask & ~taken
            lowest = jnp.min(
                jnp.where(on_offer, victim_prio, jnp.iinfo(jnp.int32).max),
                axis=1,
            )
            group = on_offer & (victim_prio == lowest[:, None])
            dist = resource_distance(needed[:, None, :], victim_res)
            pick = jnp.argmin(jnp.where(group, dist, big), axis=1)
            go = ~met & jnp.any(on_offer, axis=1)
            one = (slots == pick[:, None]) & go[:, None]
            gone = jnp.sum(jnp.where(one[:, :, None], res, 0.0), axis=1)
            available = available + gone
            return (
                taken | one,
                jnp.where(one, i + 1, step_of),
                available,
                needed - gone,
                _superset(available, ask[None, :]),
            )

        taken, step_of, _available, _needed, met = jax.lax.fori_loop(
            0, v, take_nearest, carry
        )
        # superset filter: farthest from the whole ask first, ties in the
        # order they were taken; the shortest prefix that covers
        ahead = _precedes(
            jnp.where(taken, -dist_ask, big), step_of
        ) & taken[:, :, None]
        place = jnp.sum(ahead, axis=1)  # among the taken
        freed_to = res + jnp.sum(
            jnp.where(ahead[:, :, :, None], res[:, :, None, :], 0.0), axis=1
        )
        covers = _superset(
            free[:, None, :] + freed_to, ask[None, None, :]
        ) & taken
        n_kept = jnp.min(jnp.where(covers, place + 1, v + 1), axis=1)
        n_kept = jnp.where(jnp.any(covers, axis=1), n_kept, 0)
        kept = taken & (place < n_kept[:, None])
        victims = kept | must
        k = jnp.sum(victims, axis=1).astype(jnp.int32)
        # a node with room needs no victim and is not an option here
        any_fit = met & eligible & (k > 0)
        victims = victims & any_fit[:, None]
        k = jnp.where(any_fit, k, 0)
        net = jnp.sum(jnp.where(victims, prio, 0), axis=1).astype(jnp.float32)
        freed = jnp.sum(jnp.where(victims[:, :, None], res, 0.0), axis=1)
        # the victims first, in slot order: what the host maps to ids
        stands_at = jnp.sum(_precedes(~victims), axis=1)
        order = jnp.sum(
            jnp.where(
                stands_at[:, :, None] == slots[:, None, :],
                slots[:, :, None],
                0,
            ),
            axis=1,
        )
    return any_fit, k, net, order.astype(jnp.int32), freed


@functools.partial(traced_jit, retrace_budget=8)
def find_preemption_kernel(
    capacity,  # f32[N, D]
    used,  # f32[N, D] (incl. victims)
    ask,  # f32[D]
    eligible,  # bool[N] (constraint/dc mask, ignoring resource fit)
    victim_res,  # f32[N, V, D] resources per candidate victim
    victim_prio,  # i32[N, V] victim priorities (already delta-filtered)
    victim_mask,  # bool[N, V] real victims vs padding
):
    """For every node, the victim set that frees room.

    Returns (feasible bool[N], k i32[N] victims needed, net_priority f32[N],
    order i32[N, V] victim slots, the k victims first). Host maps (node,
    order[:k]) back to allocation ids.
    """
    return _victim_sets(
        capacity, used, ask, eligible, victim_res, victim_prio, victim_mask,
        jnp.zeros_like(victim_prio),
        jnp.zeros(victim_prio.shape[0], dtype=victim_prio.dtype),
    )[:4]


@functools.partial(traced_jit, retrace_budget=8)
def choose_preemption_node_kernel(
    capacity,
    used,
    ask,
    eligible,
    victim_res,
    victim_prio,
    victim_mask,
    victim_dev,  # i32[N, V] device instances a victim holds
    dev_need,  # i32[N] instances the ask lacks on the node (0: no device ask)
):
    """Pick the best node to preempt on: the binpack fit score after the
    node's victim set is evicted and the ask placed, scaled by the
    preemption penalty. Returns (best i32, feasible bool[N], k i32[N],
    net f32[N], order i32[N, V], score f32[N]; -inf where infeasible)."""
    from .score import _pow10

    feasible, k, net, order, freed_k = _victim_sets(
        capacity, used, ask, eligible, victim_res, victim_prio, victim_mask,
        victim_dev, dev_need,
    )
    with jax.named_scope("preempt_score"):
        proposed = used - freed_k + ask
        free_frac = jnp.where(
            capacity > 0,
            (capacity - proposed) / jnp.maximum(capacity, 1e-9),
            1.0,
        )
        fit = jnp.clip(
            20.0 - _pow10(free_frac[:, 0]) - _pow10(free_frac[:, 1]),
            0.0,
            18.0,
        ) / 18.0
        score = fit * preemption_score(net)
        score = jnp.where(feasible, score, -jnp.inf)
        best = jnp.argmax(score)
    return best, feasible, k, net, order, score


def _victim_bucket(n: int) -> int:
    """Pad the victim axis to a power of two so victim-count churn doesn't
    retrigger XLA compilation (same policy as score._steps_bucket)."""
    b = 1
    while b < n:
        b <<= 1
    return b


class VictimTensors:
    """The fleet's preemption candidates under one priority ceiling,
    padded to ``[N, V]``: kept on the ``ClusterTensors`` they were built
    for (``victim_cache``) and carried from one cache generation to the
    next with the rows whose allocations changed marked stale, so a
    ranking walks the nodes touched since the last one and not the
    fleet. A carried table borrows its arrays and copies them before its
    first write: a generation that ranks nothing copies nothing, and the
    readers of an older generation keep what they were handed."""

    __slots__ = (
        "res", "prio", "mask", "dev", "dev_free", "ids", "stale",
        "borrowed", "used_at",
    )

    def __init__(self, pn: int, v: int):
        self.res = np.zeros((pn, v, 4), dtype=np.float32)
        self.prio = np.zeros((pn, v), dtype=np.int32)
        self.mask = np.zeros((pn, v), dtype=bool)
        # device instances a candidate holds, and those of the node nobody
        # holds: counts over all of a node's device groups (the exact
        # match by vendor, type and name is the host pass's)
        self.dev = np.zeros((pn, v), dtype=np.int32)
        self.dev_free = np.zeros(pn, dtype=np.int32)
        self.ids: list[list[str]] = [[] for _ in range(pn)]
        self.stale = set(range(pn))
        self.borrowed = False
        self.used_at = 0

    def carried_over(self, touched) -> "VictimTensors":
        out = VictimTensors.__new__(VictimTensors)
        out.res, out.prio, out.mask = self.res, self.prio, self.mask
        out.dev, out.dev_free, out.ids = self.dev, self.dev_free, self.ids
        out.stale = self.stale | set(touched)
        out.borrowed, out.used_at = True, self.used_at
        return out

    def own(self) -> None:
        """Before the first write: the arrays become this table's own."""
        if not self.borrowed:
            return
        self.res, self.prio = self.res.copy(), self.prio.copy()
        self.mask, self.dev = self.mask.copy(), self.dev.copy()
        self.dev_free, self.ids = self.dev_free.copy(), list(self.ids)
        self.borrowed = False

    def fill_row(self, row: int, node, live: list, max_prio: int) -> int:
        """Refill one row from the node's live allocations; returns how
        many candidates it has (more than the bucket: nothing written)."""
        cands = []
        held = 0
        for a in live:
            n_dev = sum(a.device_asks().values())
            held += n_dev
            prio = a.job.priority if a.job is not None else 50
            if prio <= max_prio:
                cands.append((a, prio, n_dev))
        if len(cands) > self.mask.shape[1]:
            return len(cands)
        self.res[row] = 0.0
        self.prio[row] = 0
        self.mask[row] = False
        self.dev[row] = 0
        for j, (a, prio, n_dev) in enumerate(cands):
            self.res[row, j] = a.comparable_resources().to_vector()
            self.prio[row, j] = prio
            self.mask[row, j] = True
            self.dev[row, j] = n_dev
        devices = node.node_resources.devices if node is not None else ()
        self.dev_free[row] = sum(
            1 for d in devices for i in d.instances if i.healthy
        ) - held
        self.ids[row] = [a.id for a, _p, _d in cands]
        return len(cands)


# Priority ceilings a cache generation keeps victim tensors for: the most
# recently ranked ones (a fleet's preemptors come at a few priorities; a
# table that owns its arrays is 4 N V (D + 3) bytes).
VICTIM_CEILINGS_KEPT = 4
# A table is refreshed in place: one refresh at a time, whichever worker
# ranks. After its refresh a generation's table is not written again (rows
# go stale only where the next generation is made), so readers need no lock.
_refresh_lock = threading.Lock()
_use_clock = itertools.count(1)


def carry_victim_cache(cache: dict, touched) -> dict:
    """A cache generation's victim tensors for the next one
    (``DeviceStateCache``'s incremental refresh): the rows in ``touched``
    marked stale, nothing copied, the least recently ranked ceilings
    beyond ``VICTIM_CEILINGS_KEPT`` dropped."""
    with _refresh_lock:
        kept = sorted(cache.items(), key=lambda kv: -kv[1].used_at)
        return {
            key: entry.carried_over(touched)
            for key, entry in kept[:VICTIM_CEILINGS_KEPT]
        }


def build_victim_tensors(ct, snap, job, exclude_ids=frozenset()):
    """Flatten preemption candidates: for every node row, the allocs whose
    priority is ≤ job.priority − 10 (preemption.go:663-697), padded to a
    power-of-two victim bucket. ``exclude_ids`` drops allocs already
    preempted by the in-flight plan (their capacity is freed once, not
    twice). Returns (victim_res, victim_prio, victim_mask,
    victim_ids[list per node])."""
    t = victim_tensors(ct, snap, job, exclude_ids)
    return t.res, t.prio, t.mask, t.ids


def victim_tensors(ct, snap, job, exclude_ids=frozenset()) -> VictimTensors:
    """``build_victim_tensors`` with the device counts beside them.

    The walk over a node's allocations is made once per cache generation
    and priority ceiling: the tensors stay on ``ct`` and a later call
    looks only at the rows whose allocations changed since (a snapshot
    that is not the one ``ct`` was built for walks the fleet and keeps
    nothing). The bucket only grows."""
    max_prio = job.priority - PREEMPTION_PRIORITY_DELTA
    shared = getattr(snap, "index", None) == ct.index
    with _refresh_lock:
        entry = ct.victim_cache.get(max_prio) if shared else None
        v = entry.mask.shape[1] if entry is not None else 1
        while True:
            if entry is None:
                entry = VictimTensors(ct.padded_n, v)
            stale = sorted(r for r in entry.stale if r < ct.num_nodes)
            if stale:
                entry.own()
            grown = 0
            for row in stale:
                live = [
                    a for a in snap.allocs_by_node(ct.node_ids[row])
                    if not a.terminal_status()
                ]
                node = ct.nodes[row] if row < len(ct.nodes) else None
                n = entry.fill_row(row, node, live, max_prio)
                if n > entry.mask.shape[1]:
                    grown = n
                    break
            if not grown:
                break
            v, entry = _victim_bucket(grown), None
        entry.stale = set()
        entry.used_at = next(_use_clock)
        if shared:
            ct.victim_cache[max_prio] = entry
    if not exclude_ids:
        return entry
    # the plan's own victims go: a private copy, their rows closed up (the
    # kernel reads a row's candidates as one padded run). The instances
    # they held are not free: the plan's placement there holds them
    out = entry.carried_over(())
    out.own()
    rows = set()
    for aid in exclude_ids:
        a = snap.alloc_by_id(aid)
        if a is not None and a.node_id in ct.node_row:
            rows.add(ct.node_row[a.node_id])
    for row in rows:
        keep = [
            j for j, aid in enumerate(entry.ids[row]) if aid not in exclude_ids
        ]
        k = len(keep)
        out.res[row], out.prio[row], out.mask[row] = 0.0, 0, False
        out.dev[row] = 0
        out.res[row, :k] = entry.res[row, keep]
        out.prio[row, :k] = entry.prio[row, keep]
        out.dev[row, :k] = entry.dev[row, keep]
        out.mask[row, :k] = True
        out.ids[row] = [entry.ids[row][j] for j in keep]
    return out


def rank_preemption_nodes(
    ct, snap, job, ask_vec, eligible, exclude_ids=frozenset(),
    ask_devices: int = 0,
):
    """One [N, V] device pass ranking every node by post-preemption fit ×
    preemption penalty; returns every feasible node row, best first, and
    the kernel's score of every row (f32[N], -inf where infeasible). The
    exact victim set per node is then chosen host-side by
    scheduler/preempt_host.select_victims (reference-exact greedy with
    maxParallel/ports/devices): the kernel orders the fleet once per
    group, the host pays exactness only on the rows a failed instance
    actually tries. The whole order is returned, not a shortlist: a group
    of any count walks it until its instances are placed or no node is
    left (under distinct_hosts every placement strikes a row).

    Spans, below whatever the caller has open: ``preempt.victims`` (the
    host walk over every node's allocations and the padding) and
    ``preempt.rank`` (upload, the kernel's dispatch, the pull)."""
    with _tracer.span("preempt.victims") as sp:
        t = victim_tensors(ct, snap, job, exclude_ids=exclude_ids)
        if sp is not None:
            sp.tags.update(
                nodes=int(ct.num_nodes),
                victims=int(t.mask.sum()),
                v_bucket=int(t.mask.shape[1]),
            )
    if not t.mask.any():
        return [], np.full(ct.padded_n, -np.inf, dtype=np.float32)
    _metrics.incr("nomad.preempt.rank_passes")
    with _tracer.span("preempt.rank") as sp:
        _best, feasible, _k, _net, _order, score = choose_preemption_node_kernel(
            jnp.asarray(ct.capacity),
            jnp.asarray(ct.used),
            jnp.asarray(ask_vec),
            jnp.asarray(eligible),
            jnp.asarray(t.res),
            jnp.asarray(t.prio),
            jnp.asarray(t.mask),
            jnp.asarray(t.dev),
            # a group that asks for no device lacks none anywhere
            jnp.asarray(np.maximum(ask_devices - t.dev_free, 0)),
        )
        feasible = np.asarray(feasible)
        score = np.asarray(score)
        rows = np.flatnonzero(feasible)
        if sp is not None:
            sp.tags["feasible"] = int(rows.size)
    return rows[np.argsort(-score[rows], kind="stable")].tolist(), score


def find_preemptions(ct, snap, job, ask_vec, eligible, exclude_ids=frozenset()):
    """Host driver: one device pass, then map the chosen node's victim
    set back to allocation ids. Returns (node_row, [alloc ids]) or
    (None, [])."""
    victim_res, victim_prio, victim_mask, victim_ids = build_victim_tensors(
        ct, snap, job, exclude_ids=exclude_ids
    )
    if not victim_mask.any():
        return None, []
    best, feasible, k, net, order, _score = choose_preemption_node_kernel(
        jnp.asarray(ct.capacity),
        jnp.asarray(ct.used),
        jnp.asarray(ask_vec),
        jnp.asarray(eligible),
        jnp.asarray(victim_res),
        jnp.asarray(victim_prio),
        jnp.asarray(victim_mask),
        jnp.zeros(victim_prio.shape, dtype=jnp.int32),
        jnp.zeros(victim_prio.shape[0], dtype=jnp.int32),
    )
    best = int(best)
    if not bool(np.asarray(feasible)[best]):
        return None, []
    kk = int(np.asarray(k)[best])
    node_order = np.asarray(order)[best]
    ids = []
    for idx in node_order[:kk]:
        if idx < len(victim_ids[best]):
            ids.append(victim_ids[best][idx])
    return best, ids
