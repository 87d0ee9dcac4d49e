"""The store's per-node live-usage index (``node_usage``) and the plan
applier's fit check that reads it: on seeded random sequences of every
write path the index equals a recomputation over ``allocs_by_node``, and
on seeded plans ``evaluate_plan`` / ``evaluate_merged_plan`` give exactly
what the exact walk (``evaluate_node_plan`` / ``_evaluate_node_members``
on every node) gives. One parametrised test each, a case a seed."""

import copy

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.broker import plan_apply
from nomad_tpu.state import StateStore
from nomad_tpu.state.snapshot import restore_snapshot, save_snapshot
from nomad_tpu.structs import (
    ComparableResources,
    NodeResources,
    Plan,
    PlanResult,
    allocs_fit,
)
from nomad_tpu.structs.network import AllocatedNetwork, AllocatedPort
from nomad_tpu.structs.node import DrainStrategy
from nomad_tpu.structs.resources import (
    AllocatedDeviceResource,
    NodeDeviceInstance,
    NodeDeviceResource,
    RequestedDevice,
)

SEEDS = range(8)
N_NODES = 10
DRAINING, INELIGIBLE, DOWN, GPU = 0, 1, 2, 3
MISSING = "node-missing"
STATUSES = ("pending", "running", "running", "complete", "failed")


def _node_id(i: int) -> str:
    return f"node-{i:02d}"


def _recount(snap, node_id: str) -> tuple:
    """The row recomputed from the allocations ``allocs_by_node`` reads."""
    live = [a for a in snap.allocs_by_node(node_id) if not a.terminal_status()]
    sums = tuple(
        sum(getattr(a.comparable_resources(), d) for a in live)
        for d in ("cpu", "memory_mb", "disk_mb", "bandwidth_mbits")
    )
    walk = sum(
        1 for a in live
        if a.allocated_networks or a.allocated_devices or a.device_asks()
    )
    return sums + (walk,)


class World:
    """A seeded fleet: a draining, an ineligible, a down and a GPU node
    among open ones, a service job and a GPU job, and allocations of every
    status on every node, some holding ports or device instances."""

    def __init__(self, seed: int):
        self.rng = np.random.default_rng(seed)
        self.store = StateStore()
        self.index = 0
        for i in range(N_NODES):
            node = mock.node(id=_node_id(i))
            node.node_resources = NodeResources(
                cpu=int(self.rng.choice([3000, 6000])),
                memory_mb=6144, disk_mb=20000,
            )
            if i == GPU:
                node.node_resources.devices.append(NodeDeviceResource(
                    vendor="nvidia", type="gpu", name="k80",
                    instances=[NodeDeviceInstance(id=f"k80-{k}")
                               for k in range(2)],
                ))
            if i == DOWN:
                node.status = "down"
            self.store.upsert_node(self.next(), node)
        self.store.update_node_drain(
            self.next(), _node_id(DRAINING), DrainStrategy())
        self.store.update_node_eligibility(
            self.next(), _node_id(INELIGIBLE), "ineligible")
        self.svc = mock.job()
        self.gpu = mock.job()
        self.gpu.id = "gpu"
        self.gpu.task_groups[0].tasks[0].resources.devices.append(
            RequestedDevice(name="gpu", count=1))
        for job in (self.svc, self.gpu):
            self.store.upsert_job(self.next(), job)
        self.store.upsert_allocs(self.next(), [
            self.alloc(_node_id(i))
            for i in range(N_NODES) for _ in range(self.rng.integers(0, 5))
        ])

    def next(self) -> int:
        self.index += 1
        return self.index

    def alloc(self, node_id: str, status=None, kind=None):
        """A new allocation: of the service job (normalized, as plans ship,
        or not), with ports, or of the GPU job, with or without instances."""
        rng = self.rng
        kind = kind or rng.choice(
            ["svc", "bare", "net", "gpu", "gpu_held"],
            p=[0.72, 0.1, 0.06, 0.06, 0.06])
        a = mock.alloc(self.gpu if kind.startswith("gpu") else self.svc)
        a.id = f"alloc-{int(rng.integers(1 << 62)):016x}"  # seeded order
        a.node_id = node_id
        a.client_status = status or str(rng.choice(STATUSES))
        if rng.random() < 0.1:
            a.desired_status = "stop"
        a.resources = ComparableResources(
            cpu=int(rng.integers(1, 6)) * 100,
            memory_mb=int(rng.integers(1, 6)) * 128,
            disk_mb=int(rng.integers(0, 3)) * 300,
            bandwidth_mbits=int(rng.integers(0, 3)) * 10,
        )
        if kind == "bare":
            a.job = None  # re-attached from the store when written
        elif kind == "net":
            port = int(rng.integers(20000, 20004))
            a.allocated_networks = [AllocatedNetwork(
                mbits=10, reserved_ports=[AllocatedPort("http", port)])]
        elif kind == "gpu_held":
            a.allocated_devices = [AllocatedDeviceResource(
                vendor="nvidia", type="gpu", name="k80",
                device_ids=[f"k80-{int(rng.integers(0, 2))}"])]
        return a

    def stored(self, k: int) -> list:
        allocs = sorted(self.store.allocs(), key=lambda a: a.id)
        if not allocs:
            return []
        pick = self.rng.choice(len(allocs), size=min(k, len(allocs)),
                               replace=False)
        return [allocs[i] for i in pick]

    def any_node(self) -> str:
        return _node_id(int(self.rng.integers(0, N_NODES)))


def _random_result(w: World) -> PlanResult:
    """A committed plan's result: stops and evictions (some of allocations
    already terminal, some filed under another node), in-place updates,
    moves and placements."""
    res = PlanResult()
    for a in w.stored(4):
        stop = a.copy_for_update()
        stop.desired_status = "stop"
        node = a.node_id if w.rng.random() < 0.8 else w.any_node()
        res.node_update.setdefault(node, []).append(stop)
    for a in w.stored(2):
        ev = a.copy_for_update()
        ev.desired_status = "evict"
        res.node_preemptions.setdefault(a.node_id, []).append(ev)
    for a in w.stored(2):
        upd = a.copy_for_update()
        upd.resources = ComparableResources(
            cpu=a.resources.cpu + 100, memory_mb=a.resources.memory_mb)
        if w.rng.random() < 0.3:
            upd.node_id = w.any_node()  # a move
        res.node_allocation.setdefault(upd.node_id, []).append(upd)
    for _ in range(int(w.rng.integers(1, 6))):
        a = w.alloc(w.any_node(), status="pending")
        res.node_allocation.setdefault(a.node_id, []).append(a)
    return res


def _write(w: World, kind: str, tmp_path):
    s = w.store
    if kind == "place":
        s.upsert_allocs(w.next(), [
            w.alloc(w.any_node()) for _ in range(int(w.rng.integers(1, 5)))])
    elif kind == "plan":
        s.upsert_plan_results(w.next(), _random_result(w), "e")
    elif kind == "client":
        ups = []
        for a in w.stored(4):
            u = copy.copy(a)
            u.client_status = str(w.rng.choice(
                ["running", "complete", "failed"]))
            ups.append(u)
        s.update_allocs_from_client(w.next(), ups)
    elif kind == "delete":
        s.delete_allocs(w.next(), [a.id for a in w.stored(2)] + ["no-such"])
    elif kind == "move":
        moved = []
        for a in w.stored(2):
            m = copy.copy(a)
            m.node_id = w.any_node()
            moved.append(m)
        s.upsert_allocs(w.next(), moved)
    elif kind == "stamp":
        ids = [a.id for a in w.stored(3)]
        s.update_alloc_health(w.next(), ids[:1], ids[1:2])
        s.update_allocs_desired_transition(
            w.next(), {i: mock.alloc().desired_transition for i in ids[2:]})
    elif kind == "restore":
        path = str(tmp_path / "state.snap")
        save_snapshot(s, path)
        w.store = restore_snapshot(path)
        w.index = w.store.latest_index


WRITES = ("place", "plan", "client", "delete", "move", "stamp", "restore")


@pytest.mark.parametrize("seed", SEEDS)
def test_the_index_equals_a_recount_over_every_write_path(seed, tmp_path):
    w = World(seed)
    nodes = [_node_id(i) for i in range(N_NODES)] + [MISSING]
    for step in range(40):  # each path once, then in a seeded order
        kind = WRITES[step] if step < len(WRITES) else str(
            w.rng.choice(WRITES))
        before = w.store.snapshot()
        rows = {n: before.node_usage(n) for n in nodes}
        _write(w, kind, tmp_path)
        for n in nodes:
            assert w.store.node_usage(n) == _recount(w.store, n), (kind, n)
            assert before.node_usage(n) == rows[n] == _recount(
                before, n), (kind, n)


# -- the indexed check against the exact walk --------------------------------


def _fill_to_the_edge(w: World, plan: Plan, node_id: str, over: int):
    """Size the node's last live placement so the plan leaves the node
    exactly full on cpu (``over`` 0) or ``over`` MHz past it, by the
    walk's own sum."""
    node = w.store.node_by_id(node_id)
    placed = plan.node_allocation[node_id]
    last = placed[-1]
    if node is None or last.terminal_status():
        return
    removed = {a.id for a in plan.node_update.get(node_id, ())} | {
        a.id for a in plan.node_preemptions.get(node_id, ())}
    new_ids = {a.id for a in placed}
    proposed = [a for a in w.store.allocs_by_node(node_id)
                if a.id not in removed and a.id not in new_ids] + placed
    _ok, _dim, used = allocs_fit(node, proposed)
    cap = node.node_resources.cpu - node.reserved.cpu
    last.resources = ComparableResources(
        cpu=max(0, last.resources.cpu + cap - used.cpu + over),
        memory_mb=last.resources.memory_mb,
        disk_mb=last.resources.disk_mb,
        bandwidth_mbits=last.resources.bandwidth_mbits,
    )


def _random_plan(w: World, nodes, normalized=False, stops=True) -> Plan:
    """Stops (some already terminal, some of another node's allocations),
    evictions, in-place updates and placements on ``nodes``, some of them
    with ports or devices; half the placing nodes left exactly full or
    one MHz over."""
    plan = Plan(eval_id=f"e{int(w.rng.integers(1 << 30))}")
    for node_id in nodes:
        held = sorted(w.store.allocs_by_node(node_id), key=lambda a: a.id)
        if stops and w.rng.random() < 0.6:
            # some of another node's, some already terminal
            for a in w.stored(int(w.rng.integers(1, 3))) + held[:1]:
                stop = a.copy_for_update()
                stop.desired_status = "stop"
                plan.node_update.setdefault(node_id, []).append(stop)
        if stops and held and w.rng.random() < 0.3:
            plan.append_preempted_alloc(held[-1], "x")
        placed = []
        if not normalized and held and w.rng.random() < 0.3:
            upd = held[0].copy_for_update()  # in place
            upd.resources = ComparableResources(
                cpu=held[0].resources.cpu + 200,
                memory_mb=held[0].resources.memory_mb)
            placed.append(upd)
        for _ in range(int(w.rng.integers(0 if placed else 1, 4))):
            a = w.alloc(node_id, status="pending",
                        kind="svc" if normalized else None)
            if normalized:
                a.job = None
            placed.append(a)
        plan.node_allocation[node_id] = placed
        if w.rng.random() < 0.5:
            _fill_to_the_edge(w, plan, node_id, int(w.rng.integers(0, 2)))
    return plan


def _ids(buckets) -> dict:
    return {n: [a.id for a in allocs] for n, allocs in buckets.items()}


def _same(got, want):
    assert _ids(got.node_allocation) == _ids(want.node_allocation)
    assert _ids(got.node_update) == _ids(want.node_update)
    assert _ids(got.node_preemptions) == _ids(want.node_preemptions)
    assert got.rejected_nodes == want.rejected_nodes
    assert got.refresh_index == want.refresh_index


def _index_may_judge(w: World, node_id: str, placed) -> bool:
    """The node is there, up and open, holds no live allocation with
    ports or devices, and gets none."""
    node = w.store.node_by_id(node_id)
    return (
        node is not None and node.status != "down" and node.drain is None
        and node.scheduling_eligibility == "eligible"
        and _recount(w.store, node_id)[4] == 0
        and not any(a.allocated_networks or a.allocated_devices
                    or a.device_asks() for a in placed)
    )


def _merged_index_admits(w: World, node_id: str, members) -> bool:
    """Every member only places normalized, networkless, deviceless new
    allocations on an open node whose union of asks fits on all four
    dimensions."""
    if any(node_id in mp.node_update or node_id in mp.node_preemptions
           for mp in members):
        return False
    new = [a for mp in members for a in mp.node_allocation.get(node_id, ())]
    held = w.store.node_alloc_ids(node_id)
    if not _index_may_judge(w, node_id, new) or any(
            a.id in held or a.job is not None for a in new):
        return False
    node = w.store.node_by_id(node_id)
    usage = _recount(w.store, node_id)
    cap = node.node_resources
    free = [cap.cpu - node.reserved.cpu - usage[0],
            cap.memory_mb - node.reserved.memory_mb - usage[1],
            cap.disk_mb - node.reserved.disk_mb - usage[2],
            cap.bandwidth_mbits() - usage[3]]
    for a in new:
        r = a.comparable_resources()
        for k, v in enumerate((r.cpu, r.memory_mb, r.disk_mb,
                               r.bandwidth_mbits)):
            free[k] -= v
    return min(free) >= 0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_indexed_check_gives_the_walks_answer(seed, monkeypatch):
    w = World(seed)
    nodes = [_node_id(i) for i in range(N_NODES)] + [MISSING]
    for _ in range(4):
        pick = [n for n in nodes if w.rng.random() < 0.7] or nodes[:1]
        plan = _random_plan(w, pick)
        got, indexed, walked = plan_apply._evaluate_plan(w.store, plan)
        with monkeypatch.context() as m:
            m.setattr(plan_apply, "_indexed_fits", lambda snap, ch: {})
            want = plan_apply.evaluate_plan(w.store, plan)
        _same(got, want)
        expect = sum(
            _index_may_judge(w, n, placed)
            for n, placed in plan.node_allocation.items()
        )
        assert (indexed, walked) == (
            expect, len(plan.node_allocation) - expect)

        members = [
            _random_plan(w, [n for n in pick if w.rng.random() < 0.6],
                         normalized=k > 0, stops=k == 0)
            for k in range(3)
        ]
        got_m, indexed, walked = plan_apply._evaluate_merged_plan(
            w.store, members)
        with monkeypatch.context() as m:
            m.setattr(plan_apply, "_fast_path_slack", lambda *a: None)
            want_m = plan_apply.evaluate_merged_plan(w.store, members)
        for g, want_r in zip(got_m, want_m):
            _same(g, want_r)
        placing = {n for mp in members for n in mp.node_allocation}
        expect = sum(
            _merged_index_admits(
                w, n, [mp for mp in members if n in mp.node_allocation
                       or n in mp.node_update or n in mp.node_preemptions])
            for n in placing
        )
        assert (indexed, walked) == (expect, len(placing) - expect)
        # commit one so the next round meets a store with more history
        w.store.upsert_plan_results(w.next(), got, plan.eval_id)
