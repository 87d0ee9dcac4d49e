"""Preemption tests — the analog of scheduler/preemption_test.go: priority
delta eligibility, minimal low-priority victim selection, and end-to-end
eviction through the plan applier."""

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.device import flatten_cluster
from nomad_tpu.device.preempt import build_victim_tensors, find_preemptions
from nomad_tpu.scheduler import Harness
from nomad_tpu.state import StateStore, SchedulerConfiguration
from nomad_tpu.structs import ALLOC_DESIRED_EVICT
from nomad_tpu.structs.resources import NodeResources


def cluster_with_load(n_nodes, jobs_priorities, per_node):
    """Fill every node with `per_node` allocs from jobs at given priorities."""
    s = StateStore()
    nodes = [mock.node() for _ in range(n_nodes)]
    for i, n in enumerate(nodes):
        s.upsert_node(i + 1, n)
    idx = 100
    filler_jobs = []
    for prio in jobs_priorities:
        j = mock.job(priority=prio)
        j.task_groups[0].tasks[0].resources.cpu = 1800
        j.task_groups[0].tasks[0].resources.memory_mb = 3500
        filler_jobs.append(j)
        s.upsert_job(idx, j)
        idx += 1
    allocs = []
    for n in nodes:
        for k in range(per_node):
            j = filler_jobs[k % len(filler_jobs)]
            allocs.append(mock.alloc(j, n))
    s.upsert_allocs(idx, allocs)
    return s, nodes, filler_jobs


class TestVictimSelection:
    def test_priority_delta_rule(self):
        """Only victims at priority ≤ preemptor − 10 are candidates
        (preemption.go:663-697)."""
        s, nodes, _ = cluster_with_load(1, [45], 2)
        snap = s.snapshot()
        ct = flatten_cluster(snap)
        high = mock.job(priority=50)  # delta 5 < 10: not allowed
        _, _, mask, _ = build_victim_tensors(ct, snap, high)
        assert not mask.any()
        higher = mock.job(priority=60)  # delta 15: allowed
        _, prio, mask, _ = build_victim_tensors(ct, snap, higher)
        assert mask.sum() == 2

    def test_minimal_lowest_priority_victims(self):
        """Victims are taken lowest-priority-first and only as many as
        needed (PreemptForTaskGroup :198-265)."""
        # node: 3900 cpu cap; two fillers at 1800 → used 3600, free 300
        s, nodes, fillers = cluster_with_load(1, [20, 40], 2)
        snap = s.snapshot()
        ct = flatten_cluster(snap)
        job = mock.job(priority=70)
        ask = np.array([1000.0, 256.0, 300.0, 0.0], dtype=np.float32)
        eligible = ct.ready.copy()
        row, victim_ids = find_preemptions(ct, snap, job, ask, eligible)
        assert row == 0
        assert len(victim_ids) == 1  # one eviction frees 1800 ≥ 700 shortfall
        victim = snap.alloc_by_id(victim_ids[0])
        assert victim.job.priority == 20  # the lowest-priority one

    def test_no_preemption_when_infeasible(self):
        """Even evicting everything can't fit an oversized ask."""
        s, nodes, _ = cluster_with_load(1, [20], 2)
        snap = s.snapshot()
        ct = flatten_cluster(snap)
        job = mock.job(priority=70)
        ask = np.array([99999.0, 256.0, 300.0, 0.0], dtype=np.float32)
        row, victims = find_preemptions(ct, snap, job, ask, ct.ready.copy())
        assert row is None and victims == []


class TestPreemptionEndToEnd:
    def test_high_priority_job_preempts(self):
        h = Harness()
        h.store.set_scheduler_config(
            1, SchedulerConfiguration(preemption_service_enabled=True)
        )
        nodes = [mock.node() for _ in range(2)]
        for i, n in enumerate(nodes):
            h.store.upsert_node(i + 2, n)
        # fill the cluster with low-priority ballast
        low = mock.job(priority=10)
        low.task_groups[0].count = 4
        low.task_groups[0].tasks[0].resources.cpu = 1800
        low.task_groups[0].tasks[0].resources.memory_mb = 3500
        h.store.upsert_job(10, low)
        h.process(mock.eval_for(low))
        assert (
            len(
                [
                    a
                    for a in h.store.allocs_by_job(low.namespace, low.id)
                    if not a.terminal_status()
                ]
            )
            == 4
        )
        # high-priority job arrives; cluster is full
        high = mock.job(priority=90)
        high.task_groups[0].count = 1
        high.task_groups[0].tasks[0].resources.cpu = 2000
        high.task_groups[0].tasks[0].resources.memory_mb = 1024
        h.store.upsert_job(20, high)
        h.process(mock.eval_for(high))
        placed = [
            a
            for a in h.store.allocs_by_job(high.namespace, high.id)
            if not a.terminal_status()
        ]
        assert len(placed) == 1
        assert placed[0].preempted_allocations
        evicted = [
            h.store.alloc_by_id(vid) for vid in placed[0].preempted_allocations
        ]
        assert all(v.desired_status == ALLOC_DESIRED_EVICT for v in evicted)
        assert all(v.preempted_by_allocation == placed[0].id for v in evicted)

    def test_preemption_creates_victim_job_evals(self):
        """The applier rolls follow-up evals for preempted jobs
        (plan_apply.go PreemptionEvals) so victims re-place elsewhere."""
        h = Harness()
        h.store.set_scheduler_config(
            1, SchedulerConfiguration(preemption_service_enabled=True)
        )
        h.store.upsert_node(2, mock.node())
        low = mock.job(priority=10)
        low.task_groups[0].count = 2
        low.task_groups[0].tasks[0].resources.cpu = 1800
        low.task_groups[0].tasks[0].resources.memory_mb = 3500
        h.store.upsert_job(10, low)
        h.process(mock.eval_for(low))
        high = mock.job(priority=90)
        high.task_groups[0].count = 1
        high.task_groups[0].tasks[0].resources.cpu = 2000
        h.store.upsert_job(20, high)
        h.process(mock.eval_for(high))
        followups = [
            e
            for e in h.created_evals
            if e.triggered_by == "preemption" and e.job_id == low.id
        ]
        assert len(followups) == 1

    def test_preemption_disabled_blocks_instead(self):
        h = Harness()  # default config: service preemption disabled
        n = mock.node()
        h.store.upsert_node(2, n)
        low = mock.job(priority=10)
        low.task_groups[0].count = 2
        low.task_groups[0].tasks[0].resources.cpu = 1800
        low.task_groups[0].tasks[0].resources.memory_mb = 3500
        h.store.upsert_job(10, low)
        h.process(mock.eval_for(low))
        high = mock.job(priority=90)
        high.task_groups[0].count = 1
        high.task_groups[0].tasks[0].resources.cpu = 2000
        h.store.upsert_job(20, high)
        h.process(mock.eval_for(high))
        placed = [
            a
            for a in h.store.allocs_by_job(high.namespace, high.id)
            if not a.terminal_status()
        ]
        assert placed == []
        assert len(h.created_evals) == 1  # blocked eval instead


def _full_fleet(n_nodes, per_node=2, cpu=1800, memory_mb=3500, priority=10):
    """A harness whose ``n_nodes`` nodes are full of ``priority`` ballast,
    service preemption on."""
    h = Harness()
    h.store.set_scheduler_config(
        1, SchedulerConfiguration(preemption_service_enabled=True)
    )
    for i in range(n_nodes):
        h.store.upsert_node(i + 2, mock.node())
    low = mock.job(priority=priority)
    low.task_groups[0].count = n_nodes * per_node
    low.task_groups[0].tasks[0].resources.cpu = cpu
    low.task_groups[0].tasks[0].resources.memory_mb = memory_mb
    h.store.upsert_job(1000, low)
    h.process(mock.eval_for(low))
    assert len(_live(h.store, low)) == n_nodes * per_node
    return h, low


def _live(store, job):
    return [
        a for a in store.allocs_by_job(job.namespace, job.id)
        if not a.terminal_status()
    ]


class TestPreemptingGroupOfAnySize:
    """Every instance that can place by evicting does, whatever the
    group's count: the ranking covers the group, not its best sixteen."""

    @pytest.mark.parametrize("count", [24, 40])
    def test_distinct_hosts_group_places_every_instance(self, count):
        from nomad_tpu.structs.job import Constraint
        from nomad_tpu.utils.metrics import global_metrics

        h, low = _full_fleet(64)
        before = dict(global_metrics.snapshot()["counters"])
        high = mock.job(priority=90)
        high.task_groups[0].count = count
        high.task_groups[0].tasks[0].resources.cpu = 2000
        high.task_groups[0].tasks[0].resources.memory_mb = 1024
        high.constraints.append(Constraint(operand="distinct_hosts"))
        h.store.upsert_job(2000, high)
        h.process(mock.eval_for(high))
        placed = _live(h.store, high)
        assert len(placed) == count
        assert len({a.node_id for a in placed}) == count
        assert all(a.preempted_allocations for a in placed)
        # one ranking for the whole group, a score on every placement
        after = global_metrics.snapshot()["counters"]
        delta = {
            k: after.get(k, 0) - before.get(k, 0)
            for k in ("nomad.preempt.rank_passes", "nomad.preempt.placements",
                      "nomad.preempt.unplaced")
        }
        assert delta == {
            "nomad.preempt.rank_passes": 1,
            "nomad.preempt.placements": count,
            "nomad.preempt.unplaced": 0,
        }
        for a in placed:
            assert 0.0 < a.metrics.scores[f"{a.node_id}.score"] <= 1.0

    def test_group_larger_than_the_fleet_leaves_a_blocked_eval(self):
        from nomad_tpu.structs.job import Constraint

        h, _low = _full_fleet(8)
        high = mock.job(priority=90)
        high.task_groups[0].count = 12
        high.task_groups[0].tasks[0].resources.cpu = 2000
        high.task_groups[0].tasks[0].resources.memory_mb = 1024
        high.constraints.append(Constraint(operand="distinct_hosts"))
        h.store.upsert_job(2000, high)
        h.process(mock.eval_for(high))
        assert len(_live(h.store, high)) == 8
        blocked = [e for e in h.created_evals if e.status == "blocked"]
        assert len(blocked) == 1
        assert blocked[0].queued_allocations == {"web": 4}


def _gpu_fleet(n_nodes, gpus=2):
    """Nodes with ``gpus`` instances each, every instance held by a
    priority-20 holder."""
    from nomad_tpu.structs.resources import (
        NodeDeviceInstance,
        NodeDeviceResource,
        RequestedDevice,
    )

    h = Harness()
    h.store.set_scheduler_config(
        1, SchedulerConfiguration(preemption_service_enabled=True)
    )
    for i in range(n_nodes):
        n = mock.node()
        n.node_resources.devices = [NodeDeviceResource(
            vendor="nvidia", type="gpu", name="a100",
            instances=[NodeDeviceInstance(id=f"g{i}-{k}") for k in range(gpus)],
        )]
        n.compute_class()
        h.store.upsert_node(i + 2, n)

    def gpu_job(priority, count, cpu):
        j = mock.job(priority=priority)
        j.task_groups[0].count = count
        r = j.task_groups[0].tasks[0].resources
        r.cpu, r.memory_mb = cpu, 512
        r.devices = [RequestedDevice(name="nvidia/gpu", count=1)]
        return j

    low = gpu_job(20, n_nodes * gpus, 400)
    h.store.upsert_job(1000, low)
    h.process(mock.eval_for(low))
    assert len(_live(h.store, low)) == n_nodes * gpus
    return h, low, gpu_job


def _held_instances(store):
    held = []
    for a in store.allocs():
        if not a.terminal_status():
            for ad in a.allocated_devices or ():
                held.extend((a.node_id, i) for i in ad.device_ids)
    return held


class TestPreemptingForDeviceInstances:
    def test_every_held_instance_is_taken_over(self):
        """Every GPU instance is held by priority 20; a priority-80 job of
        one GPU an instance takes them all, two on each node, and no
        instance ends up with two holders."""
        h, low, gpu_job = _gpu_fleet(4)
        high = gpu_job(80, 8, 500)
        h.store.upsert_job(2000, high)
        h.process(mock.eval_for(high))
        placed = _live(h.store, high)
        assert len(placed) == 8
        assert _live(h.store, low) == []
        held = _held_instances(h.store)
        assert len(held) == len(set(held)) == 8

    def test_victims_that_free_no_instance_are_rolled_back_once(
        self, monkeypatch
    ):
        """Victims that free cpu but no instance: the eviction leaves the
        plan again, the placement goes to the next node, and the node is
        offered again to the next instance."""
        from nomad_tpu.scheduler import preempt_host
        from nomad_tpu.utils.metrics import global_metrics

        h, low, gpu_job = _gpu_fleet(2)
        # cpu-only ballast beside the holders, as preemptible as they are
        ballast = mock.job(priority=20)
        ballast.task_groups[0].count = 2
        ballast.task_groups[0].tasks[0].resources.cpu = 400
        ballast.task_groups[0].tasks[0].resources.memory_mb = 512
        h.store.upsert_job(1500, ballast)
        h.process(mock.eval_for(ballast))
        assert len(_live(h.store, ballast)) == 2
        real = preempt_host.select_victims
        calls = []

        def first_call_picks_ballast(ct, snap, job, tg, ask, row, **kw):
            calls.append(row)
            if len(calls) == 1:
                return [
                    a.id for a in snap.allocs_by_node(ct.node_ids[row])
                    if a.job_id == ballast.id
                ][:1]
            return real(ct, snap, job, tg, ask, row, **kw)

        monkeypatch.setattr(
            preempt_host, "select_victims", first_call_picks_ballast
        )
        before = global_metrics.snapshot()["counters"].get(
            "nomad.preempt.device_rollbacks", 0
        )
        high = gpu_job(80, 4, 500)
        h.store.upsert_job(2000, high)
        h.process(mock.eval_for(high))
        after = global_metrics.snapshot()["counters"].get(
            "nomad.preempt.device_rollbacks", 0
        )
        assert after - before == 1
        assert calls[0] in calls[1:]  # the node was offered again
        placed = _live(h.store, high)
        assert len(placed) == 4
        held = _held_instances(h.store)
        assert len(held) == len(set(held)) == 4
        # the ballast named by the rolled-back attempt stays
        assert len(_live(h.store, ballast)) == 2

    def test_two_plans_on_one_snapshot_cannot_share_an_instance(self):
        """The applier's device accounting is by instance: a plan that
        hands out an instance a live allocation holds is refused."""
        from nomad_tpu.structs.resources import (
            AllocatedDeviceResource,
            allocs_fit,
        )

        h, low, _gpu_job = _gpu_fleet(1)
        node = next(iter(h.store.nodes()))
        live = _live(h.store, low)
        assert allocs_fit(node, live, check_devices=True)[0]
        twin = live[0].copy_for_update()
        twin.id = "twin"
        twin.allocated_devices = [AllocatedDeviceResource(
            vendor="nvidia", type="gpu", name="a100",
            device_ids=list(live[1].allocated_devices[0].device_ids),
        )]
        ok, dim, _used = allocs_fit(
            node, [live[1], twin], check_devices=True
        )
        assert not ok and "instance" in dim


class TestEvictedBatchAllocationReturns:
    def test_batch_victim_is_placed_again_after_a_deregistration(self):
        """A batch job's failed placement leaves a blocked eval as a
        service job's does (generic_sched.go:193-212), and the stop a
        deregistration commits unblocks it."""
        from nomad_tpu.server import Server, ServerConfig

        s = Server(ServerConfig(num_workers=1))
        s.establish_leadership()
        try:
            s.store.set_scheduler_config(
                s.store.latest_index + 1,
                SchedulerConfiguration(preemption_service_enabled=True),
            )
            for _ in range(2):
                s.register_node(mock.node())
            low = mock.job(priority=20)
            low.type = "batch"
            low.task_groups[0].count = 4
            low.task_groups[0].tasks[0].resources.cpu = 1800
            low.task_groups[0].tasks[0].resources.memory_mb = 3500
            s.register_job(low)
            assert s.wait_for_evals(timeout=15)
            assert len(_live(s.store, low)) == 4
            high = mock.job(priority=80)
            high.task_groups[0].count = 1
            high.task_groups[0].tasks[0].resources.cpu = 2000
            high.task_groups[0].tasks[0].resources.memory_mb = 1024
            s.register_job(high)
            assert s.wait_for_evals(timeout=15)
            assert len(_live(s.store, high)) == 1
            assert len(_live(s.store, low)) == 3
            # the victim's follow-up eval found no room and is parked
            assert s.blocked_evals.get_blocked(low.namespace, low.id)
            s.deregister_job(high.namespace, high.id)
            assert s.wait_for_evals(timeout=15)
            assert len(_live(s.store, low)) == 4
            assert s.blocked_evals.blocked_count() == 0
        finally:
            s.shutdown()


class TestOverlayReleasesAStopOnce:
    """``SharedOverlay.release`` takes a committed stop off the frozen
    base, unless the base was frozen from tensors that saw the stop."""

    @staticmethod
    def _overlay(frozen_at):
        from types import SimpleNamespace

        from nomad_tpu.server.overlay import SharedOverlay

        ct = SimpleNamespace(
            used=np.full((2, 4), 10.0, dtype=np.float32),
            layout_gen=3, index=frozen_at, nodes=None,
        )
        ov = SharedOverlay()
        ov.add_delta(ct, np.array([0]), np.zeros(4, dtype=np.float32))
        return ov

    @pytest.mark.parametrize(
        "frozen_at, stop_at, taken_off",
        [
            (5, 9, True),  # the base predates the stop
            (9, 9, False),  # the snapshot was taken after the raft apply
            (12, 9, False),
        ],
    )
    def test_base_frozen_after_the_stop_is_left_alone(
        self, frozen_at, stop_at, taken_off
    ):
        ov = self._overlay(frozen_at)
        freed = {"n1": np.array([4.0, 4.0, 0.0, 0.0], dtype=np.float32)}
        assert ov.holds_base_before(3, stop_at) is taken_off
        ov.release({"n1": 1}, 3, freed, stop_at)
        want = 6.0 if taken_off else 10.0
        assert ov._base[1, 0] == want and ov._base[0, 0] == 10.0
        # a stop after both counts
        ov.release({"n1": 1}, 3, freed, max(frozen_at, stop_at) + 1)
        assert ov._base[1, 0] == want - 4.0

    def test_another_layout_or_no_base_is_left_alone(self):
        from nomad_tpu.server.overlay import SharedOverlay

        freed = {"n1": np.ones(4, dtype=np.float32)}
        SharedOverlay().release({"n1": 1}, 3, freed, 9)  # no epoch running
        ov = self._overlay(5)
        ov.release({"n1": 1}, 4, freed, 9)
        assert ov._base[1, 0] == 10.0


class TestVictimTensorsAcrossGenerations:
    def test_carried_tables_copy_on_first_write_and_stay_bounded(self):
        from nomad_tpu.device import preempt
        from nomad_tpu.device.cache import DeviceStateCache

        s, nodes, fillers = cluster_with_load(4, [20, 30], 2)
        cache = DeviceStateCache()
        ct = cache.tensors(s.snapshot())
        job = mock.job(priority=80)
        first = preempt.victim_tensors(ct, s.snapshot(), job)
        assert first.mask.sum() == 8 and not first.borrowed
        for prio in (50, 55, 60, 65, 70):  # more ceilings than are kept
            preempt.victim_tensors(ct, s.snapshot(), mock.job(priority=prio))
        # a stop on one node: the next generation borrows the arrays and
        # marks that row stale; nothing is copied until a ranking writes
        gone = s.snapshot().allocs_by_node(nodes[0].id)[0].copy_for_update()
        gone.desired_status = "stop"
        gone.client_status = "complete"
        s.upsert_allocs(s.latest_index + 1, [gone])
        ct2 = cache.tensors(s.snapshot())
        assert len(ct2.victim_cache) == preempt.VICTIM_CEILINGS_KEPT
        assert 70 not in ct2.victim_cache  # job 80's, the least recent
        carried = ct2.victim_cache[60]
        assert carried.borrowed and carried.stale == {ct2.node_row[nodes[0].id]}
        before = ct.victim_cache[60].mask.copy()
        fresh = preempt.victim_tensors(ct2, s.snapshot(), mock.job(priority=70))
        assert not fresh.borrowed and fresh.mask.sum() == before.sum() - 1
        # the older generation's readers keep what they were handed
        assert (ct.victim_cache[60].mask == before).all()
