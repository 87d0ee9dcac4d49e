"""Property-set accounting + distinct_property / spread end-to-end.

Scenarios derived from the reference's tests (cited per test):
scheduler/feasible_test.go TestDistinctPropertyIterator_*,
scheduler/generic_sched_test.go TestServiceSched_Spread (:726) and
TestServiceSched_EvenSpread (:820), scheduler/propertyset.go semantics.
"""

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.propertyset import PropertySet
from nomad_tpu.structs import Constraint, EVAL_STATUS_COMPLETE, Plan
from nomad_tpu.structs.job import Spread, SpreadTarget


def register_and_run(h, job):
    h.store.upsert_job(h.next_index(), job)
    ev = mock.eval_for(job)
    h.store.upsert_evals(h.next_index(), [ev])
    h.process(ev)
    return ev


def cluster_with_racks(h, n_nodes, n_racks, dc="dc1"):
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.datacenter = dc
        n.meta["rack"] = f"rack-{i % n_racks}"
        h.store.upsert_node(h.next_index(), n)
        nodes.append(n)
    return nodes


# -- PropertySet unit semantics (propertyset.go:129-275) ---------------------


class TestPropertySet:
    def test_existing_counts_job_level(self):
        h = Harness()
        nodes = cluster_with_racks(h, 4, 2)
        job = mock.job()
        job.task_groups[0].count = 3
        register_and_run(h, job)
        snap = h.store.snapshot()
        ps = PropertySet(
            namespace=job.namespace, job_id=job.id, attribute="${meta.rack}"
        ).populate(snap)
        combined = ps.combined_use()
        assert sum(combined.values()) == 3
        assert set(combined) <= {"rack-0", "rack-1"}

    def test_task_group_scoping(self):
        """Only the named group's allocs count (propertyset.go:278-300
        filterAllocs)."""
        h = Harness()
        cluster_with_racks(h, 2, 1)
        job = mock.job()
        job.task_groups[0].count = 2
        register_and_run(h, job)
        snap = h.store.snapshot()
        scoped = PropertySet(
            namespace=job.namespace,
            job_id=job.id,
            attribute="${meta.rack}",
            task_group="nonexistent",
        ).populate(snap)
        assert scoped.combined_use() == {}

    def test_proposed_and_cleared_from_plan(self):
        """Plan stops discount the combined count; proposed allocs add;
        a value re-used by a proposed alloc stops discounting
        (propertyset.go:163-208)."""
        h = Harness()
        nodes = cluster_with_racks(h, 2, 2)
        job = mock.job()
        job.task_groups[0].count = 2
        register_and_run(h, job)
        snap = h.store.snapshot()
        allocs = h.store.allocs_by_job(job.namespace, job.id)
        assert len(allocs) == 2

        # stop one alloc in a plan → its rack's count clears
        plan = Plan(job=job)
        victim = allocs[0]
        plan.append_stopped_alloc(victim, "test")
        ps = PropertySet(
            namespace=job.namespace, job_id=job.id, attribute="${meta.rack}"
        ).populate(snap, plan)
        combined = ps.combined_use()
        assert sum(combined.values()) == 1

        # now also propose a replacement on the same node: the cleared
        # value is re-used, so its discount is cancelled and the value
        # counts existing + proposed (propertyset.go:199-208 — the victim
        # is still in existing, the stop no longer discounts)
        repl = victim.copy_for_update()
        repl.id = "replacement"
        plan.append_alloc(repl)
        ps2 = PropertySet(
            namespace=job.namespace, job_id=job.id, attribute="${meta.rack}"
        ).populate(snap, plan)
        combined = ps2.combined_use()
        assert combined[
            h.store.node_by_id(victim.node_id).meta["rack"]
        ] == 2
        assert sum(combined.values()) == 3

    def test_satisfies_distinct_property(self):
        ps = PropertySet(
            namespace="default",
            job_id="j",
            attribute="${meta.rack}",
            allowed_count=2,
        )
        ps.existing = {"r1": 2, "r2": 1}
        ok, _ = ps.satisfies_distinct_property("r2")
        assert ok
        ok, reason = ps.satisfies_distinct_property("r1")
        assert not ok and "used by 2" in reason
        ok, reason = ps.satisfies_distinct_property(None)
        assert not ok and "missing property" in reason


# -- distinct_property through the scheduler ---------------------------------


class TestDistinctProperty:
    def test_job_distinct_property_default_count(self):
        """One alloc per property value by default
        (feasible_test.go:1424 TestDistinctPropertyIterator_JobDistinctProperty)."""
        h = Harness()
        cluster_with_racks(h, 6, 3)  # 3 racks, 2 nodes each
        job = mock.job()
        job.task_groups[0].count = 3
        job.constraints.append(
            Constraint(l_target="${meta.rack}", operand="distinct_property")
        )
        register_and_run(h, job)
        allocs = h.store.allocs_by_job(job.namespace, job.id)
        assert len(allocs) == 3
        racks = [
            h.store.node_by_id(a.node_id).meta["rack"] for a in allocs
        ]
        assert sorted(racks) == ["rack-0", "rack-1", "rack-2"]

    def test_job_distinct_property_count(self):
        """RTarget sets the allowed count (feasible_test.go:1604
        TestDistinctPropertyIterator_JobDistinctProperty_Count)."""
        h = Harness()
        cluster_with_racks(h, 6, 2)  # 2 racks, 3 nodes each
        job = mock.job()
        job.task_groups[0].count = 4
        job.constraints.append(
            Constraint(
                l_target="${meta.rack}",
                operand="distinct_property",
                r_target="2",
            )
        )
        register_and_run(h, job)
        allocs = h.store.allocs_by_job(job.namespace, job.id)
        assert len(allocs) == 4
        racks = [h.store.node_by_id(a.node_id).meta["rack"] for a in allocs]
        assert racks.count("rack-0") == 2 and racks.count("rack-1") == 2

    def test_infeasible_when_values_exhausted(self):
        """More instances than value slots → failed placements + blocked
        eval (feasible_test.go:1893 ..._Infeasible)."""
        h = Harness()
        cluster_with_racks(h, 4, 2)
        job = mock.job()
        job.task_groups[0].count = 3
        job.constraints.append(
            Constraint(l_target="${meta.rack}", operand="distinct_property")
        )
        register_and_run(h, job)
        allocs = h.store.allocs_by_job(job.namespace, job.id)
        assert len(allocs) == 2
        assert h.evals[-1].status == EVAL_STATUS_COMPLETE
        assert h.evals[-1].failed_tg_allocs  # the third instance failed
        assert h.created_evals  # blocked eval holds the remainder

    def test_nodes_missing_property_filtered(self):
        """Nodes without the property are infeasible (propertyset.go:237
        UsedCount error → feasible.go:683 filter)."""
        h = Harness()
        nodes = cluster_with_racks(h, 2, 2)
        bare = mock.node()
        bare.datacenter = "dc1"
        bare.meta.pop("rack", None)
        h.store.upsert_node(h.next_index(), bare)
        job = mock.job()
        job.task_groups[0].count = 3
        job.constraints.append(
            Constraint(l_target="${meta.rack}", operand="distinct_property")
        )
        register_and_run(h, job)
        allocs = h.store.allocs_by_job(job.namespace, job.id)
        assert len(allocs) == 2
        assert bare.id not in {a.node_id for a in allocs}

    def test_remove_and_replace_same_value(self):
        """A stopped alloc frees its value slot for a replacement
        (feasible_test.go:1811 ..._RemoveAndReplace)."""
        h = Harness()
        cluster_with_racks(h, 2, 1)  # one rack only
        job = mock.job()
        job.task_groups[0].count = 1
        job.constraints.append(
            Constraint(l_target="${meta.rack}", operand="distinct_property")
        )
        register_and_run(h, job)
        assert len(h.store.allocs_by_job(job.namespace, job.id)) == 1

        # stop the alloc client-side, then re-evaluate: the replacement
        # must land despite the rack having been "used"
        alloc = h.store.allocs_by_job(job.namespace, job.id)[0]
        stopped = alloc.copy_for_update()
        stopped.client_status = "failed"
        h.store.upsert_allocs(h.next_index(), [stopped])
        ev = mock.eval_for(job)
        h.process(ev)
        live = [
            a
            for a in h.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status() and a.desired_status == "run"
        ]
        assert len(live) == 1


# -- spread through the scheduler (generic_sched_test.go:726,820) ------------


class TestSchedulerSpread:
    @pytest.mark.parametrize("dc1_pct", [100, 80, 50, 30, 10])
    def test_target_spread_ratios(self, dc1_pct):
        """TestServiceSched_Spread: two dcs, percent targets honored."""
        h = Harness()
        node_dc = {}
        for i in range(10):
            n = mock.node()
            n.datacenter = "dc2" if i % 2 == 0 else "dc1"
            h.store.upsert_node(h.next_index(), n)
            node_dc[n.id] = n.datacenter
        job = mock.job()
        job.datacenters = ["dc1", "dc2"]
        job.task_groups[0].count = 10
        job.task_groups[0].spreads = [
            Spread(
                attribute="${node.datacenter}",
                weight=100,
                targets=[
                    SpreadTarget(value="dc1", percent=dc1_pct),
                    SpreadTarget(value="dc2", percent=100 - dc1_pct),
                ],
            )
        ]
        register_and_run(h, job)
        allocs = h.store.allocs_by_job(job.namespace, job.id)
        assert len(allocs) == 10
        by_dc = {"dc1": 0, "dc2": 0}
        for a in allocs:
            by_dc[node_dc[a.node_id]] += 1
        assert by_dc["dc1"] == dc1_pct // 10
        assert by_dc["dc2"] == 10 - dc1_pct // 10
        assert not h.created_evals

    def test_even_spread(self):
        """TestServiceSched_EvenSpread: no targets → 5/5 split."""
        h = Harness()
        node_dc = {}
        for i in range(10):
            n = mock.node()
            n.datacenter = "dc2" if i % 2 == 0 else "dc1"
            h.store.upsert_node(h.next_index(), n)
            node_dc[n.id] = n.datacenter
        job = mock.job()
        job.datacenters = ["dc1", "dc2"]
        job.task_groups[0].count = 10
        job.task_groups[0].spreads = [
            Spread(attribute="${node.datacenter}", weight=100)
        ]
        register_and_run(h, job)
        allocs = h.store.allocs_by_job(job.namespace, job.id)
        assert len(allocs) == 10
        by_dc = {"dc1": 0, "dc2": 0}
        for a in allocs:
            by_dc[node_dc[a.node_id]] += 1
        assert by_dc == {"dc1": 5, "dc2": 5}

    def test_two_block_spread_parity(self):
        """Two spread blocks score together (two-block parity;
        spread_test.go:176 TestSpreadIterator_MultipleAttributes):
        rack spread (weight 70) + dc spread (weight 30)."""
        h = Harness()
        info = {}
        for i in range(8):
            n = mock.node()
            n.datacenter = "dc1" if i < 4 else "dc2"
            n.meta["rack"] = f"rack-{i % 4}"
            h.store.upsert_node(h.next_index(), n)
            info[n.id] = (n.datacenter, n.meta["rack"])
        job = mock.job()
        job.datacenters = ["dc1", "dc2"]
        job.task_groups[0].count = 8
        job.task_groups[0].spreads = [
            Spread(attribute="${meta.rack}", weight=70),
            Spread(attribute="${node.datacenter}", weight=30),
        ]
        register_and_run(h, job)
        allocs = h.store.allocs_by_job(job.namespace, job.id)
        assert len(allocs) == 8
        racks = {}
        dcs = {}
        for a in allocs:
            dc, rack = info[a.node_id]
            dcs[dc] = dcs.get(dc, 0) + 1
            racks[rack] = racks.get(rack, 0) + 1
        # even across 4 racks and 2 dcs
        assert all(v == 2 for v in racks.values()), racks
        assert dcs == {"dc1": 4, "dc2": 4}
