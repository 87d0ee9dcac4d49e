"""In-process server integration tests — the analog of the reference's
nomad.TestServer pattern (nomad/testing.go:44): a real Server with real
workers, broker, plan queue and applier, driven through its API."""

import copy
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs import NODE_STATUS_DOWN


@pytest.fixture()
def server():
    s = Server(ServerConfig(num_workers=2))
    s.establish_leadership()
    yield s
    s.shutdown()


def live_allocs(s, job):
    return [
        a
        for a in s.store.allocs_by_job(job.namespace, job.id)
        if not a.terminal_status()
    ]


class TestServerEndToEnd:
    def test_register_job_schedules_allocs(self, server):
        for _ in range(3):
            server.register_node(mock.node())
        job = mock.job()
        ev = server.register_job(job)
        assert server.wait_for_evals(timeout=15)
        assert len(live_allocs(server, job)) == 10
        stored_ev = server.store.eval_by_id(ev.id)
        assert stored_ev.status == "complete"

    def test_deregister_stops_allocs(self, server):
        for _ in range(2):
            server.register_node(mock.node())
        job = mock.job()
        server.register_job(job)
        assert server.wait_for_evals(timeout=15)
        server.deregister_job(job.namespace, job.id)
        assert server.wait_for_evals(timeout=15)
        assert live_allocs(server, job) == []

    def test_node_down_triggers_reschedule(self, server):
        nodes = [mock.node() for _ in range(3)]
        for n in nodes:
            server.register_node(n)
        job = mock.job()
        server.register_job(job)
        assert server.wait_for_evals(timeout=15)
        victims = server.store.allocs_by_node(nodes[0].id)
        assert victims
        server.update_node_status(nodes[0].id, NODE_STATUS_DOWN)
        assert server.wait_for_evals(timeout=15)
        live = live_allocs(server, job)
        assert len(live) == 10
        assert all(a.node_id != nodes[0].id for a in live)

    def test_blocked_eval_unblocks_on_new_node(self, server):
        server.register_node(mock.node())
        job = mock.job()
        job.task_groups[0].count = 30  # one node can't fit 30×500MHz
        server.register_job(job)
        assert server.wait_for_evals(timeout=15)
        placed_before = len(live_allocs(server, job))
        assert placed_before < 30
        assert server.blocked_evals.blocked_count() == 1
        # capacity arrives: blocked eval is released and placements finish
        for _ in range(4):
            server.register_node(mock.node())
        assert server.wait_for_evals(timeout=15)
        assert len(live_allocs(server, job)) == 30
        assert server.blocked_evals.blocked_count() == 0

    def test_failed_alloc_is_replaced(self, server):
        for _ in range(2):
            server.register_node(mock.node())
        job = mock.job()
        job.task_groups[0].count = 2
        server.register_job(job)
        assert server.wait_for_evals(timeout=15)
        a = live_allocs(server, job)[0]
        upd = a.copy_for_update()
        upd.client_status = "failed"
        server.update_allocs_from_client([upd])
        assert server.wait_for_evals(timeout=15)
        live = live_allocs(server, job)
        assert len(live) == 2
        assert a.id not in {x.id for x in live}

    def test_replacement_chain_no_churn(self, server):
        """A replaced failed alloc gets next_allocation set, so later evals
        ignore it instead of replacing again (the reschedule-churn bug)."""
        for _ in range(2):
            server.register_node(mock.node())
        from nomad_tpu.structs import ReschedulePolicy

        job = mock.job()
        job.task_groups[0].count = 2
        job.task_groups[0].reschedule_policy = ReschedulePolicy(
            delay_s=0, unlimited=True
        )
        server.register_job(job)
        assert server.wait_for_evals(timeout=15)
        a = live_allocs(server, job)[0]
        upd = a.copy_for_update()
        upd.client_status = "failed"
        server.update_allocs_from_client([upd])
        assert server.wait_for_evals(timeout=15)
        failed = server.store.alloc_by_id(a.id)
        assert failed.next_allocation  # chain recorded
        replacement = server.store.alloc_by_id(failed.next_allocation)
        assert replacement.previous_allocation == a.id
        assert replacement.reschedule_tracker is not None
        # a further no-op eval must not replace again
        ev = mock.eval_for(job)
        server.apply_eval_create([ev])
        assert server.wait_for_evals(timeout=15)
        assert len(live_allocs(server, job)) == 2
        assert server.store.alloc_by_id(failed.next_allocation) is not None

    def test_destructive_update_through_wire_plan(self, server):
        """Plans are normalized (job stripped) on the wire; the store must
        denormalize so a later spec change is still seen as destructive."""
        for _ in range(2):
            server.register_node(mock.node())
        job = mock.job()
        job.task_groups[0].count = 3
        server.register_job(job)
        assert server.wait_for_evals(timeout=15)
        assert all(
            a.job is not None for a in live_allocs(server, job)
        ), "stored allocs must carry a denormalized job"
        j2 = copy.deepcopy(job)
        j2.task_groups[0].tasks[0].config = {"command": "/bin/other"}
        server.register_job(j2)
        assert server.wait_for_evals(timeout=15)
        live = live_allocs(server, j2)
        assert len(live) == 3
        # destructive: brand-new alloc ids, not in-place updates
        assert all(a.job_version == j2.version for a in live)
        stopped = [
            a
            for a in server.store.allocs_by_job(job.namespace, job.id)
            if a.desired_status == "stop"
        ]
        assert len(stopped) == 3

    def test_sysbatch_completed_not_rerun(self, server):
        server.register_node(mock.node())
        job = mock.system_job(type="sysbatch")
        server.register_job(job)
        assert server.wait_for_evals(timeout=15)
        a = live_allocs(server, job)[0]
        upd = a.copy_for_update()
        upd.client_status = "complete"
        server.update_allocs_from_client([upd])
        # new eval (e.g. node fanout) must not re-place on the same node
        ev = mock.eval_for(job, triggered_by="node-update")
        server.apply_eval_create([ev])
        assert server.wait_for_evals(timeout=15)
        allocs = server.store.allocs_by_job(job.namespace, job.id)
        assert len(allocs) == 1  # no rerun

    def test_system_job_covers_new_nodes(self, server):
        n1 = mock.node()
        server.register_node(n1)
        job = mock.system_job()
        server.register_job(job)
        assert server.wait_for_evals(timeout=15)
        assert len(live_allocs(server, job)) == 1
        n2 = mock.node()
        server.register_node(n2)
        server.update_node_status(n2.id, "ready")
        assert server.wait_for_evals(timeout=15)
        assert {a.node_id for a in live_allocs(server, job)} == {n1.id, n2.id}


class TestLeadershipTransitions:
    def test_shutdown_waits_for_a_running_establish(self):
        """Raft runs ``establish_leadership`` on a callback thread; a
        ``shutdown`` that arrives mid-way must wait for it and then stop
        everything it started (it used to stop a worker whose thread was
        not started yet: ``tests/test_federation.py``'s teardown under
        load, ROADMAP C9)."""
        s = Server(ServerConfig(num_workers=1))
        entered, release = threading.Event(), threading.Event()
        restore = s._restore_evals

        def held_restore():
            entered.set()
            release.wait(10)
            restore()

        s._restore_evals = held_restore
        establish = threading.Thread(target=s.establish_leadership)
        establish.start()
        assert entered.wait(10)
        shutdown = threading.Thread(target=s.shutdown)
        shutdown.start()
        time.sleep(0.2)
        try:
            assert shutdown.is_alive()  # held behind the transition
        finally:
            release.set()
            establish.join(10)
            shutdown.join(20)
        assert not establish.is_alive() and not shutdown.is_alive()
        assert not s._leader and s.workers == []
