"""Batched multi-eval scheduling (SURVEY.md §7 step 5): many pending
evals packed into one device pass, replacing the reference's
worker-per-core concurrency (nomad/worker.go:85, nomad/config.go:468).
"""

import copy
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.broker.eval_broker import EvalBroker
from nomad_tpu.device.cache import DeviceStateCache
from nomad_tpu.scheduler.generic import GenericScheduler
from nomad_tpu.scheduler.scheduler import new_scheduler
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs import DrainStrategy, PlanResult
from nomad_tpu.structs.job import MigrateStrategy
from nomad_tpu.utils.metrics import global_metrics


def _ev(job_id="j1", ns="default", typ="service", prio=50):
    e = mock.eval_for(mock.job(id=job_id, priority=prio))
    e.namespace = ns
    e.type = typ
    return e


class TestDequeueMany:
    def test_returns_up_to_max(self):
        b = EvalBroker()
        b.set_enabled(True)
        for i in range(5):
            b.enqueue(_ev(job_id=f"j{i}"))
        got = b.dequeue_many(["service"], 3, timeout=1)
        assert len(got) == 3
        got2 = b.dequeue_many(["service"], 10, timeout=0.2)
        assert len(got2) == 2

    def test_per_job_serialization_within_batch(self):
        b = EvalBroker()
        b.set_enabled(True)
        b.enqueue(_ev(job_id="same"))
        b.enqueue(_ev(job_id="same"))
        b.enqueue(_ev(job_id="other"))
        got = b.dequeue_many(["service"], 10, timeout=1)
        jobs = [ev.job_id for ev, _ in got]
        assert sorted(jobs) == ["other", "same"]  # second 'same' deferred
        for ev, tok in got:
            b.ack(ev.id, tok)
        got2 = b.dequeue_many(["service"], 10, timeout=1)
        assert [ev.job_id for ev, _ in got2] == ["same"]

    def test_nonblocking_poll(self):
        b = EvalBroker()
        b.set_enabled(True)
        ev, tok = b.dequeue(["service"], timeout=0)
        assert ev is None


class TestBatchedScheduling:
    def test_burst_of_jobs_all_placed(self):
        """A burst of registrations drains through the batched pass with
        every allocation placed and every eval completed."""
        s = Server(ServerConfig(num_workers=2))
        s.establish_leadership()
        try:
            for _ in range(10):
                s.register_node(mock.node())
            # 10 nodes × ⌊3900/500⌋ = 70 slots; ask for 60
            jobs = []
            for i in range(20):
                j = mock.job(id=f"burst-{i}")
                j.task_groups[0].count = 3
                jobs.append(j)
                s.register_job(j)
            assert s.wait_for_evals(timeout=60)
            for j in jobs:
                live = [
                    a
                    for a in s.store.allocs_by_job(j.namespace, j.id)
                    if not a.terminal_status()
                ]
                assert len(live) == 3, f"{j.id}: {len(live)}"
            # every eval completed
            for j in jobs:
                evs = s.store.evals_by_job(j.namespace, j.id)
                assert evs and all(e.status == "complete" for e in evs)
        finally:
            s.shutdown()

    def test_batch_conflict_falls_back_and_converges(self):
        """Evals in one batch score against the same snapshot, so they can
        jointly overcommit a node; the applier partially rejects and the
        fallback path converges (the optimistic-concurrency contract,
        plan_apply.go:439-596)."""
        s = Server(ServerConfig(num_workers=2))
        s.establish_leadership()
        try:
            # one node with room for exactly 6 × 500 MHz (4000 - 100
            # reserved → 7×500=3500 fits, 8 doesn't)
            s.register_node(mock.node())
            jobs = []
            for i in range(8):
                j = mock.job(id=f"tight-{i}")
                j.task_groups[0].count = 1
                jobs.append(j)
                s.register_job(j)
            assert s.wait_for_evals(timeout=60)
            placed = sum(
                1
                for j in jobs
                for a in s.store.allocs_by_job(j.namespace, j.id)
                if not a.terminal_status()
            )
            assert placed == 7, f"placed {placed}"
            # the rest are blocked, not lost
            blocked = [
                e
                for j in jobs
                for e in s.store.evals_by_job(j.namespace, j.id)
                if e.status == "blocked"
            ]
            assert blocked
        finally:
            s.shutdown()


# -- a wave of migrations rides one batched pass -----------------------------


def _counter(name: str) -> float:
    return global_metrics.snapshot()["counters"].get(name, 0.0)


def _live(server, node_id=None, job=None):
    allocs = (
        server.store.allocs_by_node(node_id) if node_id is not None
        else server.store.allocs_by_job(job.namespace, job.id)
    )
    return [
        a for a in allocs
        if not a.terminal_status() and a.desired_status == "run"
    ]


def _acknowledge(server) -> None:
    """The nodes' clients report every pending allocation running."""
    updates = []
    for a in server.store.allocs():
        if a.client_status == "pending" and not a.terminal_status():
            u = copy.copy(a)
            u.client_status = "running"
            updates.append(u)
    if updates:
        server.update_allocs_from_client(updates)


def _wait(server, done, timeout=30.0) -> bool:
    deadline = time.time() + timeout
    while time.time() < deadline:
        _acknowledge(server)
        if done():
            return True
        time.sleep(0.02)
    return False


def _services_on_one_node(server, jobs: int, per_job: int):
    """``jobs`` services of ``per_job`` allocations, all on the one node
    there is, acknowledged running; then five more nodes of capacities
    that tie nowhere (under sixteen eligible nodes no lane is confined to
    a stripe: ``_decorrelate_lanes``). Returns (the full node, the jobs)."""
    victim = mock.node()
    server.register_node(victim)
    services = []
    for i in range(jobs):
        job = mock.job(id=f"svc-{i}")
        group = job.task_groups[0]
        group.count = per_job
        group.tasks[0].resources.cpu = 150 + 25 * i
        group.migrate = MigrateStrategy(max_parallel=1)
        services.append(job)
        server.register_job(job)
    assert server.wait_for_evals(timeout=30)
    _acknowledge(server)
    assert len(_live(server, victim.id)) == jobs * per_job
    for i in range(5):
        node = mock.node()
        node.node_resources.cpu = 4000 + 137 * (i + 1)
        server.register_node(node)
    assert server.wait_for_evals(timeout=30)
    return victim, services


def _used_of(snapshot, ct) -> np.ndarray:
    used = np.zeros_like(ct.used)
    for a in snapshot.allocs():
        if not a.terminal_status():
            used[ct.node_row[a.node_id]] += (
                a.comparable_resources().to_vector())
    return used


class _Kept:
    """A planner that commits nothing and calls every plan whole."""

    def __init__(self):
        self.plans = []

    def submit_plan(self, plan):
        self.plans.append(plan)
        return PlanResult(
            node_update=plan.node_update,
            node_allocation=plan.node_allocation,
        ), None

    def update_eval(self, ev):
        pass

    create_eval = reblock_eval = update_eval


def _alone_on(snapshot, ev):
    """The eval on the solo path against ``snapshot``, nothing committed:
    (the scheduler, {allocation name: node id} of its plan)."""
    planner = _Kept()
    sched = new_scheduler(
        ev.type, snapshot, planner, cache=DeviceStateCache())
    sched.process(copy.copy(ev))
    (plan,) = planner.plans
    return sched, {
        a.name: node_id
        for node_id, allocs in plan.node_allocation.items() for a in allocs
    }


@pytest.fixture
def one_batcher():
    server = Server(ServerConfig(num_workers=1, num_batch_workers=1))
    server.establish_leadership()
    yield server
    server.shutdown()


@pytest.fixture
def prepared(monkeypatch):
    """What every ``prepare_batch_attempt`` was given and gave back."""
    seen = []
    prepare = GenericScheduler.prepare_batch_attempt

    def recording(self, evaluation, ct, **kw):
        before = ct.used.copy()
        asks = prepare(self, evaluation, ct, **kw)
        seen.append({
            "eval": copy.copy(evaluation), "snapshot": self.snapshot,
            "ct": ct, "used_before": before, "used_after": ct.used.copy(),
            "asks": asks, "sched": self,
        })
        return asks

    monkeypatch.setattr(GenericScheduler, "prepare_batch_attempt", recording)
    return seen


class TestWaveWithStops:
    """A drain's wave: an eval a job the node holds, each stopping one
    allocation there and placing its replacement elsewhere. The stop frees
    room only on a node no ask may place on, so the wave stays one batched
    pass (PERF.md section 3, worker pass)."""

    @pytest.mark.parametrize("jobs, per_job", [(2, 1), (5, 1), (3, 2)])
    def test_one_batched_pass_a_wave_places_every_migration(
        self, one_batcher, prepared, jobs, per_job
    ):
        server = one_batcher
        victim, services = _services_on_one_node(server, jobs, per_job)
        before = {
            name: _counter(f"nomad.worker.{name}") for name in (
                "passes_solo", "passes_batched", "evals_batched_with_stops",
                "batch_single_fallbacks")
        }
        del prepared[:]
        server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
        assert _wait(server, lambda: (
            server.store.node_by_id(victim.id).drain is None
            and not _live(server, victim.id)))
        assert server.wait_for_evals(timeout=30)
        migrations = jobs * per_job
        moved = {
            name: _counter(f"nomad.worker.{name}") - n
            for name, n in before.items()
        }
        # a wave is an eval a job; with ``max_parallel`` 1 a job's second
        # allocation leaves in a second wave
        assert moved == {
            "passes_solo": 0, "passes_batched": per_job,
            "evals_batched_with_stops": migrations,
            "batch_single_fallbacks": 0,
        }
        assert len(prepared) == migrations
        for job in services:
            assert len(_live(server, job=job)) == per_job
        for p in prepared:
            ct, snapshot, sched = p["ct"], p["snapshot"], p["sched"]
            row = ct.node_row[victim.id]
            # the stop sits on a row every ask of the eval is closed to
            assert list(sched.plan.node_update) == [victim.id]
            assert p["asks"] and not any(a.eligible[row] for a in p["asks"])
            assert all(a.exact for a in p["asks"])
            # and stays on the ``used`` all lanes share: the snapshot's
            assert sched._plan_freed is None
            np.testing.assert_array_equal(p["used_before"], p["used_after"])
            np.testing.assert_allclose(
                p["used_after"], _used_of(snapshot, ct), atol=1e-3)
            assert p["used_after"][row].any()
            # what the pass committed for the eval is what the eval alone
            # places on the same snapshot, its stop taken off ``used``
            alone, want = _alone_on(snapshot, p["eval"])
            assert alone._plan_freed.any()  # on its own tensors' rows
            got = {
                a.name: a.node_id for a in server.store.allocs()
                if a.eval_id == p["eval"].id
            }
            assert got == want and len(got) == 1

    def test_a_member_that_loses_its_best_node_commits_after_the_others(
        self, one_batcher, prepared
    ):
        """Both replacements' best node has room for one. The loser is not
        given the runner-up of the pass's snapshot: it is placed on the
        usage that holds the winner, and its plan commits after the
        pass's merged plan, at an index of its own — what its own pass
        would have found then, without that pass."""
        server = one_batcher
        victim, services = _services_on_one_node(server, 2, 1)
        tight = mock.node()
        tight.node_resources.cpu = 400  # 300 to give: 150 or 175, not both
        server.register_node(tight)
        assert server.wait_for_evals(timeout=30)
        names = ("passes_solo", "passes_batched", "evals_batched_with_stops",
                 "batch_repair_fallbacks", "batch_deferred_commits")
        before = {n: _counter(f"nomad.worker.{n}") for n in names}
        commits = _counter("nomad.plan.merged_commits")
        del prepared[:]
        server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
        assert _wait(server, lambda: (
            server.store.node_by_id(victim.id).drain is None
            and not _live(server, victim.id)))
        assert server.wait_for_evals(timeout=30)
        assert {
            n: _counter(f"nomad.worker.{n}") - v for n, v in before.items()
        } == {"passes_solo": 0, "passes_batched": 1,
              "evals_batched_with_stops": 2, "batch_repair_fallbacks": 0,
              "batch_deferred_commits": 1}
        assert _counter("nomad.plan.merged_commits") - commits == 2
        for p in prepared:
            assert all(a.exact for a in p["asks"])
            # alone on the pass's snapshot either would have taken it
            _alone, want = _alone_on(p["snapshot"], p["eval"])
            assert list(want.values()) == [tight.id]
        placed = {
            a.eval_id: a for job in services for a in _live(server, job=job)
        }
        first, second = sorted(
            (placed[p["eval"].id] for p in prepared),
            key=lambda a: a.create_index)
        assert first.create_index < second.create_index
        assert first.node_id == tight.id
        assert second.node_id not in (tight.id, victim.id)
        # (tests/test_value_scan.py holds the second's node and score to
        # the exact re-score on the usage that holds the first)
        trace = _trace_of(second.eval_id)
        assert trace["tags"]["path"] == "batched"
        assert "solo_wait" not in {s["name"] for s in trace["spans"]}
        own = [s for s in trace["spans"] if s["name"] == "submit_plan"
               and "leader_eval" not in s["tags"] and not s["tags"].get(
                   "shared")]
        assert len(own) == 1

    def test_a_stop_within_the_evals_own_reach_leaves_for_the_solo_path(
        self, one_batcher, prepared
    ):
        """A destructive update: the old allocation's node is open to its
        replacement, so the pass must show the kernel that room."""
        server = one_batcher
        for _ in range(3):
            server.register_node(mock.node())
        job = mock.job(id="rolls")
        job.task_groups[0].count = 2
        server.register_job(job)
        assert server.wait_for_evals(timeout=30)
        shown = self._update_beside_a_registration(server, job, prepared)
        (p,) = [p for p in prepared if p["eval"].job_id == "rolls"]
        assert p["asks"] is None and p["sched"]._plan_freed is None
        (other,) = [p for p in prepared if p["eval"].job_id == "beside"]
        assert other["asks"] and not any(a.exact for a in other["asks"])
        np.testing.assert_array_equal(p["used_before"], p["used_after"])
        self._left_for_a_solo_pass_that_freed_its_stops(p, shown)

    def test_a_plan_that_evicts_leaves_for_the_solo_path(
        self, one_batcher, prepared, monkeypatch
    ):
        server = one_batcher
        for _ in range(3):
            server.register_node(mock.node())
        job = mock.job(id="rolls")
        job.task_groups[0].count = 2
        server.register_job(job)
        assert server.wait_for_evals(timeout=30)
        start = GenericScheduler._start_attempt
        evicted = []

        def evicting(self):
            placements = start(self)
            if self.eval.job_id == "rolls" and not evicted:
                # once, in the batched pass's prepare
                victim = _live(server, job=job)[0]
                evicted.append(victim)
                self.plan.node_preemptions[victim.node_id] = [victim]
            return placements

        monkeypatch.setattr(GenericScheduler, "_start_attempt", evicting)
        shown = self._update_beside_a_registration(server, job, prepared)
        assert len(evicted) == 1
        (p,) = [p for p in prepared if p["eval"].job_id == "rolls"]
        assert p["asks"] is None
        self._left_for_a_solo_pass_that_freed_its_stops(p, shown)

    @staticmethod
    def _update_beside_a_registration(server, job, prepared) -> list:
        """A new version of ``job`` and a new job, dequeued together; what
        the update's passes showed ``flatten_group_ask``: (``used``, the
        snapshot's usage, the plan)."""
        from nomad_tpu.scheduler import generic

        shown = []
        flatten = generic.flatten_group_ask

        def recording(ct, snap, job_, tg, count, **kw):
            if job_.id == job.id:
                shown.append((ct.used.copy(), _used_of(snap, ct), ct,
                              kw.get("plan")))
            return flatten(ct, snap, job_, tg, count, **kw)

        generic.flatten_group_ask = recording
        try:
            for w in server.workers:
                w.pause()
            time.sleep(0.25)  # the worker's dequeue poll holds one more turn
            del prepared[:]
            update = copy.deepcopy(job)
            update.task_groups[0].tasks[0].env = {"VERSION": "2"}
            server.register_job(update)
            server.register_job(mock.job(id="beside"))
            for w in server.workers:
                w.resume()
            assert server.wait_for_evals(timeout=30)
        finally:
            generic.flatten_group_ask = flatten
        assert len(_live(server, job=job)) == 2
        assert all(a.job_version == 1 for a in _live(server, job=job))
        return shown

    @staticmethod
    def _left_for_a_solo_pass_that_freed_its_stops(p, shown) -> None:
        trace = _trace_of(p["eval"].id)
        (wait,) = [s for s in trace["spans"] if s["name"] == "solo_wait"]
        assert wait["tags"]["reason"] == "nothing_to_batch"
        assert wait["tags"]["path"] == "batched" and wait["tags"]["evals"] == 2
        assert trace["tags"]["path"] == "solo"
        assert "stops_batched" not in {
            k for s in trace["spans"] for k in s["tags"]}
        # flattened once, in its solo pass, whose ``used`` is the
        # snapshot's less the plan's stops on rows open to the replacement
        (solo,) = shown
        used, of_snapshot, ct, plan = solo
        freed = of_snapshot - used
        rows = sorted(ct.node_row[n] for n in plan.node_update)
        assert rows and sorted(np.flatnonzero(freed.any(axis=1))) == rows
        assert ct.ready[rows].all()
        (stops,) = [s for s in trace["spans"] if s["name"] == "plan_stops"]
        assert stops["tags"]["stops"] == sum(
            map(len, plan.node_update.values())) == 2

    def test_in_lane_mode_a_member_with_stops_leaves_for_the_solo_path(
        self, prepared
    ):
        """A stop's node may be another worker's lane, and a merged plan
        answers for every node it touches: lane mode keeps such members
        on the solo path."""
        server = Server(ServerConfig(num_workers=2, num_batch_workers=2))
        server.establish_leadership()
        try:
            assert server.lane_mode
            # enough nodes that either worker's lanes hold open ones: a
            # solo pass in lane mode stays on its worker's own nodes
            nodes = [mock.node() for _ in range(24)]
            for node in nodes:
                server.register_node(node)
            services = []
            for i in range(4):
                job = mock.job(id=f"svc-{i}")
                job.task_groups[0].count = 6
                job.task_groups[0].migrate = MigrateStrategy(max_parallel=1)
                services.append(job)
                server.register_job(job)
            assert server.wait_for_evals(timeout=30)
            _acknowledge(server)
            victim = max(nodes, key=lambda n: len(_live(server, n.id)))
            held = len(_live(server, victim.id))
            assert held >= 1
            rode = _counter("nomad.worker.evals_batched_with_stops")
            conflicts = _counter("nomad.plan.lane_conflicts")
            del prepared[:]
            server.update_node_drain(
                victim.id, DrainStrategy(deadline_s=3600))
            assert _wait(server, lambda: (
                server.store.node_by_id(victim.id).drain is None
                and not _live(server, victim.id)))
            assert server.wait_for_evals(timeout=30)
            assert len(prepared) >= held  # more where one blocked
            for p in prepared:
                assert p["asks"] is None and p["sched"].plan.node_update
                trace = _trace_of(p["eval"].id)
                (wait,) = [
                    s for s in trace["spans"] if s["name"] == "solo_wait"]
                assert wait["tags"]["reason"] == "nothing_to_batch"
            for job in services:
                assert len(_live(server, job=job)) == 6
            assert _counter("nomad.worker.evals_batched_with_stops") == rode
            assert _counter("nomad.plan.lane_conflicts") == conflicts
        finally:
            server.shutdown()

    def test_a_member_that_rode_with_stops_says_so_in_its_prepare(
        self, one_batcher, prepared
    ):
        server = one_batcher
        victim, _services = _services_on_one_node(server, 2, 1)
        del prepared[:]
        server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
        assert _wait(server, lambda: (
            server.store.node_by_id(victim.id).drain is None
            and not _live(server, victim.id)))
        assert server.wait_for_evals(timeout=30)
        for p in prepared:
            trace = _trace_of(p["eval"].id)
            assert trace["tags"]["path"] == "batched"
            (prepare,) = [
                s for s in trace["spans"] if s["name"] == "prepare"]
            assert prepare["tags"]["stops_batched"] == 1
            names = {s["name"] for s in trace["spans"]}
            assert not names & {"solo_wait", "plan_stops"}


def _trace_of(eval_id: str, timeout=5.0) -> dict:
    from nomad_tpu.obs.recorder import flight_recorder

    deadline = time.time() + timeout
    while time.time() < deadline:
        for t in flight_recorder.traces():
            if t["eval_id"] == eval_id:
                return t
        time.sleep(0.02)
    raise AssertionError(f"no trace of {eval_id}")
