"""NodeDrainer tests — wave-by-wave migration off draining nodes.

Mirrors nomad/drainer/ behavior: migrate.max_parallel waves
(watch_jobs.go handleTaskGroup), system jobs last (watch_nodes.go),
deadline force-drain (drain_heap.go), drain-complete clears the strategy
but keeps the node ineligible (drainer.go handleDoneNodeDrains).
"""

import time

import pytest

from nomad_tpu import mock
from nomad_tpu.chaos import FaultPlane, FaultSpec, install, uninstall
from nomad_tpu.server.server import Server, ServerConfig
from nomad_tpu.structs import DrainStrategy
from nomad_tpu.structs.job import MigrateStrategy
from nomad_tpu.utils.metrics import global_metrics


@pytest.fixture(autouse=True)
def _no_leaked_plane():
    yield
    uninstall()


@pytest.fixture
def server():
    s = Server(ServerConfig(num_workers=2, heartbeat_ttl=60.0))
    s.establish_leadership()
    # fake client: pending allocs come up "running" shortly after
    # placement (drain waves gate on replacement health)
    import threading

    stop = threading.Event()

    def client_loop():
        import copy

        while not stop.wait(0.05):
            updates = []
            for a in list(s.store.allocs()):
                if a.desired_status == "run" and a.client_status == "pending":
                    u = copy.copy(a)
                    u.client_status = "running"
                    updates.append(u)
            if updates:
                s.update_allocs_from_client(updates)

    t = threading.Thread(target=client_loop, daemon=True)
    t.start()
    yield s
    stop.set()
    t.join(timeout=2)
    s.shutdown()


def wait_until(fn, timeout=8.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


def live_allocs_on(server, node_id):
    return [
        a
        for a in server.store.allocs_by_node(node_id)
        if not a.terminal_status() and a.desired_status == "run"
    ]


def test_drain_migrates_allocs_to_other_nodes(server):
    nodes = [mock.node() for _ in range(3)]
    for n in nodes:
        server.register_node(n)
    job = mock.job()  # count=10
    server.register_job(job)
    assert server.wait_for_evals(10)

    victim = max(
        nodes, key=lambda n: len(server.store.allocs_by_node(n.id))
    )
    n_before = len(live_allocs_on(server, victim.id))
    assert n_before > 0

    server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
    # all allocs leave the victim; job stays at full count elsewhere
    assert wait_until(lambda: not live_allocs_on(server, victim.id))
    assert wait_until(
        lambda: sum(
            1
            for a in server.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status() and a.desired_status == "run"
        )
        == 10
    )
    for a in server.store.allocs_by_job(job.namespace, job.id):
        if not a.terminal_status():
            assert a.node_id != victim.id
    # drain completes: strategy cleared, node stays ineligible
    assert wait_until(
        lambda: server.store.node_by_id(victim.id).drain is None
    )
    assert (
        server.store.node_by_id(victim.id).scheduling_eligibility
        == "ineligible"
    )


def test_drain_respects_max_parallel_waves(server):
    """With migrate.max_parallel=1 the drainer must never mark more than
    one alloc of the group migrating at a time."""
    n1, n2 = mock.node(), mock.node()
    server.register_node(n1)
    server.register_node(n2)
    job = mock.job()
    job.task_groups[0].count = 4
    job.task_groups[0].migrate = MigrateStrategy(max_parallel=1)
    server.register_job(job)
    assert server.wait_for_evals(10)

    victim = max(
        (n1, n2), key=lambda n: len(server.store.allocs_by_node(n.id))
    )
    if not live_allocs_on(server, victim.id):
        pytest.skip("all allocs landed on one node unexpectedly")
    # steady state first: everything running before the drain starts
    assert wait_until(
        lambda: all(
            a.client_status == "running"
            for a in server.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()
        )
    )

    # observe over time: the group must never dip below
    # count − max_parallel serving (running/unmarked) allocs — the
    # whole point of wave pacing (watch_jobs.go threshold)
    min_serving = 99
    server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
    deadline = time.time() + 12
    while time.time() < deadline:
        serving = [
            a
            for a in server.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()
            and not a.desired_transition.migrate
            and (a.client_status == "running" or a.node_id == victim.id)
        ]
        min_serving = min(min_serving, len(serving))
        if not live_allocs_on(server, victim.id):
            break
        time.sleep(0.02)
    assert not live_allocs_on(server, victim.id)
    assert min_serving >= job.task_groups[0].count - 1


def test_drain_cancel_clears_migrate_marks(server):
    """Cancelling a drain resets DesiredTransition.migrate so wave
    accounting and future drains start clean (drainer.go Remove)."""
    n1, n2 = mock.node(), mock.node()
    server.register_node(n1)
    server.register_node(n2)
    job = mock.job()
    job.task_groups[0].count = 4
    job.task_groups[0].migrate = MigrateStrategy(max_parallel=1)
    server.register_job(job)
    assert server.wait_for_evals(10)
    victim = max(
        (n1, n2), key=lambda n: len(server.store.allocs_by_node(n.id))
    )
    server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
    assert wait_until(
        lambda: any(
            a.desired_transition.migrate
            for a in server.store.allocs_by_job(job.namespace, job.id)
        )
    )
    server.update_node_drain(victim.id, None)
    assert wait_until(
        lambda: not any(
            a.desired_transition.migrate
            for a in server.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()
        )
    )
    assert server.store.node_by_id(victim.id).drain is None


def test_drain_deadline_forces_remaining(server):
    """A tiny deadline force-marks everything immediately."""
    n1, n2 = mock.node(), mock.node()
    server.register_node(n1)
    server.register_node(n2)
    job = mock.job()
    job.task_groups[0].count = 6
    job.task_groups[0].migrate = MigrateStrategy(max_parallel=1)
    server.register_job(job)
    assert server.wait_for_evals(10)
    victim = max(
        (n1, n2), key=lambda n: len(server.store.allocs_by_node(n.id))
    )
    server.update_node_drain(victim.id, DrainStrategy(deadline_s=-1))
    assert wait_until(lambda: not live_allocs_on(server, victim.id), timeout=5)


def test_drain_system_jobs_last(server):
    n1, n2 = mock.node(), mock.node()
    server.register_node(n1)
    server.register_node(n2)
    sysjob = mock.system_job()
    server.register_job(sysjob)
    job = mock.job()
    job.task_groups[0].count = 2
    server.register_job(job)
    assert server.wait_for_evals(10)

    victim = n1
    sys_allocs = [
        a
        for a in server.store.allocs_by_node(victim.id)
        if a.job_id == sysjob.id and not a.terminal_status()
    ]
    assert sys_allocs, "system job should land on every node"

    server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
    assert wait_until(
        lambda: not [
            a
            for a in live_allocs_on(server, victim.id)
            if a.job_id != sysjob.id
        ]
    )
    # then the system allocs are drained too
    assert wait_until(lambda: not live_allocs_on(server, victim.id))
    assert wait_until(lambda: server.store.node_by_id(victim.id).drain is None)


def test_drain_ignore_system_jobs(server):
    n1, n2 = mock.node(), mock.node()
    server.register_node(n1)
    server.register_node(n2)
    sysjob = mock.system_job()
    server.register_job(sysjob)
    job = mock.job()
    job.task_groups[0].count = 2
    server.register_job(job)
    assert server.wait_for_evals(10)

    victim = n1
    server.update_node_drain(
        victim.id,
        DrainStrategy(deadline_s=3600, ignore_system_jobs=True),
    )
    # service allocs leave; system alloc stays; drain completes anyway
    assert wait_until(
        lambda: server.store.node_by_id(victim.id).drain is None
    )
    remaining = live_allocs_on(server, victim.id)
    assert remaining and all(a.job_id == sysjob.id for a in remaining)


# -- wave migration under the fault plane (chaos-matrix coverage) ------------


def _counter(name: str) -> float:
    return global_metrics.snapshot()["counters"].get(name, 0.0)


def _job_converged(server, job, count):
    allocs = [
        a
        for a in server.store.allocs_by_job(job.namespace, job.id)
        if not a.terminal_status() and a.desired_status == "run"
    ]
    return len(allocs) == count


class TestDrainerChaos:
    def test_kill_mid_wave_still_converges(self, server):
        """A worker thread killed while committing a wave's replacement
        plan must not lose the wave: the eval is redelivered, the drain
        completes, the job lands at full count off the victim."""
        n1, n2 = mock.node(), mock.node()
        server.register_node(n1)
        server.register_node(n2)
        job = mock.job()
        job.task_groups[0].count = 4
        job.task_groups[0].migrate = MigrateStrategy(max_parallel=1)
        server.register_job(job)
        assert server.wait_for_evals(10)
        victim = max(
            (n1, n2), key=lambda n: len(server.store.allocs_by_node(n.id))
        )
        if not live_allocs_on(server, victim.id):
            pytest.skip("all allocs landed on one node unexpectedly")
        assert wait_until(
            lambda: all(
                a.client_status == "running"
                for a in server.store.allocs_by_job(job.namespace, job.id)
                if not a.terminal_status()
            )
        )

        install(FaultPlane(schedule=[
            FaultSpec("worker.commit", 0, "kill"),
            FaultSpec("plan_queue.enqueue_merged", 1, "kill"),
        ]))
        try:
            server.update_node_drain(
                victim.id, DrainStrategy(deadline_s=3600)
            )
            assert wait_until(
                lambda: not live_allocs_on(server, victim.id), timeout=15
            )
            assert wait_until(
                lambda: _job_converged(server, job, 4), timeout=15
            )
        finally:
            uninstall()
        for a in server.store.allocs_by_job(job.namespace, job.id):
            if not a.terminal_status():
                assert a.node_id != victim.id
        # graceful waves only: no deadline fired, so no forced exits
        assert _counter("nomad.drain.migrated") >= 1

    def test_deadline_expiry_under_dropped_delivery(self, server):
        """A dropped eval delivery slows the waves past the deadline;
        the force-drain sweep must still empty the node and account its
        exits as force_stops, not clean migrations."""
        n1, n2 = mock.node(), mock.node()
        server.register_node(n1)
        server.register_node(n2)
        job = mock.job()
        job.task_groups[0].count = 4
        job.task_groups[0].migrate = MigrateStrategy(max_parallel=1)
        server.register_job(job)
        assert server.wait_for_evals(10)
        victim = max(
            (n1, n2), key=lambda n: len(server.store.allocs_by_node(n.id))
        )
        if not live_allocs_on(server, victim.id):
            pytest.skip("all allocs landed on one node unexpectedly")
        forced0 = _counter("nomad.drain.force_stops")

        # the dropped delivery redelivers via the unack deadline — pull
        # it down from the production 60s so the test converges fast
        server.eval_broker.unack_timeout = 1.0
        install(FaultPlane(schedule=[
            FaultSpec("broker.dequeue", 0, "drop"),
        ]))
        try:
            server.update_node_drain(
                victim.id, DrainStrategy(deadline_s=0.3)
            )
            assert wait_until(
                lambda: _counter("nomad.drain.force_stops") > forced0,
                timeout=15,
            )
            assert wait_until(
                lambda: not live_allocs_on(server, victim.id), timeout=15
            )
        finally:
            uninstall()
        assert wait_until(
            lambda: server.store.node_by_id(victim.id).drain is None
        )

    def test_paired_node_flap_during_drain(self, server):
        """The destination node flaps (down, back up) mid-drain: the
        drain must still complete and the job converge at full count —
        no alloc stranded on the victim, none double-placed."""
        n1, n2, n3 = mock.node(), mock.node(), mock.node()
        for n in (n1, n2, n3):
            server.register_node(n)
        job = mock.job()
        job.task_groups[0].count = 6
        job.task_groups[0].migrate = MigrateStrategy(max_parallel=2)
        server.register_job(job)
        assert server.wait_for_evals(10)
        victim = max(
            (n1, n2, n3),
            key=lambda n: len(server.store.allocs_by_node(n.id)),
        )
        partner = next(n for n in (n1, n2, n3) if n.id != victim.id)
        assert wait_until(
            lambda: all(
                a.client_status == "running"
                for a in server.store.allocs_by_job(job.namespace, job.id)
                if not a.terminal_status()
            )
        )

        server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
        time.sleep(0.2)  # let the first wave land somewhere
        server.update_node_status(partner.id, "down")
        time.sleep(0.2)
        server.update_node_status(partner.id, "ready")
        server.store.node_by_id(partner.id)

        assert wait_until(
            lambda: not live_allocs_on(server, victim.id), timeout=20
        )
        assert wait_until(
            lambda: _job_converged(server, job, 6), timeout=20
        )
        # exactly-once accounting: every live alloc is on a ready,
        # non-draining node
        for a in server.store.allocs_by_job(job.namespace, job.id):
            if a.terminal_status():
                continue
            assert a.node_id != victim.id
            node = server.store.node_by_id(a.node_id)
            assert node.status == "ready"


# -- the wake, and Node.UpdateEligibility ------------------------------------


@pytest.fixture
def quiet_server():
    """A server whose drainer would poll every 10 s: what happens sooner
    happens on a wake."""
    s = Server(ServerConfig(num_workers=1, heartbeat_ttl=60.0))
    s.drainer.interval = 10.0
    s.establish_leadership()
    yield s
    s.shutdown()


def _running(server, job):
    import copy

    updates = []
    for a in server.store.allocs_by_job(job.namespace, job.id):
        if a.client_status == "pending" and not a.terminal_status():
            u = copy.copy(a)
            u.client_status = "running"
            updates.append(u)
    if updates:
        server.update_allocs_from_client(updates)


def test_a_drain_starts_on_the_commit_that_set_it(quiet_server):
    """watch_nodes.go / watch_jobs.go are blocking queries: the first marks
    follow the strategy's commit, not the next poll."""
    server = quiet_server
    n1, n2 = mock.node(), mock.node()
    server.register_node(n1)
    server.register_node(n2)
    job = mock.job()
    job.task_groups[0].count = 4
    job.task_groups[0].migrate = MigrateStrategy(max_parallel=1)
    server.register_job(job)
    assert server.wait_for_evals(10)
    _running(server, job)
    victim = max(
        (n1, n2), key=lambda n: len(live_allocs_on(server, n.id)))
    held = len(live_allocs_on(server, victim.id))
    assert held > 0
    scans = global_metrics.snapshot()["counters"].get("nomad.drain.waves", 0)

    from nomad_tpu.obs.recorder import flight_recorder

    def scans_since(t_unix):
        # this drain's scans, not an earlier test's in the same ring
        return [
            s for s in flight_recorder.background()
            if s["name"] == "drain.scan" and s["start_unix"] >= t_unix
        ]

    t0, t0_unix = time.perf_counter(), time.time()
    evals = server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
    assert evals == []  # a drain that starts makes no node evals
    assert wait_until(
        lambda: any(
            a.desired_transition.migrate
            for a in server.store.allocs_by_node(victim.id)
        ),
        timeout=2.0, interval=0.002,
    )
    # the first marks are the scan the strategy's commit woke, not the 10 s
    # poll's: the scan that marked them says who woke it
    assert wait_until(
        lambda: any(s["tags"].get("marked") for s in scans_since(t0_unix)),
        timeout=2.0, interval=0.002,
    )
    first = min(
        (s for s in scans_since(t0_unix) if s["tags"]["marked"]),
        key=lambda s: s["start_unix"],
    )
    assert "node_drain" in first["tags"]["woken_by"].split(",")
    # every later wave rides the clients' acknowledgement: the whole drain
    # ends well inside the 10 s of one poll
    deadline = time.time() + 8.0
    while time.time() < deadline and (
        server.store.node_by_id(victim.id).drain is not None
    ):
        _running(server, job)
        time.sleep(0.01)
    node = server.store.node_by_id(victim.id)
    assert node.drain is None and node.scheduling_eligibility == "ineligible"
    assert not live_allocs_on(server, victim.id)
    assert time.perf_counter() - t0 < 8.0
    waves = global_metrics.snapshot()["counters"]["nomad.drain.waves"] - scans
    assert waves == held  # max_parallel 1: a wave an allocation
    # the scans were wakes over the draining set, not walks of the fleet
    woken = [
        s for s in scans_since(t0_unix) if s["tags"]["woken_by"] != "interval"
    ]
    assert woken and all(s["tags"]["walked"] <= 1 for s in woken)
    assert {"node_drain", "client_update"} <= {
        r for s in woken for r in s["tags"]["woken_by"].split(",")}


def test_a_node_that_turns_eligible_gets_node_evals(quiet_server):
    """node_endpoint.go UpdateEligibility: evals for the system jobs when
    a node turns eligible; none when it turns ineligible or stays as it
    is; a draining node cannot be made eligible."""
    server = quiet_server
    node = mock.node()
    server.register_node(node)
    sysjob = mock.system_job()
    server.register_job(sysjob)
    assert server.wait_for_evals(10)
    made = lambda: global_metrics.snapshot()["counters"].get(  # noqa: E731
        "nomad.node.update_evals", 0)
    before = made()

    assert server.update_node_eligibility(node.id, "ineligible") == []
    assert server.store.node_by_id(node.id).scheduling_eligibility == (
        "ineligible")
    assert server.update_node_eligibility(node.id, "ineligible") == []
    evals = server.update_node_eligibility(node.id, "eligible")
    assert [e.job_id for e in evals] == [sysjob.id]
    assert all(e.triggered_by == "node-update" and e.node_id == node.id
               for e in evals)
    assert made() == before + 1
    assert server.update_node_eligibility(node.id, "eligible") == []

    server.update_node_drain(node.id, DrainStrategy(deadline_s=3600))
    with pytest.raises(ValueError):
        server.update_node_eligibility(node.id, "eligible")
    with pytest.raises(KeyError):
        server.update_node_eligibility("no-such-node", "eligible")


def test_the_http_handler_goes_through_update_node_eligibility(quiet_server):
    from nomad_tpu.api.http import APIError, HTTPAgent

    server = quiet_server
    node = mock.node()
    server.register_node(node)
    server.register_job(mock.system_job())
    assert server.wait_for_evals(10)
    http = HTTPAgent(server, None, port=0)
    calls = []
    through = server.update_node_eligibility

    def recording(node_id, eligibility):
        calls.append((node_id, eligibility))
        return through(node_id, eligibility)

    server.update_node_eligibility = recording
    out = http.handle_node_eligibility(
        "POST", {"eligibility": "ineligible"}, {}, node.id)
    assert out == {"eligibility": "ineligible", "eval_ids": []}
    out = http.handle_node_eligibility(
        "PUT", {"eligibility": "eligible"}, {}, node.id)
    assert out["eligibility"] == "eligible" and len(out["eval_ids"]) == 1
    assert calls == [(node.id, "ineligible"), (node.id, "eligible")]
    server.update_node_drain(node.id, DrainStrategy(deadline_s=3600))
    with pytest.raises(APIError) as e:
        http.handle_node_eligibility(
            "POST", {"eligibility": "eligible"}, {}, node.id)
    assert e.value.status == 400


def test_the_applier_refuses_a_node_that_started_draining_after_the_snapshot(
        quiet_server):
    """plan_apply.go evaluateNodePlan: a plan made before the drain was
    set places nothing on the node at the index it commits at."""
    from nomad_tpu.broker.plan_apply import evaluate_node_plan
    from nomad_tpu.structs import Plan

    server = quiet_server
    node = mock.node()
    server.register_node(node)
    job = mock.job()
    plan = Plan(eval_id="e", job=job)
    alloc = mock.alloc(job, node_id=node.id)
    plan.node_allocation[node.id] = [alloc]
    ok, _why = evaluate_node_plan(server.store.snapshot(), plan, node.id)
    assert ok
    server.update_node_eligibility(node.id, "ineligible")
    ok, why = evaluate_node_plan(server.store.snapshot(), plan, node.id)
    assert not ok and why == "node is not eligible"
    # an update of an allocation the node already holds stays
    server.store.upsert_allocs(server.store.latest_index + 1, [alloc])
    ok, _why = evaluate_node_plan(server.store.snapshot(), plan, node.id)
    assert ok


# -- the drain as one record --------------------------------------------------


def _two_of_one_job_on(server, max_parallel=1):
    """(the node that holds both allocations of a job of two, the job), a
    second node registered beside it once they are placed and running."""
    n1 = mock.node()
    server.register_node(n1)
    job = mock.job()
    job.task_groups[0].count = 2
    job.task_groups[0].migrate = MigrateStrategy(max_parallel=max_parallel)
    server.register_job(job)
    assert server.wait_for_evals(10)
    _running(server, job)
    assert len(live_allocs_on(server, n1.id)) == 2
    server.register_node(mock.node())
    return n1, job


def _drain_spans():
    from nomad_tpu.obs.recorder import flight_recorder

    return [s for s in flight_recorder.background() if s["name"] == "drain"]


def test_a_drain_is_one_span_whose_three_clocks_sum_to_it(quiet_server):
    """Command's commit -> strategy cleared, handed over where it ends,
    its time split among who the drain waited for."""
    from nomad_tpu.obs.recorder import flight_recorder

    server = quiet_server
    flight_recorder.clear()
    traces = []
    flight_recorder.add_listener(traces.append)
    try:
        victim, job = _two_of_one_job_on(server)
        t0 = time.perf_counter()
        server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
        deadline = time.time() + 8.0
        while time.time() < deadline and (
            server.store.node_by_id(victim.id).drain is not None
        ):
            _running(server, job)  # the fake client
            time.sleep(0.005)
        t1 = time.perf_counter()
        assert server.store.node_by_id(victim.id).drain is None
        assert server.wait_for_evals(10)
        time.sleep(0.1)  # the last trace reaches the recorder after its ack
    finally:
        flight_recorder.remove_listener(traces.append)

    (span,) = _drain_spans()
    tags = span["tags"]
    assert tags["node_id"] == victim.id
    assert tags["allocs"] == 2 and tags["migrated"] == 2
    assert tags["waves"] == 2 and tags["evals"] == 2  # max_parallel 1
    assert tags["deadlined"] is False
    clocks = [tags["sched_ms"], tags["client_ms"], tags["drainer_ms"]]
    assert all(c >= 0.0 for c in clocks)
    assert sum(clocks) == pytest.approx(span["duration_ms"], rel=0.01)
    # both waves waited for the scheduler and the drainer answered four
    # commits; the second wave waited for the client's word on the first
    # replacement unless that came before the drainer's next look
    assert tags["sched_ms"] > 0.0 and tags["drainer_ms"] > 0.0
    # the command's commit -> the commit that cleared the strategy: inside
    # what the caller saw, and it starts where ``node_drain`` applied
    assert span["duration_ms"] <= (t1 - t0) * 1000.0
    (call,) = [s for s in flight_recorder.background()
               if s["name"] == "node_drain"]
    assert call["tags"]["node_id"] == victim.id
    assert call["start_unix"] <= span["start_unix"] <= (
        call["start_unix"] + call["duration_ms"] / 1000.0)
    # the drain's parts share its identifier: the root of each of its evals
    mine = [t for t in traces if t["tags"].get("node_id") == victim.id]
    assert len(mine) == 2
    assert all(t["tags"]["triggered_by"] == "node-drain" for t in mine)
    assert server.drainer._drains == {}


def test_a_cancelled_drain_writes_no_span(quiet_server):
    from nomad_tpu.obs.recorder import flight_recorder

    server = quiet_server
    flight_recorder.clear()
    victim, job = _two_of_one_job_on(server)
    server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
    # no client answers: the first wave's replacement never turns healthy
    assert wait_until(
        lambda: any(
            a.desired_transition.migrate
            for a in server.store.allocs_by_node(victim.id)
        ),
        timeout=2.0, interval=0.002,
    )
    assert victim.id in server.drainer._drains
    server.update_node_drain(victim.id, None)
    assert server.wait_for_evals(10)
    server.drainer.scan()
    assert server.store.node_by_id(victim.id).drain is None
    assert _drain_spans() == []
    assert server.drainer._drains == {}


def test_a_drain_a_walk_finds_is_drained_and_writes_no_span(quiet_server):
    """A new leader inherits drains whose start it never saw: it ends
    them, and hands over no record of an interval it cannot know."""
    from nomad_tpu.obs.recorder import flight_recorder

    server = quiet_server
    flight_recorder.clear()
    n1 = mock.node()
    server.register_node(n1)
    server.update_node_drain(n1.id, DrainStrategy(deadline_s=3600))
    with server.drainer._lock:  # as a drainer started after the commit
        server.drainer._drains.clear()
    server.drainer.scan()
    assert wait_until(lambda: server.store.node_by_id(n1.id).drain is None)
    assert _drain_spans() == []


@pytest.mark.parametrize("states, want", [
    # (stamp, state the look found) in order -> seconds by state
    ([(1.0, "sched"), (4.0, "drainer"), (4.5, "ended")],
     {"drainer": 1.5, "sched": 3.0, "client": 0.0}),
    ([(1.0, "sched"), (2.0, "client"), (5.0, "drainer"), (5.25, "sched"),
      (6.0, "drainer"), (6.5, "ended")],
     {"drainer": 1.75, "sched": 1.75, "client": 3.0}),
    ([(0.5, "ended")], {"drainer": 0.5, "sched": 0.0, "client": 0.0}),
])
def test_a_drain_records_clocks_sum_to_its_length(states, want):
    from nomad_tpu.server.drainer import _DrainRecord

    rec = _DrainRecord(0.0)
    for at, state in states:
        rec.turn(at, state)
    assert rec.spent == pytest.approx(want)
    assert sum(rec.spent.values()) == pytest.approx(states[-1][0])


def test_the_cli_prints_a_drain_with_its_three_clocks(quiet_server, capsys):
    """"Why is my drain slow" is an operator's question: ``nomad-tpu
    trace`` answers it from ``/v1/agent/trace``'s background list."""
    from nomad_tpu.api.http import HTTPAgent
    from nomad_tpu.cli.main import main as cli_main
    from nomad_tpu.obs.recorder import flight_recorder

    server = quiet_server
    flight_recorder.clear()
    victim, job = _two_of_one_job_on(server)
    server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
    deadline = time.time() + 8.0
    while time.time() < deadline and not _drain_spans():
        _running(server, job)
        time.sleep(0.005)
    (span,) = _drain_spans()
    http = HTTPAgent(server, None, port=0)
    http.start()
    try:
        assert cli_main(["-address", http.address, "trace"]) == 0
    finally:
        http.stop()
    out = capsys.readouterr().out
    assert "1 recent node drain(s):" in out
    (line,) = [ln for ln in out.splitlines() if victim.id in ln
               and "scheduler=" in ln]
    tags = span["tags"]
    for part in (f"scheduler={tags['sched_ms']:.2f}ms",
                 f"clients={tags['client_ms']:.2f}ms",
                 f"drainer={tags['drainer_ms']:.2f}ms",
                 "allocs=2", "waves=2", "evals=2", "migrated=2",
                 "deadlined=False"):
        assert part in line, (part, line)
