"""nomad_tpu.chaos — fault plane, invariant checker, deterministic runner.

The targeted scenarios pin the recovery stories the ISSUE names: a
worker commit thread killed mid merged-plan never loses or
double-commits a member, an unacked eval is redelivered exactly once,
a duplicated ack-time redelivery converges to a no-op, and no swallow
site can absorb an injected fault without the counter + error ring
seeing it. The corpus/soak tests then let the seeded scheduler explore
interleavings no hand-written scenario would find.
"""

import threading
import time

import pytest

from nomad_tpu.chaos import (
    ChaosClock,
    ChaosFault,
    ChaosThreadKill,
    FaultPlane,
    FaultSpec,
    active_plane,
    chaos_site,
    check_cluster,
    install,
    run_chaos,
    uninstall,
)
from nomad_tpu.chaos.invariants import metrics_baseline
from nomad_tpu.chaos.plane import build_schedule
from nomad_tpu.utils.metrics import count_swallowed, global_metrics


@pytest.fixture(autouse=True)
def _no_leaked_plane():
    """A test that dies mid-install must not poison its neighbours."""
    yield
    uninstall()


def _counter(name: str) -> float:
    return global_metrics.snapshot()["counters"].get(name, 0.0)


# -- plane mechanics ---------------------------------------------------------


class TestFaultPlane:
    def test_off_by_default(self):
        assert active_plane() is None
        assert chaos_site("broker.ack") is None

    def test_schedule_is_pure_function_of_seed(self):
        a = build_schedule(seed=42, steps=100, faults=("raise", "kill"))
        b = build_schedule(seed=42, steps=100, faults=("raise", "kill"))
        assert [s.row() for s in a] == [s.row() for s in b]
        c = build_schedule(seed=43, steps=100, faults=("raise", "kill"))
        assert [s.row() for s in a] != [s.row() for s in c]

    def test_spec_rejects_out_of_contract_action(self):
        # a silent drop at plan_apply.commit would be below-contract loss
        with pytest.raises(ValueError):
            FaultSpec("plan_apply.commit", 0, "drop")
        with pytest.raises(ValueError):
            FaultSpec("no.such.site", 0, "raise")

    def test_hit_semantics_per_kind(self):
        plane = FaultPlane(schedule=[
            FaultSpec("broker.ack", 0, "raise"),
            FaultSpec("broker.ack", 1, "duplicate"),
            FaultSpec("broker.dequeue", 0, "drop"),
            FaultSpec("worker.commit", 0, "kill"),
            FaultSpec("broker.dequeue", 1, "skew", 0.5),
        ])
        install(plane)
        try:
            with pytest.raises(ChaosFault):
                chaos_site("broker.ack")
            assert chaos_site("broker.ack") == "duplicate"
            assert chaos_site("broker.ack") is None  # past the schedule
            assert chaos_site("broker.dequeue") == "drop"
            with pytest.raises(ChaosThreadKill):
                chaos_site("worker.commit")
            before = plane.clock.offset
            assert chaos_site("broker.dequeue") == "skew"
            assert plane.clock.offset == pytest.approx(before + 0.5)
            assert plane.kills == 1
            assert len(plane.raised) == 1
            assert {t[2] for t in plane.triggered} == {
                "raise", "duplicate", "drop", "kill", "skew"
            }
        finally:
            uninstall()

    def test_thread_kill_escapes_except_exception(self):
        plane = FaultPlane(schedule=[FaultSpec("worker.commit", 0, "kill")])
        install(plane)
        try:
            with pytest.raises(ChaosThreadKill):
                try:
                    chaos_site("worker.commit")
                except Exception:  # the recovery handler a crash ignores
                    pytest.fail("except Exception absorbed a thread kill")
        finally:
            uninstall()

    def test_from_env_spec_roundtrip(self):
        plane = FaultPlane.from_env(
            "seed=9,steps=50,rate=0.1,faults=raise+delay"
        )
        assert plane.seed == 9 and plane.steps == 50
        assert plane.schedule_rows() == FaultPlane(
            seed=9, steps=50, rate=0.1, faults=("raise", "delay")
        ).schedule_rows()

    def test_chaos_clock_skews_both_readings(self):
        clock = ChaosClock()
        t0, m0 = clock.time(), clock.monotonic()
        clock.skew(10.0)
        assert clock.time() - t0 >= 9.9
        assert clock.monotonic() - m0 >= 9.9


# -- swallow accounting (satellite: no invisible fault absorption) -----------


class TestSwallowAccounting:
    def test_swallowed_chaos_fault_is_counted_and_ringed(self):
        from nomad_tpu.obs.recorder import flight_recorder

        fault = ChaosFault("broker.ack", 3)
        before_faults = _counter("nomad.chaos.swallowed_faults")
        before_ring = flight_recorder.errors_total
        count_swallowed("worker", fault)
        assert fault.accounted is True
        assert _counter("nomad.chaos.swallowed_faults") == before_faults + 1
        assert flight_recorder.errors_total == before_ring + 1

    def test_plain_exception_not_tallied_as_chaos(self):
        before = _counter("nomad.chaos.swallowed_faults")
        count_swallowed("worker", ValueError("boring"))
        assert _counter("nomad.chaos.swallowed_faults") == before

    def test_swallow_ring_invariant_catches_silent_swallow(self):
        from nomad_tpu.server.server import Server

        server = Server()
        try:
            baseline = metrics_baseline()
            # a swallow counter bump with no ring event = hidden swallow
            global_metrics.incr("worker.swallowed_errors")
            report = check_cluster(server, baseline=baseline)
            assert not report.ok
            assert any(
                v.invariant == "swallow_ring" for v in report.violations
            )
        finally:
            server.shutdown()


# -- invariant checker negative tests (seeded violations are caught) ---------


class TestInvariantDetection:
    def _server(self):
        from nomad_tpu.server.server import Server

        return Server()

    def test_clean_idle_cluster_passes(self):
        server = self._server()
        try:
            assert check_cluster(server, baseline=metrics_baseline()).ok
        finally:
            server.shutdown()

    def test_lost_placement_detected(self):
        server = self._server()
        try:
            plane = FaultPlane(schedule=[])
            plane.committed["ghost-alloc"] = 1  # reported, never stored
            report = check_cluster(
                server, plane=plane, baseline=metrics_baseline()
            )
            assert any(
                v.invariant == "plan_ledger" and "ghost-alloc" in v.subject
                for v in report.violations
            )
        finally:
            server.shutdown()

    def test_double_commit_detected(self):
        server = self._server()
        try:
            plane = FaultPlane(schedule=[])
            plane.committed["dup-alloc"] = 2
            report = check_cluster(
                server, plane=plane, baseline=metrics_baseline()
            )
            assert any(
                v.invariant == "plan_ledger" and "2 times" in v.detail
                for v in report.violations
            )
        finally:
            server.shutdown()

    def test_broker_imbalance_detected(self):
        server = self._server()
        try:
            server.eval_broker.counters["dequeues"] += 1  # unresolved
            report = check_cluster(server, baseline=metrics_baseline())
            assert any(
                v.invariant == "broker_conservation"
                for v in report.violations
            )
        finally:
            server.shutdown()

    def test_leaked_overlay_marker_detected(self):
        server = self._server()
        try:
            server.placement_overlay.commit_started()
            report = check_cluster(server, baseline=metrics_baseline())
            assert any(
                v.invariant == "overlay_drained" for v in report.violations
            )
        finally:
            server.shutdown()


# -- heartbeat expiry faults -------------------------------------------------


class _FakeNode:
    def __init__(self, id):
        self.id = id

    def terminal_status(self):
        return False


class _FakeStore:
    def __init__(self, node):
        self._node = node

    def node_by_id(self, node_id):
        return self._node if node_id == self._node.id else None

    def nodes(self):
        return [self._node]


class _FakeServer:
    def __init__(self, node):
        self.store = _FakeStore(node)
        self.marked_down = []

    def update_node_status(self, node_id, status):
        self.marked_down.append((node_id, status))


class TestHeartbeatFaults:
    def test_expiry_drop_defers_then_fires(self):
        from nomad_tpu.server.heartbeat import NodeHeartbeater

        now = [0.0]
        node = _FakeNode("n1")
        fake = _FakeServer(node)
        hb = NodeHeartbeater(fake, ttl=0.1, clock=lambda: now[0])
        plane = FaultPlane(
            schedule=[FaultSpec("heartbeat.expiry", 0, "drop")]
        )
        install(plane)
        try:
            hb.heartbeat("n1")
            hb.start()
            now[0] = 1.0  # expire: first sweep hits the drop fault
            deadline = time.monotonic() + 5.0
            while not plane.triggered and time.monotonic() < deadline:
                time.sleep(0.01)
            assert plane.triggered == [("heartbeat.expiry", 0, "drop")]
            assert fake.marked_down == []  # deferred, not lost
            now[0] = 3.0  # expire the re-armed timer: no fault left
            while not fake.marked_down and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            hb.stop()
            uninstall()
        assert [nid for nid, _s in fake.marked_down] == ["n1"]


# -- end-to-end runner scenarios ---------------------------------------------


def _small_run(seed, steps=40, **kw):
    kw.setdefault("quiesce_timeout", 60.0)
    return run_chaos(seed=seed, steps=steps, **kw)


class TestChaosRunner:
    def test_same_seed_bit_identical(self):
        a = _small_run(5)
        b = _small_run(5)
        assert a.ok and b.ok, a.render() + b.render()
        assert a.canonical() == b.canonical()
        assert a.canonical_json() == b.canonical_json()

    def test_worker_thread_kill_mid_merged_plan(self):
        # one kill inside enqueue_merged (nothing lands; full re-place on
        # redelivery) and one on the commit thread's next checkpoint —
        # when it falls after the submit, the applier has committed and
        # redelivered members must converge to no-ops
        schedule = [
            FaultSpec("plan_queue.enqueue_merged", 0, "kill"),
            FaultSpec("worker.commit", 1, "kill"),
        ]
        run = _small_run(11, steps=60, schedule=schedule)
        assert run.ok, run.render()
        kills = [t for t in run.triggered if t[2] == "kill"]
        assert kills, "no kill fired: scenario did not exercise the seam"
        # the boundary handler accounted every kill; none died silently
        assert run.report.info["counters"].get(
            "nomad.chaos.thread_kills", 0
        ) >= len(kills) - 1  # worker.commit entry-kill counts too

    def test_dropped_delivery_redelivered_exactly_once(self):
        run = _small_run(
            13, steps=30,
            schedule=[FaultSpec("broker.dequeue", 0, "drop")],
        )
        assert run.ok, run.render()
        c = run.report.info["broker"]
        assert c["chaos_dropped_deliveries"] == 1
        # the lost delivery is the only unack deadline that fires
        assert c["unack_timeouts"] == 1
        assert c["dequeues"] == c["acks"] + c["nacks"] + c["unack_timeouts"]

    def test_duplicate_redelivery_converges(self):
        run = _small_run(
            17, steps=30,
            schedule=[FaultSpec("broker.ack", 0, "duplicate")],
        )
        assert run.ok, run.render()
        c = run.report.info["broker"]
        assert c["chaos_dup_enqueues"] == 1
        # the duplicate was dequeued and resolved like any other eval
        assert c["dequeues"] == c["acks"] + c["nacks"] + c["unack_timeouts"]

    def test_seed_corpus_all_faults_zero_violations(self):
        for seed in (1, 2, 3, 4, 5):
            run = _small_run(seed, steps=40)
            assert run.ok, f"seed {seed}:\n" + run.render()

    def test_uninstalls_plane_even_on_failure(self):
        with pytest.raises(TypeError):
            run_chaos(seed=1, steps="not-a-count")
        assert active_plane() is None


class TestMigrationFaults:
    """Defrag two-phase moves under the fault plane (law 16)."""

    @pytest.fixture(scope="class", autouse=True)
    def _warm_migrate_kernel(self):
        """A run whose defrag thread spends its whole length in the
        first trace+compile of ``migrate_plan_kernel`` plans no move and
        reaches no ``migrate.*`` site; compile it before the clock of
        the runs below starts."""
        _small_run(11, steps=60)

    def test_move_drop_commits_nothing(self):
        run = _small_run(
            7, steps=60,
            schedule=[FaultSpec("migrate.move_drop", 0, "drop")],
        )
        assert run.ok, run.render()
        assert ("migrate.move_drop", 0, "drop") in run.triggered
        c = run.report.info["counters"]
        assert c.get("nomad.migrate.aborted", 0) >= 1
        # the dropped move left nothing behind for law 16 to tolerate
        assert run.report.checked["migration_conservation"]
        assert c.get("nomad.migrate.capacity_violations", 0) == 0

    def test_kill_mid_move_recovered_never_doubled(self):
        run = _small_run(
            11, steps=60,
            schedule=[FaultSpec("migrate.kill_mid_move", 0, "drop")],
        )
        assert run.ok, run.render()
        assert ("migrate.kill_mid_move", 0, "drop") in run.triggered
        c = run.report.info["counters"]
        # phase B was lost once; the recovery scan finished exactly that
        # half-move — law 16 (count + mid-move capacity) stays green
        assert c.get("nomad.migrate.interrupted", 0) >= 1
        assert c.get("nomad.migrate.recovered", 0) >= 1
        assert c.get("nomad.migrate.capacity_violations", 0) == 0
        assert run.report.checked["migration_conservation"]

    def test_migration_exercised_in_default_mix(self):
        # no explicit schedule: the seeded default mix must still drive
        # real moves, and the law judges them at every quiesce point
        run = _small_run(11, steps=60)
        assert run.ok, run.render()
        c = run.report.info["counters"]
        assert c.get("nomad.migrate.planned", 0) >= 1
        assert run.report.checked["migration_conservation"]


@pytest.mark.slow
class TestChaosSoak:
    def test_twenty_seed_matrix(self):
        for seed in range(1, 21):
            run = run_chaos(seed=seed, steps=200)
            assert run.ok, f"seed {seed}:\n" + run.render()
