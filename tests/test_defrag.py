"""DefragController — two-phase live migration against a live server.

Pins the safety contract end to end: phase A (replacement placed through
a confirmed cross-lane claim and the serialized applier) before phase B
(stop-only plan), half-moves finished by the recovery scan and never
doubled, candidates another subsystem owns left alone, and the operator
surfaces (HTTP endpoint, CLI, drain telemetry counters) wired through.
"""

import copy
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.chaos import FaultPlane, FaultSpec, install, uninstall
from nomad_tpu.server.defrag import (
    DEFRAG_DESC,
    DEFRAG_STOP_DESC,
)
from nomad_tpu.server.server import Server, ServerConfig
from nomad_tpu.structs import DrainStrategy, Resources
from nomad_tpu.utils.metrics import global_metrics


@pytest.fixture(autouse=True)
def _no_leaked_plane():
    yield
    uninstall()


def _counter(name: str) -> float:
    return global_metrics.snapshot()["counters"].get(name, 0.0)


def wait_until(fn, timeout=8.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


@pytest.fixture
def server():
    s = Server(ServerConfig(num_workers=2, heartbeat_ttl=60.0))
    s.establish_leadership()
    # fake client: pending allocs come up "running" shortly after
    # placement (defrag candidates must be running; replacements flip
    # too, exactly like drain waves)
    stop = threading.Event()

    def client_loop():
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


def _thin_job(job_id, count=1):
    j = mock.job()
    j.id = job_id
    j.task_groups[0].count = count
    j.task_groups[0].tasks[0].resources = Resources(cpu=800, memory_mb=512)
    return j


def _filler_job(count):
    j = mock.job()
    j.id = "filler"
    j.task_groups[0].count = count
    # 3000cpu: exactly one per node (two never fit), so the fleet
    # fragments deterministically when the filler deregisters
    j.task_groups[0].tasks[0].resources = Resources(cpu=3000, memory_mb=1024)
    return j


def _fragment(server, n_nodes=3):
    """Deterministic fragmentation: a fat filler pins one slot per node,
    a thin job lands one alloc per node beside it, then the filler
    leaves — thin load smeared across every node."""
    nodes = [mock.node() for _ in range(n_nodes)]
    for n in nodes:
        server.register_node(n)
    server.register_job(_filler_job(n_nodes))
    assert server.wait_for_evals(10)
    thin = _thin_job("thin", count=n_nodes)
    server.register_job(thin)
    assert server.wait_for_evals(10)
    server.deregister_job(thin.namespace, "filler")
    assert server.wait_for_evals(10)
    assert wait_until(
        lambda: all(
            a.client_status == "running"
            for a in server.store.allocs_by_job(thin.namespace, thin.id)
            if not a.terminal_status()
        )
    )
    return nodes, thin


def _live_thin(server, thin):
    return [
        a
        for a in server.store.allocs_by_job(thin.namespace, thin.id)
        if not a.terminal_status()
    ]


def _spread(server, thin):
    return len({a.node_id for a in _live_thin(server, thin)})


# -- the two-phase move ------------------------------------------------------


class TestTwoPhaseMove:
    def test_cycle_consolidates_and_pairs_correctly(self, server):
        nodes, thin = _fragment(server)
        assert _spread(server, thin) == len(nodes)
        before = {a.id for a in _live_thin(server, thin)}

        total = 0
        for _ in range(8):
            moved = server.defrag.run_cycle()
            total += moved
            if _spread(server, thin) == 1:
                break
            # replacements must come up running before the next pass
            assert wait_until(
                lambda: all(
                    a.client_status == "running"
                    for a in _live_thin(server, thin)
                )
            )
        assert total > 0
        assert _spread(server, thin) < len(nodes)
        # count conserved: exactly as many live allocs as the group asks
        assert len(_live_thin(server, thin)) == len(before)

        # every completed move left the canonical pair: replacement
        # marked DEFRAG_DESC linking a source stopped with the phase-B
        # description
        replaced = [
            a
            for a in _live_thin(server, thin)
            if a.desired_description == DEFRAG_DESC
        ]
        assert replaced
        for r in replaced:
            old = server.store.alloc_by_id(r.previous_allocation)
            assert old is not None
            assert old.terminal_status() or old.desired_status == "stop"
            assert old.desired_description == DEFRAG_STOP_DESC
        assert _counter("nomad.migrate.capacity_violations") == 0.0

    def test_move_drop_site_aborts_before_any_commit(self, server):
        _, thin = _fragment(server)
        live_before = {a.id for a in _live_thin(server, thin)}
        planned0 = _counter("nomad.migrate.planned")
        aborted0 = _counter("nomad.migrate.aborted")

        install(FaultPlane(schedule=[FaultSpec("migrate.move_drop", 0, "drop")]))
        try:
            server.defrag.run_cycle()
        finally:
            uninstall()

        assert _counter("nomad.migrate.planned") > planned0
        assert _counter("nomad.migrate.aborted") == aborted0 + 1
        # the dropped move committed NOTHING: no replacement rides under
        # a still-live source (conservation holds trivially)
        for a in _live_thin(server, thin):
            if a.id in live_before:
                continue
            old = server.store.alloc_by_id(a.previous_allocation)
            assert old is None or old.terminal_status() or (
                old.desired_status == "stop"
            )

    def test_paused_controller_plans_nothing(self, server):
        _, thin = _fragment(server)
        server.defrag.paused = True
        planned0 = _counter("nomad.migrate.planned")
        assert server.defrag.run_cycle() == 0
        assert _counter("nomad.migrate.planned") == planned0
        server.defrag.paused = False
        assert server.defrag.run_cycle() > 0


# -- half-move recovery ------------------------------------------------------


def _interrupt_one_move(server):
    """Run a cycle with kill_mid_move armed: phase A commits, phase B is
    lost, leaving exactly the half-move recovery must finish."""
    interrupted0 = _counter("nomad.migrate.interrupted")
    install(
        FaultPlane(schedule=[FaultSpec("migrate.kill_mid_move", 0, "drop")])
    )
    try:
        server.defrag.run_cycle()
    finally:
        uninstall()
    assert _counter("nomad.migrate.interrupted") == interrupted0 + 1


def _half_moves(server):
    out = []
    for a in server.store.allocs():
        if a.terminal_status() or a.desired_description != DEFRAG_DESC:
            continue
        if not a.previous_allocation:
            continue
        old = server.store.alloc_by_id(a.previous_allocation)
        if old is not None and not old.terminal_status():
            out.append((a, old))
    return out


class TestRecovery:
    def test_recover_finishes_half_move(self, server):
        _, thin = _fragment(server)
        _interrupt_one_move(server)
        pairs = _half_moves(server)
        assert len(pairs) >= 1
        recovered0 = _counter("nomad.migrate.recovered")

        server.defrag.recover()

        assert _half_moves(server) == []
        assert _counter("nomad.migrate.recovered") == recovered0 + len(pairs)
        for _, old in pairs:
            cur = server.store.alloc_by_id(old.id)
            assert cur.desired_status == "stop"
            assert cur.desired_description == DEFRAG_STOP_DESC

    def test_mid_move_source_never_replanned(self, server):
        """The double-commit regression: while a half-move is in flight,
        neither half may be a candidate — a second move of the source
        would put two live replacements on one group slot (law 16)."""
        _, thin = _fragment(server)
        _interrupt_one_move(server)
        pairs = _half_moves(server)
        assert pairs
        replacement, old = pairs[0]
        # replacements flip to running just like anything else — the
        # dangerous moment is when both halves look healthy
        wait_until(
            lambda: (
                server.store.alloc_by_id(replacement.id).client_status
                == "running"
            )
        )

        snap = server.store.snapshot()
        node_row = {n.id: i for i, n in enumerate(snap.nodes())}
        candidates = {
            a.id for a, _ in server.defrag._candidates(snap, node_row)
        }
        assert old.id not in candidates, "mid-move source re-planned"
        assert replacement.id not in candidates, "mid-move replacement planned"

        # and the next full cycle (recovery scan first) converges: the
        # half-move resolves, no slot ever holds two live replacements
        server.defrag.run_cycle()
        assert _half_moves(server) == []
        by_prev = {}
        for a in _live_thin(server, thin):
            if a.desired_description == DEFRAG_DESC and a.previous_allocation:
                by_prev.setdefault(a.previous_allocation, []).append(a)
        assert all(len(v) == 1 for v in by_prev.values())


# -- candidate discipline ----------------------------------------------------


class TestCandidates:
    def test_owned_allocs_excluded(self, server):
        n1, n2 = mock.node(), mock.node()
        server.register_node(n1)
        server.register_node(n2)
        sysjob = mock.system_job()
        server.register_job(sysjob)
        gang = _thin_job("gangjob", count=2)
        gang.gang = {"groups": [gang.task_groups[0].name]}
        server.register_job(gang)
        plain = _thin_job("plain", count=2)
        server.register_job(plain)
        assert server.wait_for_evals(10)
        assert wait_until(
            lambda: all(
                a.client_status == "running"
                for a in server.store.allocs()
                if not a.terminal_status()
            )
        )
        # mark one plain alloc as drainer-owned
        from nomad_tpu.structs.alloc import DesiredTransition

        victim = next(
            a
            for a in server.store.allocs_by_job(plain.namespace, plain.id)
            if not a.terminal_status()
        )
        marked = victim.copy_for_update()
        marked.desired_transition = DesiredTransition(migrate=True)
        server.store.upsert_allocs(
            server.store.latest_index + 1, [marked]
        )

        snap = server.store.snapshot()
        node_row = {n.id: i for i, n in enumerate(snap.nodes())}
        cands = server.defrag._candidates(snap, node_row)
        ids = {a.id for a, _ in cands}
        jobs = {a.job_id for a, _ in cands}
        assert victim.id not in ids, "drainer-owned alloc offered for defrag"
        assert sysjob.id not in jobs, "system alloc offered for defrag"
        assert "gangjob" not in jobs, "gang member offered for defrag (law 15)"
        # deterministic order: sorted by (namespace, job, name)
        keys = [(a.namespace, a.job_id, a.name) for a, _ in cands]
        assert keys == sorted(keys)

    def test_notify_drain_complete_gated_on_interval(self, server):
        server.defrag.interval = 0.0
        server.defrag._wake.clear()
        server.defrag.notify_drain_complete()
        assert not server.defrag._wake.is_set()
        server.defrag.interval = 30.0
        server.defrag.notify_drain_complete()
        assert server.defrag._wake.is_set()
        server.defrag.interval = 0.0
        server.defrag._wake.clear()

    def test_status_shape(self, server):
        st = server.defrag.status()
        assert set(st) == {
            "enabled",
            "paused",
            "interval",
            "budget",
            "cycles",
            "packing_efficiency",
            "counters",
        }
        assert st["enabled"] is False
        assert all(k.startswith("nomad.migrate.") for k in st["counters"])


# -- drain telemetry (graceful vs forced split) ------------------------------


class TestDrainTelemetry:
    def test_graceful_drain_counts_migrated(self, server):
        n1, n2 = mock.node(), mock.node()
        server.register_node(n1)
        server.register_node(n2)
        job = _thin_job("drainjob", count=2)
        server.register_job(job)
        assert server.wait_for_evals(10)
        victim = max(
            (n1, n2),
            key=lambda n: len(server.store.allocs_by_node(n.id)),
        )
        migrated0 = _counter("nomad.drain.migrated")
        forced0 = _counter("nomad.drain.force_stops")
        server.update_node_drain(victim.id, DrainStrategy(deadline_s=3600))
        assert wait_until(
            lambda: not [
                a
                for a in server.store.allocs_by_node(victim.id)
                if not a.terminal_status() and a.desired_status == "run"
            ]
        )
        assert _counter("nomad.drain.migrated") > migrated0
        assert _counter("nomad.drain.force_stops") == forced0

    def test_deadline_expiry_counts_force_stops(self, server):
        n1, n2 = mock.node(), mock.node()
        server.register_node(n1)
        server.register_node(n2)
        job = _thin_job("forcejob", count=2)
        server.register_job(job)
        assert server.wait_for_evals(10)
        victim = max(
            (n1, n2),
            key=lambda n: len(server.store.allocs_by_node(n.id)),
        )
        forced0 = _counter("nomad.drain.force_stops")
        server.update_node_drain(victim.id, DrainStrategy(deadline_s=-1))
        assert wait_until(
            lambda: _counter("nomad.drain.force_stops") > forced0
        )


# -- operator surfaces: HTTP + CLI -------------------------------------------


class TestOperatorSurfaces:
    @pytest.fixture
    def http(self, server):
        from nomad_tpu.api.http import HTTPAgent

        agent = HTTPAgent(server, None, port=0)
        agent.start()
        yield agent
        agent.stop()

    def test_http_get_and_post(self, server, http):
        from nomad_tpu.api.client import NomadClient

        c = NomadClient(http.address)
        st = c._request("GET", "/v1/operator/defrag")
        assert st["enabled"] is False and st["paused"] is False

        st = c.post("/v1/operator/defrag", body={"paused": True})
        assert st["paused"] is True
        assert server.defrag.paused is True
        st = c.post("/v1/operator/defrag", body={"paused": False})
        assert st["paused"] is False

        out = c.post("/v1/operator/defrag")
        assert out.get("triggered") is True

    def test_http_trace_carries_migrate_block(self, server, http):
        from nomad_tpu.api.client import NomadClient

        global_metrics.incr("nomad.migrate.planned", 0)
        # the trace surface fingerprints every kernel spec the process
        # has recorded; late in a full tier-1 session that alone runs
        # close to the client's default 10 s
        c = NomadClient(http.address, timeout=60.0)
        idx = c._request("GET", "/v1/agent/trace")
        assert "migrate" in idx
        assert all(
            k.startswith(("nomad.migrate.", "nomad.drain."))
            for k in idx["migrate"]
        )

    def test_cli_operator_defrag(self, server, http, capsys):
        from nomad_tpu.cli.main import main

        assert main(["-address", http.address, "operator", "defrag"]) == 0
        out = capsys.readouterr().out
        assert "packing" in out or "efficiency" in out or "budget" in out

        assert (
            main(
                ["-address", http.address, "operator", "defrag", "--trigger"]
            )
            == 0
        )
        assert (
            main(["-address", http.address, "operator", "defrag", "--pause"])
            == 0
        )
        assert server.defrag.paused is True
        assert (
            main(["-address", http.address, "operator", "defrag", "--resume"])
            == 0
        )
        assert server.defrag.paused is False
