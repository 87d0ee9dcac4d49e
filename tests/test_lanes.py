"""Deterministic lane ownership (nomad_tpu.server.lanes) — the
structurally conflict-free multi-worker commit path.

Covers: the pure lane map (and its byte-identity with the eval broker's
partition hash, so broker routing IS lane routing), lane-affine dequeue,
the reserve → confirm → release cross-lane claim protocol (including
dropped handoffs and settled-node blocking), 2-worker placements being
byte-identical to the 1-worker reference on the same job stream, and the
2-worker chaos scenario. The slow soak at the bottom is the acceptance
matrix: 20 seeds × 200 steps at 4 batching workers, zero violations.
"""

import time
import zlib

import pytest

from nomad_tpu import mock
from nomad_tpu.broker.eval_broker import EvalBroker
from nomad_tpu.chaos.plane import FaultPlane, FaultSpec, install, uninstall
from nomad_tpu.chaos.runner import run_chaos
from nomad_tpu.server.lanes import LaneClaims, LaneMap
from nomad_tpu.server.server import Server, ServerConfig
from nomad_tpu.structs import Evaluation


def ev(job_id, type_="service"):
    return Evaluation(
        namespace="default", job_id=job_id, type=type_, priority=50,
        status="pending",
    )


# -- the pure map ------------------------------------------------------------


class TestLaneMap:
    def test_job_hash_is_byte_identical_to_broker_partition(self):
        """The whole point of reusing the broker's crc: an eval dequeued
        from worker w's partitions belongs to one of w's lanes BY THE
        SAME ARITHMETIC, no second hash to drift."""
        lanes = LaneMap(num_lanes=16, num_batch_workers=2)
        b = EvalBroker(n_partitions=16)
        for i in range(50):
            e = ev(f"job-{i}")
            expected = zlib.crc32(
                f"{e.namespace}/{e.job_id}".encode()
            ) % 16
            assert lanes.lane_of_job(e.namespace, e.job_id) == expected
            assert b._queue_key(e) == f"service#p{expected}"

    def test_lane_count_is_clamped_to_worker_count(self):
        assert LaneMap(num_lanes=2, num_batch_workers=4).num_lanes == 4
        assert LaneMap(num_lanes=0, num_batch_workers=1).num_lanes == 1

    def test_worker_lane_sets_partition_the_lanes(self):
        lanes = LaneMap(num_lanes=16, num_batch_workers=3)
        sets = [set(lanes.lanes_of_worker(w)) for w in range(3)]
        assert sets[0] | sets[1] | sets[2] == set(range(16))
        assert sets[0].isdisjoint(sets[1])
        assert sets[0].isdisjoint(sets[2])
        assert sets[1].isdisjoint(sets[2])
        # every batching worker owns at least one lane
        assert all(sets)

    def test_solo_workers_own_no_lanes(self):
        lanes = LaneMap(num_lanes=16, num_batch_workers=2)
        assert lanes.lanes_of_worker(2) == ()
        assert lanes.lanes_of_worker(7) == ()

    def test_assignment_is_deterministic_across_instances(self):
        a = LaneMap(num_lanes=16, num_batch_workers=4)
        b = LaneMap(num_lanes=16, num_batch_workers=4)
        for i in range(40):
            assert a.lane_of_node(f"node-{i}") == b.lane_of_node(f"node-{i}")
            assert a.owner_of_job("default", f"j{i}") == b.owner_of_job(
                "default", f"j{i}"
            )

    def test_lane_map_independent_of_worker_count(self):
        """lane_of_* must be a function of the id alone: re-running a
        cluster with a different worker count moves lane OWNERSHIP, never
        the lanes themselves (byte-identity depends on this)."""
        one = LaneMap(num_lanes=16, num_batch_workers=1)
        four = LaneMap(num_lanes=16, num_batch_workers=4)
        for i in range(40):
            assert one.lane_of_node(f"n-{i}") == four.lane_of_node(f"n-{i}")
            assert one.lane_of_job("ns", f"j-{i}") == four.lane_of_job(
                "ns", f"j-{i}"
            )

    def test_assignments_surface(self):
        lanes = LaneMap(num_lanes=4, num_batch_workers=2)
        assert lanes.assignments() == {0: (0, 2), 1: (1, 3)}


# -- lane-affine dequeue -----------------------------------------------------


class TestLaneAffineDequeue:
    def test_tuple_partition_dequeues_exactly_the_owned_lanes(self):
        lanes = LaneMap(num_lanes=16, num_batch_workers=2)
        b = EvalBroker(n_partitions=16)
        b.set_enabled(True)
        evs = [ev(f"job-{i}") for i in range(60)]
        b.enqueue_all(evs)
        got0 = b.dequeue_many(
            ["service"], 60, timeout=0.1, partition=lanes.lanes_of_worker(0)
        )
        got1 = b.dequeue_many(
            ["service"], 60, timeout=0.1, partition=lanes.lanes_of_worker(1)
        )
        ids0 = {e.job_id for e, _ in got0}
        ids1 = {e.job_id for e, _ in got1}
        assert ids0.isdisjoint(ids1)
        assert ids0 | ids1 == {f"job-{i}" for i in range(60)}
        # every dequeued eval really belongs to the dequeuing worker
        for e, _tok in got0:
            assert lanes.owner_of_job(e.namespace, e.job_id) == 0
        for e, _tok in got1:
            assert lanes.owner_of_job(e.namespace, e.job_id) == 1

    def test_single_int_partition_still_works(self):
        b = EvalBroker(n_partitions=4)
        b.set_enabled(True)
        b.enqueue_all([ev(f"j-{i}") for i in range(12)])
        total = 0
        for p in range(4):
            total += len(
                b.dequeue_many(["service"], 12, timeout=0.05, partition=p)
            )
        assert total == 12


# -- the claim protocol ------------------------------------------------------


class _IdleOverlay:
    def passes_in_flight(self):
        return 0

    def pending_on(self, node_id):
        return False


class _BusyOverlay(_IdleOverlay):
    def passes_in_flight(self):
        return 1


class _DirtyOverlay(_IdleOverlay):
    def __init__(self, dirty):
        self.dirty = set(dirty)

    def pending_on(self, node_id):
        return node_id in self.dirty


class _Overlays:
    def __init__(self, per_worker):
        self.per_worker = per_worker

    def for_worker(self, w):
        return self.per_worker[w]


class TestLaneClaims:
    def _claims(self, overlays=None):
        return LaneClaims(
            LaneMap(num_lanes=16, num_batch_workers=2),
            overlays=overlays,
            sleep=lambda _s: None,
        )

    def _foreign_node(self, claims, claimant):
        """A node id NOT owned by ``claimant`` (so the claim is a real
        cross-lane handoff)."""
        for i in range(64):
            nid = f"claim-node-{i}"
            if claims.lanes.owner_of_node(nid) != claimant:
                return nid
        raise AssertionError("no foreign node found")

    def test_reserve_refuses_overlapping_claims(self):
        claims = self._claims()
        nid = self._foreign_node(claims, 0)
        first = claims.reserve(0, "ev-1", {nid: []})
        assert first is not None
        assert claims.reserve(0, "ev-2", {nid: []}) is None
        assert claims.counters["reserve_refused"] == 1
        claims.release(first)
        assert claims.drained()
        # released: reservable again
        assert claims.reserve(0, "ev-3", {nid: []}) is not None

    def test_confirm_rejected_while_owner_pass_in_flight(self):
        claims = self._claims(
            overlays=_Overlays({0: _IdleOverlay(), 1: _BusyOverlay()})
        )
        # claimant 0 grabs a node owned by worker 1, whose pass never
        # quiesces: the bounded wait expires and the handoff is rejected
        nid = next(
            f"n-{i}" for i in range(64)
            if claims.lanes.owner_of_node(f"n-{i}") == 1
        )
        claim = claims.reserve(0, "ev-1", {nid: []})
        assert claim is not None
        assert claims.confirm(claim) is False
        assert claims.counters["confirm_rejected"] == 1

    def test_confirm_rejected_on_pending_peer_delta(self):
        nid = "dirty-node"
        claims = LaneClaims(
            LaneMap(num_lanes=16, num_batch_workers=2),
            sleep=lambda _s: None,
        )
        owner = claims.lanes.owner_of_node(nid)
        claimant = 1 - owner
        claims.overlays = _Overlays({
            owner: _DirtyOverlay({nid}),
            claimant: _IdleOverlay(),
        })
        claim = claims.reserve(claimant, "ev-1", {nid: []})
        assert claim is not None
        assert claims.confirm(claim) is False

    def test_confirm_succeeds_when_owner_is_quiesced(self):
        claims = self._claims(
            overlays=_Overlays({0: _IdleOverlay(), 1: _IdleOverlay()})
        )
        nid = self._foreign_node(claims, 0)
        claim = claims.reserve(0, "ev-1", {nid: []})
        assert claims.confirm(claim) is True
        assert claim.confirmed
        assert claims.counters["confirms"] == 1

    def test_dropped_handoff_releases_cleanly(self):
        """A chaos-dropped confirmation must fail the handoff AND leave
        no leaked reservation once the caller releases."""
        plane = FaultPlane(
            schedule=[FaultSpec("lane.handoff_drop", 0, "drop")]
        )
        install(plane)
        try:
            claims = self._claims()
            nid = self._foreign_node(claims, 0)
            claim = claims.reserve(0, "ev-1", {nid: []})
            assert claim is not None
            assert claims.confirm(claim) is False
            assert claims.counters["handoff_drops"] == 1
            claims.release(claim, committed=False)
        finally:
            uninstall()
        assert claims.drained()
        assert claims.blocked_node_ids() == frozenset()

    def test_committed_release_settles_until_owner_rebases(self):
        claims = self._claims()
        nid = self._foreign_node(claims, 0)
        owner = claims.lanes.owner_of_node(nid)
        claim = claims.reserve(0, "ev-1", {nid: []})
        assert claims.confirm(claim) is True
        claims.release(claim, committed=True)
        # active claim gone, but the node stays blocked for everyone
        assert claims.drained()
        assert nid in claims.blocked_node_ids()
        # and is NOT reservable while settled
        assert claims.reserve(0, "ev-2", {nid: []}) is None
        # owner rebases onto a fresh epoch: unblocked
        claims.clear_settled(owner)
        assert claims.blocked_node_ids() == frozenset()
        assert claims.reserve(0, "ev-3", {nid: []}) is not None

    def test_release_is_idempotent(self):
        claims = self._claims()
        nid = self._foreign_node(claims, 0)
        claim = claims.reserve(0, "ev-1", {nid: []})
        claims.release(claim)
        claims.release(claim)
        claims.release(claim, committed=True)  # late flags change nothing
        assert claims.counters["releases"] == 1
        assert claims.settled_count() == 0

    def test_snapshot_shape(self):
        claims = self._claims()
        nid = self._foreign_node(claims, 0)
        claims.reserve(0, "ev-1", {nid: []})
        snap = claims.snapshot()
        assert snap["active_claims"] == 1
        assert snap["claimed_nodes"] == [nid]
        assert snap["counters"]["reserves"] == 1


class TestLaneModeConfig:
    def test_two_batchers_without_lanes_are_refused(self):
        """Lane mode is what keeps two batching workers' passes apart
        (partitioned broker, claims); switching it off is only allowed
        where there is nothing to keep apart."""
        with pytest.raises(ValueError, match="lane_mode=False"):
            ServerConfig(num_workers=2, num_batch_workers=2, lane_mode=False)
        assert ServerConfig(num_workers=2, num_batch_workers=2).lane_mode
        assert not ServerConfig(
            num_workers=2, num_batch_workers=1, lane_mode=False
        ).lane_mode
        assert not ServerConfig(num_workers=1).lane_mode


# -- byte-identity: 2 workers ≡ 1 worker -------------------------------------


def _lane_cluster(num_batch_workers):
    s = Server(
        ServerConfig(
            num_workers=num_batch_workers,
            num_batch_workers=num_batch_workers,
            # the 1-worker reference opts INTO lane mode so both runs
            # take the identical code path (lane-salted batch passes,
            # lane-partitioned broker); at 1 worker it owns every lane
            lane_mode=True,
            heartbeat_ttl=3600.0,
        )
    )
    s.establish_leadership()
    for i in range(12):
        s.register_node(
            mock.node(id=f"lane-node-{i:02d}", name=f"lane-node-{i:02d}")
        )
    return s


def _job(seq, count):
    j = mock.job(id=f"lane-job-{seq:03d}", name=f"lane-job-{seq:03d}")
    j.task_groups[0].count = count
    j.task_groups[0].tasks[0].resources.cpu = 200 + 50 * (seq % 3)
    return j


def _drain_lanes(server, timeout=10.0):
    """Wait until no claim is active and every settled node has been
    rebased (the workers' idle loop clears them within a poll or two) —
    the point where the NEXT eval sees an unblocked cluster, which is
    what 'same seeded stream' means for the byte-identity contract."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        claims = server.lane_claims
        if claims.drained() and claims.settled_count() == 0:
            return True
        time.sleep(0.01)
    return False


def _placements(server, prefix="lane-job-"):
    return sorted(
        (a.job_id, a.name, a.node_id)
        for a in server.store.allocs()
        if a.job_id.startswith(prefix) and not a.terminal_status()
    )


class TestByteIdentity:
    @pytest.mark.slow
    def test_two_worker_placements_identical_to_one_worker(self):
        """Same seeded job stream, registered sequentially with a drain
        between registrations (so scheduling order is pinned and only
        the worker count varies): every placement must land on the SAME
        node either way. This is the determinism half of the lane
        contract — lane_of_* is worker-count independent, the placement
        salt derives from the job's lane, and the overlay each eval
        scores against is equally fresh in both runs."""
        streams = []
        for workers in (1, 2):
            s = _lane_cluster(workers)
            try:
                for seq in range(10):
                    s.register_job(_job(seq, count=1 + seq % 3))
                    assert s.wait_for_evals(timeout=60)
                    assert _drain_lanes(s)
                streams.append(_placements(s))
            finally:
                s.shutdown()
        assert streams[0] == streams[1]
        assert len(streams[0]) == sum(1 + seq % 3 for seq in range(10))


# -- chaos scenarios ---------------------------------------------------------


class TestLaneChaos:
    def test_two_worker_chaos_run_zero_violations(self):
        run = run_chaos(seed=3, steps=40, num_batch_workers=2)
        assert run.ok, run.render()
        lanes = run.report.info.get("lanes", {})
        assert lanes.get("active_claims") == 0
        c = run.report.info.get("counters", {})
        assert c.get("nomad.plan.lane_conflicts", 0) == 0

    def test_handoff_faults_and_kill_mid_handoff_converge(self):
        """The satellite-2 scenario: dropped handoffs, delayed reserves,
        and a worker thread killed mid-handoff must all release their
        reservations — claims drained, zero lane conflicts."""
        schedule = [
            FaultSpec("lane.handoff_delay", 0, "delay"),
            FaultSpec("lane.handoff_drop", 0, "drop"),
            FaultSpec("lane.handoff_drop", 1, "kill"),
        ]
        run = run_chaos(
            seed=9, steps=60, num_batch_workers=2, schedule=schedule
        )
        assert run.ok, run.render()
        lanes = run.report.info.get("lanes", {})
        assert lanes.get("active_claims") == 0


@pytest.mark.slow
class TestLaneSoak:
    def test_twenty_seed_matrix_at_four_workers(self):
        """The acceptance matrix: 20 seeds × 200 steps with the full
        fault set (including handoff faults and thread kills) at
        num_batch_workers=4 — every run zero violations and
        nomad.plan.lane_conflicts == 0."""
        for seed in range(1, 21):
            run = run_chaos(seed=seed, steps=200, num_batch_workers=4)
            assert run.ok, f"seed {seed}:\n" + run.render()
            c = run.report.info.get("counters", {})
            assert c.get("nomad.plan.lane_conflicts", 0) == 0, (
                f"seed {seed}: lane conflicts"
            )
            lanes = run.report.info.get("lanes", {})
            assert lanes.get("active_claims") == 0, f"seed {seed}"
