"""Gang scheduling & topology-constrained placement on cp-pack.

Pins the tentpole contracts from the ISSUE: the gang stanza validates
with exact messages at jobspec parse and job admission, the gang device
kernel is byte-identical to its NumPy host oracle across seeds and
meshes, a gang-less batch routed through cp-gang is bit-identical to
cp-pack (the Python gate dispatches to the UNCHANGED cp_place_kernel),
the atomic-release post-pass leaves an infeasible gang fully absent,
the scheduler-level seam (law 15) releases every member and lands the
whole gang in ONE blocked eval with per-group gang rejections that
survive the codec, the ``gang.commit_drop`` chaos site holds the
invariant, and the seeded A/B report is byte-reproducible with its
canonical schema pinned.
"""

import json

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.chaos import uninstall
from nomad_tpu.client.fingerprint import normalize_topology
from nomad_tpu.device.cp import (
    cp_gang_place_kernel,
    oracle_cp_gang_place,
    release_incomplete_gangs,
    topo_onehot,
)
from nomad_tpu.jobspec import JobspecError, parse_job_file
from nomad_tpu.scheduler.cp import (
    GANG_SCHEMA,
    CpGangPlacementKernel,
    CpPlacementKernel,
    build_cp_asks,
    build_cp_batch,
    build_gang_asks,
    build_gang_inputs,
    build_topo_fleet,
    cp_schema_of,
    run_gang_ab,
)
from nomad_tpu.scheduler.hetero import build_mixed_fleet
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.state import SchedulerConfiguration
from nomad_tpu.structs import Resources, Task, TaskGroup
from nomad_tpu.structs.job import (
    JobValidationError,
    validate_gang,
    validate_job,
)
from nomad_tpu.utils import backend
from nomad_tpu.utils.metrics import global_metrics


@pytest.fixture(autouse=True)
def _no_leaked_plane():
    yield
    uninstall()


def _counter(name: str) -> float:
    return global_metrics.snapshot()["counters"].get(name, 0.0)


def _fleet_and_gang_asks(n_nodes=64, n_jobs=4, groups=3, seed=7):
    ct = build_topo_fleet(n_nodes, seed=seed)
    return ct, build_gang_asks(ct, n_jobs, groups, seed=seed + 1)


def _gang_io(batch, gi):
    return (
        batch.capacity, batch.used, batch.asks, batch.counts,
        batch.eligible, batch.scores, batch.prio, batch.job_counts,
        batch.distinct, batch.jobgrp, gi.gang, gi.w_rack, gi.w_pod,
        gi.w_ici, gi.rack_oh, gi.pod_oh, gi.ici_oh, batch.lam0,
    )


def _gang_job(counts=(2, 2), resources=None):
    """Two-group gang job on mock nodes (no topology — the gang is
    about atomicity here, the topology term prices to zero)."""
    j = mock.job(id="gang-job", name="gang-job")
    res = resources or [Resources(cpu=500, memory_mb=256)] * len(counts)
    j.task_groups = [
        TaskGroup(
            name=f"g{i}",
            count=c,
            tasks=[Task(name=f"g{i}", driver="exec", resources=res[i])],
        )
        for i, c in enumerate(counts)
    ]
    j.gang = {"groups": [tg.name for tg in j.task_groups]}
    return j


# -- gang stanza validation ---------------------------------------------------


class TestGangStanza:
    HCL = """
job "train" {
  datacenters = ["dc1"]
  group "workers" { count = 4
    task "w" { driver = "exec" resources { cpu = 500 memory = 256 } } }
  group "ps" { count = 2
    task "p" { driver = "exec" resources { cpu = 500 memory = 256 } } }
  gang {
    groups = ["workers", "ps"]
    colocate { level = "rack" weight = 2.0 }
  }
}
"""

    def test_jobspec_gang_round_trips(self):
        job = parse_job_file(self.HCL)
        assert job.gang == {
            "groups": ["workers", "ps"],
            "colocate": {"level": "rack", "weight": 2.0},
        }
        validate_job(job)  # raises JobValidationError on any problem

    def test_jobspec_bad_gang_raises(self):
        bad = self.HCL.replace('level = "rack"', 'level = "row"')
        with pytest.raises(JobspecError) as e:
            parse_job_file(bad)
        assert "gang.colocate.level must be one of rack/pod/ici" in str(
            e.value
        )

    @pytest.mark.parametrize(
        "gang,needle",
        [
            ({"teams": ["a"]}, "gang has unknown key 'teams'"),
            (
                {"groups": []},
                "gang.groups must be a non-empty list of group names",
            ),
            (
                {"groups": ["a", "a"]},
                "gang.groups lists 'a' twice",
            ),
            (
                # ici is a real level now (hop-distance pricing) — an
                # unknown level still rejects
                {"groups": ["a"], "spread": {"level": "row"}},
                "gang.spread.level must be one of rack/pod/ici, got 'row'",
            ),
            (
                {
                    "groups": ["a"],
                    "colocate": {"level": "pod"},
                    "spread": {"level": "pod"},
                },
                "gang.colocate and gang.spread both target level 'pod'",
            ),
            (
                {"groups": ["a"], "colocate": {"level": "rack",
                                               "weight": "big"}},
                "gang.colocate.weight must be a number, got str",
            ),
        ],
    )
    def test_validation_matrix(self, gang, needle):
        assert needle in "\n".join(validate_gang(gang))

    def test_admission_checks_member_references(self):
        j = _gang_job()
        j.gang = {"groups": ["g0", "ghost"]}
        with pytest.raises(JobValidationError) as e:
            validate_job(j)
        assert "gang.groups references unknown group 'ghost'" in str(e.value)

    def test_normalize_topology_drops_malformed(self):
        assert normalize_topology("rack=r03,pod=p1,ici=2.1") == {
            "rack": "r03", "pod": "p1", "ici": "2.1",
        }
        assert normalize_topology("rack=r1,row=7,pod=,junk") == {
            "rack": "r1"
        }

    def test_topology_feeds_computed_node_class(self):
        a = mock.node(topology={"rack": "r01", "pod": "p0"})
        b = mock.node(topology={"rack": "r02", "pod": "p0"})
        b.id, b.name = a.id, a.name
        a.compute_class()
        b.compute_class()
        assert a.computed_class != b.computed_class
        assert a.lookup_attribute("node.topology.rack") == "r01"


# -- device/oracle byte parity ------------------------------------------------


class TestGangOracleParity:
    @pytest.mark.parametrize("seed", [3, 11])
    def test_device_matches_oracle_bitwise(self, seed):
        ct, asks = _fleet_and_gang_asks(64, 4, 3, seed=seed)
        batch = build_cp_batch(ct, asks)
        gi = build_gang_inputs(ct, asks)
        d = cp_gang_place_kernel(
            *_gang_io(batch, gi), steps=batch.steps, max_c=batch.max_c
        )
        o = oracle_cp_gang_place(
            *_gang_io(batch, gi), batch.steps, batch.max_c
        )
        np.testing.assert_array_equal(np.asarray(d[0]), o[0])
        for di, oi in ((d[1], o[1]), (d[2], o[2]), (d[4], o[4])):
            # f32 outputs compare as uint32 views: byte-identical
            np.testing.assert_array_equal(
                np.asarray(di).view(np.uint32), oi.view(np.uint32)
            )
        assert int(np.asarray(d[3])) == o[3]
        np.testing.assert_array_equal(np.asarray(d[5]), o[5])
        assert (np.asarray(d[0]) >= 0).any()

    def test_identical_score_rows_do_not_deadlock(self):
        """Gang members of one job share a score row (same ask) — the
        commit-as-you-win reservation design must make round progress
        where a per-round all-members-win gate would starve."""
        ct, asks = _fleet_and_gang_asks(32, 1, 3, seed=5)
        batch = build_cp_batch(ct, asks)
        gi = build_gang_inputs(ct, asks)
        choices = np.asarray(cp_gang_place_kernel(
            *_gang_io(batch, gi), steps=batch.steps, max_c=batch.max_c
        )[0])
        per_member = (choices >= 0).sum(axis=1)
        assert (per_member == batch.counts).all()


class TestMeshEquivalence:
    @pytest.fixture
    def mesh_env(self, monkeypatch):
        def activate(spec):
            monkeypatch.setenv("NOMAD_TPU_MESH", spec)
            backend.reset_mesh()
            return backend.get_mesh()

        yield activate
        monkeypatch.delenv("NOMAD_TPU_MESH", raising=False)
        backend.reset_mesh()

    @pytest.mark.parametrize("spec", ["2,4", "1,8", "4,2"])
    def test_mesh_run_byte_equal_to_degenerate(self, spec, mesh_env):
        """The gang KERNEL is bit-portable: the same host batch run
        degenerate and sharded yields identical bytes on all six
        outputs. The batch is built once, before the mesh activates —
        the upstream score_matrix_kernel's ``exp`` is a pre-existing
        1-ulp leak across shardings (device/score.py ``_pow10``), so
        batch bytes are mesh-dependent; the contract pinned here is the
        gang solver's, on fixed inputs."""
        ct, asks = _fleet_and_gang_asks(64, 4, 3)
        batch = build_cp_batch(ct, asks)
        gi = build_gang_inputs(ct, asks)
        io = _gang_io(batch, gi)
        ref = [
            np.asarray(x)
            for x in cp_gang_place_kernel(
                *io, steps=batch.steps, max_c=batch.max_c
            )
        ]
        mesh_env(spec)
        sharded = cp_gang_place_kernel(
            *io, steps=batch.steps, max_c=batch.max_c
        )
        for r, s in zip(ref, sharded):
            s = np.asarray(s)
            if r.dtype == np.float32:
                np.testing.assert_array_equal(
                    r.view(np.uint32), s.view(np.uint32)
                )
            else:
                np.testing.assert_array_equal(r, s)

    @pytest.mark.parametrize("spec", ["2,4", "4,2"])
    def test_plugin_matches_oracle_under_active_mesh(self, spec, mesh_env):
        """Per-mesh oracle parity: whatever batch the sharded scoring
        stack produces, the gang kernel's outputs on it are byte-equal
        to the NumPy oracle on the same bytes."""
        mesh_env(spec)
        ct, asks = _fleet_and_gang_asks(64, 4, 3)
        batch = build_cp_batch(ct, asks)
        gi = build_gang_inputs(ct, asks)
        d = cp_gang_place_kernel(
            *_gang_io(batch, gi), steps=batch.steps, max_c=batch.max_c
        )
        o = oracle_cp_gang_place(
            *_gang_io(batch, gi), batch.steps, batch.max_c
        )
        np.testing.assert_array_equal(np.asarray(d[0]), o[0])
        np.testing.assert_array_equal(
            np.asarray(d[1]).view(np.uint32), o[1].view(np.uint32)
        )


# -- gang-less bit-identity through the cp-gang plugin ------------------------


class TestGangLessIdentity:
    def test_gangless_batch_bit_identical_to_cp_pack(self):
        """No gang members → CpGangPlacementKernel dispatches to the
        parent's UNCHANGED cp_place_kernel at the Python level: existing
        cp-pack users see identical bytes and zero added retraces."""
        from nomad_tpu.analysis import retrace

        ct = build_mixed_fleet(64, seed=7)
        asks = build_cp_asks(ct, 6, 6, seed=8)
        ref = CpPlacementKernel().place(ct, asks)
        base = dict(retrace.counts())
        got = CpGangPlacementKernel().place(ct, asks)
        assert dict(retrace.counts()) == base
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a.node_rows, b.node_rows)
            np.testing.assert_array_equal(
                np.asarray(a.scores).view(np.uint32),
                np.asarray(b.scores).view(np.uint32),
            )


# -- atomic release post-pass -------------------------------------------------


class TestAtomicRelease:
    def test_incomplete_gang_fully_released(self):
        # two gangs of two members; gang 2's second member never placed
        choices = np.array(
            [[0, 1], [2, 3], [4, 5], [-1, -1]], dtype=np.int32
        )
        scores = np.ones_like(choices, dtype=np.float32)
        asks = np.full((4, 2), 10.0, dtype=np.float32)
        counts = np.array([2, 2, 2, 2], dtype=np.int32)
        gang = np.array([1, 1, 2, 2], dtype=np.int32)
        used = np.full((8, 2), 10.0, dtype=np.float32)
        c2, s2, u2, released = release_incomplete_gangs(
            choices, scores, used, asks, counts, gang
        )
        assert released == [2]
        # gang 1 untouched, gang 2 fully absent with capacity returned
        np.testing.assert_array_equal(c2[:2], choices[:2])
        assert (c2[2:] == -1).all() and (s2[2:] == 0).all()
        np.testing.assert_array_equal(u2[4:6], np.zeros((2, 2)))
        np.testing.assert_array_equal(u2[:4], used[:4])


# -- scheduler seam: law-15 atomic commit -------------------------------------


class TestSchedulerAtomicity:
    def _harness(self, n_nodes=6, algorithm=None):
        h = Harness()
        for _ in range(n_nodes):
            h.store.upsert_node(h.next_index(), mock.node())
        if algorithm:
            h.store.set_scheduler_config(
                h.next_index(),
                SchedulerConfiguration(scheduler_algorithm=algorithm),
            )
        return h

    def test_feasible_gang_places_every_member(self):
        h = self._harness()
        j = _gang_job(counts=(2, 2))
        h.store.upsert_job(h.next_index(), j)
        h.process(mock.eval_for(j))
        live = [
            a
            for a in h.store.allocs_by_job(j.namespace, j.id)
            if not a.terminal_status()
        ]
        assert len(live) == 4
        assert {a.task_group for a in live} == {"g0", "g1"}

    def test_infeasible_member_releases_whole_gang(self):
        """One member that fits nowhere must drag the whole gang into a
        single blocked eval — never a striped partial placement."""
        h = self._harness()
        j = _gang_job(
            counts=(2, 2),
            resources=[
                Resources(cpu=500, memory_mb=256),
                Resources(cpu=100_000, memory_mb=256),
            ],
        )
        h.store.upsert_job(h.next_index(), j)
        before = _counter("nomad.gang.releases")
        h.process(mock.eval_for(j))
        assert _counter("nomad.gang.releases") == before + 1
        live = [
            a
            for a in h.store.allocs_by_job(j.namespace, j.id)
            if not a.terminal_status()
        ]
        assert live == []
        blocked = [
            e for e in h.created_evals if e.triggered_by
        ] or h.created_evals
        assert blocked, "expected a blocked eval for the released gang"
        failed = blocked[-1].failed_tg_allocs
        assert set(failed) == {"g0", "g1"}
        for metric in failed.values():
            assert metric.rejections.get("gang-infeasible", 0) >= 1

    def test_gang_rejections_survive_codec_round_trip(self):
        from nomad_tpu.api.codec import decode_eval, encode

        h = self._harness()
        j = _gang_job(
            counts=(1, 1),
            resources=[
                Resources(cpu=500, memory_mb=256),
                Resources(cpu=100_000, memory_mb=256),
            ],
        )
        h.store.upsert_job(h.next_index(), j)
        h.process(mock.eval_for(j))
        ev = h.created_evals[-1]
        back = decode_eval(encode(ev))
        assert set(back.failed_tg_allocs) == {"g0", "g1"}
        got = back.failed_tg_allocs["g1"].rejections
        assert got.get("gang-infeasible", 0) >= 1

    def test_cp_gang_algorithm_end_to_end(self):
        h = self._harness(algorithm="cp-gang")
        j = _gang_job(counts=(2, 2))
        h.store.upsert_job(h.next_index(), j)
        before = _counter("nomad.cp.gang_groups_in")
        h.process(mock.eval_for(j))
        assert _counter("nomad.cp.gang_groups_in") == before + 2
        live = [
            a
            for a in h.store.allocs_by_job(j.namespace, j.id)
            if not a.terminal_status()
        ]
        assert len(live) == 4


# -- chaos: gang.commit_drop holds law 15 -------------------------------------


class TestChaosCommitDrop:
    def test_forced_drop_releases_and_invariants_hold(self):
        from nomad_tpu.chaos.plane import FaultSpec
        from nomad_tpu.chaos.runner import run_chaos

        before = _counter("nomad.gang.releases")
        run = run_chaos(
            seed=5,
            steps=40,
            schedule=[FaultSpec("gang.commit_drop", 0, "drop")],
            quiesce_timeout=45.0,
        )
        assert run.ok, run.report.render()
        assert run.report.checked.get("gang_atomicity") is True
        assert ("gang.commit_drop", 0, "drop") in run.triggered
        assert _counter("nomad.gang.releases") > before


# -- seeded A/B smoke (the run_gang_ab gate) ----------------------------------


class TestBenchGangSmoke:
    @pytest.fixture(scope="class")
    def report(self):
        return run_gang_ab(n_nodes=64, n_jobs=8, groups=3, seed=42)

    def test_gate_passes(self, report):
        assert report["oracle_mismatches"] == 0
        assert report["binpack"]["gangs_fragmented"] >= 1
        n = report["config"]["gangs"]
        assert report["cp_gang"]["gangs_intact"] == n
        assert report["cp_gang"]["topology_satisfied"] == n
        assert report["ab"]["objective_delta"] >= 0
        assert report["ok"]

    def test_canonical_schema_pinned(self, report):
        assert cp_schema_of(report) == GANG_SCHEMA

    def test_report_byte_reproducible(self, report):
        again = run_gang_ab(n_nodes=64, n_jobs=8, groups=3, seed=42)
        assert json.dumps(report, sort_keys=True) == json.dumps(
            again, sort_keys=True
        )
