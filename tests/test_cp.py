"""nomad_tpu CP dispatcher — batched joint placement as a relaxation.

Pins the tentpole contracts from the ISSUE: the device kernel is
byte-identical to its NumPy host oracle across seeds (uint32 views,
scheduler/hetero.py's discipline), mesh runs are byte-equal to the
degenerate single-device run, explain-off traces the identical jaxpr
set with zero added retraces, a tripped breaker falls back to greedy
binpack bit-for-bit, value-block/slot-cap batches delegate, the
``cp.round_perturb`` chaos action perturbs prices without breaking
law 13 (``cp_assignment_conservation``), and the seeded A/B report is
byte-reproducible with its canonical schema pinned.
"""

import json

import numpy as np
import pytest

from nomad_tpu.chaos import FaultPlane, FaultSpec, install, uninstall
from nomad_tpu.device.cp import cp_place_kernel, oracle_cp_place
from nomad_tpu.device.score import PlacementKernel
from nomad_tpu.scheduler import algorithms
from nomad_tpu.scheduler.cp import (
    CP_SCHEMA,
    CpPlacementKernel,
    build_cp_asks,
    build_cp_batch,
    cp_schema_of,
    run_cp_ab,
)
from nomad_tpu.scheduler.hetero import build_mixed_fleet
from nomad_tpu.utils import backend
from nomad_tpu.utils.metrics import global_metrics


@pytest.fixture(autouse=True)
def _no_leaked_plane():
    yield
    uninstall()


def _counter(name: str) -> float:
    return global_metrics.snapshot()["counters"].get(name, 0.0)


def _fleet_and_asks(n_nodes=64, n_jobs=6, count=6, seed=7):
    ct = build_mixed_fleet(n_nodes, seed=seed)
    return ct, build_cp_asks(ct, n_jobs, count, seed=seed + 1)


def _kernel_io(batch):
    return (
        batch.capacity, batch.used, batch.asks, batch.counts,
        batch.eligible, batch.scores, batch.prio, batch.job_counts,
        batch.distinct, batch.jobgrp, batch.lam0,
    )


# -- device/oracle byte parity ----------------------------------------------


class TestOracleParity:
    @pytest.mark.parametrize("seed", [3, 11])
    def test_device_matches_oracle_bitwise(self, seed):
        ct, asks = _fleet_and_asks(96, 7, 8, seed=seed)
        batch = build_cp_batch(ct, asks)
        d = cp_place_kernel(
            *_kernel_io(batch), steps=batch.steps, max_c=batch.max_c
        )
        o = oracle_cp_place(*_kernel_io(batch), batch.steps, batch.max_c)
        d_choices = np.asarray(d[0])
        d_scores = np.asarray(d[1])
        d_used = np.asarray(d[2])
        d_lam = np.asarray(d[4])
        np.testing.assert_array_equal(d_choices, o[0])
        # f32 outputs compare as uint32 views: byte-identical, not close
        np.testing.assert_array_equal(
            d_scores.view(np.uint32), o[1].view(np.uint32)
        )
        np.testing.assert_array_equal(
            d_used.view(np.uint32), o[2].view(np.uint32)
        )
        np.testing.assert_array_equal(
            d_lam.view(np.uint32), o[4].view(np.uint32)
        )
        assert int(np.asarray(d[3])) == o[3]
        # the pass did real work: something committed, nothing oversubscribed
        assert (d_choices >= 0).any()
        assert (d_used <= batch.capacity + 0).all()


# -- mesh equivalence --------------------------------------------------------


@pytest.fixture
def mesh_env(monkeypatch):
    def activate(spec):
        monkeypatch.setenv("NOMAD_TPU_MESH", spec)
        backend.reset_mesh()
        return backend.get_mesh()

    yield activate
    monkeypatch.delenv("NOMAD_TPU_MESH", raising=False)
    backend.reset_mesh()


class TestMeshEquivalence:
    @pytest.mark.parametrize("spec", ["2,4", "1,8", "4,2"])
    def test_mesh_run_byte_equal_to_degenerate(self, spec, mesh_env):
        ct, asks = _fleet_and_asks(64, 6, 6)
        ref = CpPlacementKernel().place(ct, asks)
        mesh_env(spec)
        sharded = CpPlacementKernel().place(ct, asks)
        for a, b in zip(ref, sharded):
            np.testing.assert_array_equal(a.node_rows, b.node_rows)
            np.testing.assert_array_equal(
                np.asarray(a.scores).view(np.uint32),
                np.asarray(b.scores).view(np.uint32),
            )


# -- observational invariance (explain seam) ---------------------------------


class TestObservationalInvariance:
    def test_explain_off_bit_identical_zero_added_retraces(self):
        from nomad_tpu.analysis import retrace

        ct, asks = _fleet_and_asks(64, 6, 6)
        kernel = CpPlacementKernel()
        kernel.place(ct, asks)  # warm the shape bucket
        base = dict(retrace.counts())
        off = kernel.place(ct, asks)
        assert dict(retrace.counts()) == base
        on = kernel.place(ct, asks, explain=True)
        assert dict(retrace.counts()) == base, (
            "explain=True must not add a single retrace"
        )
        for a, b in zip(off, on):
            np.testing.assert_array_equal(a.node_rows, b.node_rows)
            np.testing.assert_array_equal(a.scores, b.scores)
        assert all(r.explanation is None for r in off)
        assert all(r.explanation is not None for r in on)

    def test_cp_provenance_block(self):
        from nomad_tpu.obs.explain import explanation_to_dict

        ct, asks = _fleet_and_asks(64, 6, 6)
        results = CpPlacementKernel().place(ct, asks, explain=True)
        for res in results:
            ex = res.explanation
            assert ex.algorithm == "cp-pack"
            cp = ex.cp
            assert set(cp) == {"iterations", "gap", "agreement"}
            assert cp["iterations"] > 0
            assert cp["gap"] >= 0.0
            assert 0.0 <= cp["agreement"] <= 1.0
            d = explanation_to_dict(ex)
            assert d["cp"] == cp
            assert d["top_candidates"]


# -- breaker fallback --------------------------------------------------------


class TestBreakerFallback:
    def test_tripped_breaker_falls_back_to_binpack_bitwise(self):
        from nomad_tpu.resilience import breaker as rbr

        ct, asks = _fleet_and_asks(64, 6, 6)
        expected = PlacementKernel("binpack").place(ct, asks)
        before = _counter("nomad.cp.fallback_passes")
        # trip ONLY the cp breaker: the global forced-open switch would
        # also flip the base kernel's own breaker-protected paths
        rbr.breaker_for("cp_place_kernel").force_open()
        try:
            got = CpPlacementKernel().place(ct, asks)
        finally:
            rbr.reset_all()
        assert _counter("nomad.cp.fallback_passes") == before + 1
        for a, b in zip(expected, got):
            np.testing.assert_array_equal(a.node_rows, b.node_rows)
            np.testing.assert_array_equal(
                np.asarray(a.scores).view(np.uint32),
                np.asarray(b.scores).view(np.uint32),
            )


# -- delegation for features the relaxation does not model -------------------


class TestDelegation:
    def test_slot_capped_batch_delegates_to_base(self):
        ct, asks = _fleet_and_asks(64, 6, 6)
        asks[0].slot_caps = np.full(
            ct.padded_n, 1.0e6, dtype=np.float32
        )  # semantically a no-op cap, but outside the relaxation's model
        expected = PlacementKernel("binpack").place(ct, asks)
        before = _counter("nomad.cp.groups_in")
        got = CpPlacementKernel().place(ct, asks)
        # delegated pass records no CP ledger entries (law 13 is per-pass)
        assert _counter("nomad.cp.groups_in") == before
        for a, b in zip(expected, got):
            np.testing.assert_array_equal(a.node_rows, b.node_rows)


# -- chaos: price perturbation stays conservation-safe -----------------------


class TestChaosPerturb:
    def test_round_perturb_counts_and_conserves(self):
        ct, asks = _fleet_and_asks(64, 6, 6)
        plane = FaultPlane(
            schedule=[FaultSpec("cp.round_perturb", 0, "perturb")]
        )
        install(plane)
        before = {
            k: _counter(f"nomad.cp.{k}")
            for k in (
                "groups_in", "placed_groups", "deferred_groups",
                "failed_groups", "capacity_violations", "chaos_perturbs",
            )
        }
        results = CpPlacementKernel().place(ct, asks)
        after = {
            k: _counter(f"nomad.cp.{k}")
            for k in before
        }
        assert after["chaos_perturbs"] == before["chaos_perturbs"] + 1
        assert after["groups_in"] == before["groups_in"] + len(asks)
        resolved = sum(
            after[k] - before[k]
            for k in ("placed_groups", "deferred_groups", "failed_groups")
        )
        assert resolved == len(asks)
        assert after["capacity_violations"] == before["capacity_violations"]
        assert sum(
            int((np.asarray(r.node_rows) >= 0).sum()) for r in results
        ) > 0

    def test_perturb_rides_default_mix(self):
        from nomad_tpu.chaos.plane import FAULT_KINDS, SITES, build_schedule

        assert "perturb" in FAULT_KINDS
        assert SITES["cp.round_perturb"] == ("perturb",)
        rows = [
            s.row() for s in build_schedule(seed=42, steps=400)
        ]
        assert any("cp.round_perturb" in r for r in rows)


# -- invariant law 13 --------------------------------------------------------


class TestConservationLaw13:
    def test_checked_and_tamper_detected(self):
        from nomad_tpu import mock
        from nomad_tpu.chaos import check_cluster
        from nomad_tpu.chaos.invariants import INVARIANTS, metrics_baseline
        from nomad_tpu.server import Server, ServerConfig

        assert "cp_assignment_conservation" in INVARIANTS
        baseline = metrics_baseline()
        ct, asks = _fleet_and_asks(64, 6, 6)
        CpPlacementKernel().place(ct, asks)  # global nomad.cp.* ledger
        server = Server(ServerConfig(num_workers=1))
        try:
            server.establish_leadership()
            server.register_node(mock.node())
            report = check_cluster(server, plane=None, baseline=baseline)
            assert report.ok, report.render()
            assert report.checked["cp_assignment_conservation"]
            # a pass that loses a group must be caught, not absorbed
            global_metrics.incr("nomad.cp.groups_in")
            try:
                tampered = check_cluster(
                    server, plane=None, baseline=baseline
                )
                assert not tampered.ok
                assert any(
                    v.invariant == "cp_assignment_conservation"
                    for v in tampered.violations
                )
            finally:
                # rebalance the process-global ledger for later tests
                global_metrics.incr("nomad.cp.placed_groups")
        finally:
            server.shutdown()


# -- registry + error paths (satellite) --------------------------------------


class TestRegistry:
    def test_cp_pack_registered_with_mesh_seam(self):
        assert algorithms.is_registered("cp-pack")
        algo = algorithms.get_algorithm("cp-pack")
        kern = algo.make_kernel()
        assert isinstance(kern, CpPlacementKernel)
        cfg = backend.get_mesh()
        kern2 = algorithms.make_kernel("cp-pack", mesh=cfg)
        assert kern2.mesh_cfg() is cfg

    def test_unknown_algorithm_lists_available(self):
        with pytest.raises(algorithms.UnknownAlgorithmError) as e:
            algorithms.get_algorithm("cp-bogus")
        msg = str(e.value)
        for name in algorithms.available():
            assert name in msg

    def test_cli_rejects_unknown_algorithm(self, capsys):
        from nomad_tpu.cli.main import main

        with pytest.raises(SystemExit) as e:
            main(["operator", "scheduler", "--algorithm", "cp-bogus"])
        assert e.value.code == 2
        err = capsys.readouterr().err
        assert "invalid choice" in err
        assert "cp-pack" in err

    def test_scheduler_config_selects_cp_pack_end_to_end(self):
        """An eval processed under scheduler_algorithm = cp-pack places
        through the joint relaxation — the CP pass ledger moves, and
        the allocations land like any other algorithm's."""
        from nomad_tpu import mock
        from nomad_tpu.scheduler.testing import Harness
        from nomad_tpu.state import SchedulerConfiguration

        h = Harness()
        for dc in ("tpu-v5e", "tpu-v5e", "gpu-a100", "cpu", "cpu", "cpu"):
            h.store.upsert_node(h.next_index(), mock.node(device_class=dc))
        h.store.set_scheduler_config(
            h.next_index(),
            SchedulerConfiguration(scheduler_algorithm="cp-pack"),
        )
        j = mock.job()
        j.task_groups[0].count = 3
        h.store.upsert_job(h.next_index(), j)
        before = _counter("nomad.cp.groups_in")
        h.process(mock.eval_for(j))
        assert _counter("nomad.cp.groups_in") > before
        allocs = [
            a
            for a in h.store.allocs_by_job(j.namespace, j.id)
            if not a.terminal_status()
        ]
        assert len(allocs) == 3
        assert len({a.node_id for a in allocs}) >= 1


# -- seeded A/B smoke (the run_cp_ab gate) -----------------------------------


class TestBenchCpSmoke:
    @pytest.fixture(scope="class")
    def report(self):
        return run_cp_ab(n_nodes=64, n_jobs=6, count_per_job=6, seed=42)

    def test_gate_passes(self, report):
        assert report["oracle_mismatches"] == 0
        ab = report["ab"]
        assert (
            ab["cp_beats_score"] and ab["preemptions_avoided"] >= 0
        ) or (
            ab["cp_avoids_preemptions"] and ab["score_delta"] >= 0
        )
        assert report["ok"]
        assert len(report["config"]["device_classes"]) >= 3

    def test_canonical_schema_pinned(self, report):
        assert cp_schema_of(report) == CP_SCHEMA

    def test_report_byte_reproducible(self, report):
        again = run_cp_ab(n_nodes=64, n_jobs=6, count_per_job=6, seed=42)
        assert json.dumps(report, sort_keys=True) == json.dumps(
            again, sort_keys=True
        )
