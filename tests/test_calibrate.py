"""nomad_tpu.obs.calibrate — the telemetry-driven calibration plane.

Covers the two feedback loops and their safety rails: the throughput
estimator (recorder fan-out in, EMA cells out, starvation-safe reads,
clamp band, chaos telemetry drops), the calibration table (provenance,
probe-artifact ingestion, Little's-law threshold derivation, the
admission/breaker consumer seams), the scheduler throughput-source seam
(declared mode byte-identical with zero added retraces, learned mode
substituting estimator values), the HTTP/CLI/SLO surfaces, invariant
law 14 (``calibration_sanity``) tamper detection, and the
``run_calib_ab`` A/B harness at smoke scale.
"""

import json
import math

import numpy as np
import pytest

from nomad_tpu.obs.calibrate import (
    DEFAULT_CONSTANTS,
    CalibrationTable,
    ThroughputEstimator,
    calibration_overview,
    derive_admission_thresholds,
    global_estimator,
    global_table,
    learned_tp_matrix,
    run_calib_ab,
    synth_execute_trace,
    write_probe_artifact,
)
from nomad_tpu.obs.recorder import FlightRecorder


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def fed_estimator(n: int = 24, rate: float = 4.0, **kw):
    est = ThroughputEstimator(recorder=FlightRecorder(), **kw)
    for _ in range(n):
        est.observe("tpu-v4", "kind0", rate)
    return est


# -- throughput estimator ----------------------------------------------------


class TestEstimator:
    def test_constant_stream_converges_exactly(self):
        est = fed_estimator(n=24, rate=4.0)
        v, src = est.value("tpu-v4", "kind0", declared=1.0)
        assert src == "learned"
        assert v == pytest.approx(4.0)

    def test_noisy_stream_converges_near_truth(self):
        est = ThroughputEstimator(recorder=FlightRecorder())
        for k in range(64):
            est.observe("cpu", "kind2", 0.5 * (1.0 + 0.1 * math.sin(k)))
        v, src = est.value("cpu", "kind2", declared=1.0)
        assert src == "learned"
        assert v == pytest.approx(0.5, rel=0.15)

    def test_sample_floor_answers_declared(self):
        est = fed_estimator(n=7)  # floor is 8
        v, src = est.value("tpu-v4", "kind0", declared=2.5)
        assert (v, src) == (2.5, "default")
        est.observe("tpu-v4", "kind0", 4.0)  # 8th sample crosses the floor
        v, src = est.value("tpu-v4", "kind0", declared=2.5)
        assert src == "learned"

    def test_unknown_cell_answers_declared(self):
        est = ThroughputEstimator(recorder=FlightRecorder())
        assert est.value("gpu-a100", "kind1", declared=3.5) == (
            3.5, "default",
        )

    def test_clamp_band_bounds_learned_answers(self):
        est = fed_estimator(n=24, rate=1000.0, clamp_band=8.0)
        v, src = est.value("tpu-v4", "kind0", declared=1.0)
        assert (v, src) == (8.0, "learned")
        est2 = fed_estimator(n=24, rate=0.0001, clamp_band=8.0)
        v2, _ = est2.value("tpu-v4", "kind0", declared=1.0)
        assert v2 == pytest.approx(1.0 / 8.0)

    def test_rejects_garbage_samples(self):
        est = ThroughputEstimator(recorder=FlightRecorder())
        for bad in (0.0, -1.0, float("nan"), float("inf")):
            est.observe("cpu", "kind0", bad)
        assert est.cell_count() == 0

    def test_max_cells_bounds_accumulation(self):
        est = ThroughputEstimator(recorder=FlightRecorder(), max_cells=4)
        for i in range(10):
            est.observe(f"class-{i}", "kind0", 1.0)
        assert est.cell_count() == 4
        assert est.snapshot()["overflow"] == 6

    def test_confidence_monotone(self):
        est = ThroughputEstimator(recorder=FlightRecorder())
        assert est.confidence("cpu", "kind0") == 0.0
        for _ in range(8):
            est.observe("cpu", "kind0", 1.0)
        assert est.confidence("cpu", "kind0") == pytest.approx(0.5)
        for _ in range(100):
            est.observe("cpu", "kind0", 1.0)
        assert est.confidence("cpu", "kind0") > 0.9

    def test_clock_threads_through_fakeclock(self):
        clock = FakeClock()
        est = ThroughputEstimator(recorder=FlightRecorder(), clock=clock)
        est.observe("cpu", "kind0", 1.0)
        clock.advance(10.0)
        est.observe("cpu", "kind0", 1.0)
        assert est._cells[("cpu", "kind0")].updated_at == clock.t


class TestRecorderFeed:
    def test_execute_spans_feed_cells_via_fanout(self):
        rec = FlightRecorder()
        est = ThroughputEstimator(recorder=rec)
        est.attach()
        try:
            for k in range(12):
                rec.record(synth_execute_trace(
                    f"t{k}", "tpu-v4", "kind0",
                    work_units=4.0, duration_ms=1000.0,
                ))
        finally:
            est.detach()
        v, src = est.value("tpu-v4", "kind0", declared=1.0)
        assert (v, src) == (pytest.approx(4.0), "learned")

    def test_untagged_spans_are_ignored(self):
        rec = FlightRecorder()
        est = ThroughputEstimator(recorder=rec)
        est.attach()
        try:
            rec.record({
                "eval_id": "plain", "status": "acked", "started_at": 0.0,
                "duration_ms": 5.0, "tags": {},
                "spans": [{
                    "span_id": 1, "parent_id": None, "name": "dequeue",
                    "start_unix": 0.0, "duration_ms": 5.0,
                    "status": "ok", "tags": {},
                }],
            })
        finally:
            est.detach()
        assert est.cell_count() == 0

    def test_attach_is_refcounted(self):
        rec = FlightRecorder()
        est = ThroughputEstimator(recorder=rec)
        est.attach()
        est.attach()
        est.detach()
        assert est._on_trace in rec._listeners
        est.detach()
        assert est._on_trace not in rec._listeners

    def test_chaos_telemetry_drop_starves_cell_to_declared(self):
        from nomad_tpu.chaos.plane import FaultPlane, FaultSpec, install, \
            uninstall

        est = ThroughputEstimator(recorder=FlightRecorder())
        plane = FaultPlane(schedule=[
            FaultSpec("calib.telemetry_drop", i, "drop") for i in range(6)
        ])
        install(plane)
        try:
            for _ in range(10):
                est.observe("tpu-v4", "kind0", 4.0)
        finally:
            uninstall()
        # 6 dropped, 4 landed: below the floor of 8 → declared answer
        assert est.snapshot()["dropped"] == 6
        assert est.value("tpu-v4", "kind0", declared=1.5) == (
            1.5, "default",
        )


# -- calibration table -------------------------------------------------------


class TestCalibrationTable:
    def test_defaults_match_shipped_constants(self):
        t = CalibrationTable()
        for name, default in DEFAULT_CONSTANTS:
            e = t.entry(name)
            assert e["value"] == float(default)
            assert e["source"] == "default"

    def test_set_records_provenance(self):
        t = CalibrationTable()
        t.set("admission.brownout_backlog", 128.0, source="probe",
              samples=40, window="2s")
        e = t.entry("admission.brownout_backlog")
        assert e["source"] == "probe"
        assert e["samples"] == 40
        assert e["window"] == "2s"
        assert e["updated_at_index"] == 1
        assert e["default"] == 512.0  # the shipped value survives

    def test_set_rejects_unknown_name_and_garbage(self):
        t = CalibrationTable()
        with pytest.raises(KeyError):
            t.set("admission.not_a_constant", 1.0)
        with pytest.raises(ValueError):
            t.set("admission.brownout_backlog", float("nan"))
        with pytest.raises(ValueError):
            t.set("admission.brownout_backlog", 1.0, source="vibes")

    def test_admission_overrides_shape_matches_controller(self):
        from nomad_tpu.server.admission import AdmissionController

        t = CalibrationTable()
        # every key the view emits must be accepted by the controller
        AdmissionController(clock=FakeClock(), **t.admission_overrides())

    def test_breaker_defaults_view(self):
        t = CalibrationTable()
        assert t.breaker_defaults() == {
            "execute_deadline": 5.0, "compile_deadline": 60.0,
        }

    def test_reset_restores_defaults(self):
        t = CalibrationTable()
        t.set("admission.shed_backlog", 9.0, source="learned")
        t.reset()
        e = t.entry("admission.shed_backlog")
        assert (e["value"], e["source"]) == (2048.0, "default")


class TestProbeArtifact:
    def test_little_law_threshold_derivation(self):
        t = CalibrationTable()
        d = derive_admission_thresholds(100.0, table=t)
        # 100/s × 2.5s brownout target, × 10s shed target
        assert d["admission.brownout_backlog"] == 250.0
        assert d["admission.shed_backlog"] == 1000.0
        assert d["admission.imbalance_min_backlog"] == 31.0

    def test_derivation_floors_tiny_rates(self):
        t = CalibrationTable()
        d = derive_admission_thresholds(1.0, table=t)
        assert d["admission.brownout_backlog"] == 16.0
        assert d["admission.shed_backlog"] == 32.0  # 2× brownout floor
        assert d["admission.imbalance_min_backlog"] == 8.0

    def test_write_then_load_roundtrip(self, tmp_path):
        path = tmp_path / "CALIB_r01.json"
        write_probe_artifact(
            str(path), rate_per_s=100.0, seed=7, nodes=200,
            probe_seconds=2.0, samples=40,
        )
        # canonical: sorted keys, byte-reproducible
        raw = path.read_text()
        assert raw == json.dumps(
            json.loads(raw), indent=2, sort_keys=True
        ) + "\n"
        t = CalibrationTable()
        assert t.load_probe_artifact(str(path)) == 3
        e = t.entry("admission.brownout_backlog")
        assert e["value"] == 250.0
        assert e["source"] == "probe"
        assert e["samples"] == 40
        assert e["window"] == "2s"
        assert t.snapshot()["probe"]["rate_evals_per_s"] == 100.0
        assert t.snapshot()["by_source"]["probe"] == 3

    def test_load_rejects_wrong_kind_and_bad_rate(self):
        t = CalibrationTable()
        with pytest.raises(ValueError):
            t.load_probe_artifact({"kind": "not_a_probe"})
        with pytest.raises(ValueError):
            t.load_probe_artifact(
                {"kind": "saturation_search", "rate_evals_per_s": -1.0}
            )


# -- consumer seams ----------------------------------------------------------


class TestConsumerSeams:
    def test_admission_defaults_come_from_global_table(self):
        from nomad_tpu.server.admission import AdmissionController

        global_table.set(
            "admission.brownout_backlog", 99.0, source="probe"
        )
        try:
            ac = AdmissionController(clock=FakeClock())
            assert ac.brownout_backlog == 99.0
        finally:
            global_table.reset()
        assert AdmissionController(
            clock=FakeClock()
        ).brownout_backlog == 512.0

    def test_explicit_overrides_beat_the_table(self):
        from nomad_tpu.server.admission import AdmissionController

        ac = AdmissionController(clock=FakeClock(), brownout_backlog=7.0)
        assert ac.brownout_backlog == 7.0

    def test_breaker_deadlines_come_from_global_table(self):
        from nomad_tpu.resilience import breaker as bk

        bk.reset_all()
        global_table.set(
            "resilience.execute_deadline_s", 1.25, source="probe"
        )
        try:
            br = bk.breaker_for("calib-test-kernel")
            assert br.execute_deadline == 1.25
            assert br.compile_deadline == 60.0
        finally:
            global_table.reset()
            bk.reset_all()

    def test_breaker_configure_still_overrides(self):
        from nomad_tpu.resilience import breaker as bk

        bk.reset_all()
        prev = bk.configure(execute_deadline=0.5)
        try:
            assert bk.breaker_for("calib-cfg-kernel").execute_deadline == 0.5
        finally:
            bk.configure(**prev)
            bk.reset_all()


# -- scheduler throughput-source seam ----------------------------------------


class TestThroughputSourceSeam:
    def _fleet(self, n_nodes=64, n_jobs=6, count=4, seed=9):
        from nomad_tpu.scheduler.hetero import build_mixed_asks, \
            build_mixed_fleet

        ct = build_mixed_fleet(n_nodes, seed=seed)
        return ct, build_mixed_asks(
            ct, n_jobs=n_jobs, count_per_job=count, seed=seed
        )

    def test_unknown_source_rejected(self):
        from nomad_tpu.scheduler.hetero import HeteroPlacementKernel

        with pytest.raises(ValueError):
            HeteroPlacementKernel("maxmin", throughput_source="psychic")

    def test_declared_mode_is_byte_identical_with_estimator_attached(self):
        from nomad_tpu.analysis import retrace
        from nomad_tpu.scheduler.hetero import HeteroPlacementKernel

        ct, asks = self._fleet()
        est = fed_estimator()
        plain = HeteroPlacementKernel("maxmin").place(ct, asks)
        before = dict(retrace.counts())
        pinned = HeteroPlacementKernel(
            "maxmin", throughput_source="declared", estimator=est
        ).place(ct, asks)
        after = dict(retrace.counts())
        for r0, r1 in zip(plain, pinned):
            assert r0.node_rows.tobytes() == r1.node_rows.tobytes()
            assert r0.scores.tobytes() == r1.scores.tobytes()
        assert after == before  # zero added jaxpr traces

    def test_learned_matrix_preserves_shape_dtype_and_anchors(self):
        from nomad_tpu.scheduler.hetero import build_hetero_batch

        ct, asks = self._fleet()
        for j, a in enumerate(asks):
            a.profile = f"kind{j % 3}"
        batch = build_hetero_batch(ct, asks)
        est = ThroughputEstimator(recorder=FlightRecorder())
        out = learned_tp_matrix(est, ct, asks, batch.tp)
        assert out.shape == batch.tp.shape and out.dtype == batch.tp.dtype
        # no samples anywhere → every cell answers its declared anchor
        np.testing.assert_array_equal(out, batch.tp)

    def test_learned_matrix_substitutes_learned_cells(self):
        from nomad_tpu.scheduler.hetero import build_hetero_batch

        ct, asks = self._fleet()
        ids, vocab = ct.device_class_column()
        cls_name = next(
            n for n in vocab
            if n and np.any(np.asarray(ids) == vocab[n])
        )
        for a in asks:
            a.profile = "kindX"
        batch = build_hetero_batch(ct, asks)
        est = ThroughputEstimator(recorder=FlightRecorder())
        for _ in range(24):
            est.observe(cls_name, "kindX", 2.0)
        out = learned_tp_matrix(est, ct, asks, batch.tp)
        rows = np.flatnonzero(np.asarray(ids) == vocab[cls_name])
        anchor = float(batch.tp[0, rows[0]])
        want, _ = est.value(cls_name, "kindX", declared=anchor)
        assert float(out[0, rows[0]]) == pytest.approx(want)

    def test_job_profile_key(self):
        from types import SimpleNamespace

        from nomad_tpu import mock
        from nomad_tpu.device.flatten import job_profile_key

        job = mock.job()
        assert job_profile_key(job) == ""  # empty throughputs → no profile
        job.throughputs = {"tpu-v4": 4.0, "cpu": 0.5}
        assert job_profile_key(job) == "tp:cpu=0.5,tpu-v4=4"
        # an explicit calibration profile wins over the declared map
        named = SimpleNamespace(
            calibration_profile="tuned", throughputs={"cpu": 1.0}
        )
        assert job_profile_key(named) == "tuned"

    def test_scheduler_config_carries_throughput_source(self):
        from nomad_tpu.state.store import SchedulerConfiguration

        assert SchedulerConfiguration().throughput_source == "declared"
        cfg = SchedulerConfiguration(throughput_source="learned")
        assert cfg.throughput_source == "learned"

    def test_wire_throughput_source(self):
        from nomad_tpu.scheduler.generic import wire_throughput_source
        from nomad_tpu.scheduler.hetero import HeteroPlacementKernel
        from nomad_tpu.state.store import SchedulerConfiguration

        k = HeteroPlacementKernel("maxmin")
        wire_throughput_source(k, SchedulerConfiguration())
        assert k.throughput_source == "declared" and k.estimator is None
        wire_throughput_source(
            k, SchedulerConfiguration(throughput_source="learned")
        )
        assert k.throughput_source == "learned"
        assert k.estimator is global_estimator


# -- surfaces ----------------------------------------------------------------


class TestSloBlock:
    def test_measured_includes_calibration_and_schema_pins_it(self):
        from nomad_tpu.obs.slo import SLO_SCHEMA, SloCollector, \
            slo_schema_of

        c = SloCollector(recorder=FlightRecorder())
        slo = c.measured()
        assert set(slo["calibration"]) == {
            "constants", "probe_sourced", "learned_cells",
            "estimator_samples",
        }
        slo["verdict"] = {"pass": True, "failures": []}
        assert slo_schema_of(slo) == SLO_SCHEMA

    def test_overview_reads_given_table_and_estimator(self):
        t = CalibrationTable()
        t.set("admission.shed_backlog", 100.0, source="probe")
        est = fed_estimator()
        o = calibration_overview(table=t, estimator=est)
        assert o == {
            "constants": len(DEFAULT_CONSTANTS), "probe_sourced": 1,
            "learned_cells": 1, "estimator_samples": 24,
        }


class TestServerIntegration:
    def test_server_owns_table_and_attaches_global_estimator(self):
        from nomad_tpu.server import Server, ServerConfig

        from nomad_tpu.obs.recorder import flight_recorder

        # the attach is refcounted on the process-global estimator, so
        # measure the delta rather than absolute listener membership —
        # another live server elsewhere in the suite keeps it attached
        before = global_estimator._attached
        server = Server(ServerConfig(num_workers=1))
        try:
            assert server.calibration.get(
                "admission.brownout_backlog"
            ) == 512.0
            assert server.throughput_estimator is global_estimator
            assert global_estimator._attached == before + 1
            assert global_estimator._on_trace in flight_recorder._listeners
        finally:
            server.shutdown()
        # shutdown released this server's attach
        assert global_estimator._attached == before

    def test_calibration_artifact_drives_admission_thresholds(
        self, tmp_path
    ):
        from nomad_tpu.server import Server, ServerConfig

        path = tmp_path / "CALIB_r01.json"
        write_probe_artifact(str(path), rate_per_s=100.0, probe_seconds=2.0)
        server = Server(ServerConfig(
            num_workers=1, calibration_artifact=str(path),
        ))
        try:
            e = server.calibration.entry("admission.brownout_backlog")
            assert (e["value"], e["source"]) == (250.0, "probe")
            # the admission controller admitted under the derived value
            assert server.admission.brownout_backlog == 250.0
        finally:
            server.shutdown()

    def test_http_calibration_endpoint_and_config_roundtrip(
        self, tmp_path
    ):
        from nomad_tpu.api.client import NomadClient
        from nomad_tpu.api.http import HTTPAgent
        from nomad_tpu.server import Server, ServerConfig

        path = tmp_path / "CALIB_r01.json"
        write_probe_artifact(str(path), rate_per_s=50.0, probe_seconds=2.0)
        server = Server(ServerConfig(
            num_workers=1, calibration_artifact=str(path),
        ))
        server.establish_leadership()
        http = HTTPAgent(server, None, port=0)
        http.start()
        try:
            c = NomadClient(http.address)
            out = c._request("GET", "/v1/agent/calibration")
            assert set(out) == {"table", "estimator", "throughput_source"}
            assert out["throughput_source"] == "declared"
            bb = out["table"]["constants"]["admission.brownout_backlog"]
            assert bb["source"] == "probe"
            assert out["table"]["by_source"]["probe"] == 3
            # flip the scheduler's throughput source through the config
            cfg = c._request("GET", "/v1/operator/scheduler/configuration")
            assert cfg["throughput_source"] == "declared"
            c._request(
                "POST", "/v1/operator/scheduler/configuration",
                body={"throughput_source": "learned"},
            )
            cfg = c._request("GET", "/v1/operator/scheduler/configuration")
            assert cfg["throughput_source"] == "learned"
            with pytest.raises(Exception):
                c._request(
                    "POST", "/v1/operator/scheduler/configuration",
                    body={"throughput_source": "psychic"},
                )
        finally:
            http.stop()
            server.shutdown()

    def test_cli_calibrate_status_and_report(self, capsys):
        from nomad_tpu.api.http import HTTPAgent
        from nomad_tpu.cli.main import main as cli_main
        from nomad_tpu.server import Server, ServerConfig

        server = Server(ServerConfig(num_workers=1))
        server.establish_leadership()
        http = HTTPAgent(server, None, port=0)
        http.start()
        try:
            rc = cli_main(
                ["-address", http.address, "calibrate", "status"]
            )
            out = capsys.readouterr().out
            assert rc == 0
            assert "constants: 21" in out
            assert "throughput source: declared" in out
            rc = cli_main(
                ["-address", http.address, "calibrate", "report", "-json"]
            )
            out = capsys.readouterr().out
            assert rc == 0
            assert json.loads(out)["throughput_source"] == "declared"
        finally:
            http.stop()
            server.shutdown()


# -- invariant law 14 --------------------------------------------------------


class TestCalibrationSanityLaw:
    def test_law_checked_and_tamper_detected(self):
        from nomad_tpu.chaos import check_cluster
        from nomad_tpu.chaos.invariants import metrics_baseline
        from nomad_tpu.server import Server, ServerConfig

        baseline = metrics_baseline()
        server = Server(ServerConfig(num_workers=1))
        try:
            server.establish_leadership()
            for _ in range(12):
                server.throughput_estimator.observe("tpu-v4", "kind0", 4.0)
            report = check_cluster(server, plane=None, baseline=baseline)
            assert report.ok, report.render()
            assert report.checked.get("calibration_sanity") is True
            assert report.info["calibration_estimator"]["learned_cells"] == 1
            # a poisoned cell must be caught, not served
            cell = server.throughput_estimator._cells[("tpu-v4", "kind0")]
            cell.ema = float("nan")
            tampered = check_cluster(server, plane=None, baseline=baseline)
            assert not tampered.ok
            assert any(
                v.invariant == "calibration_sanity"
                for v in tampered.violations
            )
        finally:
            server.shutdown()
            global_estimator.reset()

    def test_source_dishonesty_detected(self):
        from nomad_tpu.chaos import check_cluster
        from nomad_tpu.chaos.invariants import metrics_baseline
        from nomad_tpu.server import Server, ServerConfig

        baseline = metrics_baseline()
        server = Server(ServerConfig(num_workers=1))
        try:
            server.establish_leadership()
            server.calibration.set(
                "admission.shed_backlog", 64.0, source="probe"
            )
            assert check_cluster(
                server, plane=None, baseline=baseline
            ).ok
            # a non-finite table value must fail the law
            entry = server.calibration._entries["admission.shed_backlog"]
            entry.value = float("inf")
            tampered = check_cluster(server, plane=None, baseline=baseline)
            assert any(
                v.invariant == "calibration_sanity"
                for v in tampered.violations
            )
        finally:
            server.shutdown()


# -- lint: NTA018 ------------------------------------------------------------


class TestProvenanceLint:
    def run(self, src, relpath="nomad_tpu/server/admission.py"):
        from nomad_tpu.analysis import lint
        from nomad_tpu.analysis.rules.provenance import (
            ConstantProvenanceDiscipline,
        )

        return lint.check_source(
            src, relpath, rules=[ConstantProvenanceDiscipline()]
        )

    def test_flags_bare_threshold_comparison(self):
        fs = self.run("def f(x):\n    return x >= 70\n")
        assert [f.rule for f in fs] == ["NTA018"]
        assert "70" in fs[0].message

    def test_structural_literals_are_legal(self):
        fs = self.run(
            "def f(x):\n"
            "    return x > 0 and x >= -1 and x != 1 and x < 1.0\n"
        )
        assert fs == []

    def test_flags_module_level_defaults_dict(self):
        fs = self.run(
            "_DEFAULTS = {'a': 512.0, 'b': 2048.0, 'c': 2.5}\n"
        )
        assert [f.rule for f in fs] == ["NTA018"]

    def test_small_or_unnamed_dicts_are_legal(self):
        assert self.run("_DEFAULTS = {'a': 1.0, 'b': 2.0}\n") == []
        assert self.run("COSTS = {'a': 1.0, 'b': 2.0, 'c': 3.0}\n") == []
        assert self.run(
            "def f():\n"
            "    _DEFAULTS = {'a': 1.0, 'b': 2.0, 'c': 3.0}\n"
            "    return _DEFAULTS\n"
        ) == []

    def test_scoped_to_the_two_threshold_files(self):
        src = "def f(x):\n    return x >= 70\n"
        assert self.run(src, "nomad_tpu/scheduler/hetero.py") != []
        assert self.run(src, "nomad_tpu/obs/calibrate.py") == []
        assert self.run(src, "nomad_tpu/server/server.py") == []

    def test_repo_is_clean_modulo_baseline(self):
        from nomad_tpu.analysis import lint
        from nomad_tpu.analysis.rules.provenance import (
            ConstantProvenanceDiscipline,
        )

        root = lint.repo_root()
        findings = lint.run_lint(
            root, rules=[ConstantProvenanceDiscipline()]
        )
        baseline = lint.load_baseline(lint.default_baseline_path())
        new = [f for f in findings if f.fingerprint not in baseline]
        assert new == [], [f.render() for f in new]
        # exactly the two grandfathered tier_of cutpoints
        assert len(findings) == 2
        assert {f.symbol for f in findings} == {"tier_of"}


class TestWallclockObsScope:
    def run(self, src, relpath):
        from nomad_tpu.analysis import lint
        from nomad_tpu.analysis.rules.wallclock import (
            BareWallClockInBrokerServer,
        )

        return lint.check_source(
            src, relpath, rules=[BareWallClockInBrokerServer()]
        )

    def test_obs_is_in_scope_loadgen_exempt(self):
        src = "import time\n\ndef f():\n    return time.time()\n"
        assert self.run(src, "nomad_tpu/obs/recorder.py") != []
        assert self.run(src, "nomad_tpu/obs/loadgen.py") == []

    def test_obs_tree_is_clean(self):
        from pathlib import Path

        from nomad_tpu.analysis import lint
        from nomad_tpu.analysis.rules.wallclock import (
            BareWallClockInBrokerServer,
        )

        root = lint.repo_root()
        findings = lint.run_lint(
            root,
            paths=sorted((root / "nomad_tpu" / "obs").glob("*.py")),
            rules=[BareWallClockInBrokerServer()],
        )
        assert findings == [], [f.render() for f in findings]


# -- the run_calib_ab gate ---------------------------------------------------


class TestCalibAB:
    @pytest.fixture(scope="class")
    def report(self):
        return run_calib_ab(
            n_nodes=200, n_jobs=6, count_per_job=10, seed=42
        )

    def test_gate_passes(self, report):
        assert report["ok"], report["ab"]

    def test_declared_hidden_yet_quality_reproduced(self, report):
        assert report["ab"]["worst_share_within_tolerance"]
        assert report["ab"]["makespan_within_tolerance"]
        assert report["ab"]["learned"]["maxmin_improves_worst_share"]

    def test_declared_mode_pinned_bit_identical(self, report):
        assert report["declared_mode_identical"] is True
        assert report["added_retraces"] == 0

    def test_estimator_learned_every_cell(self, report):
        est = report["estimator"]
        assert est["learned_cells"] == est["cell_count"] > 0
        assert est["dropped"] == 0 and est["overflow"] == 0

    def test_report_is_canonical_json(self, report):
        s = json.dumps(report, sort_keys=True)
        assert json.loads(s) == json.loads(
            json.dumps(json.loads(s), sort_keys=True)
        )
