"""nomad_tpu.obs — zero-dependency tracing, profiling and SLOs.

Four parts (see trace.py / recorder.py / slo.py / loadgen.py and
utils/backend.py):

- **Spans**: ``global_tracer`` keys one trace tree per eval id and
  carries it across the worker → plan-queue → applier thread handoff.
- **Kernel profiling**: ``utils/backend.traced_jit`` reports per-kernel
  wall time, compile events and the abstract shapes that triggered them,
  attached to the enclosing span when one is active.
- **Flight recorder**: ``flight_recorder`` rings the last N completed
  traces + error events, surfaced at ``/v1/agent/trace`` and rendered by
  the ``nomad-tpu trace`` CLI.
- **SLO plane**: ``SloCollector`` windows eval/placement latency from
  the recorder's trace feed into bounded histograms; ``run_soak``
  replays a seeded Poisson traffic schedule against a live cluster and
  reports against declared ``SloTargets`` (``/v1/agent/slo``,
  ``nomad-tpu slo report``; tier-1: ``tests/test_slo.py``).
- **Calibration plane**: ``CalibrationTable`` gives every operational
  constant a provenance (``default``/``probe``/``learned``);
  ``ThroughputEstimator`` learns per-(device class × job profile)
  throughputs from the recorder's trace feed (``/v1/agent/calibration``,
  ``nomad-tpu calibrate``, ``run_calib_ab``).
"""

# calibrate imports before loadgen: loadgen pulls in the server stack,
# which lazily re-enters obs — calibrate must already be importable
from .calibrate import (
    CalibrationTable,
    ThroughputEstimator,
    calibration_overview,
    derive_admission_thresholds,
    global_estimator,
    global_table,
    run_calib_ab,
    write_probe_artifact,
)
from .loadgen import SoakRun, build_schedule, run_soak, saturation_search
from .recorder import (
    FlightRecorder,
    flight_recorder,
    phase_breakdown,
    render_trace,
    trace_latencies,
)
from .slo import (
    SLO_SCHEMA,
    SloCollector,
    SloTargets,
    build_report,
    live_report,
    slo_schema_of,
)
from .trace import Span, SpanContext, Tracer, global_tracer

__all__ = [
    "CalibrationTable",
    "FlightRecorder",
    "SLO_SCHEMA",
    "SloCollector",
    "SloTargets",
    "SoakRun",
    "Span",
    "SpanContext",
    "ThroughputEstimator",
    "Tracer",
    "build_report",
    "build_schedule",
    "calibration_overview",
    "derive_admission_thresholds",
    "flight_recorder",
    "global_estimator",
    "global_table",
    "global_tracer",
    "live_report",
    "phase_breakdown",
    "render_trace",
    "run_calib_ab",
    "run_soak",
    "saturation_search",
    "slo_schema_of",
    "trace_latencies",
    "write_probe_artifact",
]
