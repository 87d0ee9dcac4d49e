"""jaxlint: static analysis over the traced device-kernel fleet.

Covers the whole PR-16 surface: kernel registry + spec recording in
``utils.backend``, abstract re-tracing (``jaxlint.retracer``), the JXL
rule set against seeded fixture kernels (each rule gets a trigger and a
non-trigger), canonical fingerprint stability (in-process, and across a
real subprocess), the JXL006 invariance differ (mesh-on/off and
explain-on/off fingerprint equality, fleet-wide — the former per-test
spot checks promoted to proven invariants), the repo-clean ratchet
(zero unbaselined findings at HEAD), and the combined
``python -m nomad_tpu.analysis`` exit-code plumbing.

All tests here are CPU-only and fast — no slow marker, they ride tier-1.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu.analysis import lint
import importlib

# the package __init__ re-exports the fingerprint FUNCTION, which
# shadows the submodule of the same name on attribute-style imports
jxl_fp = importlib.import_module("nomad_tpu.analysis.jaxlint.fingerprint")
from nomad_tpu.analysis.jaxlint import (  # noqa: E402
    diff as jxl_diff,
    engine,
    exercise,
    retracer,
    rules,
)
from nomad_tpu.utils import backend

REPO_ROOT = lint.repo_root()


@pytest.fixture(scope="module")
def fleet_registry():
    """Exercise the production fleet once per module; every production
    kernel has recorded specs afterwards."""
    return exercise.exercise_fleet()


def fixture_kernel(fn, trace_name, **kwargs):
    """Register a test-local kernel (non-production name, so fleet-wide
    checks ignore it) and return its registry entry."""
    backend.traced_jit(fn, trace_name=trace_name, **kwargs)
    return backend.kernel_registry()[trace_name]


def entry_of(fn, trace_name, *args, **jit_kwargs):
    """Register, call once to record a spec, return the entry."""
    wrapped = backend.traced_jit(fn, trace_name=trace_name, **jit_kwargs)
    wrapped(*args)
    return backend.kernel_registry()[trace_name]


# -- registry + spec recording ----------------------------------------------


class TestKernelRegistry:
    def test_traced_jit_registers_and_records_specs(self):
        def add_one(x):
            return x + 1

        e = entry_of(
            add_one, "test_jaxlint.reg.add_one",
            jnp.zeros(4, np.float32), retrace_budget=2,
        )
        assert e.retrace_budget == 2
        assert len(e.specs) == 1
        spec = e.last_spec()
        assert spec["args"][0] == ("aval", (4,), "float32", False)

    def test_static_args_recorded_as_values(self):
        def topk(x, k):
            return jnp.sort(x)[:k]

        wrapped = backend.traced_jit(
            topk, trace_name="test_jaxlint.reg.topk",
            static_argnames=("k",), retrace_budget=2,
        )
        wrapped(jnp.arange(8.0), k=3)
        e = backend.kernel_registry()["test_jaxlint.reg.topk"]
        assert e.last_spec()["kwargs"]["k"] == ("static", 3)

    def test_spec_ring_is_bounded(self):
        def echo(x):
            return x

        wrapped = backend.traced_jit(
            echo, trace_name="test_jaxlint.reg.echo", retrace_budget=99,
        )
        for n in range(backend._KERNEL_SPECS_MAX + 3):
            wrapped(jnp.zeros(n + 1, np.float32))
        e = backend.kernel_registry()["test_jaxlint.reg.echo"]
        assert len(e.specs) == backend._KERNEL_SPECS_MAX

    def test_production_filter_excludes_test_kernels(self, fleet_registry):
        prod = retracer.production_kernels()
        assert all(n.startswith("nomad_tpu.") for n in prod)
        assert "nomad_tpu.device.score.place_closed_form_kernel" in prod
        assert not any(n.startswith("test_jaxlint.") for n in prod)


# -- retracer ----------------------------------------------------------------


class TestRetracer:
    def test_retrace_matches_direct_make_jaxpr(self):
        def double(x):
            return x * 2

        e = entry_of(
            double, "test_jaxlint.rt.double",
            jnp.zeros((3, 2), np.float32), retrace_budget=2,
        )
        closed = retracer.retrace(e)
        direct = jax.make_jaxpr(double)(
            jax.ShapeDtypeStruct((3, 2), np.float32)
        )
        assert jxl_fp.fingerprint(closed) == jxl_fp.fingerprint(direct)

    def test_retrace_bakes_statics(self):
        def head(x, k):
            return x[:k]

        wrapped = backend.traced_jit(
            head, trace_name="test_jaxlint.rt.head",
            static_argnames=("k",), retrace_budget=4,
        )
        wrapped(jnp.arange(8.0), k=3)
        e = backend.kernel_registry()["test_jaxlint.rt.head"]
        closed = retracer.retrace(e)
        assert closed.out_avals[0].shape == (3,)

    def test_no_spec_raises(self):
        def never(x):
            return x

        e = fixture_kernel(
            never, "test_jaxlint.rt.never", retrace_budget=1
        )
        with pytest.raises(retracer.UnretraceableSpec, match="no recorded"):
            retracer.retrace(e)

    def test_opaque_spec_raises(self):
        def takes_obj(x):
            return jnp.zeros(2)

        e = fixture_kernel(
            takes_obj, "test_jaxlint.rt.opaque", retrace_budget=1
        )
        e.specs["fake"] = {
            "args": [("opaque", "object")], "kwargs": {},
        }
        with pytest.raises(retracer.UnretraceableSpec, match="opaque"):
            retracer.retrace(e, e.specs["fake"])

    def test_spec_label_includes_statics_and_omitted_defaults(self):
        def gated(x, steps, extra=None):
            return x * steps if extra is None else x * steps + extra

        wrapped = backend.traced_jit(
            gated, trace_name="test_jaxlint.rt.gated",
            static_argnames=("steps",), retrace_budget=4,
        )
        wrapped(jnp.zeros(2, np.float32), steps=3)
        e = backend.kernel_registry()["test_jaxlint.rt.gated"]
        sig = next(iter(e.specs))
        assert retracer.spec_label(e, sig) == "extra=None, steps=3"


# -- JXL rules against fixture kernels ---------------------------------------


def findings_for(entry, rule_fn):
    closed = retracer.retrace(entry)
    return rule_fn(entry, closed)


class TestJXL001Callbacks:
    def test_pure_callback_triggers(self):
        def leaky(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v) * 2,
                jax.ShapeDtypeStruct((4,), np.float32),
                x,
            )
            return y + 1

        e = entry_of(
            leaky, "test_jaxlint.jxl001.leaky",
            jnp.zeros(4, np.float32), retrace_budget=1,
        )
        fs = findings_for(e, rules.check_callback_purity)
        assert [f.rule for f in fs] == ["JXL001"]
        assert "pure_callback" in fs[0].message

    def test_pure_math_is_clean(self):
        def clean(x):
            return jnp.tanh(x).sum()

        e = entry_of(
            clean, "test_jaxlint.jxl001.clean",
            jnp.zeros(4, np.float32), retrace_budget=1,
        )
        assert findings_for(e, rules.check_callback_purity) == []


class TestJXL002TransferHygiene:
    def test_closure_captured_array_triggers(self):
        table = np.arange(512, dtype=np.float32)

        def baked(x):
            return x + jnp.asarray(table)

        e = entry_of(
            baked, "test_jaxlint.jxl002.baked",
            jnp.zeros(512, np.float32), retrace_budget=1,
        )
        fs = findings_for(e, rules.check_transfer_hygiene)
        assert [f.rule for f in fs] == ["JXL002"]
        assert "512" in fs[0].message

    def test_small_const_is_legitimate(self):
        bounds = np.array([0.0, 1.0], dtype=np.float32)

        def clamped(x):
            b = jnp.asarray(bounds)
            return jnp.clip(x, b[0], b[1])

        e = entry_of(
            clamped, "test_jaxlint.jxl002.clamped",
            jnp.zeros(8, np.float32), retrace_budget=1,
        )
        assert findings_for(e, rules.check_transfer_hygiene) == []


class TestJXL003DtypeDiscipline:
    def test_weak_typed_output_triggers(self):
        def weak_out(x):
            # both branches are Python scalars -> weak f32 output whose
            # width would follow ambient x64 config
            return jnp.where(x.sum() > 0, 1.0, 2.0)

        e = entry_of(
            weak_out, "test_jaxlint.jxl003.weak",
            jnp.zeros(4, np.float32), retrace_budget=1,
        )
        fs = findings_for(e, rules.check_dtype_discipline)
        assert [f.rule for f in fs] == ["JXL003"]
        assert "weak-typed" in fs[0].message

    def test_wide_dtype_triggers(self):
        def widened(x):
            return x.astype(jnp.float64)

        e = fixture_kernel(
            widened, "test_jaxlint.jxl003.wide", retrace_budget=1
        )
        with jax.enable_x64(True):
            closed = jax.make_jaxpr(widened)(
                jax.ShapeDtypeStruct((4,), np.float32)
            )
        fs = rules.check_dtype_discipline(e, closed)
        assert [f.rule for f in fs] == ["JXL003"]
        assert "float64" in fs[0].message

    def test_pinned_f32_is_clean(self):
        def pinned(x):
            return (x * jnp.float32(1.5)).astype(jnp.float32)

        e = entry_of(
            pinned, "test_jaxlint.jxl003.pinned",
            jnp.zeros(4, np.float32), retrace_budget=1,
        )
        assert findings_for(e, rules.check_dtype_discipline) == []


class TestJXL004Determinism:
    def test_multi_index_scatter_add_triggers(self):
        def histo(x, idx):
            return jnp.zeros(8, np.float32).at[idx].add(x)

        e = entry_of(
            histo, "test_jaxlint.jxl004.histo",
            jnp.ones(16, np.float32),
            jnp.zeros(16, np.int32),
            retrace_budget=1,
        )
        fs = findings_for(e, rules.check_determinism)
        assert [f.rule for f in fs] == ["JXL004"]
        assert "scatter-add" in fs[0].message

    def test_scalar_scatter_is_clean(self):
        # .at[i].add() with a scalar index is a single update: jax marks
        # it unique_indices=True, and order cannot matter anyway
        def bump(x, i):
            return x.at[i].add(1.0)

        e = entry_of(
            bump, "test_jaxlint.jxl004.bump",
            jnp.zeros(8, np.float32), jnp.asarray(3, np.int32),
            retrace_budget=1,
        )
        assert findings_for(e, rules.check_determinism) == []

    def test_argsort_stable_is_clean(self):
        def ranked(x):
            return jnp.argsort(x)

        e = entry_of(
            ranked, "test_jaxlint.jxl004.ranked",
            jnp.zeros(8, np.float32), retrace_budget=1,
        )
        assert findings_for(e, rules.check_determinism) == []


class TestJXL005RetraceHazards:
    def test_closure_scalar_triggers(self):
        limit = 7

        def capped(x):
            return jnp.minimum(x, limit)

        e = entry_of(
            capped, "test_jaxlint.jxl005.capped",
            jnp.zeros(4, np.float32), retrace_budget=1,
        )
        fs = rules.check_retrace_hazards(e)
        assert [f.rule for f in fs] == ["JXL005"]
        assert "'limit'" in fs[0].message

    def test_phantom_static_and_missing_budget_trigger(self):
        def k(x):
            return x

        e = backend.KernelEntry(
            "test_jaxlint.jxl005.phantom", "phantom", k,
            {"static_argnames": ("nope",)}, None,
        )
        msgs = [f.message for f in rules.check_retrace_hazards(e)]
        assert any("'nope'" in m for m in msgs)
        assert any("retrace_budget" in m for m in msgs)

    def test_declared_static_is_clean(self):
        def k(x, steps):
            return x * steps

        wrapped = backend.traced_jit(
            k, trace_name="test_jaxlint.jxl005.ok",
            static_argnames=("steps",), retrace_budget=4,
        )
        wrapped(jnp.zeros(4, np.float32), steps=2)
        e = backend.kernel_registry()["test_jaxlint.jxl005.ok"]
        assert rules.check_retrace_hazards(e) == []


# -- JXL006: fingerprints ----------------------------------------------------


class TestFingerprints:
    def test_same_program_same_fingerprint(self):
        a = jax.make_jaxpr(lambda x: x * 2 + 1)(
            jax.ShapeDtypeStruct((4,), np.float32)
        )
        b = jax.make_jaxpr(lambda y: y * 2 + 1)(
            jax.ShapeDtypeStruct((4,), np.float32)
        )
        assert jxl_fp.fingerprint(a) == jxl_fp.fingerprint(b)

    def test_different_program_different_fingerprint(self):
        a = jax.make_jaxpr(lambda x: x * 2)(
            jax.ShapeDtypeStruct((4,), np.float32)
        )
        b = jax.make_jaxpr(lambda x: x * 3)(
            jax.ShapeDtypeStruct((4,), np.float32)
        )
        assert jxl_fp.fingerprint(a) != jxl_fp.fingerprint(b)

    def test_shape_change_changes_fingerprint(self):
        f = lambda x: x.sum()  # noqa: E731
        a = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), np.float32))
        b = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((8,), np.float32))
        assert jxl_fp.fingerprint(a) != jxl_fp.fingerprint(b)

    def test_canonical_text_has_no_addresses(self, fleet_registry):
        prod = retracer.production_kernels(fleet_registry)
        e = prod["nomad_tpu.device.score.place_closed_form_kernel"]
        text = jxl_fp.canonical_text(retracer.retrace(e))
        assert not jxl_fp._ADDR_RE.search(text)

    def test_fingerprint_table_covers_fleet(self, fleet_registry):
        table = jxl_fp.fingerprint_table(fleet_registry)
        for short in (
            "place_closed_form_kernel",
            "place_value_scan_kernel",
            "place_spread_chunked_kernel",
            "place_spread_opv_kernel",
            "score_matrix_kernel",
            "find_preemption_kernel",
            "choose_preemption_node_kernel",
            "hetero_place_kernel",
            "cp_place_kernel",
        ):
            assert short in table and table[short], short
            for fp in table[short].values():
                assert len(fp) == 16 and not fp.startswith("error:"), (
                    short, table[short],
                )

    def test_throughput_gate_is_two_distinct_configs(self, fleet_registry):
        table = jxl_fp.fingerprint_table(fleet_registry)
        sm = table["score_matrix_kernel"]
        assert "throughputs=None" in sm
        with_tp = [k for k in sm if k != "throughputs=None"]
        assert with_tp and sm["throughputs=None"] != sm[with_tp[0]]

    def test_fingerprints_stable_across_processes(self):
        """The whole point of canonicalization: two fresh interpreters
        re-derive byte-identical fingerprint tables. (Two subprocesses,
        not subprocess-vs-this-process: under the full suite other test
        files drive the production kernels at other aval shapes whose
        specs share a static-label, so this process's label-keyed table
        is not comparable entry-by-entry.)"""
        code = (
            "import json\n"
            "from nomad_tpu.analysis.jaxlint.exercise import exercise_fleet\n"
            "from nomad_tpu.analysis.jaxlint.fingerprint import"
            " fingerprint_table\n"
            "exercise_fleet()\n"
            "print(json.dumps(fingerprint_table(), sort_keys=True))\n"
        )
        tables = []
        for _ in range(2):
            r = subprocess.run(
                [sys.executable, "-c", code],
                capture_output=True, text=True, cwd=str(REPO_ROOT),
                env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300,
            )
            assert r.returncode == 0, r.stderr
            tables.append(json.loads(r.stdout.strip().splitlines()[-1]))
        assert tables[0], "exercise produced an empty fingerprint table"
        assert tables[0] == tables[1]


# -- JXL006: invariance differ -----------------------------------------------


class TestInvarianceDiffer:
    @pytest.fixture(scope="class")
    def proofs(self):
        return jxl_diff.prove_all()

    def test_explain_on_off_adds_no_traced_program(self, proofs):
        rep = proofs["explain"]
        assert rep["ok"], rep
        assert "place_closed_form_kernel" in rep["kernels"]
        for k, v in rep["kernels"].items():
            assert v["added_traces"] == 0, (k, v)
            assert v["added_specs"] == [], (k, v)
            assert v["fingerprints_equal"], (k, v)

    def test_incremental_on_off_adds_no_traced_program(self, proofs):
        """The jaxpr half of the incremental-rescoring bit-identity pin
        (device/cache.py): serving ``used`` from the persisted score
        state must trace the identical kernel set — zero new traces,
        zero new specs, every fingerprint unchanged."""
        rep = proofs["incremental"]
        assert rep["ok"], rep
        assert "place_closed_form_kernel" in rep["kernels"]
        for k, v in rep["kernels"].items():
            assert v["added_traces"] == 0, (k, v)
            assert v["added_specs"] == [], (k, v)
            assert v["fingerprints_equal"], (k, v)

    def test_incremental_differ_restores_ambient_state(self, proofs):
        assert os.environ.get("NOMAD_TPU_INCREMENTAL") in (None, "off")

    def test_mesh_on_off_jaxprs_identical(self, proofs):
        rep = proofs["mesh"]
        assert not rep.get("skipped"), (
            "conftest forces 8 virtual devices; mesh differ must run"
        )
        assert rep["ok"], rep
        for short in (
            "place_closed_form_kernel",
            "hetero_place_kernel",
            "cp_place_kernel",
        ):
            assert short in rep["kernels"], rep["kernels"].keys()
            for label, row in rep["kernels"][short].items():
                assert row["equal"], (short, label, row)

    def test_mesh_differ_restores_ambient_state(self, proofs):
        assert os.environ.get("NOMAD_TPU_MESH") in (None, "off")


# -- engine + ratchet --------------------------------------------------------


class TestEngineAndRatchet:
    def test_fleet_is_clean_at_head(self, fleet_registry):
        """The tier-1 acceptance gate: every production kernel analyzed,
        zero findings beyond the checked-in (empty) baseline."""
        findings, reports = engine.analyze_kernels(fleet_registry)
        baseline = lint.load_baseline(engine.default_baseline_path())
        new, _ = lint.diff_against_baseline(findings, baseline)
        assert len(reports) >= 9
        assert new == [], "new jaxlint findings:\n" + "\n".join(
            f.render() for f in new
        )

    def test_run_jaxlint_exit_zero_at_head(self, fleet_registry):
        code, new, fixed, reports = engine.run_jaxlint()
        assert code == 0 and new == []

    def test_seeded_callback_kernel_fails_ratchet(self, tmp_path):
        def dirty(x):
            return jax.pure_callback(
                lambda v: np.asarray(v), jax.ShapeDtypeStruct((2,), np.float32), x
            )

        e = entry_of(
            dirty, "test_jaxlint.ratchet.dirty",
            jnp.zeros(2, np.float32), retrace_budget=1,
        )
        fs = rules.check_kernel(e, retracer.retrace(e))
        assert any(f.rule == "JXL001" for f in fs)
        # a fresh empty baseline reports it as new; absorbing it makes a
        # second diff clean — the same ratchet discipline as the source lint
        bp = tmp_path / "baseline.json"
        new, _ = lint.diff_against_baseline(fs, lint.load_baseline(bp))
        assert new
        lint.write_baseline(fs, bp)
        new, _ = lint.diff_against_baseline(fs, lint.load_baseline(bp))
        assert new == []

    def test_finding_fingerprints_survive_kernel_motion(self):
        a = lint.Finding("JXL001", "nomad_tpu/device/score.py", 100,
                         "k", "msg")
        b = lint.Finding("JXL001", "nomad_tpu/device/score.py", 999,
                         "k", "msg")
        assert a.fingerprint == b.fingerprint


# -- combined CLI ------------------------------------------------------------


class TestCombinedCLI:
    def test_combined_default_runs_both_and_exits_zero(self):
        r = subprocess.run(
            [sys.executable, "-m", "nomad_tpu.analysis", "--json"],
            capture_output=True, text=True, cwd=str(REPO_ROOT),
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        data = json.loads(r.stdout)
        assert data["source"]["new"] == []
        assert data["kernels"]["new"] == []
        assert data["kernels"]["analyzed"] >= 9
