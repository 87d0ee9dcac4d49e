"""chip_smoke.py and the loud-failure contract of the measurement paths.

- the smoke's phases (A binpack, B config 3, C scale/deregister/node
  failures) pass every check at toy size on the CPU — everything but the
  platform demand, which only ``__main__`` makes;
- ``chip_smoke.py`` as ``__main__`` with no TPU exits non-zero within
  seconds and prints no result;
- a tripped breaker, a reference-path pass, an undrained broker or an
  unaccounted alloc is a failure of ``chip_smoke`` (``check_device_path``
  and the ``device_path_failures`` list it reads, ``_submit``,
  ``check_store``), not a slower run;
- the persistent compile cache is placeable from outside.
"""

import os
import subprocess
import sys
import time

import pytest

import chip_smoke
from nomad_tpu.resilience import breaker as rbr
from nomad_tpu.utils import backend
from nomad_tpu.utils.metrics import global_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_device_path_state():
    """The smoke's checks read process-global counters and breakers
    that earlier tests in this process may have left non-zero."""
    global_metrics.reset()
    rbr.reset_all()
    yield
    global_metrics.reset()
    rbr.reset_all()


def test_phases_pass_every_check_at_toy_size():
    report = chip_smoke.run_phases(
        64, jobs_a=3, jobs_b=4, per_job=40, down_nodes=2
    )
    a, b, c = report["phase_a"], report["phase_b"], report["phase_c"]
    assert set(a["kernel_calls"]) == {"place_closed_form_kernel"}
    assert "place_spread_opv_kernel" in b["kernel_calls"]
    acct = a["accounting_all_jobs_so_far"]
    assert (acct["placed"], acct["total"]) == (120, 120)
    acct = c["accounting_all_jobs_so_far"]
    assert acct["unaccounted_allocs"] == 0
    # scale-ups of +10 on two phase-B jobs, two more deregistered
    assert acct["total"] == 3 * 40 + 2 * 50
    assert c["nodes_down"] == 2 and c["allocs_lost_on_down_nodes"] > 0
    assert c["solo_path_evals"] > 0
    assert report["mesh"]["active"] is False


def test_main_without_tpu_exits_nonzero_and_prints_no_result():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr
    assert time.monotonic() - t0 < 30


@pytest.mark.parametrize("failed", [None, "phase b: 1 worker nacks"])
def test_last_stdout_line_has_exactly_ok_and_device(failed):
    """The driver parses the last line: keys exactly ``ok`` and
    ``device``; the report (and the reason) ride on the line before."""
    import json

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    lines = chip_smoke.result_lines(
        failed is None, device, {"parity": {}}, failed
    )
    assert all("\n" not in line for line in lines)
    assert json.loads(lines[-1]) == {"ok": failed is None, "device": device}
    detail = json.loads(lines[-2])
    assert detail["report"] == {"parity": {}}
    assert detail.get("failed") == failed


class TestFailureIsLoud:
    @pytest.fixture
    def server(self):
        from nomad_tpu.server import Server, ServerConfig

        server = Server(ServerConfig(num_workers=0))
        yield server
        server.shutdown()

    def test_clean_state_passes(self, server):
        chip_smoke.check_device_path(server)
        assert chip_smoke.device_path_failures() == []

    def test_deadline_trip_fails_both(self, server):
        rbr.breaker_for("nomad_tpu.test.kernel").record_timeout(
            RuntimeError("compile blew its deadline")
        )
        with pytest.raises(chip_smoke.SmokeFailure, match="trips=1"):
            chip_smoke.check_device_path(server)
        [reason] = chip_smoke.device_path_failures()
        assert "nomad_tpu.test.kernel" in reason and "trips=1" in reason

    def test_trip_survives_a_metrics_reset(self, server):
        """The breaker registry is read as well as the counters: a
        trip before a ``global_metrics.reset()`` must still fail the run."""
        rbr.breaker_for("nomad_tpu.test.kernel").record_timeout()
        global_metrics.reset()
        assert chip_smoke.device_path_failures()
        with pytest.raises(chip_smoke.SmokeFailure, match="breaker"):
            chip_smoke.check_device_path(server)

    def test_reference_path_pass_fails_both(self, server):
        global_metrics.incr("nomad.resilience.fallback_passes")
        with pytest.raises(chip_smoke.SmokeFailure, match="fallback_passes"):
            chip_smoke.check_device_path(server)
        assert chip_smoke.device_path_failures() == [
            "nomad.resilience.fallback_passes=1"
        ]

    def test_swallowed_kernel_error_fails_smoke(self, server):
        global_metrics.incr("nomad.worker.batch_kernel_errors")
        with pytest.raises(chip_smoke.SmokeFailure, match="batch_kernel"):
            chip_smoke.check_device_path(server)

    def test_undrained_broker_fails_smoke(self, server, monkeypatch):
        monkeypatch.setattr(server, "wait_for_evals", lambda timeout: False)
        with pytest.raises(chip_smoke.SmokeFailure, match="not drained"):
            chip_smoke._submit(server, [])

    def test_unaccounted_alloc_fails_both(self, server):
        with pytest.raises(chip_smoke.SmokeFailure, match="unaccounted"):
            chip_smoke.check_store(server, {"never-registered": 5})


class TestCompileCachePlacement:
    @pytest.fixture
    def updates(self, monkeypatch):
        """Re-arm the one-shot configuration and record what it would
        set, without touching the live jax config."""
        import jax

        seen = {}
        monkeypatch.setattr(backend, "_cache_configured", False)
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: seen.__setitem__(k, v)
        )
        monkeypatch.setattr(
            jax.monitoring, "register_event_listener", lambda fn: None
        )
        return seen

    def test_env_set_means_no_directory_in_code(self, updates, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        backend.configure_compile_cache()
        assert "jax_compilation_cache_dir" not in updates
        # the thresholds still drop, or these kernels are never stored
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0

    def test_env_unset_means_fixed_in_checkout_path(
        self, updates, monkeypatch
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        backend.configure_compile_cache()
        assert updates["jax_compilation_cache_dir"] == os.path.join(
            REPO, ".jax_cache"
        )
        assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1

    def test_configures_once(self, updates):
        backend.configure_compile_cache()
        updates.clear()
        backend.configure_compile_cache()
        assert updates == {}
