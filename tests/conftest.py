"""Test config: force an 8-device virtual CPU platform so every sharding
test exercises a real multi-device mesh without TPU hardware.

``XLA_FLAGS`` and ``JAX_PLATFORMS`` are read when the backend
initialises (the first ``jax.devices()``), so both are set here before
jax is imported; ``jax.config.update`` covers an interpreter that had
jax imported already. The persistent compile cache follows the same
placement rule as the program (utils/backend.configure_compile_cache).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from nomad_tpu.utils.backend import configure_compile_cache  # noqa: E402

configure_compile_cache()

assert (
    jax.devices()[0].platform == "cpu" and len(jax.devices()) == 8
), "tests require the 8-device virtual CPU platform"

import pytest  # noqa: E402

# Test modules whose subjects are the lock-heavy subsystems: under
# NOMAD_TPU_RACECHECK=1 every test in them runs inside a lock-graph
# detection window (nomad_tpu/analysis/race.py) and fails on lock-order
# cycles or guarded-field violations even when the timing never fires.
_RACECHECK_MODULES = {
    "test_concurrency_invariants",
    "test_broker",
    "test_cluster",
}


@pytest.fixture(autouse=True)
def _lock_graph_racecheck(request):
    from nomad_tpu.analysis import race

    mod = request.module.__name__.rpartition(".")[2]
    if not race.enabled() or mod not in _RACECHECK_MODULES:
        yield
        return
    with race.racecheck():
        yield
