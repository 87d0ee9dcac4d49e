"""The deployment ``system-10k`` (``benchmark/system/``) at its rehearsal
size: one run of the cell through ``run.main`` with its judge reading 0 on
every exact number, ``benchmark/reference/system.py`` on its own rules,
and each control of the cell failing its own limit. One parametrised test
a rule, a case a seed."""

import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.reference import system as ref  # noqa: E402
from benchmark.system import control  # noqa: E402

CELL = control.CELL
EXACT = (
    "unfinished_requests", "system_nodes_missing", "system_allocs_duplicated",
    "old_version_left", "nodes_over_capacity", "allocs_off_fleet",
    "unrelated_allocs_stopped", "breaker_trips", "reference_path_passes",
    "nacks", "swallowed_errors", "failed_evals", "live_allocs_out_of_band",
    "window_stalled",
)
# what this deployment brings, read on the host in a rehearsal (the two
# device metrics need a chip)
NEW_METRICS = (
    "system_diff_ms_p50", "system_place_ms_p50", "plan_store_write_ms_p50",
    "system_replaced",
)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark/reference/system.py")) as f:
        source = f.read()
    assert "nomad_tpu" not in source.split('"""', 2)[2]


def test_the_cell_rehearses_correct_through_run_main():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147484029", "--seconds", "4",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, proc.stderr[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    compared = result["compared"]
    for name in EXACT:
        assert compared[name] == {"value": 0, "limit": 0}, name
    assert compared["score_mismatch_share"]["value"] == 0.0
    # an update moves no occupancy
    steady = result["steady"]
    assert steady["live_allocs_min"] == steady["live_allocs_max"] == 624
    metrics = result["metrics"]
    for name in NEW_METRICS:
        assert metrics[name]["value"] > 0, name
    # every node replaced by each update of the window
    assert metrics["system_replaced"]["value"] % 96 == 0
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {
        m["name"] for m in bench["per_layer"]
        if CELL in m.get("workloads", ()) and m["source"] != "device_trace"
    }
    assert listed <= set(metrics), listed - set(metrics)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_rolling_diff_replaces_at_most_max_parallel_by_node(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(64, 257))
    held = rng.permutation(n)[: n - 5]
    versions = rng.integers(0, 3, size=held.size)
    k = int(rng.integers(1, 20))
    d = ref.diff(np.arange(n), held, versions, 3, True, max_parallel=k)
    older = np.sort(held[versions != 3])
    assert list(d["replace"]) == list(older[:k])
    assert d["limit_reached"] == (older.size > k)
    assert sorted(d["place"]) == sorted(set(range(n)) - set(held.tolist()))
    whole = ref.diff(np.arange(n), held, versions, 3, True)
    assert list(whole["replace"]) == list(older)
    inplace = ref.diff(np.arange(n), held, versions, 3, False)
    assert list(inplace["inplace"]) == list(older)
    assert not inplace["replace"].size


@functools.lru_cache(maxsize=None)
def _start(seed: int):
    _cell, _bench, config, traffic = run.load_cell(CELL, True)
    return config, control.filled(config, traffic, seed)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_sound_reference_comes_out_correct(seed):
    config, start = _start(seed)
    (correct, compared), numbers = control.judge_reference(
        config, start, seed, 6)
    assert correct, {k: c for k, c in compared.items()
                     if c["value"] is None or c["value"] > c["limit"]}
    assert numbers["updates_judged"] >= 6


@pytest.mark.parametrize("fault", ref.FAULTS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_control_fails_the_limit_it_is_written_for(fault, seed):
    config, start = _start(seed)
    (correct, compared), _numbers = control.judge_reference(
        config, start, seed, 6, fault)
    assert not correct
    c = compared[control.FAILS[fault]]
    assert c["value"] > c["limit"]
