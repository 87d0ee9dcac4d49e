"""The deployment ``rollout-10k`` (``benchmark/rollout/``) at its rehearsal
size: one run of the cell through ``run.main``, the program's reconciler
beside ``benchmark/reference/rollout.py`` on seeded jobs, and each control
of the cell failing its own limit. One parametrised test a rule, a case a
seed."""

import copy
import functools
import json
import os
import random
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.reference import rollout as ref  # noqa: E402
from benchmark.rollout import control  # noqa: E402

CELL = control.CELL
NEW_METRICS = (
    "reconcile_ms_p50", "plan_stops_ms_p50", "client_update_ms_p50",
    "deployment_tick_ms_p50", "rollout_round_lag_ms_p50", "rollout_rounds",
    "destructive_updates", "plan_stops_committed",
)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark/reference/rollout.py")) as f:
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
    for exact in ("unfinished_requests", "nodes_over_capacity",
                  "job_count_off", "max_parallel_exceeded",
                  "old_version_placed", "alloc_names_duplicated",
                  "rollouts_unfinished", "deployments_failed"):
        assert compared[exact] == {"value": 0, "limit": 0}
    # a rollout moves no occupancy
    steady = result["steady"]
    assert steady["live_allocs_min"] == steady["live_allocs_max"] == 240
    metrics = result["metrics"]
    # every span and counter this deployment brings is read
    for name in NEW_METRICS:
        assert metrics[name]["value"] > 0, name
    assert metrics["deployments_failed"]["value"] == 0
    assert metrics["compiles_in_window.lat"]["value"] == 0
    assert metrics["full_flattens"]["value"] == 0
    # a service's rollout is a registration and the watcher's rounds, each
    # a solo pass: the window holds more passes than arrivals
    assert metrics["passes_solo"]["value"] > result["attempted"]


def _seeded_group(seed: int):
    """A job of 12-40 at version 1 with ``max_parallel`` 1-6 and its live
    allocations: some still on version 0, some on version 1 healthy or
    not, some names missing."""
    from nomad_tpu import mock
    from nomad_tpu.structs.deployment import (
        AllocDeploymentStatus,
        Deployment,
        DeploymentState,
    )
    from nomad_tpu.structs.job import UpdateStrategy

    rng = random.Random(f"{seed}:group")
    count = rng.randrange(12, 41)
    old = mock.job()
    group = old.task_groups[0]
    group.count = count
    group.update = UpdateStrategy(max_parallel=rng.randrange(1, 7))
    group.tasks[0].env = {"VERSION": "0"}
    new = copy.deepcopy(old)
    new.version = 1
    new.task_groups[0].tasks[0].env = {"VERSION": "1"}
    deployment = Deployment(
        job_id=new.id, job_version=1,
        task_groups={group.name: DeploymentState(desired_total=count)},
    )
    allocs = []
    done = rng.randrange(0, count)  # names below it were replaced already
    for k in range(count):
        if rng.random() < 0.1:
            continue  # a name the job is short of
        on_new = k < done
        a = mock.alloc(new if on_new else old)
        a.name = f"{new.id}.{group.name}[{k}]"
        if on_new:
            a.deployment_id = deployment.id
            if rng.random() < 0.7:
                a.deployment_status = AllocDeploymentStatus(healthy=True)
        allocs.append(a)
    rng.shuffle(allocs)
    return new, deployment, allocs


@pytest.mark.parametrize("seed", range(12))
def test_the_reconciler_stops_and_places_the_references_names(seed):
    from nomad_tpu.scheduler.reconcile import reconcile

    job, deployment, allocs = _seeded_group(seed)
    group = job.task_groups[0]
    results = reconcile(job, job.id, allocs, {}, deployment=deployment)
    healthy = [
        a.deployment_status is not None and a.deployment_status.is_healthy()
        for a in allocs
    ]
    want_stop, want_place = ref.round_plan(
        group.count, [a.index() for a in allocs],
        [a.job_version for a in allocs], healthy, 1,
        group.update.max_parallel,
    )
    stopped = sorted(old.index() for old, _pr in results.destructive_update)
    assert stopped == want_stop.tolist()
    index = lambda name: int(name[name.rindex("[") + 1:-1])  # noqa: E731
    placed = sorted(
        [index(pr.name) for _old, pr in results.destructive_update]
        + [index(pr.name) for pr in results.place]
    )
    assert placed == want_place.tolist()
    assert results.stop == [] and results.inplace_update == []


@pytest.mark.parametrize("seed", range(4))
def test_a_group_without_an_update_strategy_is_replaced_all_at_once(seed):
    from nomad_tpu.scheduler.reconcile import reconcile

    job, _deployment, allocs = _seeded_group(seed)
    job.task_groups[0].update = None
    for a in allocs:
        a.job.task_groups[0].update = None
    results = reconcile(job, job.id, allocs, {}, batch=True)
    want_stop, _place = ref.round_plan(
        job.task_groups[0].count, [a.index() for a in allocs],
        [a.job_version for a in allocs], [False] * len(allocs), 1, None,
    )
    assert sorted(
        old.index() for old, _pr in results.destructive_update
    ) == want_stop.tolist()
    assert len(want_stop) == sum(a.job_version == 0 for a in allocs)


@functools.lru_cache(maxsize=None)
def _start(seed: int):
    """The rehearsal's fleet under six jobs of 40 with ``max_parallel`` 4
    (the rehearsal's own jobs of 8 hold one allocation a rack: spread counts
    that forget the old version then differ too little to tell)."""
    _cell, _bench, config, traffic = run.load_cell(CELL, rehearse=True)
    traffic["job"]["count"] = 40
    traffic["job"]["update"]["max_parallel"] = 4
    return config, control.filled(config, traffic, seed)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_sound_reference_comes_out_correct(seed):
    config, start = _start(seed)
    (correct, compared), numbers = control.judge_reference(
        config, start, seed, 6)
    assert correct, compared
    assert numbers["rollouts_judged"] == 6
    assert numbers["watcher_evals_judged"] > 0
    for share in ("stop_set_mismatch_share", "score_mismatch_share",
                  "jobs_off_best_share"):
        assert numbers[share] == 0.0


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_control_fails_the_limit_it_is_written_for(fault, seed):
    config, start = _start(seed)
    (correct, compared), _numbers = control.judge_reference(
        config, start, seed, 6, fault)
    failed = {
        k for k, c in compared.items()
        if c["value"] is None or c["value"] > c["limit"]
    }
    assert not correct
    assert control.FAILS[fault] in failed, compared


def test_the_walk_from_an_empty_state_is_the_plain_references():
    """``rollout.walk`` with nothing placed is ``placement.greedy_walk``."""
    from benchmark.gen.fleet import fleet_spec
    from benchmark.reference import placement as plain

    config, start = _start(1)
    fleet = fleet_spec(config["fleet"])
    spec = start["specs"][0]
    used = {d: np.zeros(fleet["n"]) for d in plain.DIMS}
    a = plain.greedy_walk(fleet, used, spec, None)
    zero = np.zeros(fleet["n"], dtype=np.int64)
    racks = np.zeros(int(fleet["rack"].max()) + 1, dtype=np.int64)
    b = ref.walk(fleet, used, spec, None, zero, racks, steps=spec["count"])
    np.testing.assert_array_equal(a["rows"], b["rows"])
    np.testing.assert_array_equal(a["served"], b["served"])
