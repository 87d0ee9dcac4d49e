"""The deployment ``drain-10k`` (``benchmark/drain/``) at its rehearsal
size: one run of the cell through ``run.main``, the program's drainer
beside ``benchmark/reference/drain.py`` on seeded groups, and each control
of the cell failing its own limit. One parametrised test a rule, a case a
seed."""

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
from benchmark.drain import control  # noqa: E402
from benchmark.drain.driver import node_order  # noqa: E402
from benchmark.reference import drain as ref  # noqa: E402

CELL = control.CELL
NEW_METRICS = (
    "node_drain_ms_p50", "drain_scan_ms_p50", "drain_wave_lag_ms_p50",
    "attr_column_ms_p50", "drain_waves", "drain_evals", "drain_migrated",
    "drain_started", "drain_completed", "node_rows_patched",
    # the rollout layer's, which every migration runs too
    "reconcile_ms_p50", "plan_stops_ms_p50", "client_update_ms_p50",
    "plan_stops_committed",
)
# the drain as one operation, and the waits of its evals (PR 37)
DRAIN_RECORD_METRICS = (
    "drain_ms_p50", "drain_sched_ms_p50", "drain_client_wait_ms_p50",
    "drain_drainer_ms_p50", "drain_evals_wait_ms_p50",
    "drain_evals_busy_ms_p50",
)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark/reference/drain.py")) as f:
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
                  "placed_on_ineligible", "job_count_off",
                  "migrate_parallel_exceeded", "unmarked_alloc_stopped",
                  "drained_node_not_empty", "drains_unfinished",
                  "alloc_names_duplicated", "blocked_evals_left",
                  "drain_force_stops"):
        assert compared[exact] == {"value": 0, "limit": 0}
    # a migration moves no occupancy
    steady = result["steady"]
    assert steady["live_allocs_min"] == steady["live_allocs_max"] == 240
    metrics = result["metrics"]
    # every span and counter this deployment brings is read
    for name in NEW_METRICS:
        assert metrics[name]["value"] > 0, name
    # a value each, 0 where the median drained node held nothing (the toy
    # fleet's does); the three clocks of the median drain are no more
    # than the longest drain
    for name in DRAIN_RECORD_METRICS:
        assert metrics[name]["value"] >= 0.0, name
    assert metrics["drain_ms_p50"]["value"] > 0.0
    for name in ("overlay_wait_total_ms", "request_wait_ms_p50"):
        assert metrics[name]["value"] > 0.0, name
    assert metrics["request_wait_ms_p50"]["value"] >= (
        metrics["broker_wait_p50_ms"]["value"])
    # the drainer woke on the commit: far under its 250 ms poll
    assert metrics["drain_wave_lag_ms_p50"]["value"] < 50.0
    for alarm in ("drain_force_stops", "attr_columns_rebuilt",
                  "compiles_in_window.lat", "full_flattens"):
        assert metrics[alarm]["value"] == 0, alarm
    # a wave's evals stay in the one batched pass that dequeued them (PR
    # 38): a migration's stop frees room only on the node that drains.
    # What is left of the solo path is the wave of one eval
    rode = metrics["evals_batched_with_stops"]["value"]
    assert 0 < rode <= metrics["drain_evals"]["value"]
    assert metrics["passes_solo"]["value"] + rode >= (
        metrics["drain_evals"]["value"])
    assert metrics["passes_solo"]["value"] < metrics["drain_evals"]["value"]
    assert metrics["drain_migrated"]["value"] == (
        metrics["drain_evals"]["value"])
    # a migration is one stop in the plan that places its replacement
    assert metrics["plan_stops_committed"]["value"] == (
        metrics["drain_migrated"]["value"])
    # kernel_cost counts a drain as one job of 250 asks: not read here
    assert "place_kernel_roofline.lat" not in metrics


@pytest.mark.parametrize("stride, step, n", [
    (988, 4, 10000), (988, 4, 96), (997, 1, 10000), (997, 1, 96)])
def test_no_node_comes_twice_in_the_order(stride, step, n):
    order = node_order(n, {"stride": stride, "phase_step": step}, 2147484029)
    reach = n // step if step > 1 else n
    rows = [next(order) for _ in range(reach)]
    assert len(set(rows)) == reach
    if step == 4:  # the ssd nodes
        assert all(r % 4 == 0 for r in rows)
    if step == 4 and n == 10000:  # both classes soon, every rack in 25
        assert len({r % 3 == 1 for r in rows[:6]}) == 2
        assert {r % 25 for r in rows[:25]} == set(range(25))


def _seeded_group(seed: int):
    """A service of 12-40 with ``migrate.max_parallel`` 1-4 on a server's
    store: some allocations on the node that drains, some marked already,
    some replacements not yet running."""
    from nomad_tpu import mock
    from nomad_tpu.structs import DrainStrategy
    from nomad_tpu.structs.alloc import DesiredTransition
    from nomad_tpu.structs.job import MigrateStrategy

    rng = random.Random(f"{seed}:group")
    count = rng.randrange(12, 41)
    job = mock.job()
    group = job.task_groups[0]
    group.count = count
    group.migrate = MigrateStrategy(max_parallel=rng.randrange(1, 5))
    victim, other = mock.node(), mock.node()
    victim.drain = DrainStrategy(deadline_s=3600)
    victim.scheduling_eligibility = "ineligible"
    allocs = []
    for k in range(count):
        a = mock.alloc(job, node_id=(
            victim.id if rng.random() < 0.4 else other.id))
        a.name = f"{job.id}.{group.name}[{k}]"
        a.client_status = "running" if rng.random() < 0.9 else "pending"
        if a.node_id == victim.id and rng.random() < 0.15:
            a.desired_transition = DesiredTransition(migrate=True)
        allocs.append(a)
    return job, victim, other, allocs


@pytest.mark.parametrize("seed", range(12))
def test_the_drainer_marks_as_many_as_the_reference_allows(seed):
    """watch_jobs.go handleTaskGroup beside ``reference.may_mark``."""
    from nomad_tpu.server.server import Server, ServerConfig

    job, victim, other, allocs = _seeded_group(seed)
    group = job.task_groups[0]
    server = Server(ServerConfig(num_workers=0))
    try:
        store = server.store
        store.upsert_node(1, victim)
        store.upsert_node(2, other)
        store.upsert_job(3, job)
        store.upsert_allocs(4, allocs)
        marked_before = {a.id for a in allocs if a.desired_transition.migrate}
        want = ref.may_mark(
            group.count, group.migrate.max_parallel,
            [a.desired_transition.migrate for a in allocs],
            # an allocation on the draining node serves until it is marked
            [a.client_status == "running" or a.node_id == victim.id
             for a in allocs],
            [a.node_id == victim.id for a in allocs],
        )
        server.drainer.scan()
        marked = {
            a.id for a in store.allocs_by_job(job.namespace, job.id)
            if a.desired_transition.migrate
        }
        assert len(marked - marked_before) == want
        assert all(
            store.alloc_by_id(i).node_id == victim.id for i in marked)
        evals = [e for e in store.evals() if e.triggered_by == "node-drain"]
        assert len(evals) == (1 if want else 0)
    finally:
        server.shutdown()


@pytest.mark.parametrize("seed", range(6))
def test_the_reconciler_stops_and_places_the_references_names(seed):
    """reconcile_util.go filterByTainted beside ``reference.eval_plan``:
    only marked allocations leave a draining node, each replaced under its
    name."""
    from nomad_tpu.scheduler.reconcile import reconcile

    job, victim, _other, allocs = _seeded_group(seed)
    results = reconcile(job, job.id, allocs, {victim.id: victim})
    want_stop, want_place = ref.eval_plan(
        job.task_groups[0].count, [a.index() for a in allocs],
        [a.desired_transition.migrate for a in allocs],
        [a.node_id == victim.id for a in allocs],
    )
    assert sorted(s.alloc.index() for s in results.stop) == want_stop.tolist()
    index = lambda name: int(name[name.rindex("[") + 1:-1])  # noqa: E731
    assert sorted(index(pr.name) for pr in results.place) == (
        want_place.tolist())
    assert all(
        pr.previous_alloc.desired_transition.migrate for pr in results.place)


@functools.lru_cache(maxsize=None)
def _start(seed: int):
    """The rehearsal's fleet under six services of 40 (the rehearsal's own
    jobs of 8 hold one allocation a rack and a node: no node then holds two
    of one job, and ``max_parallel`` ignored changes nothing)."""
    _cell, _bench, config, traffic = run.load_cell(CELL, rehearse=True)
    traffic["job"]["count"] = 40
    return config, traffic, control.filled(config, traffic, seed)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_sound_reference_comes_out_correct(seed):
    config, traffic, start = _start(seed)
    (correct, compared), numbers = control.judge_reference(
        config, traffic, start, seed, 12)
    assert correct, compared
    assert numbers["drains_judged"] == 12
    assert numbers["evals_judged"] > 0
    for share in ("mark_set_mismatch_share", "score_mismatch_share",
                  "jobs_off_best_share"):
        assert numbers[share] == 0.0


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_control_fails_the_limit_it_is_written_for(fault, seed):
    config, traffic, start = _start(seed)
    (correct, compared), _numbers = control.judge_reference(
        config, traffic, start, seed, 12, fault)
    failed = {
        k for k, c in compared.items()
        if c["value"] is None or c["value"] > c["limit"]
    }
    assert not correct
    assert control.FAILS[fault] in failed, compared


@pytest.mark.parametrize("second_wave, exceeded", [(120, 0), (108, 1)])
def test_a_mark_is_dated_by_its_own_wave_whatever_eval_replaced_it(
        second_wave, exceeded):
    """One job on two draining nodes (rows 10 and 20), ``max_parallel`` 1.
    Node 10's mark is replaced by another eval of the job (a node eval made
    at the call, as the program before PR 35 made them); its wave's eval
    waits in the broker and later places the replacement of node 20's mark.
    Dated by the eval that placed the replacement, both marks would stand
    at index 102. A second wave from before the first replacement is
    acknowledged (index 110) does exceed the budget."""
    from benchmark.drain import judge

    i64 = functools.partial(np.asarray, dtype=np.int64)
    a = {
        # the two marked allocations, then their replacements
        "node": i64([10, 20, 30, 31]), "job": i64([0, 0, 0, 0]),
        "create": i64([5, 5, 105, 125]), "stop": i64([105, 125, 0, 0]),
        "marked": np.asarray([True, True, False, False]),
        "next": i64([2, 3, -1, -1]), "eval": i64([-1, -1, 0, 1]),
        "evals": {
            # the node eval, node 10's wave, node 20's wave
            "job": i64([0, 0, 0]), "node": i64([10, 10, 20]),
            "create": i64([100, 102, second_wave]),
            "drain": np.asarray([False, True, True]),
        },
    }
    mark = judge.mark_index(a)
    assert mark.tolist() == [102, second_wave, -1, -1]
    acked = i64([0, 0, 110, 130])
    assert judge.migrate_parallel_exceeded(
        a, mark, acked, {0: 1}) == exceeded


def test_the_walk_with_every_node_open_is_the_plain_references():
    """``drain.walk`` with nothing placed and nothing masked is
    ``placement.greedy_walk``; a masked node is never chosen."""
    from benchmark.gen.fleet import fleet_spec
    from benchmark.reference import placement as plain

    config, _traffic, start = _start(1)
    fleet = fleet_spec(config["fleet"])
    spec = start["specs"][0]
    used = {d: np.zeros(fleet["n"]) for d in plain.DIMS}
    a = plain.greedy_walk(fleet, used, spec, None)
    zero = np.zeros(fleet["n"], dtype=np.int64)
    racks = np.zeros(int(fleet["rack"].max()) + 1, dtype=np.int64)
    every = np.ones(fleet["n"], dtype=bool)
    b = ref.walk(fleet, used, spec, None, zero, racks, every,
                 steps=spec["count"])
    np.testing.assert_array_equal(a["rows"], b["rows"])
    np.testing.assert_array_equal(a["served"], b["served"])
    shut = every.copy()
    shut[a["rows"][:5]] = False
    c = ref.walk(fleet, used, spec, None, zero, racks, shut,
                 steps=spec["count"])
    assert not np.isin(c["rows"], a["rows"][:5]).any()
