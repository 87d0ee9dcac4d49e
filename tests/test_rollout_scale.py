"""A rollout through the served path at small scale: a 96-node, 8-rack
fleet, two services of 40 with ``max_parallel`` 4 and a batch job of 40
rolled to their next version at once, the benchmark's rollout driver
playing the nodes' clients. One parametrised test a rule, a case a seed; the
rules are the configuration ``rollout-10k``'s guarantees, read from the
store and the commit log as its judge reads them, and what the kernel was
shown is set beside ``benchmark/reference/rollout.py``."""

import functools
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.gen import fleet as gen_fleet  # noqa: E402
from benchmark.gen.jobs import plain_spec  # noqa: E402
from benchmark.reference import rollout as ref  # noqa: E402
from benchmark.rollout import jobs as rollout_jobs  # noqa: E402
from benchmark.rollout import judge  # noqa: E402
from benchmark.rollout.driver import Driver, clock  # noqa: E402

SEEDS = [11, 2147484001]
COUNT, MAX_PARALLEL = 40, 4
FLEET = {
    "nodes": 96, "racks": 8, "ssd_every": 4, "big_every": 3, "big_offset": 1,
    "classes": {
        "small": {"cpu": 4000, "memory_mb": 8192, "disk_mb": 102400},
        "big": {"cpu": 8000, "memory_mb": 16384, "disk_mb": 102400},
    },
    "reserved": {"cpu": 100, "memory_mb": 256, "disk_mb": 4096},
}
SHAPE = {
    "count": COUNT, "memory_mb": 256, "disk_mb": 300,
    "spread": {"attribute": "${attr.platform.rack}", "weight": 50},
    "affinity": {"l_target": "${attr.storage.type}", "r_target": "ssd",
                 "operand": "=", "weight": 50},
    "update": {"max_parallel": MAX_PARALLEL, "min_healthy_time_s": 0,
               "health_check": "task_states"},
}
CYCLE = [("service", 250), ("service", 500), ("batch", 250)]


def _specs(seed: int) -> list:
    return [
        rollout_jobs.versioned(
            plain_spec(f"roll-{seed}-{i}", {"cpu": cpu, "type": kind}, SHAPE),
            0, SHAPE["update"],
        )
        for i, (kind, cpu) in enumerate(CYCLE)
    ]


@functools.lru_cache(maxsize=None)
def rolled(seed: int) -> dict:
    """One run: the three jobs placed at version 0, then all three rolled
    at once; returns the judge's answers, the fleet table, the specs, and
    what every pass with stops showed the kernel beside what the snapshot
    and the plan said."""
    from nomad_tpu.scheduler import generic
    from nomad_tpu.server import Server, ServerConfig

    shown: list = []
    flatten = generic.flatten_group_ask

    def recording(ct, snap, job, tg, count, **kw):
        ga = flatten(ct, snap, job, tg, count, **kw)
        plan = kw.get("plan")
        if plan is not None and plan.node_update:
            node_of = lambda a: int(a.node_id[-12:])  # noqa: E731
            stopped = [a for stops in plan.node_update.values() for a in stops]
            used = np.zeros((ct.num_nodes, 3))
            for a in snap.allocs():
                if not a.terminal_status():
                    r = a.resources
                    used[ct.node_row[a.node_id]] += (
                        r.cpu, r.memory_mb, r.disk_mb)
            for a in stopped:
                r = a.resources
                used[ct.node_row[a.node_id]] -= (r.cpu, r.memory_mb, r.disk_mb)
            row_node = [int(nid[-12:]) for nid in ct.node_ids[: ct.num_nodes]]
            shown.append({
                "job_rows": [
                    node_of(a) for a in snap.allocs_by_job("default", job.id)
                    if not a.terminal_status()
                ],
                "stopped_rows": [node_of(a) for a in stopped],
                "row_node": row_node,
                # the count the kernel starts each node's rack from
                "rack_count_of_row": ga.blocks.counts0[0][
                    ga.blocks.value_ids[0][: ct.num_nodes]],
                "job_counts": ga.job_counts[: ct.num_nodes].copy(),
                "used": ct.used[: ct.num_nodes, :3].copy(),
                "used_from_snapshot": used,
            })
        return ga

    generic.flatten_group_ask = recording
    server = Server(ServerConfig(num_workers=1, num_batch_workers=1))
    server.establish_leadership()
    sent: dict = {}

    def remember(spec):
        sent[len(sent)] = spec
        return rollout_jobs.make_job(spec)

    try:
        fleet = gen_fleet.seed_fleet(server, {"fleet": FLEET})
        specs = _specs(seed)
        from benchmark.driver import Driver as Plain

        first = Plain(server, iter(specs), remember, [], 0, patient=True)
        for _ in specs:  # version 0, one at a time
            first.send_register(clock())
            first.drain(120.0)
        assert [r.note for r in first.requests if not r.ok] == []
        driver = Driver(server, iter(()), remember, specs, 3, patient=True)
        for _ in specs:
            driver.send_register(clock())
        driver.patience_s = 120.0
        driver.drain(120.0)
        answers = judge.extract_answers(
            server.store, {s["id"]: j for j, s in sent.items()})
        requests = first.requests + driver.requests
        rolling = list(driver.rolling)
    finally:
        server.shutdown()
        generic.flatten_group_ask = flatten
    return {"fleet": fleet, "specs": sent, "answers": answers,
            "requests": requests, "shown": shown, "rolling": rolling}


def _last(run: dict) -> dict:
    """job ordinal -> spec, the last spec sent under each id."""
    last = {s["id"]: j for j, s in run["specs"].items()}
    return {j: run["specs"][j] for j in last.values()}


@pytest.mark.parametrize("seed", SEEDS)
def test_every_registration_and_rollout_ended(seed):
    run = rolled(seed)
    assert [r.note for r in run["requests"] if r.ok is not True] == []
    assert run["rolling"] == []
    a = run["answers"]
    for j, spec in _last(run).items():
        live = (a["job"] == j) & (a["stop"] == 0)
        assert int(live.sum()) == COUNT
        assert (a["version"][live] == 1).all()


@pytest.mark.parametrize("seed", SEEDS)
def test_the_budget_is_never_exceeded_at_any_index(seed):
    run = rolled(seed)
    limits = {j: MAX_PARALLEL for j, s in _last(run).items() if s["update"]}
    assert len(limits) == 2
    assert judge.max_parallel_exceeded(run["answers"], limits) == 0
    # and the rule can tell: one less than the budget is exceeded
    tight = {j: MAX_PARALLEL - 1 for j in limits}
    assert judge.max_parallel_exceeded(run["answers"], tight) > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_live_count_never_leaves_the_jobs_count(seed):
    run = rolled(seed)
    counts = {j: COUNT for j in _last(run)}
    assert judge.job_count_off(run["answers"], counts) == 0
    # the batch job was replaced in one plan: 40 stops and 40 placements
    # at one index
    a = run["answers"]
    (batch,) = [j for j, s in _last(run).items() if s["type"] == "batch"]
    stops = a["stop"][(a["job"] == batch) & (a["stop"] > 0)]
    assert stops.size == COUNT and np.unique(stops).size == 1
    new = a["create"][(a["job"] == batch) & (a["version"] == 1)]
    assert (new == stops[0]).all()


@pytest.mark.parametrize("seed", SEEDS)
def test_names_stay_unique_and_are_handed_on(seed):
    a = rolled(seed)["answers"]
    assert judge.names_duplicated(a) == 0
    for j in np.unique(a["job"]):
        live = (a["job"] == j) & (a["stop"] == 0)
        assert sorted(a["name_idx"][live]) == list(range(COUNT))


@pytest.mark.parametrize("seed", SEEDS)
def test_a_round_replaces_the_lowest_names_first(seed):
    """reconcile.go computeGroup: ``destructive.nameOrder()[:min]``."""
    run = rolled(seed)
    a = run["answers"]
    for j, spec in _last(run).items():
        if not spec["update"]:
            continue
        new = (a["job"] == j) & (a["version"] == 1)
        order = np.argsort(a["create"][new], kind="stable")
        names = a["name_idx"][new][order].reshape(-1, MAX_PARALLEL)
        assert [sorted(r) for r in names.tolist()] == [
            list(range(k, k + MAX_PARALLEL))
            for k in range(0, COUNT, MAX_PARALLEL)
        ]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_kernel_is_shown_the_references_view_with_the_stops_taken_off(
        seed):
    """Usage, the job's allocations per node and the spread counts per
    rack, each as the snapshot had them less the plan's own stops
    (``reference/rollout.py`` ``freed_view``)."""
    run = rolled(seed)
    fleet = run["fleet"]
    # ten rounds a service and one replacement of the batch job
    assert len(run["shown"]) == 2 * (COUNT // MAX_PARALLEL) + 1
    for s in run["shown"]:
        spec = {"cpu": 0, "memory_mb": 0, "disk_mb": 0}
        zero = {d: np.zeros(fleet["n"]) for d in ref.DIMS}
        _used, mine, racks = ref.freed_view(
            fleet, zero, spec, s["job_rows"], s["stopped_rows"])
        assert s["stopped_rows"], "a pass with no stop was recorded"
        assert len(s["job_rows"]) == COUNT
        assert int(racks.sum()) == COUNT - len(s["stopped_rows"])
        node = np.asarray(s["row_node"])
        np.testing.assert_array_equal(
            s["rack_count_of_row"], racks[fleet["rack"][node]])
        np.testing.assert_array_equal(s["job_counts"], mine[node])
        np.testing.assert_allclose(s["used"], s["used_from_snapshot"])


@pytest.mark.parametrize("seed", SEEDS)
def test_the_deployments_end_successful_and_the_versions_stable(seed):
    run = rolled(seed)
    a = run["answers"]
    assert sorted(a["deployments"]["status"]) == ["successful"] * 2
    for j, spec in _last(run).items():
        if spec["update"]:
            assert a["jobs"][j] == (1, True)
    numbers = judge.judge(
        run["fleet"], run["specs"], run["requests"], a,
        (0.0, clock() + 60.0), seed,
    )
    for exact in ("unfinished_requests", "nodes_over_capacity",
                  "allocs_off_fleet", "job_count_off",
                  "max_parallel_exceeded", "old_version_placed",
                  "alloc_names_duplicated", "rollouts_unfinished",
                  "deployments_failed"):
        assert numbers[exact] == 0, (exact, numbers)
    assert numbers["rollouts_judged"] == 3
    assert numbers["stop_set_mismatch_share"] == 0.0
    assert numbers["score_mismatch_share"] == 0.0


class _Epoch:
    """A shared overlay, fresh (``base`` None) or in the middle of an epoch
    whose usage is ``base``; records the usage a pass freezes a base from."""

    def __init__(self, base):
        self.base = base
        self.frozen_from = None

    def begin_pass(self, ct):
        return None if self.base is None else self.base.copy()

    def read_ordinal(self):
        return 0

    def add_delta(self, ct, rows, ask, writer=None):
        if self.frozen_from is None:
            self.frozen_from = np.asarray(ct.used).copy()

    def pass_finished(self):
        pass

    def end_scoring(self):
        pass


@pytest.mark.parametrize("fresh", [True, False], ids=["fresh", "mid-epoch"])
def test_the_overlays_usage_has_the_plans_stops_freed_and_its_base_has_not(
        fresh):
    """One destructive update of a 4-alloc job through the scheduler alone
    (``scheduler/testing.py`` ``Harness``). Mid-epoch the kernel scores on
    the overlay's usage, which knows nothing of this plan's stops: they are
    taken off it, as they are off the snapshot's on a fresh epoch. A base
    this pass freezes still holds them: ``overlay.release`` takes them off
    when the stops commit, and only once."""
    import copy

    from nomad_tpu import mock
    from nomad_tpu.scheduler import generic
    from nomad_tpu.scheduler.scheduler import new_scheduler
    from nomad_tpu.scheduler.testing import Harness
    from nomad_tpu.structs import Evaluation

    h = Harness()
    for _ in range(8):
        h.store.upsert_node(h.next_index(), mock.node())
    job = mock.job()
    job.task_groups[0].count = 4
    job.task_groups[0].tasks[0].env = {"VERSION": "0"}

    def register(j):
        h.store.upsert_job(h.next_index(), j)
        ev = Evaluation(
            namespace=j.namespace, priority=j.priority, type=j.type,
            triggered_by="job-register", job_id=j.id, status="pending",
        )
        h.store.upsert_evals(h.next_index(), [ev])
        return ev

    h.process(register(job))
    newer = copy.deepcopy(job)
    newer.task_groups[0].tasks[0].env = {"VERSION": "1"}
    ev = register(newer)
    snap = h.store.snapshot()
    base = h.device_cache.tensors(snap).used.copy()  # the stops still held
    overlay = _Epoch(None if fresh else base)
    seen = {}
    make = generic.make_kernel

    def recording_kernel(name):
        kernel = make(name)
        place = kernel.place

        def recording_place(ct, asks, **kw):
            override = kw.get("used_override")
            seen["shown"] = (ct.used if override is None else override).copy()
            seen["override"] = override is not None
            return place(ct, asks, **kw)

        kernel.place = recording_place
        return kernel

    generic.make_kernel = recording_kernel
    try:
        new_scheduler(
            "service", snap, h, cache=h.device_cache, overlay=overlay
        ).process(ev)
    finally:
        generic.make_kernel = make
    stops = [a for v in h.plans[-1].node_update.values() for a in v]
    assert len(stops) == 4 and seen["override"] is (not fresh)
    freed = base - seen["shown"]
    want = np.zeros_like(base)
    ct = h.device_cache.tensors(snap)
    for a in stops:
        want[ct.node_row[a.node_id]] += a.comparable_resources().to_vector()
    np.testing.assert_allclose(freed, want)
    assert want.sum() > 0
    np.testing.assert_allclose(overlay.frozen_from, base)


@pytest.mark.parametrize("release", ["end_scoring", "pass_finished"])
def test_a_pass_reads_the_overlay_only_after_the_last_one_wrote(release):
    """Two passes on two threads (a solo pass on the commit thread beside
    the worker's next pass): the second's ``begin_pass`` waits until the
    first has written its placements, and then sees them."""
    import threading
    import types

    from nomad_tpu.server.overlay import SharedOverlay

    overlay = SharedOverlay()
    used = np.zeros((8, 4), dtype=np.float32)
    ct = types.SimpleNamespace(used=used, layout_gen=1, index=7, nodes=None)
    ask = np.array([500.0, 256.0, 300.0, 0.0], dtype=np.float32)
    assert overlay.begin_pass(ct) is None  # a fresh epoch
    seen = {}

    def second():
        seen["usage"] = overlay.begin_pass(ct)
        overlay.pass_finished()

    t = threading.Thread(target=second)
    t.start()
    t.join(timeout=0.3)
    assert t.is_alive() and not seen  # it waits for the first's write
    overlay.add_delta(ct, np.array([3]), ask)
    getattr(overlay, release)()
    t.join(timeout=5.0)
    assert not t.is_alive()
    np.testing.assert_array_equal(seen["usage"][3], ask)
    overlay.pass_finished()
    assert overlay.snapshot_markers() == (0, 0)
    # the holder may begin again (a retry inside one pass) without waiting
    overlay.begin_pass(ct)
    overlay.begin_pass(ct)
    overlay.pass_finished()
    overlay.pass_finished()
