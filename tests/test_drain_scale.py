"""Drains through the served path at small scale: a 96-node, 8-rack fleet,
three services of 40 with ``migrate.max_parallel`` 1 (the ssd nodes hold
several allocations of one job), the benchmark's drain driver playing the
nodes' clients and the operator, six nodes drained one after another and
set eligible again. One parametrised test a rule, a case a seed; the rules
are the configuration ``drain-10k``'s guarantees, read from the store and
the commit log as its judge reads them, and what the kernel was shown is
set beside ``benchmark/reference/drain.py``."""

import functools
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.drain import jobs as drain_jobs  # noqa: E402
from benchmark.drain import judge  # noqa: E402
from benchmark.drain.driver import Driver, clock  # noqa: E402
from benchmark.drain.warm import acknowledge_running  # noqa: E402
from benchmark.gen import fleet as gen_fleet  # noqa: E402
from benchmark.gen.jobs import plain_spec  # noqa: E402
from benchmark.reference import drain as ref  # noqa: E402
from benchmark.rollout.judge import job_count_off  # noqa: E402

SEEDS = [11, 2147484001]
COUNT, DRAINS = 40, 6
FLEET = {
    "nodes": 96, "racks": 8, "ssd_every": 4, "big_every": 3, "big_offset": 1,
    "classes": {
        "small": {"cpu": 4000, "memory_mb": 8192, "disk_mb": 102400},
        "big": {"cpu": 8000, "memory_mb": 16384, "disk_mb": 102400},
    },
    "reserved": {"cpu": 100, "memory_mb": 256, "disk_mb": 4096},
}
MIGRATE = {"max_parallel": 1, "health_check": "task_states",
           "min_healthy_time_s": 0, "healthy_deadline_s": 300}
SHAPE = {
    "count": COUNT, "memory_mb": 256, "disk_mb": 300,
    "spread": {"attribute": "${attr.platform.rack}", "weight": 50},
    "affinity": {"l_target": "${attr.storage.type}", "r_target": "ssd",
                 "operand": "=", "weight": 50},
}
# rows 0, 2, .. 10 whatever the seed: row 0 (ssd, the first node binpack
# fills) holds ten allocations, five of each of two jobs
TRAFFIC = {"drain": {"stride": 2, "phase_step": 96, "deadline_s": 3600,
                     "eligible_after_s": 0.2, "give_up_s": 60.0}}


def _specs(seed: int) -> list:
    return [
        {**plain_spec(f"drain-{seed}-{i}", {"cpu": cpu, "type": "service"},
                      SHAPE), "migrate": MIGRATE}
        for i, cpu in enumerate((250, 500, 250))
    ]


@functools.lru_cache(maxsize=None)
def drained(seed: int) -> dict:
    """One run: the three services placed and acknowledged, then six
    nodes drained one after the other; returns the judge's answers, the
    fleet table, the specs, the requests, and what every pass with a stop
    showed the kernel beside what the snapshot and the plan said."""
    from nomad_tpu.scheduler import generic
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.state import StateStore

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
            shown.append({
                "job_rows": [
                    node_of(a) for a in snap.allocs_by_job("default", job.id)
                    if not a.terminal_status()
                ],
                "stopped_rows": [node_of(a) for a in stopped],
                "stopped_vector": [
                    (a.resources.cpu, a.resources.memory_mb,
                     a.resources.disk_mb) for a in stopped],
                "row_node": [
                    int(nid[-12:]) for nid in ct.node_ids[: ct.num_nodes]],
                "closed_nodes": sorted(
                    int(n.id[-12:]) for n in snap.nodes() if not n.ready()),
                "eligible": ga.eligible[: ct.num_nodes].copy(),
                # the count the kernel starts each node's rack from
                "rack_count_of_row": ga.blocks.counts0[0][
                    ga.blocks.value_ids[0][: ct.num_nodes]],
                "job_counts": ga.job_counts[: ct.num_nodes].copy(),
                "used": ct.used[: ct.num_nodes, :3].copy(),
                "used_from_snapshot": used,
            })
        return ga

    generic.flatten_group_ask = recording
    # the counter is the process's: tests before this one may have forced
    forced = judge.extract_answers(StateStore(), {})["drain_force_stops"]
    server = Server(ServerConfig(num_workers=1, num_batch_workers=1))
    server.establish_leadership()
    sent: dict = {}

    def remember(spec):
        sent[len(sent)] = spec
        return drain_jobs.make_job(spec)

    try:
        fleet = gen_fleet.seed_fleet(server, {"fleet": FLEET})
        specs = _specs(seed)
        from benchmark.driver import Driver as Plain

        first = Plain(server, iter(specs), remember, [], 0, patient=True)
        for _ in specs:  # one at a time
            first.send_register(clock())
            first.drain(120.0)
        assert [r.note for r in first.requests if not r.ok] == []
        n = acknowledge_running(server, [s["id"] for s in specs])
        assert n == 3 * COUNT
        driver = Driver(
            server, iter(()), remember,
            {"drains_sent": 0, "live_allocs": n}, 3, patient=True,
            traffic=TRAFFIC, seed=seed,
        )
        for _ in range(DRAINS):  # one after the other
            driver.send_register(clock())
            driver.drain(120.0)
        answers = judge.extract_answers(
            server.store, {s["id"]: j for j, s in sent.items()})
        answers["drain_force_stops"] -= forced
        requests = first.requests + driver.requests
        full_flattens = server.device_cache.full_flattens
    finally:
        server.shutdown()
        generic.flatten_group_ask = flatten
    return {"fleet": fleet, "specs": sent, "answers": answers,
            "requests": requests, "shown": shown,
            "full_flattens": full_flattens}


def _patched(run: dict) -> tuple:
    drains = judge.drains_of(run["requests"])
    stop, acked = judge.with_the_drivers_indices(run["answers"], drains)
    return {**run["answers"], "stop": stop}, acked, drains


@pytest.mark.parametrize("seed", SEEDS)
def test_every_drain_ends_with_the_node_empty_and_still_ineligible(seed):
    run = drained(seed)
    assert [r.note for r in run["requests"] if r.ok is not True] == []
    a, _acked, drains = _patched(run)
    assert len(drains) == DRAINS
    # some node held several allocations of one job: a wave each
    assert max(r.count for r in drains) >= 4
    held_jobs = [
        np.bincount(a["job"][[a["ids"][i] for i in r.held]], minlength=3)
        for r in drains if r.held
    ]
    assert max(int(h.max()) for h in held_jobs) >= 2
    for r in drains:
        # set, cleared by the drainer with the node left ineligible, then
        # set eligible by the operator
        assert 0 < r.drain_index < r.clear_index < r.eligible_index
        there = a["node"] == r.node_row
        left = there & (a["create"] < r.clear_index) & (
            (a["stop"] == 0) | (a["stop"] > r.clear_index))
        assert not left.any()
        assert len(r.stops) == len(r.acks) == r.count
    assert a["nodes_draining"].size == a["nodes_ineligible"].size == 0
    assert run["full_flattens"] == 1


@pytest.mark.parametrize("seed", SEEDS)
def test_the_live_count_never_leaves_the_jobs_count(seed):
    a, _acked, _drains = _patched(drained(seed))
    assert job_count_off(a, {0: COUNT, 1: COUNT, 2: COUNT}) == 0
    # the marked allocation is stopped in the plan that places its
    # replacement, under its name
    moved = np.flatnonzero(a["prev"] >= 0)
    assert moved.size == int((a["stop"] > 0).sum()) > 0
    np.testing.assert_array_equal(
        a["create"][moved], a["stop"][a["prev"][moved]])
    np.testing.assert_array_equal(
        a["name_idx"][moved], a["name_idx"][a["prev"][moved]])
    # and with the store's own last-write index in its place the rule
    # can tell: the client's ``complete`` lands a commit later
    raw = drained(seed)["answers"]
    assert job_count_off(raw, {0: COUNT, 1: COUNT, 2: COUNT}) > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_nothing_lands_on_a_node_that_drains_or_is_ineligible(seed):
    run = drained(seed)
    a, _acked, drains = _patched(run)
    closed = judge.Closed(run["fleet"]["n"], drains)
    assert judge.placed_on_ineligible(a, closed) == 0
    # only marked allocations left, and only from draining nodes
    gone = a["stop"] > 0
    assert a["marked"][gone].all()
    for i in np.flatnonzero(gone):
        assert closed.at(int(a["stop"][i]), draining_only=True)[a["node"][i]]
    # the rule can tell: a replacement's own node, called closed
    moved = np.flatnonzero(a["prev"] >= 0)
    fake = judge.Closed(run["fleet"]["n"], drains)
    fake.rows = np.r_[fake.rows, a["node"][moved[0]]]
    fake.since = np.r_[fake.since, a["create"][moved[0]] - 1]
    fake.until = np.r_[fake.until, a["create"][moved[0]] + 1]
    # a wave's replacements commit at one index, several on a node at times
    there = (a["node"] == a["node"][moved[0]]) & (
        a["create"] == a["create"][moved[0]])
    assert judge.placed_on_ineligible(a, fake) == int(there.sum()) >= 1


@pytest.mark.parametrize("seed", SEEDS)
def test_the_budget_is_never_exceeded_at_any_index(seed):
    a, acked, _drains = _patched(drained(seed))
    mark = judge.mark_index(a)
    one = {0: 1, 1: 1, 2: 1}
    assert judge.migrate_parallel_exceeded(a, mark, acked, one) == 0
    # and the rule can tell: a budget of none is exceeded by every mark
    none = {0: 0, 1: 0, 2: 0}
    assert judge.migrate_parallel_exceeded(a, mark, acked, none) > 0
    # a wave marks one allocation of a group: as many drainer evals as
    # migrations, and each placed one allocation
    ev = a["evals"]
    drainer = np.flatnonzero(ev["drain"])
    assert drainer.size == int((a["stop"] > 0).sum())
    placed = np.bincount(a["eval"][a["prev"] >= 0], minlength=ev["job"].size)
    assert (placed[drainer] == 1).all()


@pytest.mark.parametrize("seed", SEEDS)
def test_the_kernel_is_shown_the_references_view_with_the_stop_taken_off(
        seed):
    """Eligible rows, usage, the job's allocations per node and the spread
    counts per rack, each as the snapshot had them less the plan's own
    stop, with every node that drains or is ineligible masked out
    (``reference/drain.py`` ``freed_view`` and ``walk``'s mask)."""
    run = drained(seed)
    fleet = run["fleet"]
    a, _acked, _drains = _patched(run)
    assert len(run["shown"]) == int((a["stop"] > 0).sum())
    kept = []
    for s in run["shown"]:
        spec = {"cpu": 0, "memory_mb": 0, "disk_mb": 0}
        zero = {d: np.zeros(fleet["n"]) for d in ref.DIMS}
        _used, mine, racks = ref.freed_view(
            fleet, zero, spec, s["job_rows"], s["stopped_rows"])
        assert len(s["stopped_rows"]) == 1
        assert len(s["job_rows"]) == COUNT
        assert int(racks.sum()) == COUNT - 1
        node = np.asarray(s["row_node"])
        np.testing.assert_array_equal(
            s["rack_count_of_row"], racks[fleet["rack"][node]])
        np.testing.assert_array_equal(s["job_counts"], mine[node])
        # the node the stop leaves is in the tensors and not eligible
        assert s["stopped_rows"][0] in s["closed_nodes"]
        want = ~np.isin(node, s["closed_nodes"])
        np.testing.assert_array_equal(s["eligible"], want)
        # usage is the reference's freed view on every row the lane may
        # place on, and on every other but the stopped one. There a solo
        # pass has taken the stop off its own ``used``; a member of a
        # batched pass leaves it on the ``used`` all lanes share
        over = s["used"] - s["used_from_snapshot"]
        stopped_row = int(np.flatnonzero(node == s["stopped_rows"][0])[0])
        assert not s["eligible"][stopped_row]
        kept.append(bool(over[stopped_row].any()))
        if kept[-1]:
            np.testing.assert_allclose(
                over[stopped_row], s["stopped_vector"][0])
            over[stopped_row] = 0
        np.testing.assert_allclose(over, 0, atol=1e-3)
    # a wave is an eval a job the node holds: several rode one batched pass
    assert any(kept)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_judge_reads_the_run_correct(seed):
    run = drained(seed)
    numbers = judge.judge(
        run["fleet"], run["specs"], run["requests"], run["answers"],
        (0.0, clock() + 60.0), seed,
    )
    for exact in ("unfinished_requests", "nodes_over_capacity",
                  "allocs_off_fleet", "placed_on_ineligible",
                  "job_count_off", "migrate_parallel_exceeded",
                  "unmarked_alloc_stopped", "drained_node_not_empty",
                  "drains_unfinished", "alloc_names_duplicated",
                  "blocked_evals_left", "drain_force_stops"):
        assert numbers[exact] == 0, (exact, numbers)
    assert numbers["drains_judged"] == DRAINS
    assert numbers["evals_judged"] > 0
    assert numbers["mark_set_mismatch_share"] == 0.0
    assert numbers["score_mismatch_share"] == 0.0
    assert numbers["jobs_off_best_share"] == 0.0
