"""The loss of nodes (``benchmark/node_loss/``, ``reference/node_loss.py``):
the program beside the plain reference on seeded fleets of 96 to 256 nodes
in 8 racks under 6 to 12 jobs, a rack down at a time, one seed a case; the
storm in miniature; the rack's return; ``NodeHeartbeater.expire`` beside
the heartbeater's own sweep; the repairs this path forced on the shared
code; the cell through ``run.main`` at its rehearsal size and each control
failing its own limit."""

import copy
import functools
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.node_loss import control, judge  # noqa: E402
from benchmark.node_loss.driver import Driver, rack_order  # noqa: E402
from benchmark.reference import node_loss as ref  # noqa: E402
from benchmark.reference import placement as plain  # noqa: E402

CELL = control.CELL
EXACT = (
    "unfinished_requests", "nodes_over_capacity", "allocs_off_fleet",
    "placed_on_down_node", "lost_not_marked", "job_count_off",
    "replacement_unlinked", "unrelated_allocs_stopped",
    "alloc_names_duplicated", "blocked_evals_left", "lost_counter_off",
    "expired_counter_off",
)
PROGRAM = ("breaker_trips", "reference_path_passes", "nacks",
           "swallowed_errors", "live_allocs_out_of_band", "window_stalled")
# what this deployment brings, read on the host in a rehearsal
NEW_METRICS = (
    "node_status_ms_p50", "node_update_evals", "noop_evals",
    "loss_evals_busy_ms_p50", "loss_recover_ms_p50",
)


def _counter(name: str) -> float:
    from nomad_tpu.utils.metrics import global_metrics

    return global_metrics.snapshot()["counters"].get(name, 0)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark/reference/node_loss.py")) as f:
        source = f.read()
    assert "nomad_tpu" not in source.split('"""', 2)[2]


def test_the_cell_rehearses_through_run_main():
    """The toy fleet's racks are 12 nodes and its failures 0.7 s apart, so
    passes contend far more than at the cell's size: the shares are read
    and reported here, the exact numbers held to 0."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147484029", "--seconds", "4",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["failed"] == 0 and result["attempted"] > 0
    compared = result["compared"]
    for name in EXACT + PROGRAM:
        assert compared[name] == {"value": 0, "limit": 0}, name
    for share in ("score_mismatch_share", "jobs_off_best_share"):
        assert 0.0 <= compared[share]["value"] <= 1.0
    # a rack's allocations leave the accounting as it goes down and come
    # back with the replacements; no more than the band is out at once
    steady = result["steady"]
    assert steady["live_allocs_max"] == 480
    assert 480 - 180 <= steady["live_allocs_min"] < 480
    metrics = result["metrics"]
    for name in NEW_METRICS:
        assert metrics[name]["value"] > 0, name
    assert metrics["loss_evals_wait_ms_p50"]["value"] >= 0.0
    # a job's second node eval of one failure finds nothing to do
    assert metrics["noop_evals"]["value"] < metrics["node_update_evals"][
        "value"]
    # every lost allocation is one stop committed
    assert metrics["plan_stops_committed"]["value"] > 0
    # the lost stops ride the batched pass: their nodes are down
    assert metrics["evals_batched_with_stops"]["value"] > 0


@pytest.mark.parametrize("rack, stride, n", [(25, 7, 25), (8, 7, 8)])
def test_no_rack_comes_twice_in_the_order(rack, stride, n):
    order = rack_order(rack, {"stride": stride}, 2147484029)
    assert len({next(order) for _ in range(n)}) == n


# -- the spread boost a lost rack leaves behind --------------------------------
@pytest.mark.parametrize("seed", range(8))
def test_the_host_and_device_boosts_follow_the_reference(seed):
    """``evenSpreadScoreBoost`` over the combined-use map, a value the map
    holds at 0 included: the kernels' tables, the host's re-score and the
    reference give one boost per value."""
    import jax.numpy as jnp

    from nomad_tpu.device.flatten import ValueBlocks
    from nomad_tpu.device.score import (
        BLOCK_EVEN_SPREAD,
        EVEN_HELD_AT_ZERO,
        _block_tables,
        _host_block_tables,
    )

    rng = np.random.default_rng(seed)
    v = 8
    counts = rng.integers(0, 4, v).astype(np.float32)
    counts[rng.integers(0, v)] = 0.0
    if seed == 0:
        counts[:] = 0.0  # every allocation of the job stopped
    held_zero = (counts == 0) & (rng.random(v) < 0.5)
    desired = np.where(held_zero, EVEN_HELD_AT_ZERO, -1.0).astype(np.float32)
    blocks = ValueBlocks(
        value_ids=np.zeros((1, 8), dtype=np.int32), counts0=counts[None],
        desired=desired[None], caps=np.full((1, v), np.inf, np.float32),
        weights=np.ones(1, np.float32),
        kinds=np.array([BLOCK_EVEN_SPREAD], dtype=np.int32),
    )
    want = ref.even_spread_boost(counts, held_zero)
    host, _ = _host_block_tables(counts[None].copy(), blocks)
    dev, _ = _block_tables(
        jnp.asarray(counts[None]), jnp.asarray(desired[None]),
        jnp.asarray(blocks.caps), jnp.asarray(blocks.weights),
        jnp.asarray(blocks.kinds),
    )
    np.testing.assert_allclose(host[0], want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dev)[0], want, rtol=1e-6,
                               atol=1e-6)


def test_a_rack_held_at_zero_leaves_every_other_rack_at_minus_one():
    counts = np.array([10, 0, 11, 10], dtype=float)
    held = np.array([True, True, True, True])
    np.testing.assert_array_equal(
        ref.even_spread_boost(counts, held), [-1.0, 1.0, -1.0, -1.0])
    # the rack the job never used is not in the map: the min is the
    # others'
    held[1] = False
    np.testing.assert_allclose(
        ref.even_spread_boost(counts, held), [0.1, 1.0, -0.1, 0.1])
    # a plan that stops every allocation of the job: as an empty map
    zero = np.zeros(4)
    np.testing.assert_array_equal(
        ref.even_spread_boost(zero, [True] * 4), zero)


# -- the program beside the reference, a rack down at a time -------------------
def _fleet_config(seed: int) -> tuple:
    _cell, _bench, config, traffic = run.load_cell(CELL, rehearse=True)
    config = copy.deepcopy(config)
    traffic = copy.deepcopy(traffic)
    config["fleet"]["nodes"] = (96, 160, 256)[seed % 3]
    n_jobs = (6, 9, 12)[seed % 3]
    traffic["job"]["count"] = 24
    config["live_allocs"] = n_jobs * 24
    traffic["failure"]["ready_after_s"] = 0.5
    return config, traffic


def _loaded(seed: int):
    """A server whose fleet holds the seed's jobs, registered one at a
    time, and the driver that takes its racks down."""
    from benchmark.gen import fleet as gfleet
    from benchmark.gen import jobs as gjobs
    from benchmark import warm as base
    from nomad_tpu.server import Server, ServerConfig

    config, traffic = _fleet_config(seed)
    server = Server(ServerConfig(**config["server"]))
    server.establish_leadership()
    fleet = gfleet.seed_fleet(server, config)
    specs_sent: dict = {}

    def remember(spec):
        specs_sent[len(specs_sent)] = spec
        return gjobs.make_job(spec)

    stream = gjobs.job_specs(traffic, seed, "t")
    live, requests, n_jobs = base.prefill(
        server, config, traffic, stream, remember, seed, lambda _m: None)
    driver = Driver(
        server, iter(()), remember,
        {"failures_sent": 0, "racks": config["fleet"]["racks"],
         "live_allocs": sum(c for _j, c in live)},
        n_jobs, patient=True, traffic=traffic, seed=seed,
    )
    driver.counted_before = {n: _counter(n) for n in judge.COUNTERS}
    return server, fleet, specs_sent, requests, driver


def _fail(server, driver, racks: int = 1) -> None:
    """``racks`` racks down, every node before the first eval runs (the
    workers held meanwhile): the node evals wait in the broker together,
    and no plan meets a node that goes down after its snapshot."""
    from benchmark.driver import clock

    for w in server.workers:
        w.pause()
    time.sleep(0.3)  # an idle worker's 0.2 s dequeue returns empty
    try:
        for _ in range(racks):
            driver.send_register(clock())
    finally:
        for w in server.workers:
            w.resume()
    driver.drain(60.0)
    assert server.wait_for_evals(30)


def _judged(server, fleet, specs_sent, requests, driver, seed) -> dict:
    job_ids = {s["id"]: j for j, s in specs_sent.items()}
    answers = judge.extract_answers(server.store, job_ids)
    # the counters are the process's: what this server counted
    for name in judge.COUNTERS:
        answers["counters"][name] -= driver.counted_before[name]
    return judge.judge(fleet, specs_sent, requests + driver.requests,
                       answers, (0.0, 1e18), seed), answers


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_one_rack_down_matches_the_reference(seed):
    server, fleet, specs, requests, driver = _loaded(seed)
    expired = _counter("nomad.heartbeat.expired")
    lost = _counter("nomad.plan.allocs_lost")
    full = driver._live_allocs
    try:
        _fail(server, driver)
        failure = driver.failures[0]
        store = server.store
        held = [i for r in failure.requests for i in r.held]
        assert held, "the rack held nothing"
        # the driver's accounting: the rack's allocations gone as it went
        # down, each back as its job recovered
        track = [live for _t, live in driver.live_alloc_track]
        assert min(track) == full - len(held) and track[-1] == full
        assert full == sum(1 for a in store.allocs()
                           if not a.terminal_status())
        for alloc_id in held:
            a = store.alloc_by_id(alloc_id)
            assert a.terminal_status() and a.client_status == "lost"
            nxt = store.alloc_by_id(a.next_allocation)
            assert nxt is not None and nxt.previous_allocation == a.id
            assert nxt.name == a.name
            assert nxt.node_id not in failure.node_ids
        for node_id in failure.node_ids:
            assert not [a for a in store.allocs_by_node(node_id)
                        if not a.terminal_status()]
        assert _counter("nomad.heartbeat.expired") - expired == len(
            failure.node_ids)
        assert _counter("nomad.plan.allocs_lost") - lost == len(held)
        numbers, answers = _judged(server, fleet, specs, requests, driver,
                                   seed)
    finally:
        server.shutdown()
    for name in EXACT:
        assert numbers[name] == 0, (name, numbers)
    assert numbers["evals_judged"] > 0
    # every recorded score is the reference's on the cluster at its commit
    assert numbers["score_mismatch_share"] == 0.0, numbers
    assert numbers["score_error_median"] < 1e-4
    # and the failed rack held at 0, with no feasible node, wherever the
    # plan stopped every allocation the job had there
    rack, node, at_zero = failure.rack, answers["node"], 0
    for e in np.unique(answers["eval"][answers["eval"] >= 0]):
        if answers["evals"]["node"][e] < 0:
            continue
        placed = np.flatnonzero(answers["eval"] == e)
        commit = int(answers["create"][placed].min())
        j = int(answers["job"][placed[0]])
        mine = np.flatnonzero(answers["job"] == j)
        before = mine[(answers["create"][mine] < commit) & (
            (answers["stop"][mine] == 0) | (answers["stop"][mine] >= commit))]
        stopped = before[answers["stop"][before] == commit]
        there = before[fleet["rack"][node[before]] == rack]
        if not there.size or not np.isin(there, stopped).all():
            continue
        used = {d: np.zeros(fleet["n"]) for d in plain.DIMS}
        _view, _mine, racks, held_racks = ref.freed_view(
            fleet, used, specs[j], node[before], node[stopped])
        assert racks[rack] == 0 and held_racks[rack]
        at_zero += 1
    assert at_zero > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_storm_in_miniature_places_each_job_once(seed):
    """Two racks down together: a job with allocations on several of their
    nodes has an eval for each, enqueued together. None is ever above its
    count; the later evals find nothing left and are counted no-ops."""
    server, fleet, specs, requests, driver = _loaded(seed)
    noop = _counter("nomad.worker.noop_evals")
    made = _counter("nomad.node.update_evals")
    try:
        _fail(server, driver, racks=2)
        numbers, answers = _judged(server, fleet, specs, requests, driver,
                                   seed)
        evals = [e for e in server.store.evals()
                 if e.triggered_by == "node-update"]
    finally:
        server.shutdown()
    for name in EXACT:
        assert numbers[name] == 0, (name, numbers)
    made = _counter("nomad.node.update_evals") - made
    assert made == len(evals)
    jobs = {e.job_id for e in evals}
    assert len(evals) > len(jobs)  # several evals a job
    placing = {
        answers["evals"]["job"][e]
        for e in np.unique(answers["eval"][answers["eval"] >= 0])
        if answers["evals"]["node"][e] >= 0
    }
    noop = _counter("nomad.worker.noop_evals") - noop
    assert 0 < noop <= made - len(placing)


class _Lost:
    """A failure as the judge reads it: node 4 down at index 3."""

    down_index = {4: 3}
    ready_index: dict = {}


def _pass_read_answers(served_row: int, read: int = 3) -> tuple:
    """Five nodes in two racks; job 0 (spread over the racks) lost its
    allocation on node 4 and eval 0, on the snapshot at index 5 and overlay
    read ``read``, places the replacement at index 10 on ``served_row``.
    Around it, other evals of other reads: 1,000 MHz on node 3 at index 8
    from read 2, which the pass had read (its recorded score holds it);
    3,000 MHz on node 2 at index 9 from read 4, a retry that read after the
    pass and committed before it; and 2,000 MHz on node 1 at index 11 from
    read 1, in flight when the pass read."""
    n = 5
    fleet = {"n": n, "rack": np.array([0, 0, 1, 1, 1]),
             "ssd": np.zeros(n), "cpu": np.full(n, 4000.0),
             "memory_mb": np.full(n, 8192.0),
             "disk_mb": np.full(n, 102400.0)}
    spec = {"count": 2, "cpu": 500, "memory_mb": 256, "disk_mb": 300,
            "spread": {"attribute": "${attr.platform.rack}", "weight": 50},
            "affinity": None, "type": "service"}
    # job 0's live one, its lost one, the three of other evals, the
    # replacement
    cols = {
        "node": [0, 4, 3, 2, 1, served_row], "job": [0, 0, 1, 2, 3, 0],
        "create": [1, 1, 8, 9, 11, 10], "stop": [0, 10, 0, 0, 0, 0],
        "name_idx": [0, 1, 0, 0, 0, 1], "eval": [-1, -1, 1, 2, 3, 0],
        "cpu": [500, 500, 1000, 3000, 2000, 500],
        "read": [0, 0, 2, 4, 1, read],
    }
    a = {k: np.asarray(v, dtype=np.int64) for k, v in cols.items()}
    a["res"] = {"cpu": a["cpu"].astype(float),
                "memory_mb": np.full(6, 256.0), "disk_mb": np.full(6, 300.0)}
    a["evals"] = {"snap": np.asarray([5, 7, 8, 4], dtype=np.int64)}
    # the score the pass recorded: on the cluster it read, the retry out
    # and the placement in flight in
    seen = plain.usage_before(
        fleet, a["node"], np.asarray([1, 1, 8, 10, 9, 10]), a["stop"],
        a["res"], 10)
    view, mine, racks, held = ref.freed_view(
        fleet, seen, spec, a["node"][:2], a["node"][1:2])
    a["score"] = np.full(6, np.nan)
    a["score"][5] = ref.scores(fleet, view, spec, mine, racks, held,
                               np.arange(n) != 4)[served_row]
    return fleet, spec, a


def test_a_pass_is_judged_on_the_cluster_it_read():
    """A placement is judged on the usage of the overlay read it carries:
    a later read's retry committed before it is out, an earlier read's
    placement still in flight is in. The best on that view is not off the
    best, though the cluster at its commit offers better; a placement off
    the best there still is, and so is one whose pass read less than its
    stamp says."""
    down = judge.Down(5, [_Lost()])
    fleet, spec, a = _pass_read_answers(3)
    # at its own commit node 2, fuller by the unread retry, scores higher
    used = plain.usage_before(fleet, a["node"], a["create"], a["stop"],
                              a["res"], 10)
    view, mine, racks, held = ref.freed_view(
        fleet, used, spec, a["node"][:2], a["node"][1:2])
    w = ref.walk(fleet, view, spec, a["node"][5:], mine, racks, held,
                 np.arange(5) != 4)
    assert (w["best"] - w["served"]).sum() / w["best"].sum() > judge.JOB_OFF_BEST
    out = judge._judge_eval(fleet, a, spec, 0, down)
    assert not out["off"] and out["errors"].max() < judge.SCORE_MATCH
    assert out["in_flight"] and out["cut"]
    # node 0 sits in the rack the job still holds: off on its view
    fleet, spec, a = _pass_read_answers(0)
    assert judge._judge_eval(fleet, a, spec, 0, down)["off"]
    # stamped as a read after the retry, which its choice left out
    fleet, spec, a = _pass_read_answers(3, read=5)
    assert judge._judge_eval(fleet, a, spec, 0, down)["off"]


def test_a_failed_eval_is_excused_only_on_a_dying_rack():
    """A batch job's eval that ran out of plan attempts while a node went
    down (after it was made, by the commit that failed it) is the applier
    refusing it there; a service eval, one that failed otherwise, or one
    with no node going down in its life, is not."""
    down = judge.Down(5, [_Lost()])  # node 4 down at index 3
    specs = {0: {"type": "batch"}, 1: {"type": "service"}}
    ev = {"failed": np.array([True, True, True, True, False]),
          "max_plans": np.array([True, True, False, True, True]),
          "create": np.array([2, 2, 2, 3, 2]),
          "modify": np.array([6, 6, 6, 6, 6]),
          "job": np.array([0, 1, 0, 0, 0])}
    assert judge.failed_evals(specs, ev, down) == (1, 3)


def test_the_racks_return_makes_no_service_eval_and_unblocks():
    """A rack back ``ready`` holds no live allocation: its node evals are
    none (createNodeEvals makes one a job with an allocation there, and a
    system job has none here). A job that could not place while the rack
    was down is unblocked by the node writes and placed on it."""
    from benchmark.driver import clock
    from benchmark.gen.jobs import make_job
    from nomad_tpu.structs import Constraint
    from nomad_tpu.structs.evaluation import EVAL_STATUS_BLOCKED

    server, fleet, specs, requests, driver = _loaded(1)
    driver.ready_after_s = 3600.0  # the rack comes back below, by hand
    try:
        failure = driver.send_register(clock())
        assert _wait(lambda: not driver.collect() and not driver.in_flight)
        store = server.store
        # a job that fits only on the rack that is down
        job = make_job(dict(specs[0], id="t-rack", count=1, spread=None,
                            affinity=None))
        job.constraints = [Constraint(
            l_target="${attr.platform.rack}", r_target=f"r{failure.rack}",
            operand="=")]
        server.register_job(job)
        assert server.wait_for_evals(10)
        assert [e for e in store.evals()
                if e.job_id == "t-rack" and e.status == EVAL_STATUS_BLOCKED]
        before = len(store.evals())
        made = []
        for node_id in failure.node_ids:
            made += server.update_node_status(node_id, "ready")
        assert made == [] and len(store.evals()) >= before
        assert server.wait_for_evals(10)
        live = [a for a in store.allocs_by_job("default", "t-rack")
                if not a.terminal_status()]
        assert len(live) == 1 and live[0].node_id in failure.node_ids
    finally:
        server.shutdown()


def test_expire_and_the_sweep_make_the_same_commits():
    """The heartbeater's sweep, its timers run out, and ``expire`` on the
    same nodes in the same order write the same node commits and make the
    same node evals."""
    from nomad_tpu import mock
    from nomad_tpu.server import Server, ServerConfig

    def commits(sweep: bool) -> tuple:
        server = Server(ServerConfig(num_workers=0, heartbeat_ttl=0.2))
        server.establish_leadership()
        try:
            nodes = [mock.node() for _ in range(6)]
            for i, n in enumerate(nodes):
                n.id = f"00000000-0000-4000-8000-{i:012d}"
                server.store.upsert_node(i + 1, n)
            job = mock.job(id="web-1")
            server.store.upsert_job(10, job)
            allocs = []
            for k, n in enumerate(nodes):
                a = mock.alloc(job, node_id=n.id)
                a.name = f"{job.id}.{job.task_groups[0].name}[{k}]"
                allocs.append(a)
            server.store.upsert_allocs(11, allocs)
            down = [n.id for n in nodes[:4]]
            if sweep:
                for node_id in down:
                    server.heartbeater.heartbeat(node_id)
                assert _wait(lambda: all(
                    server.store.node_by_id(i).status == "down"
                    for i in down))
            else:
                assert server.heartbeater.expire(down) == 4
            statuses = sorted(
                (n.modify_index, n.id, n.status) for n in server.store.nodes())
            evals = sorted(
                (e.create_index, e.job_id, e.node_id, e.triggered_by)
                for e in server.store.evals())
            return statuses, evals
        finally:
            server.shutdown()

    assert commits(sweep=True) == commits(sweep=False)


def _wait(fn, timeout: float = 10.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(0.02)
    return False


# -- the repairs on the shared path ---------------------------------------------
def test_a_retry_links_the_lost_allocation_its_plan_stopped():
    """The applier refused a replacement beside its lost stop (its node
    went down after the plan's snapshot): the retry places the name the
    job is short of as that allocation's replacement, and the names of an
    eval are placed in order."""
    from nomad_tpu import mock
    from nomad_tpu.scheduler.reconcile import reconcile

    job = mock.job()
    job.task_groups[0].count = 4
    group = job.task_groups[0].name
    allocs = []
    for k in (3, 0, 2, 1):
        a = mock.alloc(job, node_id=f"n{k}")
        a.name = f"{job.id}.{group}[{k}]"
        allocs.append(a)
    gone = allocs[0]
    gone.desired_status, gone.client_status = "stop", "lost"
    gone.modify_index = 7
    results = reconcile(job, job.id, allocs, {})
    assert [p.name for p in results.place] == [gone.name]
    assert results.place[0].previous_alloc is gone
    # names in order, whatever order the allocations came in
    down = mock.node()
    down.status = "down"
    for a in allocs[1:]:
        a.node_id = down.id
    results = reconcile(job, job.id, allocs, {down.id: down})
    names = [p.name for p in results.place]
    assert names == sorted(names, key=lambda s: int(s[s.rindex("[") + 1:-1]))
    assert len(names) == 4


def test_a_lost_stop_frees_its_room_in_the_overlay():
    """A stop marked ``lost`` hands back what the allocation held until
    the plan: the overlay's frozen base counted it."""
    from nomad_tpu import mock
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.structs import PlanResult

    server = Server(ServerConfig(num_workers=0))
    try:
        node = mock.node()
        server.store.upsert_node(1, node)
        job = mock.job()
        server.store.upsert_job(2, job)
        a = mock.alloc(job, node_id=node.id)
        server.store.upsert_allocs(3, [a])
        ct = server.device_cache.tensors(server.store.snapshot())
        ov = server.placement_overlay
        row = ct.node_row[node.id]
        ov.add_delta(ct, np.array([row]), np.zeros_like(ct.used[0]))
        held = float(ov._base[row][0])
        assert held >= a.resources.cpu
        stop = copy.copy(a)
        stop.desired_status, stop.client_status = "stop", "lost"
        server._unblock_on_stops(
            [PlanResult(node_update={node.id: [stop]})], ct.index + 1)
        assert float(ov._base[row][0]) == pytest.approx(
            held - a.comparable_resources().to_vector()[0])
    finally:
        server.shutdown()


def test_the_overlay_numbers_its_reads_for_each_thread():
    """Each ``begin_pass`` is a read of its own, numbered in the order the
    reads were taken; a thread sees the number of its own last read."""
    import threading
    from types import SimpleNamespace

    from nomad_tpu.server.overlay import SharedOverlay

    ov = SharedOverlay()
    ct = SimpleNamespace(layout_gen=0)
    assert ov.read_ordinal() == 0
    ov.begin_pass(ct)
    ov.pass_finished()
    got = []

    def other():
        ov.begin_pass(ct)
        got.append(ov.read_ordinal())
        ov.pass_finished()

    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert (ov.read_ordinal(), got) == (1, [2])


def test_each_placement_carries_the_read_it_was_scored_on():
    """Every placement of a rack's loss carries the overlay read its pass
    took; the members of one batched pass share it, and a retry after a
    plan refused in part reads anew."""
    server, fleet, specs, requests, driver = _loaded(2)
    try:
        fill = {a.id: a.metrics.usage_read for a in server.store.allocs()}
        _fail(server, driver)
        reads: dict = {}
        for a in server.store.allocs():
            if a.id not in fill:
                reads.setdefault(a.metrics.usage_read, set()).add(
                    a.create_index)
    finally:
        server.shutdown()
    assert min(fill.values()) > 0 and min(reads) > max(fill.values())
    # one read, one pass: its placements commit at its merged commit and,
    # for the members it deferred, at one each after it
    assert any(len(at) > 1 for at in reads.values()) or len(reads) > 1


def test_the_store_keeps_the_index_of_its_own_last_write():
    """``alloc_modify_index`` moves with a server write of the allocation
    only: a successor's link and a client's update leave it."""
    from nomad_tpu import mock
    from nomad_tpu.state import StateStore

    store = StateStore()
    job = mock.job()
    a = mock.alloc(job)
    store.upsert_allocs(5, [a])
    stopped = copy.copy(a)
    stopped.desired_status, stopped.client_status = "stop", "lost"
    store.upsert_allocs(6, [stopped])
    b = mock.alloc(job)
    b.previous_allocation = a.id
    store.upsert_allocs(9, [b])
    got = store.alloc_by_id(a.id)
    assert got.next_allocation == b.id and got.modify_index == 9
    assert got.alloc_modify_index == 6
    update = copy.copy(store.alloc_by_id(b.id))
    update.client_status = "running"
    store.update_allocs_from_client(10, [update])
    assert store.alloc_by_id(b.id).alloc_modify_index == 9


# -- the controls ----------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _start(seed: int):
    """The rehearsal's fleet under jobs of 40 (its own jobs of 16 hold two
    allocations a rack: a job's later eval of a failure is rarer)."""
    _cell, _bench, config, traffic = run.load_cell(CELL, rehearse=True)
    traffic["job"]["count"] = 40
    config["live_allocs"] = 480
    return config, traffic, control.filled(config, traffic, seed)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_sound_reference_comes_out_correct(seed):
    config, traffic, start = _start(seed)
    (correct, compared), numbers = control.judge_reference(
        config, traffic, start, seed, 2)
    assert correct, compared
    assert numbers["evals_judged"] > 0
    for share in ("score_mismatch_share", "jobs_off_best_share"):
        assert numbers[share] == 0.0


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("fault", control.FAULTS)
def test_a_control_fails_the_limit_it_is_written_for(fault, seed):
    config, traffic, start = _start(seed)
    (correct, compared), _numbers = control.judge_reference(
        config, traffic, start, seed, 2, fault)
    failed = {
        k for k, c in compared.items()
        if c["value"] is None or c["value"] > c["limit"]
    }
    assert not correct
    assert control.FAILS[fault] in failed, compared
