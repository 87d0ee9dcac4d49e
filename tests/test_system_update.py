"""The system scheduler's diff (scheduler/util.go diffSystemAllocs,
evictAndPlace, inplaceUpdate) against ``benchmark/reference/system.py`` on
seeded fleets: placements, destructive updates (stop and placement at one
commit index, on the same node, scored with the old allocation gone), in
place updates, an unchanged job, a stopped job, the rolling limit and its
follow-up eval; and the probe that found an update placing nothing, on the
served path. One parametrised test a rule, a case a seed."""

import copy
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import system as ref  # noqa: E402
from benchmark.reference.placement import DIMS  # noqa: E402
from nomad_tpu import mock  # noqa: E402
from nomad_tpu.scheduler.testing import Harness  # noqa: E402
from nomad_tpu.structs import NodeResources, Resources  # noqa: E402
from nomad_tpu.structs.job import UpdateStrategy  # noqa: E402

# (seed, nodes): ISSUE 39 asks for seeded fleets of 64-256 nodes and a
# destructive update on 256
FLEETS = ((11, 64), (12, 160), (13, 256))
ASKS = ((100, 128), (200, 256), (150, 192))


def _row(node_id: str) -> int:
    return int(node_id[-12:])


def _agent(k: int, version_env: str = "0"):
    cpu, mem = ASKS[k % len(ASKS)]
    job = mock.system_job()
    job.id = f"agent-{k}"
    job.priority = 50
    task = job.task_groups[0].tasks[0]
    task.resources = Resources(cpu=cpu, memory_mb=mem)
    task.env = {"VERSION": version_env}
    return job


def _ask(job) -> dict:
    r = job.task_groups[0].combined_resources()
    return {"cpu": float(r.cpu), "memory_mb": float(r.memory_mb),
            "disk_mb": float(r.disk_mb)}


class Cluster:
    """A Harness over a seeded fleet of ``n`` nodes (two classes), with
    ``n_agents`` agents registered and services packed around them, some
    nodes to the last MHz."""

    def __init__(self, seed: int, n: int):
        rng = np.random.default_rng(seed)
        self.n = n
        self.h = Harness()
        self.fleet = {"n": self.n}
        raw = {d: np.zeros(self.n) for d in DIMS}
        for i in range(self.n):
            big = rng.random() < 1 / 3
            node = mock.node(id=f"00000000-0000-4000-8000-{i:012d}")
            node.node_resources = NodeResources(
                cpu=8000 if big else 4000,
                memory_mb=16384 if big else 8192,
                disk_mb=100 * 1024,
            )
            node.compute_class()
            self.h.store.upsert_node(self.h.next_index(), node)
            raw["cpu"][i] = node.node_resources.cpu - node.reserved.cpu
            raw["memory_mb"][i] = (
                node.node_resources.memory_mb - node.reserved.memory_mb)
            raw["disk_mb"][i] = (
                node.node_resources.disk_mb - node.reserved.disk_mb)
        self.fleet.update(raw)
        self.agents = [_agent(k) for k in range(int(rng.integers(2, 4)))]
        for job in self.agents:
            self.h.store.upsert_job(self.h.next_index(), job)
            self.h.process(mock.eval_for(job))
        self._fill(rng)

    def _fill(self, rng) -> None:
        """A few services' allocations on every node, up to a random share
        of what the agents left; a third of the nodes to the last MHz."""
        svc = mock.job()
        svc.id = "svc"
        self.h.store.upsert_job(self.h.next_index(), svc)
        used = self.usage()
        allocs = []
        for i in range(self.n):
            free_cpu = self.fleet["cpu"][i] - used["cpu"][i]
            free_mem = self.fleet["memory_mb"][i] - used["memory_mb"][i]
            full = rng.random() < 1 / 3
            share = 1.0 if full else rng.uniform(0.2, 0.9)
            for k in range(3 if full else int(rng.integers(1, 4))):
                a = mock.alloc(svc, self.h.store.node_by_id(
                    f"00000000-0000-4000-8000-{i:012d}"))
                a.resources.cpu = int(free_cpu * share) // 3
                a.resources.memory_mb = int(free_mem * share) // 3
                a.resources.disk_mb = 300
                allocs.append(a)
        self.h.store.upsert_allocs(self.h.next_index(), allocs)

    def usage(self) -> dict:
        used = {d: np.zeros(self.n) for d in DIMS}
        for a in self.h.store.allocs():
            if a.terminal_status():
                continue
            vec = a.comparable_resources().to_vector()
            for k, d in enumerate(DIMS):
                used[d][_row(a.node_id)] += vec[k]
        return used

    def live(self, job):
        return [
            a for a in self.h.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()
        ]

    def register(self, job):
        """Register ``job`` (a new version where the id is live) and run
        its eval; returns the plans it submitted."""
        self.h.store.upsert_job(self.h.next_index(), job)
        before = len(self.h.plans)
        self.h.process(mock.eval_for(job))
        return self.h.plans[before:]


def _next_version(job, env: str):
    nxt = copy.deepcopy(job)
    nxt.task_groups[0].tasks[0].env = {"VERSION": env}
    return nxt


@pytest.fixture(scope="module", params=FLEETS, ids=lambda f: f"{f[1]}-nodes")
def cluster(request):
    return Cluster(*request.param)


def _scores(allocs) -> dict:
    return {
        _row(a.node_id): a.metrics.scores[f"{a.node_id}.score"]
        for a in allocs
    }


def test_a_registration_places_the_references_nodes_and_scores(cluster):
    job = _agent(9)
    used = cluster.usage()
    cluster.register(job)
    live = cluster.live(job)
    want = ref.serve_update(cluster.fleet, used, _ask(job), [], [], 0)
    assert sorted(_row(a.node_id) for a in live) == sorted(want["placed"])
    got = _scores(live)
    for row, score in zip(want["placed"], want["score"]):
        assert abs(got[int(row)] - score) < 1e-4
    # stopped again: the fixture's fleet is shared by the tests below
    stopped = copy.deepcopy(job)
    stopped.stop = True
    cluster.register(stopped)
    assert cluster.live(job) == []


def test_a_destructive_update_replaces_every_node_in_one_plan(cluster):
    job = cluster.agents[0]
    old = {a.id: a for a in cluster.live(job)}
    used = cluster.usage()
    nxt = _next_version(job, "1")
    plans = cluster.register(nxt)
    assert len(plans) == 1  # one eval, one plan, nothing left over
    (plan,) = plans
    stops = [a for v in plan.node_update.values() for a in v]
    places = [a for v in plan.node_allocation.values() for a in v]
    assert {a.id for a in stops} == set(old)
    assert sorted(_row(a.node_id) for a in stops) == sorted(
        _row(a.node_id) for a in places)
    live = cluster.live(job)
    assert len(live) == cluster.n
    assert {a.job_version for a in live} == {nxt.version}
    # stop and placement at one commit index, on the same node
    gone = {_row(a.node_id): cluster.h.store.alloc_by_id(a.id) for a in stops}
    for a in live:
        assert gone[_row(a.node_id)].modify_index == a.create_index
    want = ref.serve_update(
        cluster.fleet, used, _ask(nxt),
        [_row(a.node_id) for a in old.values()],
        [a.job_version for a in old.values()], nxt.version,
    )
    assert want["unplaced"] == 0
    assert sorted(want["placed"]) == sorted(_row(a.node_id) for a in live)
    got = _scores(live)
    err = [abs(got[int(r)] - s) for r, s in zip(want["placed"], want["score"])]
    assert max(err) < 1e-4
    cluster.agents[0] = nxt


def test_a_node_full_only_because_of_the_old_version_takes_the_new(cluster):
    job = cluster.agents[0]
    used = cluster.usage()
    ask = _ask(job)
    full = np.flatnonzero(
        used["cpu"] + ask["cpu"] > cluster.fleet["cpu"])
    assert full.size, "the fill left no node full"
    nxt = _next_version(job, "full")
    cluster.register(nxt)
    on = {_row(a.node_id) for a in cluster.live(job)
          if a.job_version == nxt.version}
    assert set(full.tolist()) <= on
    cluster.agents[0] = nxt


def test_an_in_place_update_changes_no_node(cluster):
    job = cluster.agents[-1]
    before = {a.id: a.node_id for a in cluster.live(job)}
    same = copy.deepcopy(job)  # registered again unchanged: a new version
    (plan,) = cluster.register(same)
    assert not plan.node_update
    want = ref.diff(np.arange(cluster.n), [_row(n) for n in before.values()],
                    [job.version] * len(before), same.version,
                    destructive=False)
    assert want["inplace"].size == len(before) and not want["replace"].size
    after = {a.id: a.node_id for a in cluster.live(same)}
    assert after == before
    assert {a.job_version for a in cluster.live(same)} == {same.version}
    cluster.agents[-1] = same


def test_an_unchanged_job_submits_no_plan(cluster):
    job = cluster.agents[-1]
    before = len(cluster.h.plans)
    cluster.h.process(mock.eval_for(job))
    assert len(cluster.h.plans) == before
    assert cluster.h.evals[-1].status == "complete"


def test_the_rolling_limit_and_its_follow_up_eval(cluster):
    job = cluster.agents[1]
    k = 7
    nxt = _next_version(job, "rolled")
    nxt.task_groups[0].update = UpdateStrategy(max_parallel=k, stagger_s=5.0)
    created = len(cluster.h.created_evals)
    t0 = time.time()
    (plan,) = cluster.register(nxt)
    stops = [a for v in plan.node_update.values() for a in v]
    places = [a for v in plan.node_allocation.values() for a in v]
    assert len(stops) == len(places) == k
    held = [a for a in cluster.live(job) if a.job_version != nxt.version]
    assert len(held) == cluster.n - k
    want = ref.diff(np.arange(cluster.n),
                    [_row(a.node_id) for a in held] + [
                        _row(a.node_id) for a in places],
                    [a.job_version for a in held] + [nxt.version] * k,
                    nxt.version, True, max_parallel=k)
    assert want["limit_reached"] == (cluster.n - k > k)
    follow = cluster.h.created_evals[created:]
    assert len(follow) == 1
    (f,) = follow
    assert f.triggered_by == "rolling-update" and f.job_id == nxt.id
    assert t0 + 5.0 <= f.wait_until_unix <= time.time() + 5.0
    assert cluster.h.evals[-1].next_eval == f.id
    # the follow-ups go on until every node is on the new version, at most
    # max_parallel an eval
    rounds = 1
    while len(cluster.h.created_evals) > created + rounds - 1:
        before = len(cluster.h.plans)
        cluster.h.process(cluster.h.created_evals[-1])
        rounds += 1
        for p in cluster.h.plans[before:]:
            assert sum(map(len, p.node_update.values())) <= k
        assert rounds <= cluster.n
    live = cluster.live(nxt)
    assert {a.job_version for a in live} == {nxt.version}
    assert len(live) == cluster.n
    assert rounds == -(-cluster.n // k)  # evals in all
    cluster.agents[1] = nxt


def test_a_stopped_job_stops_everything_and_places_nothing(cluster):
    job = copy.deepcopy(cluster.agents[0])
    job.stop = True
    (plan,) = cluster.register(job)
    assert not plan.node_allocation
    assert sum(map(len, plan.node_update.values())) == cluster.n
    assert cluster.live(job) == []


def test_an_update_of_a_live_system_job_places_every_node_served_path():
    """The probe: before, the update's eval was acked with no plan and 0 of
    N nodes held the new version. The applier checks every node of it
    from the store's usage index: the exact walk judges none."""
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.utils.metrics import global_metrics

    def counts():
        c = global_metrics.snapshot()["counters"]
        return [c.get(f"nomad.plan.nodes_{k}", 0)
                for k in ("indexed", "walked")]

    before = counts()
    server = Server(ServerConfig(num_workers=1, num_batch_workers=1))
    server.establish_leadership()
    try:
        n = 12
        for _ in range(n):
            server.register_node(mock.node())
        job = _agent(0)
        server.register_job(job)
        assert server.wait_for_evals(timeout=30)
        nxt = _next_version(job, "1")
        ev = server.register_job(nxt)
        assert server.wait_for_evals(timeout=30)
        assert server.store.eval_by_id(ev.id).status == "complete"
        live = [
            a for a in server.store.allocs_by_job("default", job.id)
            if not a.terminal_status()
        ]
        version = server.store.job_by_id("default", job.id).version
        assert version == 1
        assert sorted(a.job_version for a in live) == [version] * n
        indexed, walked = (b - a for a, b in zip(before, counts()))
        assert walked == 0 and indexed >= 2 * n
    finally:
        server.shutdown()
