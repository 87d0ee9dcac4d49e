"""The system pass writes the generic pass's phases (``prepare`` with
``flatten``, ``system.diff`` under it, ``invoke_scheduler`` with the
kernel and ``explain``, ``build_plan`` with ``system.place``,
``submit_plan``), counts what it replaced, and the store's write of a plan
lies inside ``plan_apply.commit`` with its ``allocs`` and ``stops``; at
toy size on the served path."""

import copy
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.obs.recorder import flight_recorder
from nomad_tpu.obs.trace import global_tracer
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.utils.metrics import global_metrics

N = 6
COUNTERS = ("nomad.system.placed", "nomad.system.replaced",
            "nomad.system.inplace", "nomad.system.stopped")


def _spans(trace, name):
    return [s for s in trace["spans"] if s["name"] == name]


def _one(trace, name):
    (s,) = _spans(trace, name)
    return s


def _parent(trace, span):
    return next(s for s in trace["spans"] if s["span_id"] == span["parent_id"])


def _end(span):
    return span["start_unix"] + span["duration_ms"] / 1000.0


@pytest.fixture(scope="module")
def system_traces():
    """A system job registered on ``N`` nodes, then its next version
    (destructive), then registered unchanged (in place), then stopped:
    {kind: trace}, and the counters' deltas."""
    global_tracer.set_enabled(True)
    global_tracer.reset()
    got = {}

    def keep(trace):
        got[trace["eval_id"]] = trace

    flight_recorder.add_listener(keep)
    before = dict(global_metrics.snapshot()["counters"])
    server = Server(ServerConfig(num_workers=1, num_batch_workers=1))
    server.establish_leadership()
    evals = {}
    try:
        for _ in range(N):
            server.register_node(mock.node())
        job = mock.system_job()
        job.task_groups[0].tasks[0].env = {"V": "0"}
        steps = [("register", job)]
        nxt = copy.deepcopy(job)
        nxt.task_groups[0].tasks[0].env = {"V": "1"}
        steps.append(("destructive", nxt))
        steps.append(("inplace", copy.deepcopy(nxt)))
        stopped = copy.deepcopy(nxt)
        stopped.stop = True
        steps.append(("stop", stopped))
        for kind, j in steps:
            evals[kind] = server.register_job(j).id
            assert server.wait_for_evals(timeout=30)
        deadline = time.time() + 5.0
        while time.time() < deadline and not set(evals.values()) <= set(got):
            time.sleep(0.02)
    finally:
        server.shutdown()
        flight_recorder.remove_listener(keep)
    after = global_metrics.snapshot()["counters"]
    out = {kind: got[eid] for kind, eid in evals.items()}
    out["counters"] = {
        k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS
    }
    return out


@pytest.mark.parametrize("kind", ["register", "destructive"])
def test_a_placing_pass_writes_the_generic_phases(system_traces, kind):
    t = system_traces[kind]
    top = [s["name"] for s in t["spans"] if s["parent_id"] == t["spans"][0][
        "span_id"]]
    for phase in ("prepare", "invoke_scheduler", "build_plan", "submit_plan"):
        assert phase in top, (phase, top)
    assert _parent(t, _one(t, "flatten"))["name"] == "prepare"
    assert _parent(t, _one(t, "system.diff"))["name"] == "prepare"
    assert _parent(t, _one(t, "system.place"))["name"] == "build_plan"
    steps = sorted(s["tags"]["step"] for s in _spans(t, "explain"))
    assert steps == ["final", "groups"]
    for s in _spans(t, "explain"):
        assert _parent(t, s)["name"] == "invoke_scheduler"
    kernel = [s for s in t["spans"] if s["name"].startswith("kernel:")]
    assert kernel and "score_matrix_kernel" in kernel[0]["name"]
    place = _one(t, "system.place")
    assert place["tags"] == {**place["tags"], "nodes": N, "placed": N,
                             "failed": 0}


@pytest.mark.parametrize("kind,counts", [
    ("register", {"place": N}),
    ("destructive", {"destructive": N}),
    ("inplace", {"inplace": N}),
    ("stop", {"stop": N}),
])
def test_the_diff_counts_each_category(system_traces, kind, counts):
    tags = _one(system_traces[kind], "system.diff")["tags"]
    want = dict.fromkeys(
        ("place", "destructive", "inplace", "ignore", "stop", "migrate",
         "lost"), 0)
    want.update(counts)
    assert {k: tags[k] for k in want} == want


@pytest.mark.parametrize("kind,allocs,stops", [
    ("register", N, 0), ("destructive", N, N), ("inplace", N, 0),
    ("stop", 0, N),
])
def test_the_store_write_lies_in_the_commit_with_its_counts(
        system_traces, kind, allocs, stops):
    t = system_traces[kind]
    write = _one(t, "plan_apply.store_write")
    commit = _parent(t, write)
    assert commit["name"] == "plan_apply.commit"
    assert write["start_unix"] >= commit["start_unix"] - 2e-6
    assert _end(write) <= _end(commit) + 2e-6
    assert write["tags"]["allocs"] == allocs
    assert write["tags"]["stops"] == stops


@pytest.mark.parametrize("kind,indexed", [
    ("register", N), ("destructive", N), ("inplace", N), ("stop", 0),
])
def test_the_verify_counts_the_nodes_the_usage_index_judged(
        system_traces, kind, indexed):
    tags = _one(system_traces[kind], "plan_apply.evaluate")["tags"]
    assert (tags["indexed"], tags["walked"]) == (indexed, 0)


def test_a_pass_without_placements_neither_scores_nor_walks(system_traces):
    for kind in ("inplace", "stop"):
        names = {s["name"] for s in system_traces[kind]["spans"]}
        assert "system.diff" in names and "submit_plan" in names
        assert not {"invoke_scheduler", "system.place"} & names


def test_the_counters_count_what_committed(system_traces):
    assert system_traces["counters"] == {
        "nomad.system.placed": 2 * N,
        "nomad.system.replaced": N,
        "nomad.system.inplace": N,
        "nomad.system.stopped": N,
    }


def test_a_merged_commits_store_write_lies_in_its_commit():
    """A batched pass of two registrations: the one store write of the
    merged plan moves under the pass's ``plan_apply.commit``."""
    global_tracer.set_enabled(True)
    got = {}

    def keep(trace):
        got[trace["eval_id"]] = trace

    flight_recorder.add_listener(keep)
    server = Server(ServerConfig(num_workers=1))
    server.establish_leadership()
    try:
        for _ in range(4):
            server.register_node(mock.node())
        for w in server.workers:
            w.pause()
        time.sleep(0.25)  # the worker's 0.2 s dequeue poll holds one more turn
        ids = []
        for k in range(2):
            job = mock.job()
            job.task_groups[0].count = 2
            ids.append(server.register_job(job).id)
        for w in server.workers:
            w.resume()
        assert server.wait_for_evals(timeout=30)
        deadline = time.time() + 5.0
        while time.time() < deadline and not set(ids) <= set(got):
            time.sleep(0.02)
    finally:
        server.shutdown()
        flight_recorder.remove_listener(keep)
    writes = [
        (t, s) for t in (got[i] for i in ids) for s in _spans(
            t, "plan_apply.store_write")
    ]
    assert len(writes) == 1  # one write, in the leader's trace
    t, write = writes[0]
    assert _parent(t, write)["name"] == "plan_apply.commit"
    assert write["tags"] == {"allocs": 4, "stops": 0}
