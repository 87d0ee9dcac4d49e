"""nomad_tpu.obs: span/tracer API, cross-thread trace propagation across
the worker → plan-queue → applier handoff, flight-recorder ring,
/v1/agent/trace surface, kernel profiling hooks, and the tracing
overhead guard.

All tests here are CPU-only and ride tier-1.
"""

import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.obs.recorder import (
    FlightRecorder,
    flight_recorder,
    phase_breakdown,
    render_trace,
)
from nomad_tpu.obs.trace import SpanContext, Tracer, global_tracer
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.utils import backend
from nomad_tpu.utils.metrics import count_swallowed, global_metrics


@pytest.fixture(autouse=True)
def _clean_obs():
    global_tracer.set_enabled(True)
    global_tracer.reset()
    flight_recorder.clear()
    yield
    global_tracer.set_enabled(True)
    global_tracer.reset()
    flight_recorder.clear()


def span_by_name(trace, name):
    matches = [s for s in trace["spans"] if s["name"] == name]
    assert matches, f"no span named {name!r} in {trace['spans']}"
    return matches[0]


# -- Tracer unit tests ------------------------------------------------------


class TestTracer:
    def test_span_nesting_parents_via_thread_stack(self):
        t = Tracer()
        t.begin("e1")
        with t.activate("e1"):
            with t.span("outer") as outer:
                with t.span("inner") as inner:
                    assert inner.parent_id == outer.span_id
        tr = t.finish("e1")
        outer_d = span_by_name(tr, "outer")
        assert outer_d["parent_id"] == tr["spans"][0]["span_id"]  # root

    def test_begin_is_idempotent_and_merges_tags(self):
        t = Tracer()
        a = t.begin("e1", tags={"x": 1})
        b = t.begin("e1", tags={"y": 2})
        assert a is b
        assert t.finish("e1")["tags"] == {"x": 1, "y": 2}
        # second finish is a no-op, not a duplicate record
        assert t.finish("e1") is None

    def test_finish_hands_trace_to_recorder(self):
        rec = FlightRecorder()
        t = Tracer(recorder=rec)
        t.begin("e1")
        t.finish("e1", status="acked")
        assert rec.get("e1")["status"] == "acked"

    def test_ctx_handoff_across_threads(self):
        """The worker → applier handoff: a SpanContext captured on one
        thread parents spans opened on another."""
        t = Tracer()
        t.begin("e1")
        got = {}

        def applier(ctx):
            with t.attach(ctx):
                with t.span("plan_apply") as sp:
                    got["parent"] = sp.parent_id

        with t.activate("e1"):
            with t.span("submit_plan") as submit:
                ctx = t.current_ctx()
                assert isinstance(ctx, SpanContext)
                th = threading.Thread(target=applier, args=(ctx,))
                th.start()
                th.join()
        tr = t.finish("e1")
        assert got["parent"] == span_by_name(tr, "submit_plan")["span_id"]
        assert submit.span_id == got["parent"]

    def test_span_with_no_active_trace_yields_none(self):
        t = Tracer()
        with t.span("orphan") as sp:
            assert sp is None

    def test_late_span_after_finish_is_counted_dropped(self):
        t = Tracer()
        root = t.begin("e1")
        t.finish("e1")
        with t.span("late", parent=root) as sp:
            assert sp is None
        assert t.dropped_spans() == 1

    def test_disabled_tracer_noops_but_timer_still_samples(self):
        t = Tracer()
        assert t.set_enabled(False) is True
        assert t.begin("e1") is None
        global_metrics.reset()
        with t.span("x", timer="obs.test.disabled_timer") as sp:
            assert sp is None
        snap = global_metrics.snapshot()
        assert "obs.test.disabled_timer" in snap["samples"]
        assert t.active_count() == 0

    def test_disabling_drops_inflight_traces(self):
        t = Tracer()
        t.begin("e1")
        t.set_enabled(False)
        assert t.active_count() == 0
        assert t.finish("e1") is None

    def test_span_error_status_and_reraise(self):
        t = Tracer()
        t.begin("e1")
        with t.activate("e1"):
            with pytest.raises(ValueError):
                with t.span("boom"):
                    raise ValueError("x")
        tr = t.finish("e1")
        assert span_by_name(tr, "boom")["status"] == "error"

    def test_add_span_retroactive_defaults_to_root_parent(self):
        t = Tracer()
        t.begin("e1")
        t.add_span("e1", "dequeue", 0.5, tags={"shared": False})
        tr = t.finish("e1")
        d = span_by_name(tr, "dequeue")
        assert d["parent_id"] == tr["spans"][0]["span_id"]
        assert d["duration_ms"] == pytest.approx(500.0)


# -- FlightRecorder ---------------------------------------------------------


class TestFlightRecorder:
    def test_ring_evicts_oldest_first(self):
        rec = FlightRecorder(capacity=3)
        for i in range(4):
            rec.record({"eval_id": f"e{i}", "spans": []})
        assert len(rec) == 3
        assert rec.get("e0") is None
        assert [t["eval_id"] for t in rec.traces()] == ["e3", "e2", "e1"]

    def test_rerecord_moves_to_newest(self):
        rec = FlightRecorder(capacity=3)
        for i in range(3):
            rec.record({"eval_id": f"e{i}", "spans": []})
        rec.record({"eval_id": "e0", "spans": [], "retry": True})
        rec.record({"eval_id": "e3", "spans": []})
        # e1 (now the oldest) was evicted, re-recorded e0 survived
        assert rec.get("e1") is None
        assert rec.get("e0")["retry"] is True

    def test_error_ring_caps_and_reads_newest_first(self):
        rec = FlightRecorder(error_capacity=2)
        for i in range(3):
            rec.record_error("comp", f"err-{i}", eval_id=f"e{i}")
        errs = rec.errors()
        assert [e["error"] for e in errs] == ["err-2", "err-1"]

    def test_list_summarizes(self):
        rec = FlightRecorder()
        rec.record(
            {
                "eval_id": "e1",
                "status": "acked",
                "started_at": 1.0,
                "duration_ms": 2.5,
                "tags": {"job_id": "j"},
                "spans": [{}, {}],
            }
        )
        (s,) = rec.list()
        assert s == {
            "eval_id": "e1",
            "status": "acked",
            "started_at": 1.0,
            "duration_ms": 2.5,
            "spans": 2,
            "tags": {"job_id": "j"},
        }

    def test_count_swallowed_lands_in_error_ring(self):
        count_swallowed("obstest", ValueError("boom"))
        errs = flight_recorder.errors()
        assert errs and errs[0]["component"] == "obstest"
        assert "boom" in errs[0]["error"]

    def test_render_trace_indents_children(self):
        t = Tracer()
        t.begin("e1", tags={"job_id": "j1"})
        with t.activate("e1"):
            with t.span("invoke_scheduler"):
                with t.span("kernel_score"):
                    pass
        out = render_trace(t.finish("e1", status="acked"))
        lines = out.splitlines()
        assert lines[0].startswith("eval e1  acked")
        assert "job_id=j1" in lines[0]
        assert lines[1].startswith("  invoke_scheduler")
        assert lines[2].startswith("    kernel_score")

    def test_phase_breakdown_excludes_root(self):
        t = Tracer()
        t.begin("e1")
        t.add_span("e1", "snapshot", 0.010)
        t.add_span("e1", "snapshot", 0.030)
        bd = phase_breakdown([t.finish("e1")])
        assert set(bd) == {"snapshot"}
        assert bd["snapshot"]["count"] == 2
        assert bd["snapshot"]["mean_ms"] == pytest.approx(20.0, abs=0.01)
        assert bd["snapshot"]["max_ms"] == pytest.approx(30.0, abs=0.01)


# -- kernel profiling hooks -------------------------------------------------


class TestKernelProfile:
    def test_traced_jit_records_compile_dispatch_and_shapes(self):
        import jax.numpy as jnp

        @backend.traced_jit
        def _obs_toy_kernel(x):
            return x * 2.0

        backend.reset_kernel_profile()
        global_metrics.reset()
        _obs_toy_kernel(jnp.ones((4,)))  # trace 1
        _obs_toy_kernel(jnp.ones((4,)))  # cached
        _obs_toy_kernel(jnp.ones((8,)))  # trace 2 (new abstract shape)

        (name,) = [
            k for k in backend.kernel_profile() if "_obs_toy_kernel" in k
        ]
        prof = backend.kernel_profile()[name]
        assert prof["calls"] == 3
        assert prof["traces"] == 2
        shapes = [e["shape"] for e in prof["recent_traces"]]
        assert any("[4]" in s for s in shapes)
        assert any("[8]" in s for s in shapes)
        assert prof["last_trace_shape"] == shapes[-1]

        samples = global_metrics.snapshot()["samples"]
        assert samples["nomad.kernel._obs_toy_kernel.compile"]["count"] == 2
        assert samples["nomad.kernel._obs_toy_kernel.dispatch"]["count"] == 1

    def test_kernel_call_attaches_span_under_active_trace(self):
        import jax.numpy as jnp

        @backend.traced_jit
        def _obs_span_kernel(x):
            return x + 1.0

        global_tracer.begin("ek1")
        with global_tracer.activate("ek1"):
            _obs_span_kernel(jnp.ones((2,)))
        tr = global_tracer.finish("ek1")
        k = span_by_name(tr, "kernel:_obs_span_kernel")
        assert k["tags"]["traced"] is True
        assert "float32[2]" in k["tags"]["shape"]
        assert k["parent_id"] == tr["spans"][0]["span_id"]


# -- end-to-end: trace of a real eval through the Server --------------------


def _wait_trace(eval_id, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        tr = flight_recorder.get(eval_id)
        if tr is not None:
            return tr
        time.sleep(0.02)
    return None


LIFECYCLE = {
    "dequeue",
    "snapshot",
    "invoke_scheduler",
    "submit_plan",
    "plan_apply",
    "wait_for_index",
}


class TestEndToEndTrace:
    def test_eval_yields_full_lifecycle_trace(self):
        server = Server(ServerConfig(num_workers=1))
        server.establish_leadership()
        try:
            for _ in range(3):
                server.register_node(mock.node())
            ev = server.register_job(mock.job())
            assert server.wait_for_evals(timeout=15)
            tr = _wait_trace(ev.id)
        finally:
            server.shutdown()

        assert tr is not None, "eval left no trace in the flight recorder"
        assert tr["status"] == "acked"
        names = {s["name"] for s in tr["spans"]}
        assert LIFECYCLE <= names, f"missing {LIFECYCLE - names}"

        # one root, every parent resolves inside the trace
        ids = {s["span_id"] for s in tr["spans"]}
        roots = [s for s in tr["spans"] if s["parent_id"] is None]
        assert len(roots) == 1
        assert all(
            s["parent_id"] in ids for s in tr["spans"] if s["parent_id"]
        )

        # the cross-thread handoff: plan-queue wait + plan_apply parent
        # under the worker's submit_plan span
        submit = span_by_name(tr, "submit_plan")
        assert span_by_name(tr, "plan_apply")["parent_id"] == submit["span_id"]
        assert (
            span_by_name(tr, "plan_queue.wait")["parent_id"]
            == submit["span_id"]
        )
        assert span_by_name(tr, "dequeue")["tags"]["queue_wait_ms"] >= 0

        # nothing leaked: no orphan actives, no dropped spans
        assert global_tracer.active_count() == 0
        assert global_tracer.dropped_spans() == 0

    def test_a_node_down_writes_node_status_and_its_evals_register(self):
        """``update_node_status`` is one background span ``node_status``
        (entry -> node evals enqueued); each node eval's trace carries
        ``register`` from that entry, as a drain's do."""
        server = Server(ServerConfig(num_workers=1))
        server.establish_leadership()
        try:
            nodes = [mock.node() for _ in range(2)]
            for n in nodes:
                server.register_node(n)
            job = mock.job()
            job.task_groups[0].count = 4
            server.register_job(job)
            assert server.wait_for_evals(timeout=15)
            victim = max(nodes, key=lambda n: len([
                a for a in server.store.allocs_by_node(n.id)
                if not a.terminal_status()]))
            held = len([a for a in server.store.allocs_by_node(victim.id)
                        if not a.terminal_status()])
            assert held > 0
            (ev,) = server.update_node_status(victim.id, "down")
            assert server.wait_for_evals(timeout=15)
            tr = _wait_trace(ev.id)
        finally:
            server.shutdown()
        (span,) = [s for s in flight_recorder.background()
                   if s["name"] == "node_status"]
        assert span["tags"] == {
            "node_id": victim.id, "status": "down", "allocs": held,
            "node_evals": 1,
        }
        assert tr is not None and tr["tags"]["node_id"] == victim.id
        reg, deq = span_by_name(tr, "register"), span_by_name(tr, "dequeue")
        assert reg["start_unix"] >= span["start_unix"] - 1e-3
        assert _end(reg) == pytest.approx(deq["start_unix"], abs=1e-4)

    def test_http_trace_endpoints(self):
        from nomad_tpu.api.client import APIException, NomadClient
        from nomad_tpu.api.http import HTTPAgent

        server = Server(ServerConfig(num_workers=1))
        server.establish_leadership()
        http = HTTPAgent(server, None, port=0)
        http.start()
        try:
            c = NomadClient(http.address)
            for _ in range(2):
                server.register_node(mock.node())
            ev = server.register_job(mock.job())
            assert server.wait_for_evals(timeout=15)
            assert _wait_trace(ev.id) is not None

            idx = c._request("GET", "/v1/agent/trace")
            assert ev.id in [t["eval_id"] for t in idx["traces"]]
            assert "errors" in idx and "kernels" in idx
            # the background ring beside the traces, newest first, and
            # one name of it on request
            with global_tracer.background("tick"):
                pass
            global_tracer.add_background(
                "drain", 0.25, start=time.perf_counter() - 0.25,
                tags={"node_id": "n-1"})
            idx = c._request("GET", "/v1/agent/trace")
            assert idx["background"][0]["name"] == "drain"
            assert idx["background"][0]["duration_ms"] == 250.0
            assert "tick" in {s["name"] for s in idx["background"]}
            only = c._request(
                "GET", "/v1/agent/trace", params={"background": "drain"})
            assert [s["tags"] for s in only["background"]] == [
                {"node_id": "n-1"}]

            tr = c._request("GET", f"/v1/agent/trace/{ev.id}")
            assert {s["name"] for s in tr["spans"]} >= LIFECYCLE

            with pytest.raises(APIException):
                c._request("GET", "/v1/agent/trace/no-such-eval")
        finally:
            http.stop()
            server.shutdown()


# -- one clock, true starts ---------------------------------------------------


def _end(span):
    return span["start_unix"] + span["duration_ms"] / 1000.0


def _outside_parent(trace, slack_s=2e-6):
    """Spans that start before or end after their parent (``register`` and
    ``dequeue`` precede the root, which opens at the dequeue, by design)."""
    by_id = {s["span_id"]: s for s in trace["spans"]}
    out = []
    for s in trace["spans"]:
        parent = by_id.get(s["parent_id"])
        if parent is None:
            continue
        if parent["parent_id"] is None and s["name"] in ("register", "dequeue"):
            continue
        if (
            s["start_unix"] < parent["start_unix"] - slack_s
            or _end(s) > _end(parent) + slack_s
        ):
            out.append((s["name"], parent["name"]))
    return out


class TestTrueStarts:
    @pytest.mark.parametrize("ago", [2.0, 0.75])
    def test_retroactive_span_starts_where_its_caller_says(self, ago):
        t = Tracer()
        t.begin("e1")
        began = time.perf_counter() - ago  # long before the call
        t.add_span("e1", "waited", 0.5, start=began)
        sp = span_by_name(t.finish("e1"), "waited")
        assert sp["start_unix"] == pytest.approx(t.unix_at(began), abs=1e-6)
        assert sp["duration_ms"] == pytest.approx(500.0)

    def test_starts_and_durations_share_one_clock(self):
        """``start_unix`` is one wall anchor plus the monotonic offset: two
        spans lie as far apart as ``perf_counter`` says."""
        t = Tracer()
        t.begin("e1")
        with t.activate("e1"):
            with t.span("a"):
                pass
            t1 = time.perf_counter()
            with t.span("b"):
                pass
        tr = t.finish("e1")
        gap = span_by_name(tr, "b")["start_unix"] - _end(span_by_name(tr, "a"))
        assert 0.0 <= gap <= time.perf_counter() - t1 + 1e-4
        assert t.unix_at(t1) == pytest.approx(time.time(), abs=5.0)

    def test_kernel_span_starts_at_its_dispatch(self):
        t = Tracer()
        t.begin("e1")
        t0 = time.perf_counter() - 1.0
        with t.activate("e1"):
            t.record_kernel("k", 0.25, start=t0)
        sp = span_by_name(t.finish("e1"), "kernel:k")
        assert sp["start_unix"] == pytest.approx(t.unix_at(t0), abs=1e-6)

    def test_phase_carries_the_roots_pass_tags(self):
        t = Tracer()
        t.begin("e1", tags={"pass_id": "0-7", "path": "solo", "evals": 1,
                            "job_id": "j"})
        with t.activate("e1"):
            with t.phase("prepare", tags={"x": 1}):
                with t.span("below"):
                    pass
        tr = t.finish("e1")
        assert span_by_name(tr, "prepare")["tags"] == {
            "pass_id": "0-7", "path": "solo", "evals": 1, "x": 1,
        }
        assert span_by_name(tr, "below")["tags"] == {}


# -- one pass record on both paths ------------------------------------------

TRACE_KEYS = {"eval_id", "status", "started_at", "duration_ms", "tags", "spans"}
SPAN_KEYS = {
    "span_id", "parent_id", "name", "start_unix", "duration_ms", "status",
    "tags",
}
# what only a batched pass does on the worker thread: it hands its commit
# to the pipeline thread, after the last one has ended
BATCHED_ONLY = {"join_commit"}


def _top_level(trace):
    root = trace["spans"][0]["span_id"]
    return [s for s in trace["spans"] if s["parent_id"] == root]


def _job(job_id, count=2):
    job = mock.job()
    job.id = job_id
    job.task_groups[0].count = count
    return job


@pytest.fixture(scope="module")
def pass_traces():
    """One solo pass, one batched pass of two registrations (enqueued
    before the worker is unpaused) and a deregistration enqueued while its
    job's registration is still ahead of it: {name: trace}, the counters'
    deltas under "counters"."""
    global_tracer.set_enabled(True)
    global_tracer.reset()
    got = {}

    def keep(trace):
        got[trace["eval_id"]] = trace

    flight_recorder.add_listener(keep)
    before = dict(global_metrics.snapshot()["counters"])
    server = Server(ServerConfig(num_workers=1))
    server.establish_leadership()
    try:
        for _ in range(4):
            server.register_node(mock.node())
        solo = server.register_job(_job("pass-solo"))
        assert server.wait_for_evals(timeout=30)
        for w in server.workers:
            w.pause()
        time.sleep(0.25)  # the worker's 0.2 s dequeue poll holds one more turn
        lead = server.register_job(_job("pass-a"))
        member = server.register_job(_job("pass-b"))
        for w in server.workers:
            w.resume()
        assert server.wait_for_evals(timeout=30)
        for w in server.workers:
            w.pause()
        time.sleep(0.25)
        first = server.register_job(_job("pass-c"))
        gated = server.deregister_job("default", "pass-c")
        for w in server.workers:
            w.resume()
        assert server.wait_for_evals(timeout=30)
        names = {"solo": solo.id, "lead": lead.id, "member": member.id,
                 "first": first.id, "gated": gated.id}
        deadline = time.time() + 5.0
        while time.time() < deadline and not set(names.values()) <= set(got):
            time.sleep(0.02)
    finally:
        server.shutdown()
        flight_recorder.remove_listener(keep)
    after = global_metrics.snapshot()["counters"]
    out = {name: got[eid] for name, eid in names.items()}
    out["counters"] = {
        k: after.get(k, 0) - before.get(k, 0)
        for k in ("nomad.worker.passes_solo", "nomad.worker.passes_batched")
    }
    return out


class TestPassRecord:
    def test_both_paths_write_the_same_top_level_phases(self, pass_traces):
        solo = {s["name"] for s in _top_level(pass_traces["solo"])}
        lead = {s["name"] for s in _top_level(pass_traces["lead"])}
        member = {s["name"] for s in _top_level(pass_traces["member"])}
        assert pass_traces["solo"]["tags"]["path"] == "solo"
        assert pass_traces["lead"]["tags"]["path"] == "batched"
        assert lead - BATCHED_ONLY == solo
        assert member == lead
        assert solo == {"register", "dequeue", "wait_for_index", "snapshot",
                        "prepare", "overlay.wait", "invoke_scheduler",
                        "build_plan", "submit_plan"}

    def test_every_member_carries_the_pass_id(self, pass_traces):
        lead, member = pass_traces["lead"], pass_traces["member"]
        assert lead["tags"]["pass_id"] == member["tags"]["pass_id"]
        assert lead["tags"]["evals"] == member["tags"]["evals"] == 2
        assert lead["tags"]["leader"] is True
        assert member["tags"]["leader"] is False
        assert member["tags"]["leader_eval"] == lead["eval_id"]
        assert pass_traces["solo"]["tags"]["pass_id"] != lead["tags"]["pass_id"]
        for name in ("lead", "member", "solo"):
            t = pass_traces[name]
            for s in _top_level(t):
                if s["name"] not in ("register", "dequeue"):
                    assert s["tags"]["pass_id"] == t["tags"]["pass_id"], s
                    assert s["tags"]["path"] == t["tags"]["path"]

    @pytest.mark.parametrize("members", [("solo",), ("lead", "member")])
    def test_kernel_place_exactly_once_per_pass(self, pass_traces, members):
        spans = [s for m in members for s in pass_traces[m]["spans"]]
        assert [s["name"] for s in spans].count("kernel.place") == 1
        kernels = [s for s in spans if s["name"].startswith("kernel:")]
        place = [s for s in spans if s["name"] == "kernel.place"][0]
        assert kernels and all(
            k["parent_id"] == place["span_id"] for k in kernels
        )
        stages = {s["name"] for s in spans
                  if s["parent_id"] == place["span_id"]}
        assert {"place.assemble", "place.upload", "place.pull"} <= stages

    def test_stages_lie_below_the_leaders_phases_only(self, pass_traces):
        """What happens below a shared phase is written once, in the
        leader's trace; the other member holds the phases, tagged shared,
        and below its own ``prepare`` its own ``reconcile``."""
        member = pass_traces["member"]
        root = member["spans"][0]["span_id"]
        own = span_by_name(member, "prepare")
        assert "leader_eval" not in own["tags"]
        below = [s for s in member["spans"]
                 if s["parent_id"] not in (None, root)]
        assert [(s["name"], s["parent_id"]) for s in below] == [
            ("reconcile", own["span_id"])]
        lead = pass_traces["lead"]
        copies = {s["name"] for s in _top_level(member)
                  if s["tags"].get("leader_eval") == lead["eval_id"]}
        assert {"wait_for_index", "snapshot", "overlay.wait",
                "invoke_scheduler", "join_commit", "submit_plan"} == copies
        assert not any("leader_eval" in s["tags"] for s in lead["spans"][1:])
        for name in ("snapshot", "overlay.wait", "invoke_scheduler",
                     "submit_plan"):
            theirs, ours = span_by_name(member, name), span_by_name(lead, name)
            # a copy is the leader's interval, to the digit
            assert theirs["start_unix"] == ours["start_unix"]
            assert theirs["duration_ms"] == ours["duration_ms"]
            assert theirs["tags"]["shared"] is ours["tags"]["shared"] is True
        assert "shared" not in span_by_name(
            pass_traces["solo"], "snapshot")["tags"]
        assert span_by_name(lead, "flatten")["parent_id"] == span_by_name(
            lead, "snapshot")["span_id"]
        assert span_by_name(lead, "plan_apply")["parent_id"] == span_by_name(
            lead, "submit_plan")["span_id"]

    def test_a_solo_pass_flattens_only_when_it_places(self, pass_traces):
        """The solo path refreshes the tensors where it builds its asks,
        inside ``prepare``; a pass with nothing to place (a
        deregistration) never touches the cache."""
        solo, gated = pass_traces["solo"], pass_traces["gated"]
        assert span_by_name(solo, "flatten")["parent_id"] == span_by_name(
            solo, "prepare")["span_id"]
        names = [s["name"] for s in gated["spans"]]
        assert "prepare" in names
        assert not {"flatten", "invoke_scheduler", "kernel.place"} & set(names)

    @pytest.mark.parametrize("name", ["solo", "lead", "member", "first",
                                      "gated"])
    def test_dequeue_wait_tags_sum_to_the_queue_wait(self, pass_traces, name):
        d = span_by_name(pass_traces[name], "dequeue")
        tags = d["tags"]
        assert tags["ready_wait_ms"] + tags["gate_wait_ms"] + tags[
            "deferred_ms"] == pytest.approx(tags["queue_wait_ms"], abs=1e-6)
        assert d["duration_ms"] == pytest.approx(tags["queue_wait_ms"],
                                                 abs=1e-3)
        # the eval's own stay, not the worker's blocked wait for work
        assert _end(d) <= pass_traces[name]["spans"][0]["start_unix"] + 1e-3

    def test_second_eval_of_a_job_in_flight_waits_at_the_gate(
        self, pass_traces
    ):
        gated = span_by_name(pass_traces["gated"], "dequeue")["tags"]
        first = span_by_name(pass_traces["first"], "dequeue")["tags"]
        assert gated["gate_wait_ms"] > 0
        assert first["gate_wait_ms"] == 0 and first["ready_wait_ms"] > 0

    def test_register_span_precedes_the_queue_wait(self, pass_traces):
        for name in ("solo", "lead", "gated"):
            t = pass_traces[name]
            reg, deq = span_by_name(t, "register"), span_by_name(t, "dequeue")
            assert reg["duration_ms"] > 0
            assert _end(reg) == pytest.approx(deq["start_unix"], abs=1e-4)

    @pytest.mark.parametrize("name", ["solo", "lead", "member", "gated"])
    def test_no_child_lies_outside_its_parent(self, pass_traces, name):
        assert _outside_parent(pass_traces[name]) == []

    @pytest.mark.parametrize("name", ["solo", "lead"])
    def test_plan_apply_children_inside_it_and_disjoint(
        self, pass_traces, name
    ):
        t = pass_traces[name]
        apply_ = span_by_name(t, "plan_apply")
        ev = span_by_name(t, "plan_apply.evaluate")
        co = span_by_name(t, "plan_apply.commit")
        assert ev["parent_id"] == co["parent_id"] == apply_["span_id"]
        assert apply_["start_unix"] - 2e-6 <= ev["start_unix"]
        assert _end(ev) <= co["start_unix"] + 2e-6
        assert _end(co) <= _end(apply_) + 2e-6
        wait = span_by_name(t, "plan_queue.wait")
        assert _end(wait) <= apply_["start_unix"] + 2e-6

    @pytest.mark.parametrize("name", ["solo", "lead", "member"])
    def test_recorded_key_sets_are_unchanged(self, pass_traces, name):
        t = pass_traces[name]
        assert set(t) == TRACE_KEYS
        assert all(set(s) == SPAN_KEYS for s in t["spans"])

    def test_pass_counters_count_each_path(self, pass_traces):
        c = pass_traces["counters"]
        # solo, first and gated (each dequeued alone) against one batch
        assert c["nomad.worker.passes_batched"] >= 1
        assert c["nomad.worker.passes_solo"] >= 1
        assert sum(c.values()) == len({
            s["tags"]["pass_id"]
            for name in ("solo", "lead", "member", "first", "gated")
            for s in _top_level(pass_traces[name])
            if "pass_id" in s["tags"]
        })


@pytest.fixture(scope="module")
def set_aside_traces():
    """Three live jobs deregistered while the worker is paused: one
    dequeue of three evals with stops, each set aside by the batched pass
    for a solo pass of its own. {job id: trace}; "alone" is a
    deregistration dequeued alone."""
    global_tracer.set_enabled(True)
    global_tracer.reset()
    got = {}

    def keep(trace):
        got[trace["eval_id"]] = trace

    flight_recorder.add_listener(keep)
    server = Server(ServerConfig(num_workers=1))
    server.establish_leadership()
    try:
        for _ in range(4):
            server.register_node(mock.node())
        for name in ("aside-a", "aside-b", "aside-c", "aside-alone"):
            server.register_job(_job(name))
        assert server.wait_for_evals(timeout=30)
        alone = server.deregister_job("default", "aside-alone")
        assert server.wait_for_evals(timeout=30)
        for w in server.workers:
            w.pause()
        time.sleep(0.25)  # the worker's dequeue poll holds one more turn
        evals = [server.deregister_job("default", name)
                 for name in ("aside-a", "aside-b", "aside-c")]
        for w in server.workers:
            w.resume()
        assert server.wait_for_evals(timeout=30)
        names = {"alone": alone.id, **{
            name: ev.id for name, ev in zip("abc", evals)}}
        deadline = time.time() + 5.0
        while time.time() < deadline and not set(names.values()) <= set(got):
            time.sleep(0.02)
    finally:
        server.shutdown()
        flight_recorder.remove_listener(keep)
    return {name: got[eid] for name, eid in names.items()}


class TestSoloWait:
    """A member a batched pass sets aside waits for the commit thread to
    reach it: ``solo_wait``, from the set-aside to its own solo pass."""

    @pytest.mark.parametrize("name, ahead", [("a", 0), ("b", 1), ("c", 2)])
    def test_each_member_set_aside_holds_one_solo_wait(
        self, set_aside_traces, name, ahead
    ):
        t = set_aside_traces[name]
        assert t["tags"]["batch_size"] == 3
        waits = [s for s in t["spans"] if s["name"] == "solo_wait"]
        assert len(waits) == 1
        wait = waits[0]
        assert wait["parent_id"] == t["spans"][0]["span_id"]
        assert wait["tags"]["reason"] == "nothing_to_batch"
        assert wait["tags"]["ahead"] == ahead
        # the pass it left, not the solo pass it went on to
        batched = span_by_name(t, "join_commit")["tags"]
        assert batched["path"] == wait["tags"]["path"] == "batched"
        assert wait["tags"]["pass_id"] == batched["pass_id"]
        assert wait["tags"]["evals"] == 3
        assert t["tags"]["path"] == "solo"
        assert t["tags"]["pass_id"] != wait["tags"]["pass_id"]
        # set aside inside the batched pass, after the member's prepare;
        # its end is the start of the solo pass's first phase
        own = [s for s in _top_level(t)
               if s["tags"].get("pass_id") == t["tags"]["pass_id"]]
        first = min(own, key=lambda s: s["start_unix"])
        assert first["name"] == "wait_for_index"
        assert _end(wait) == pytest.approx(first["start_unix"], abs=1e-3)
        assert _end(wait) <= first["start_unix"] + 2e-6
        prepared = [s for s in _top_level(t) if s["name"] == "prepare"][0]
        assert _end(prepared) <= wait["start_unix"] + 2e-6

    def test_each_waits_for_the_solo_passes_ahead_of_it(
        self, set_aside_traces
    ):
        a, b, c = (
            span_by_name(set_aside_traces[n], "solo_wait") for n in "abc")
        assert a["duration_ms"] < b["duration_ms"] < c["duration_ms"]
        # b's wait ends no sooner than a's whole solo pass
        assert _end(b) >= _end(set_aside_traces["a"]["spans"][0]) - 1e-3

    def test_an_eval_dequeued_alone_writes_none(self, set_aside_traces):
        t = set_aside_traces["alone"]
        assert t["tags"]["batch_size"] == 1
        assert "solo_wait" not in {s["name"] for s in t["spans"]}

    @pytest.mark.parametrize("name", ["a", "b", "c", "alone"])
    def test_the_set_aside_traces_nest(self, set_aside_traces, name):
        assert _outside_parent(set_aside_traces[name]) == []


class _Tensors:
    layout_gen = 0


def _pass_under(overlay, eval_id, hold_s, entered=None, go=None):
    """One pass's read-then-write of ``overlay`` in ``eval_id``'s trace."""
    global_tracer.begin(eval_id, tags={
        "pass_id": f"0-{eval_id}", "path": "solo", "evals": 1})
    with global_tracer.activate(eval_id):
        if go is not None:
            go.wait(5.0)
        overlay.begin_pass(_Tensors())
        if entered is not None:
            entered.set()
        time.sleep(hold_s)
        overlay.pass_finished()
    return global_tracer.finish(eval_id)


class TestOverlayWait:
    """``overlay.wait``: the acquire in ``SharedOverlay.begin_pass``."""

    def test_a_lone_pass_waits_for_nobody(self):
        from nomad_tpu.server.overlay import SharedOverlay

        t = _pass_under(SharedOverlay(), "lone", 0.0)
        wait = span_by_name(t, "overlay.wait")
        assert wait["tags"] == {
            "pass_id": "0-lone", "path": "solo", "evals": 1,
            "waited": False, "timed_out": False,
        }
        assert wait["duration_ms"] < 1.0
        assert wait["parent_id"] == t["spans"][0]["span_id"]

    def test_a_second_pass_waits_for_the_firsts_hold(self):
        from nomad_tpu.server.overlay import SharedOverlay

        overlay, hold_s = SharedOverlay(), 0.05
        entered, out = threading.Event(), {}

        def second():
            out["second"] = _pass_under(overlay, "second", 0.0, go=entered)

        th = threading.Thread(target=second)
        th.start()
        out["first"] = _pass_under(overlay, "first", hold_s, entered=entered)
        th.join(5.0)
        first = span_by_name(out["first"], "overlay.wait")
        assert first["tags"]["waited"] is False
        wait = span_by_name(out["second"], "overlay.wait")
        assert wait["tags"]["waited"] is True
        assert wait["tags"]["timed_out"] is False
        assert wait["tags"]["pass_id"] == "0-second"
        # it began once the first held the lock and lasted to its release:
        # the first's hold, less the moment the second took to get going
        assert wait["start_unix"] >= _end(first) - 2e-6
        assert _end(wait) >= _end(first) + hold_s - 1e-3
        assert wait["duration_ms"] >= hold_s * 1000.0 - 10.0

    def test_a_pass_outside_any_trace_writes_nothing(self):
        from nomad_tpu.server.overlay import SharedOverlay

        overlay = SharedOverlay()
        assert overlay.begin_pass(_Tensors()) is None
        overlay.pass_finished()
        assert global_tracer.active_count() == 0


@pytest.fixture(scope="module")
def rollout_traces():
    """A service of 4 with ``max_parallel`` 2 rolled to its next version,
    this test playing the nodes' clients (each new allocation acknowledged
    running and healthy, one batch a round): the evals' traces, the
    background spans, the counters' deltas and the stamps taken around
    every scan of the watcher."""
    import copy

    from nomad_tpu.structs.deployment import AllocDeploymentStatus
    from nomad_tpu.structs.job import UpdateStrategy

    global_tracer.set_enabled(True)
    global_tracer.reset()
    flight_recorder.clear()
    got = []
    flight_recorder.add_listener(got.append)
    before = dict(global_metrics.snapshot()["counters"])
    server = Server(ServerConfig(num_workers=1))
    scans = []
    scan = server.deployment_watcher._scan

    def stamped_scan():
        t0 = time.perf_counter()
        seen = scan()
        scans.append((t0, time.perf_counter(), seen))
        return seen

    server.deployment_watcher._scan = stamped_scan
    server.establish_leadership()

    def version(v):
        job = _job("rolling", count=4)
        job.task_groups[0].update = UpdateStrategy(
            max_parallel=2, min_healthy_time_s=0.0,
            health_check="task_states")
        job.task_groups[0].tasks[0].env = {"VERSION": str(v)}
        return job

    try:
        for _ in range(6):
            server.register_node(mock.node())
        server.register_job(version(0))
        assert server.wait_for_evals(timeout=30)
        server.register_job(version(1))
        acked, batches = set(), []
        deadline = time.time() + 30.0
        while time.time() < deadline:
            d = server.store.latest_deployment_by_job("default", "rolling")
            if d is not None and d.status == "successful":
                break
            new = [
                a for a in server.store.allocs_by_job("default", "rolling")
                if a.job_version == 1 and not a.terminal_status()
                and a.id not in acked
            ]
            if new:
                updates = []
                for a in new:
                    u = copy.copy(a)
                    u.client_status = "running"
                    u.deployment_status = AllocDeploymentStatus(healthy=True)
                    updates.append(u)
                    acked.add(a.id)
                server.update_allocs_from_client(updates)
                batches.append(len(updates))
            time.sleep(0.01)
        assert d is not None and d.status == "successful"
        assert server.wait_for_evals(timeout=30)
        time.sleep(0.1)  # the last trace reaches the recorder after its ack
        job = server.store.job_by_id("default", "rolling")
    finally:
        server.shutdown()
        flight_recorder.remove_listener(got.append)
    after = global_metrics.snapshot()
    return {
        "traces": [t for t in got if t["tags"].get("job_id") == "rolling"],
        "background": flight_recorder.background(),
        "scans": scans, "batches": batches, "stable": job.stable,
        "gauges": after["gauges"],
        "counters": {
            k: v - before.get(k, 0) for k, v in after["counters"].items()
            if k.startswith(("nomad.deployment.", "nomad.plan.stops",
                             "nomad.worker.destructive"))
        },
    }


class TestRolloutRecord:
    """The spans and counters of a rollout (PERF.md section 3, layer
    rollout): present, each starting where its interval started."""

    def test_the_rollout_ran_in_two_rounds_and_ended(self, rollout_traces):
        r = rollout_traces
        assert r["batches"] == [2, 2] and r["stable"] is True
        by = [t["tags"]["triggered_by"] for t in r["traces"]]
        assert by.count("job-register") == 2
        assert by.count("deployment-watcher") == 1

    def test_reconcile_lies_under_prepare_with_its_counts(
            self, rollout_traces):
        rounds = [
            t for t in rollout_traces["traces"]
            if span_by_name(t, "reconcile")["tags"]["destructive"]
        ]
        assert len(rounds) == 2
        for t in rounds:
            by_id = {s["span_id"]: s for s in t["spans"]}
            rec = span_by_name(t, "reconcile")
            assert by_id[rec["parent_id"]]["name"] == "prepare"
            # the allocations left alone: two deferred in the first
            # round; in the second the two replaced and their two
            # replacements
            tags = dict(rec["tags"])
            assert tags.pop("ignore") in (2, 4)
            assert tags == {
                "place": 0, "destructive": 2, "inplace": 0, "stop": 0,
                "migrate": 0, "max_parallel": 2,
            }
            assert _outside_parent(t) == []

    def test_plan_stops_is_written_where_the_plans_stops_are_freed(
            self, rollout_traces):
        for t in rollout_traces["traces"]:
            stops = [s for s in t["spans"] if s["name"] == "plan_stops"]
            rec = span_by_name(t, "reconcile")
            assert len(stops) == (1 if rec["tags"]["destructive"] else 0)
            for s in stops:
                assert s["tags"] == {"stops": 2}
                by_id = {x["span_id"]: x for x in t["spans"]}
                assert by_id[s["parent_id"]]["name"] == "prepare"
                assert s["start_unix"] >= _end(rec) - 2e-6

    def test_a_tick_starts_where_its_interval_started(self, rollout_traces):
        ticks = [s for s in rollout_traces["background"]
                 if s["name"] == "deployment.tick"]
        scans = rollout_traces["scans"]
        assert len(ticks) == len(scans) >= 2
        for span, (t0, t1, seen) in zip(ticks, scans):
            # the span opens before the scan it times and closes after it
            assert span["start_unix"] <= global_tracer.unix_at(t0)
            assert global_tracer.unix_at(t0) - span["start_unix"] < 1e-3
            assert _end(span) >= global_tracer.unix_at(t1) - 2e-6
            assert span["tags"] == seen
            assert set(seen) == {"scanned", "active", "healthy", "evals"}
        assert sum(s["tags"]["healthy"] for s in ticks) == 4
        assert sum(s["tags"]["evals"] for s in ticks) == 1
        assert max(s["tags"]["active"] for s in ticks) == 1

    def test_round_lag_is_the_difference_of_two_recorded_times(
            self, rollout_traces):
        (t,) = [t for t in rollout_traces["traces"]
                if t["tags"]["triggered_by"] == "deployment-watcher"]
        tags = t["tags"]
        assert tags["round_lag_ms"] == pytest.approx(
            (tags["enqueue_unix"] - tags["health_unix"]) * 1000.0, abs=2e-3)
        # the health commit is the first client update's, stamped when it
        # was applied; the eval was ready in the broker from the enqueue
        first = [s for s in rollout_traces["background"]
                 if s["name"] == "client_update"][0]
        assert 0.0 <= tags["health_unix"] - _end(first) < 5e-3
        ready = span_by_name(t, "dequeue")["start_unix"]
        assert -5e-3 < tags["enqueue_unix"] - ready < 5e-3
        # the watcher polls: the verdict waits for the next tick
        assert 0.0 < tags["round_lag_ms"] < 2000.0

    def test_client_update_spans_carry_their_batch(self, rollout_traces):
        updates = [s for s in rollout_traces["background"]
                   if s["name"] == "client_update"]
        assert [s["tags"]["allocs"] for s in updates] == [2, 2]
        assert all(s["duration_ms"] > 0 for s in updates)

    def test_background_spans_are_no_traces(self, rollout_traces):
        assert all("pass_id" in t["tags"] for t in rollout_traces["traces"])
        assert all(s["parent_id"] is None
                   for s in rollout_traces["background"])

    def test_the_rollouts_counters(self, rollout_traces):
        assert rollout_traces["counters"] == {
            "nomad.deployment.created": 1,
            "nomad.deployment.successful": 1,
            "nomad.deployment.evals_created": 1,
            "nomad.deployment.health_applied": 4,
            "nomad.plan.stops_committed": 4,
            "nomad.worker.destructive_updates": 4,
        }
        assert "nomad.deployment.active" in rollout_traces["gauges"]


class TestDisabledTracer:
    def test_no_stage_span_is_built_and_the_timers_still_sample(self):
        from nomad_tpu.obs import trace as trace_mod

        built = []
        real = trace_mod.Span.__init__

        def counting(self, *a, **kw):
            built.append(a[1] if len(a) > 1 else kw.get("name"))
            real(self, *a, **kw)

        server = Server(ServerConfig(num_workers=1))
        server.establish_leadership()
        try:
            for _ in range(3):
                server.register_node(mock.node())
            global_tracer.set_enabled(False)
            global_metrics.reset()
            trace_mod.Span.__init__ = counting
            ev = server.register_job(_job("off-1"))
            assert server.wait_for_evals(timeout=30)
        finally:
            trace_mod.Span.__init__ = real
            global_tracer.set_enabled(True)
            server.shutdown()
        assert built == []
        assert flight_recorder.get(ev.id) is None
        samples = global_metrics.snapshot()["samples"]
        for name in ("nomad.worker.wait_for_index",
                     "nomad.worker.invoke_scheduler",
                     "nomad.worker.submit_plan", "nomad.plan.apply",
                     "nomad.plan.evaluate"):
            assert samples[name]["count"] >= 1, name


# -- overhead guard ---------------------------------------------------------


def _run_workload(server, round_id, n_jobs=4):
    jobs = []
    for j in range(n_jobs):
        job = mock.job()
        job.id = f"ovh-{round_id}-{j}"
        job.task_groups[0].count = 4
        jobs.append(job)
    t0 = time.perf_counter()
    for job in jobs:
        server.register_job(job)
    assert server.wait_for_evals(timeout=60)
    elapsed = time.perf_counter() - t0
    for job in jobs:
        server.deregister_job(job.namespace, job.id)
    assert server.wait_for_evals(timeout=60)
    return elapsed


class TestTracingOverhead:
    def test_enabled_within_5_percent_of_disabled(self):
        """Tracing must be cheap enough to leave on: enabled e2e wall
        time within 5% of disabled (plus absolute slack — these runs
        are tens of milliseconds, where scheduler jitter dominates)."""
        server = Server(ServerConfig(num_workers=1))
        server.establish_leadership()
        try:
            for _ in range(4):
                server.register_node(mock.node())
            _run_workload(server, "warm")  # compile + warm every path
            enabled, disabled = [], []
            for i in range(3):
                global_tracer.set_enabled(False)
                disabled.append(_run_workload(server, f"off{i}"))
                global_tracer.set_enabled(True)
                enabled.append(_run_workload(server, f"on{i}"))
        finally:
            global_tracer.set_enabled(True)
            server.shutdown()
        assert min(enabled) <= min(disabled) * 1.05 + 0.5, (
            f"tracing overhead too high: enabled={enabled} "
            f"disabled={disabled}"
        )
