"""The benchmark's readers over recorded traces (``benchmark/readers/
pass_wall.py``, ``latency_untraced.py``) and the checks of
``benchmark/tests/check_traces.py``, on hand-made traces: CPU only, no
server, so tier-1 counts them."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "benchmark", "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

import check_traces  # noqa: E402
from benchmark.readers import (  # noqa: E402
    drain_evals,
    eval_wait,
    held_span_quantile,
    latency_untraced,
    loss_evals,
    pass_stage_sum,
    pass_wall,
    span_sum,
)
from nomad_tpu.obs.recorder import flight_recorder  # noqa: E402
from nomad_tpu.obs.trace import global_tracer  # noqa: E402

PHASES = ["wait_for_index", "snapshot", "prepare", "invoke_scheduler",
          "join_commit"]
T0 = 1_000.0  # span times below are seconds after T0


def span(sid, parent, name, start, dur_ms, **tags):
    return {"span_id": sid, "parent_id": parent, "name": name,
            "start_unix": T0 + start, "duration_ms": dur_ms,
            "status": "ok", "tags": tags}


def trace(eval_id, spans, **tags):
    return {"eval_id": eval_id, "status": "acked", "started_at": T0,
            "duration_ms": 100.0, "tags": tags, "spans": spans}


def solo_pass():
    """Pass s-1: snapshot [0, 1) ms, prepare [1, 3) with flatten [1.5, 2.5),
    invoke_scheduler [3, 13) with kernel.place [4, 12) holding a kernel
    [5, 6) and a pull [6, 11); submit_plan later, outside the pass."""
    p = {"pass_id": "s-1", "path": "solo", "evals": 1}
    return trace("e-solo", [
        span(1, None, "eval", 0.0, 100.0),
        span(2, 1, "snapshot", 0.000, 1.0, **p),
        span(4, 1, "prepare", 0.001, 2.0, **p),
        span(3, 4, "flatten", 0.0015, 1.0, full=False),
        span(5, 1, "invoke_scheduler", 0.003, 10.0, **p),
        span(6, 5, "kernel.place", 0.004, 8.0),
        span(7, 6, "kernel:place_closed_form_kernel", 0.005, 1.0),
        span(8, 6, "place.pull", 0.006, 5.0),
        span(9, 1, "submit_plan", 0.020, 5.0, **p),
    ], **p)


def batched_pass():
    """Pass b-2, two members: the leader holds snapshot [0.100, 0.104) and
    invoke_scheduler [0.106, 0.116) with kernel.place filling it; each
    member its own prepare ([0.104, 0.105), [0.105, 0.106)); the second
    member holds copies of the shared phases."""
    p = {"pass_id": "b-2", "path": "batched", "evals": 2}
    lead = trace("e-lead", [
        span(10, None, "eval", 0.1, 50.0),
        span(11, 10, "snapshot", 0.100, 4.0, shared=True, **p),
        span(12, 10, "prepare", 0.104, 1.0, **p),
        span(13, 10, "invoke_scheduler", 0.106, 10.0, shared=True, **p),
        span(14, 13, "kernel.place", 0.106, 10.0),
        span(15, 14, "kernel:place_spread_opv_kernel", 0.107, 1.0),
    ], leader=True, **p)
    member = trace("e-member", [
        span(20, None, "eval", 0.1, 50.0),
        span(21, 20, "snapshot", 0.100, 4.0, shared=True,
             leader_eval="e-lead", **p),
        span(22, 20, "prepare", 0.105, 1.0, **p),
        span(23, 20, "invoke_scheduler", 0.106, 10.0, shared=True,
             leader_eval="e-lead", **p),
    ], leader_eval="e-lead", **p)
    return [lead, member]


class TestUnion:
    @pytest.mark.parametrize("intervals, want", [
        ([], 0.0),
        ([(0.0, 1.0)], 1.0),
        ([(0.0, 1.0), (2.0, 3.0)], 2.0),
        ([(0.0, 2.0), (1.0, 3.0)], 3.0),
        ([(1.0, 3.0), (0.0, 5.0), (2.0, 4.0)], 5.0),
        ([(0.0, 1.0), (1.0, 2.0)], 2.0),
    ])
    def test_union_of_intervals(self, intervals, want):
        assert pass_wall.union_s(intervals) == pytest.approx(want)


class TestPassWall:
    def test_solo_pass_runs_from_first_phase_start_to_last_phase_end(self):
        ctx = {"traces": [solo_pass()]}
        # snapshot starts at 0, invoke_scheduler ends at 13 ms; submit_plan
        # is no phase of the pass proper
        assert pass_wall.read(ctx, 0.5, PHASES) == pytest.approx(13.0)

    def test_batched_pass_spans_every_members_phases_once(self):
        ctx = {"traces": batched_pass()}
        assert pass_wall.read(ctx, 0.5, PHASES) == pytest.approx(16.0)
        found = pass_wall.passes(ctx["traces"], PHASES)
        assert set(found) == {"b-2"}
        # the member's own prepare counts, the copies it holds do not
        assert sorted(s["span_id"] for s in found["b-2"]["phases"]) == [
            11, 12, 13, 22]

    def test_untraced_is_the_wall_less_the_union_of_the_leaves(self):
        ctx = {"traces": [solo_pass()]}
        # leaves: snapshot 1.0, flatten 1.0, kernel 1.0, pull 5.0 of 13 ms
        assert pass_wall.read(
            ctx, 0.5, PHASES, untraced=True) == pytest.approx(5.0)

    def test_a_whole_stage_covers_its_own_time_beside_its_children(self):
        ctx = {"traces": [solo_pass()]}
        # prepare's 2.0 count, not only the flatten inside it
        assert pass_wall.read(
            ctx, 0.5, PHASES, untraced=True, whole=["prepare"]
        ) == pytest.approx(4.0)

    def test_copies_in_other_members_cover_nothing(self):
        ctx = {"traces": batched_pass()}
        # leaves: snapshot 4, the two prepares 1 + 1, the kernel 1, of 16
        assert pass_wall.read(
            ctx, 0.5, PHASES, untraced=True) == pytest.approx(9.0)

    def test_holding_keeps_the_passes_that_hold_that_phase(self):
        light = trace("e-dereg", [
            span(1, None, "eval", 0.2, 5.0),
            span(2, 1, "snapshot", 0.200, 2.0, pass_id="s-9", path="solo"),
            span(3, 1, "prepare", 0.202, 1.0, pass_id="s-9", path="solo"),
        ], pass_id="s-9", path="solo")
        ctx = {"traces": [light, solo_pass()]}
        assert pass_wall.read(ctx, 0.5, PHASES) == pytest.approx(3.0)
        assert pass_wall.read(
            ctx, 0.5, PHASES, holding="invoke_scheduler"
        ) == pytest.approx(13.0)

    def test_quantile_over_both_paths(self):
        ctx = {"traces": [solo_pass()] + batched_pass()}
        assert pass_wall.read(ctx, 0.5, PHASES) == pytest.approx(13.0)
        assert pass_wall.read(ctx, 1.0, PHASES) == pytest.approx(16.0)

    def test_stage_sum_adds_a_stages_spans_within_each_pass(self):
        t = solo_pass()
        p = {"step": "groups"}
        t["spans"] += [
            span(40, 5, "explain", 0.0120, 0.25, **p),
            span(41, 5, "explain", 0.0125, 0.5, step="final"),
        ]
        ctx = {"traces": [t] + batched_pass()}
        # one pass explains (0.25 + 0.5 ms); the batched one does not
        assert pass_stage_sum.read(
            ctx, "explain", 0.5, PHASES) == pytest.approx(0.75)
        assert pass_stage_sum.read(ctx, "place.pull", 1.0, PHASES) == 5.0
        assert pass_stage_sum.read(ctx, "repair", 0.5, PHASES) is None

    def test_a_program_without_pass_ids_gives_nothing(self):
        old = trace("e-old", [
            span(1, None, "eval", 0.0, 10.0),
            span(2, 1, "snapshot", 0.0, 2.0, shared=True),
            span(3, 1, "invoke_scheduler", 0.002, 5.0),
        ])
        assert pass_wall.read({"traces": [old]}, 0.5, PHASES) is None
        assert pass_wall.read({"traces": []}, 0.5, PHASES, True) is None


def _request(eval_id, due, sent, done, ok=True):
    return types.SimpleNamespace(
        eval_id=eval_id, due=due, sent=sent, done=done, ok=ok)


class TestLatencyUntraced:
    def _trace(self, eval_id, t, with_register=True):
        """Spans on the tracer's own clock, ``t`` a perf_counter stamp:
        register [t, t+1ms), dequeue [t+1, t+3), invoke_scheduler
        [t+4, t+8) and flush_status [t+8, t+9): 1 ms of 9 uncovered."""
        u = global_tracer.unix_at(t) - T0
        spans = [
            span(1, None, "eval", u + 0.003, 7.0),
            span(3, 1, "dequeue", u + 0.001, 2.0),
            span(4, 1, "invoke_scheduler", u + 0.004, 4.0),
            span(5, 1, "flush_status", u + 0.008, 1.0),
        ]
        if with_register:
            spans.insert(1, span(2, 1, "register", u, 1.0))
        return trace(eval_id, spans)

    def test_latency_less_the_union_of_lateness_and_spans(self):
        t = 5_000.0
        ctx = {
            "traces": [self._trace("e1", t)],
            # due 2 ms before it was sent, done 1.5 ms after the flush
            "registers": [_request("e1", t - 0.002, t, t + 0.0105)],
        }
        # 12.5 ms of latency; covered: 2 late + 1 + 2 + 4 + 1 = 10
        assert latency_untraced.read(ctx, 0.5) == pytest.approx(2.5, abs=1e-3)

    def test_spans_outside_due_to_done_are_clipped(self):
        t = 6_000.0
        ctx = {
            "traces": [self._trace("e1", t)],
            "registers": [_request("e1", t, t, t + 0.0085)],
        }
        # done falls inside flush_status: only the 1 ms gap is uncovered
        assert latency_untraced.read(ctx, 0.5) == pytest.approx(1.0, abs=1e-3)

    def test_failed_and_unknown_requests_are_left_out(self):
        t = 7_000.0
        ctx = {
            "traces": [self._trace("e1", t)],
            "registers": [
                _request("e1", t, t, t + 0.009),
                _request("e1", t, t, t + 0.5, ok=False),
                _request("e-unknown", t, t, t + 0.5),
            ],
        }
        assert latency_untraced.read(ctx, 1.0) == pytest.approx(1.0, abs=1e-3)

    def test_a_program_without_register_spans_gives_nothing(self):
        t = 8_000.0
        ctx = {
            "traces": [self._trace("e1", t, with_register=False)],
            "registers": [_request("e1", t, t, t + 0.009)],
        }
        assert latency_untraced.read(ctx, 0.5) is None


def set_aside_pass():
    """Pass b-7, a wave of two evals with stops, both set aside: the
    leader's dequeue [0.198, 0.200), the pass's snapshot [0.200, 0.201),
    prepares, the overlay's wait [0.2025, 0.2030) and join_commit
    [0.203, 0.204) (the member holds copies), then on the commit thread
    the leader's solo pass s-8 [0.2045, 0.2105) after a solo_wait from
    0.2015 and the member's s-9 [0.211, 0.216) after one from 0.202; the
    member's own overlay.wait [0.212, 0.2125)."""
    p = {"pass_id": "b-7", "path": "batched", "evals": 2}
    lead = trace("e-w1", [
        span(30, None, "eval", 0.200, 11.0),
        span(31, 30, "dequeue", 0.198, 2.0, queue_wait_ms=2.0),
        span(32, 30, "snapshot", 0.200, 1.0, shared=True, **p),
        span(33, 30, "prepare", 0.201, 0.5, **p),
        span(34, 30, "solo_wait", 0.2015, 3.0, reason="nothing_to_batch",
             ahead=0, **p),
        span(35, 30, "overlay.wait", 0.2025, 0.5, shared=True, waited=True,
             timed_out=False, **p),
        span(36, 30, "join_commit", 0.203, 1.0, shared=True, **p),
        span(37, 30, "prepare", 0.2045, 2.0, pass_id="s-8", path="solo",
             evals=1),
        span(38, 30, "submit_plan", 0.2065, 4.0, pass_id="s-8", path="solo",
             evals=1),
    ], triggered_by="node-drain", node_id="n-1", leader=True, **p)
    member = trace("e-w2", [
        span(40, None, "eval", 0.200, 16.5),
        span(41, 40, "dequeue", 0.199, 1.0, queue_wait_ms=1.0),
        span(42, 40, "snapshot", 0.200, 1.0, shared=True,
             leader_eval="e-w1", **p),
        span(43, 40, "prepare", 0.2015, 0.5, **p),
        span(44, 40, "solo_wait", 0.202, 9.0, reason="nothing_to_batch",
             ahead=1, **p),
        span(45, 40, "overlay.wait", 0.2025, 0.5, shared=True, waited=True,
             timed_out=False, leader_eval="e-w1", **p),
        span(46, 40, "join_commit", 0.203, 1.0, shared=True,
             leader_eval="e-w1", **p),
        span(47, 40, "prepare", 0.211, 1.0, pass_id="s-9", path="solo",
             evals=1),
        span(48, 40, "overlay.wait", 0.212, 0.5, pass_id="s-9", path="solo",
             evals=1, waited=False, timed_out=False),
        span(49, 40, "submit_plan", 0.2125, 3.5, pass_id="s-9", path="solo",
             evals=1),
    ], triggered_by="node-drain", node_id="n-1", leader_eval="e-w1", **p)
    return lead, member


class TestSpanSum:
    def test_every_span_of_the_name_counts_copies_too(self):
        ctx = {"traces": list(set_aside_pass())}
        # the leader's, the member's copy of it, the member's own
        assert span_sum.read(ctx, "overlay.wait") == pytest.approx(1.5)
        assert span_sum.read(ctx, "solo_wait") == pytest.approx(12.0)

    def test_a_program_without_the_span_gives_nothing(self):
        ctx = {"traces": [solo_pass(), *batched_pass()]}
        assert span_sum.read(ctx, "overlay.wait") is None
        assert span_sum.read({"traces": []}, "overlay.wait") is None


class TestEvalWait:
    KINDS = ["job-register", "node-drain"]

    def test_the_union_of_an_evals_four_waits(self):
        lead, member = set_aside_pass()
        # dequeue 2 + solo_wait 3 (the overlay's wait and join_commit lie
        # inside it, and count once)
        assert eval_wait.read(
            {"traces": [lead]}, 0.5, self.KINDS) == pytest.approx(5.0)
        # dequeue 1 + solo_wait 9 (the copies inside it) + its own wait 0.5
        assert eval_wait.read(
            {"traces": [member]}, 0.5, self.KINDS) == pytest.approx(10.5)
        assert eval_wait.read(
            {"traces": [lead, member]}, 1.0, self.KINDS
        ) == pytest.approx(10.5)

    def test_a_members_copy_counts_for_the_member(self):
        lead, member = set_aside_pass()
        member["spans"] = [
            s for s in member["spans"] if s["name"] != "solo_wait"]
        # dequeue 1, the copies [0.2025, 0.2030) and [0.203, 0.204), its
        # own wait 0.5
        assert eval_wait.read(
            {"traces": [lead, member]}, 1.0, self.KINDS
        ) == pytest.approx(5.0)
        assert eval_wait.read(
            {"traces": [member]}, 0.5, self.KINDS) == pytest.approx(3.0)

    def test_other_kinds_of_eval_are_left_out(self):
        lead, member = set_aside_pass()
        member["tags"]["triggered_by"] = "job-deregister"
        ctx = {"traces": [lead, member]}
        assert eval_wait.read(ctx, 1.0, self.KINDS) == pytest.approx(5.0)
        assert eval_wait.read(ctx, 1.0, ["job-deregister"]) == (
            pytest.approx(10.5))
        assert eval_wait.read(ctx, 0.5, ["node-update"]) is None

    def test_a_program_without_the_new_waits_gives_nothing(self):
        old = [solo_pass(), *batched_pass()]
        for t in old:
            t["tags"]["triggered_by"] = "job-register"
            t["spans"].append(span(99, t["spans"][0]["span_id"], "dequeue",
                                   -0.001, 1.0, queue_wait_ms=1.0))
        assert eval_wait.read({"traces": old}, 0.5, self.KINDS) is None


class TestDrainEvals:
    """``drain`` spans come from the recorder's background ring, on the
    tracer's clock: the traces above are moved onto it."""

    T = 9_000.0  # perf_counter stamp of the hand-built traces' T0

    @pytest.fixture(autouse=True)
    def _ring(self):
        flight_recorder.clear()
        yield
        flight_recorder.clear()

    def _shift(self, traces):
        by = global_tracer.unix_at(self.T) - T0
        for t in traces:
            for s in t["spans"]:
                s["start_unix"] += by
        return traces

    def _drain(self, node_id, start, dur_s):
        global_tracer.add_background(
            "drain", dur_s, start=self.T + start,
            tags={"node_id": node_id, "sched_ms": 1.0})

    def _ctx(self, traces):
        return {"traces": self._shift(list(traces)),
                "t_open": self.T, "t_close": self.T + 1.0}

    def test_work_first_then_what_only_waits_cover(self):
        self._drain("n-1", 0.197, 0.020)  # [0.197, 0.217)
        ctx = self._ctx(set_aside_pass())
        # busy: [0.200, 0.202) snapshot and prepares, [0.2045, 0.2105),
        # [0.211, 0.212), [0.2125, 0.216)
        assert drain_evals.read(ctx, 0.5, "busy") == pytest.approx(
            12.5, abs=1e-3)
        # everything covered is [0.198, 0.216) = 18; less busy
        assert drain_evals.read(ctx, 0.5, "wait") == pytest.approx(
            5.5, abs=1e-3)

    def test_spans_are_clipped_to_the_drain(self):
        self._drain("n-1", 0.197, 0.017)  # ends at 0.214, inside s-9
        ctx = self._ctx(set_aside_pass())
        assert drain_evals.read(ctx, 0.5, "busy") == pytest.approx(
            10.5, abs=1e-3)
        assert drain_evals.read(ctx, 0.5, "wait") == pytest.approx(
            5.5, abs=1e-3)

    def test_an_eval_of_another_node_or_from_outside_is_left_out(self):
        self._drain("n-1", 0.197, 0.020)
        lead, member = set_aside_pass()
        member["tags"]["node_id"] = "n-2"
        early = solo_pass()  # the same node, but it began before the drain
        early["tags"]["node_id"] = "n-1"
        ctx = self._ctx([lead, member, early])
        # the leader alone: busy [0.200, 0.2015) and [0.2045, 0.2105)
        assert drain_evals.read(ctx, 0.5, "busy") == pytest.approx(
            7.5, abs=1e-3)
        # covered [0.198, 0.2105) = 12.5
        assert drain_evals.read(ctx, 0.5, "wait") == pytest.approx(
            5.0, abs=1e-3)

    def test_a_drain_that_straddles_the_windows_edge_is_left_out(self):
        self._drain("n-1", 0.197, 0.020)
        self._drain("n-1", 0.990, 0.020)  # ends after the close
        self._drain("n-1", -0.010, 0.020)  # began before the opening
        ctx = self._ctx(set_aside_pass())
        assert drain_evals.read(ctx, 0.0, "busy") == pytest.approx(
            12.5, abs=1e-3)
        assert drain_evals.read(ctx, 1.0, "busy") == pytest.approx(
            12.5, abs=1e-3)

    def test_a_program_without_the_span_or_the_tag_gives_nothing(self):
        ctx = self._ctx(set_aside_pass())
        assert drain_evals.read(ctx, 0.5, "busy") is None  # no drain span
        self._drain("n-1", 0.197, 0.020)
        old = self._ctx([solo_pass(), *batched_pass()])  # no node_id
        assert drain_evals.read(old, 0.5, "busy") is None
        assert drain_evals.read(old, 0.5, "wait") is None


class TestLossEvals:
    """A failure's requests (one a job, due at the failure) over the node
    evals of their jobs on the failure's nodes, on the tracer's clock as
    ``TestDrainEvals`` has them."""

    T = 9_000.0

    def _ctx(self, traces, requests):
        by = global_tracer.unix_at(self.T) - T0
        for t in traces:
            for s in t["spans"]:
                s["start_unix"] += by
        return {"traces": traces, "requests": requests,
                "t_open": self.T, "t_close": self.T + 1.0}

    def _requests(self, dones, job_ids, node="n-1"):
        failure = types.SimpleNamespace(node_ids=[node], due=self.T + 0.197)
        return [
            types.SimpleNamespace(failure=failure, job_id=j, ok=True,
                                  due=self.T + 0.197, done=self.T + d)
            for j, d in zip(job_ids, dones)
        ]

    def _traces(self):
        lead, member = set_aside_pass()
        lead["tags"]["job_id"], member["tags"]["job_id"] = "j-1", "j-2"
        return [lead, member]

    def test_a_jobs_evals_split_into_work_and_waits(self):
        ctx = self._ctx(self._traces(), self._requests([0.217], ["j-1"]))
        # the leader's: busy [0.200, 0.2015) and [0.2045, 0.2105); covered
        # [0.198, 0.2105)
        assert loss_evals.read(ctx, 0.5, "busy") == pytest.approx(
            7.5, abs=1e-3)
        assert loss_evals.read(ctx, 0.5, "wait") == pytest.approx(
            5.0, abs=1e-3)

    def test_evals_of_other_nodes_and_other_jobs_are_left_out(self):
        traces = self._traces()
        ctx = self._ctx(traces, self._requests([0.217], ["j-3"]))
        assert loss_evals.read(ctx, 0.5, "busy") == 0.0
        ctx = self._ctx(self._traces(),
                        self._requests([0.217], ["j-1"], node="n-9"))
        assert loss_evals.read(ctx, 0.5, "busy") == 0.0

    def test_recover_is_due_to_the_last_job_done(self):
        ctx = self._ctx(self._traces(),
                        self._requests([0.217, 0.300], ["j-1", "j-2"]))
        assert loss_evals.read(ctx, 0.5, "recover") == pytest.approx(103.0)

    def test_another_deployments_requests_give_nothing(self):
        reqs = [types.SimpleNamespace(job_id="j-1", ok=True, due=self.T,
                                      done=self.T + 0.1)]
        ctx = self._ctx(self._traces(), reqs)
        for part in ("busy", "wait", "recover"):
            assert loss_evals.read(ctx, 0.5, part) is None


class TestHeldSpanQuantile:
    T = 9_000.0

    @pytest.fixture(autouse=True)
    def _ring(self):
        flight_recorder.clear()
        yield
        flight_recorder.clear()

    def test_reads_the_window_the_ring_holds(self):
        for k, ms in enumerate((1.0, 3.0, 2.0)):
            global_tracer.add_background("node_status", ms / 1000.0,
                                         start=self.T + 0.1 * (k + 1))
        ctx = {"t_open": self.T, "t_close": self.T + 1.0}
        assert held_span_quantile.read(ctx, "node_status", 0.5) == (
            pytest.approx(2.0))

    def test_a_full_ring_that_lost_the_windows_start_reads_nothing(
            self, monkeypatch):
        from nomad_tpu.obs import recorder

        monkeypatch.setattr(recorder, "DEFAULT_BACKGROUND_CAPACITY", 3)
        for k in range(3):
            global_tracer.add_background("node_status", 0.001,
                                         start=self.T + 0.1 * (k + 1))
        ctx = {"t_open": self.T, "t_close": self.T + 1.0}
        assert held_span_quantile.read(ctx, "node_status", 0.5) is None


class TestCheckTraces:
    def test_a_set_aside_pass_passes_every_check(self):
        """``solo_wait`` is a child of the root and may overlap the copies
        beside it; the gap table shows the root's gaps around it covered."""
        lead, member = set_aside_pass()
        report = check_traces.check([lead, member], {"metrics": {}})
        assert report["ok"], report
        assert report["distinct_pass_ids"] == 3
        gaps = check_traces.gaps([lead])
        assert "eval: prepare -> join_commit" not in gaps
        assert gaps["eval: join_commit -> prepare"] == pytest.approx(
            0.0005, abs=1e-6)
        lead["spans"] = [
            s for s in lead["spans"]
            if s["name"] not in ("solo_wait", "overlay.wait")]
        assert check_traces.gaps([lead])[
            "eval: prepare -> join_commit"] == pytest.approx(0.0015, abs=1e-6)

    def test_sound_traces_pass_every_check(self):
        traces = [solo_pass()] + batched_pass()
        result = {"metrics": {"passes_solo": {"value": 1.0},
                              "passes_batched": {"value": 1.0}}}
        report = check_traces.check(traces, result)
        assert report["ok"] is True
        assert report["distinct_pass_ids"] == 2
        assert report["passes_counted_in_window"] == 2.0
        assert report["nesting_faults"] == {}
        assert check_traces.pass_ids(traces[0]) == {"s-1"}
        whole = {"distinct_pass_ids": 3, "passes_counted": 2.0}
        assert check_traces.check(traces, result, whole)["ok"] is False

    def test_a_child_outside_its_parent_is_found(self):
        t = solo_pass()
        t["spans"].append(span(30, 5, "repair", 0.012, 3.0))  # ends at 15
        assert check_traces.nesting_faults(t) == [
            "repair ends after invoke_scheduler"]
        t["spans"][-1] = span(30, 5, "repair", 0.001, 1.0)
        assert check_traces.nesting_faults(t) == [
            "repair starts before invoke_scheduler"]

    def test_register_and_dequeue_may_precede_the_root(self):
        t = solo_pass()
        t["spans"].append(span(31, 1, "dequeue", -0.010, 9.0))
        t["spans"].append(span(32, 1, "register", -0.012, 2.0))
        assert check_traces.nesting_faults(t) == []

    def test_overlapping_children_of_plan_apply_are_found(self):
        t = trace("e", [
            span(1, None, "eval", 0.0, 10.0),
            span(2, 1, "plan_apply", 0.001, 5.0),
            span(3, 2, "plan_apply.evaluate", 0.001, 3.0),
            span(4, 2, "plan_apply.commit", 0.003, 2.0),
        ])
        assert check_traces.nesting_faults(t) == [
            "plan_apply.evaluate overlaps plan_apply.commit"]
        t["spans"][3] = span(4, 2, "plan_apply.commit", 0.004, 2.0)
        assert check_traces.nesting_faults(t) == []

    def test_a_pass_with_kernels_needs_exactly_one_kernel_place(self):
        lead, member = batched_pass()
        ids, bad = check_traces.pass_faults([lead, member])
        assert ids == {"b-2"} and bad == []
        lead["spans"] = [s for s in lead["spans"]
                         if s["name"] != "kernel.place"]
        lead["spans"][-1]["parent_id"] = 13  # the kernel, now under invoke
        ids, bad = check_traces.pass_faults([lead, member])
        assert bad == ["b-2"]

    def test_gaps_are_summed_by_where_they_lie(self):
        g = check_traces.gaps([solo_pass()])
        assert g["prepare: (start) -> flatten"] == pytest.approx(0.0005)
        assert g["kernel.place: place.pull -> (end)"] == pytest.approx(0.001)
        assert g["eval: invoke_scheduler -> submit_plan"] == pytest.approx(
            0.007)
        assert "eval: snapshot -> prepare" not in g  # they touch

    def test_stage_table_leaves_out_copies(self):
        table = check_traces.stage_table(batched_pass())
        assert table["snapshot"]["count"] == 1
        assert table["prepare"]["count"] == 2
        assert table["invoke_scheduler"]["p50_ms"] == pytest.approx(10.0)
