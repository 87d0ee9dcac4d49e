"""nomad_tpu.resilience — kernel circuit breaker, watchdog deadlines,
RPC retry idempotency, eval-lifecycle deadlines, degraded-mode identity.

The load-bearing claims pinned here:

- the breaker FSM (closed → open → half-open) under a fake clock:
  trip thresholds, immediate timeout trips, seeded-jitter backoff
  doubling, single-probe admission;
- a mid-pass kernel trip finishes the pass on the eager reference path
  with placements byte-identical to an all-CPU (forced-open) run —
  sibling members of a merged commit never fail;
- RPC retry is idempotency-aware: dial failures retry for every
  method, post-send connection loss retries only registered-idempotent
  methods (plan submission stays at-most-once);
- an eval that blows its processing deadline is nacked with escalating
  broker redelivery delay and parked as failed (structured reason) at
  the attempt cap;
- chaos kernel.hang scenarios trip breakers and still converge with
  zero invariant violations.
"""

import queue
import threading
import time

import numpy as np
import pytest

from nomad_tpu.chaos import (
    FaultSpec,
    install,
    run_chaos,
    uninstall,
)
from nomad_tpu.resilience import breaker as rbr
from nomad_tpu.resilience.breaker import (
    CircuitBreaker,
    breaker_for,
    set_forced_open,
)
from nomad_tpu.resilience.errors import (
    EvalDeadlineExceeded,
    KernelDeadlineExceeded,
)
from nomad_tpu.resilience.watchdog import DeadlineExecutor
from nomad_tpu.utils.metrics import global_metrics


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    """Breakers, forced-open, tunable defaults, and the chaos plane are
    process-global: every test starts and ends from a clean slate."""
    prev = rbr.configure()  # no-op call: snapshot current defaults
    rbr.reset_all()
    yield
    uninstall()
    rbr.configure(**prev)
    rbr.reset_all()


def _counter(name: str) -> float:
    return global_metrics.snapshot()["counters"].get(name, 0.0)


def wait_until(cond, timeout=15.0, interval=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    # EvalBroker takes a clock object exposing .time()
    def time(self) -> float:
        return self.t


# -- breaker FSM -------------------------------------------------------------


class TestCircuitBreaker:
    def _mk(self, **kw):
        clk = FakeClock()
        kw.setdefault("failure_threshold", 3)
        kw.setdefault("backoff_base", 1.0)
        kw.setdefault("backoff_cap", 30.0)
        return CircuitBreaker("test.kernel", clock=clk, **kw), clk

    def test_trips_after_threshold_consecutive_failures(self):
        br, _ = self._mk()
        for _ in range(2):
            br.record_failure(RuntimeError("boom"))
            assert br.state == "closed" and br.allow()
        br.record_failure(RuntimeError("boom"))
        assert br.state == "open"
        assert not br.allow()

    def test_success_resets_the_failure_streak(self):
        br, _ = self._mk()
        br.record_failure(RuntimeError("a"))
        br.record_failure(RuntimeError("b"))
        br.record_success()
        br.record_failure(RuntimeError("c"))
        br.record_failure(RuntimeError("d"))
        assert br.state == "closed"  # streak restarted at the success

    def test_timeout_trips_immediately(self):
        br, _ = self._mk()
        br.record_timeout(KernelDeadlineExceeded("test.kernel", 5.0))
        assert br.state == "open"
        assert br.snapshot()["trips"] == 1

    def test_half_open_admits_exactly_one_probe(self):
        br, clk = self._mk()
        br.record_timeout(RuntimeError("hang"))
        assert not br.allow()  # still inside the backoff window
        clk.t += br.snapshot()["backoff_s"] + 0.001
        assert br.allow()  # the single half-open probe
        assert br.state == "half_open"
        assert not br.allow()  # concurrent callers stay on fallback

    def test_probe_success_closes(self):
        br, clk = self._mk()
        br.record_timeout(RuntimeError("hang"))
        clk.t += br.snapshot()["backoff_s"] + 0.001
        assert br.allow()
        br.record_success()
        assert br.state == "closed"
        assert br.allow()

    def test_probe_failure_reopens_with_doubled_backoff(self):
        br, clk = self._mk()
        br.record_timeout(RuntimeError("hang"))
        first = br.snapshot()["backoff_s"]
        clk.t += first + 0.001
        assert br.allow()
        br.record_failure(RuntimeError("still down"))
        assert br.state == "open"
        second = br.snapshot()["backoff_s"]
        # raw backoff doubled (1 s → 2 s); jitter is bounded [0.5, 1.5]
        # per stage so the doubled stage must exceed the first stage's
        # floor ratio even at worst-case jitter draw
        assert second > first * (0.5 / 1.5)
        assert br.snapshot()["trips"] == 2

    def test_backoff_jitter_is_seeded_by_name_and_trip(self):
        a, _ = self._mk()
        b, _ = self._mk()
        a.record_timeout(RuntimeError("x"))
        b.record_timeout(RuntimeError("x"))
        assert a.snapshot()["backoff_s"] == b.snapshot()["backoff_s"]

    def test_forced_open_overrides_every_breaker(self):
        br = breaker_for("some.kernel")
        assert br.allow()
        set_forced_open(True)
        assert not br.allow()
        assert rbr.degraded()
        set_forced_open(False)
        assert br.allow()

    def test_trip_emits_counter_gauge_and_flight_record(self):
        from nomad_tpu.obs.recorder import flight_recorder

        before = _counter("nomad.resilience.trips_total")
        br = breaker_for("obs.kernel")
        br.record_timeout(RuntimeError("hang"))
        assert _counter("nomad.resilience.trips_total") == before + 1
        gauges = global_metrics.snapshot()["gauges"]
        assert gauges["nomad.resilience.breaker_state.obs.kernel"] == 2
        assert any(
            e["component"] == "resilience" and "obs.kernel" in e["error"]
            for e in flight_recorder.errors()
        )

    def test_configure_rejects_unknown_tunable(self):
        with pytest.raises(TypeError):
            rbr.configure(not_a_knob=1)

    def test_configure_pushes_tunables_onto_live_breakers(self):
        br = breaker_for("live.kernel")
        prev = rbr.configure(execute_deadline=0.123)
        try:
            assert br.execute_deadline == 0.123
        finally:
            rbr.configure(**prev)


# -- watchdog ----------------------------------------------------------------


class TestDeadlineExecutor:
    def test_returns_result_and_reuses_worker(self):
        ex = DeadlineExecutor()
        for i in range(5):
            assert ex.run(lambda i=i: i * 2, name="k", deadline_s=5.0) == i * 2
        assert ex.spawned == 1  # the happy path reuses one idle thread

    def test_timeout_raises_and_poisons_the_worker(self):
        ex = DeadlineExecutor()
        release = threading.Event()
        with pytest.raises(KernelDeadlineExceeded) as ei:
            ex.run(lambda: release.wait(5.0), name="k", deadline_s=0.05)
        assert ei.value.phase == "execute"
        assert ex.poisoned == 1
        release.set()
        # the pool recovers with a fresh worker
        assert ex.run(lambda: "ok", name="k", deadline_s=5.0) == "ok"
        assert ex.spawned == 2

    def test_exceptions_propagate_to_the_caller(self):
        ex = DeadlineExecutor()
        with pytest.raises(ValueError, match="inner"):
            ex.run(lambda: (_ for _ in ()).throw(ValueError("inner")),
                   name="k", deadline_s=5.0)

    def test_extend_probe_buys_the_compile_deadline(self):
        ex = DeadlineExecutor()
        out = ex.run(
            lambda: time.sleep(0.15) or "compiled",
            name="k",
            deadline_s=0.05,
            extend_deadline_s=5.0,
            extend_probe=lambda: True,  # "a trace started" → compiling
        )
        assert out == "compiled"

    def test_extended_timeout_reports_compile_phase(self):
        ex = DeadlineExecutor()
        release = threading.Event()
        with pytest.raises(KernelDeadlineExceeded) as ei:
            ex.run(
                lambda: release.wait(5.0),
                name="k",
                deadline_s=0.03,
                extend_deadline_s=0.1,
                extend_probe=lambda: True,
            )
        assert ei.value.phase == "compile"
        release.set()


# -- kernel fallback byte-identity -------------------------------------------


def _tiny_workload(n_nodes=200, n_jobs=4, count=25):
    from nomad_tpu.mock import build_asks, build_cluster

    ct = build_cluster(n_nodes)
    return ct, build_asks(ct, n_jobs, count)


def _rows(results):
    return [
        (r.node_rows.copy(), np.asarray(r.scores).copy())
        for r in results
    ]


def _identical(a, b):
    assert len(a) == len(b)
    for (ra, sa), (rb, sb) in zip(a, b):
        assert np.array_equal(ra, rb)
        assert np.array_equal(sa, sb)


class TestKernelFallback:
    def test_mid_pass_trip_matches_all_cpu_run(self):
        """A hang on the first kernel call of a pass trips the breaker;
        the call finishes on the reference path and every subsequent
        call routes there too — so the tripped pass's placements are
        byte-identical to a from-scratch forced-open (all-CPU) run."""
        from nomad_tpu.device.score import PlacementKernel

        ct, asks = _tiny_workload()
        kernel = PlacementKernel("binpack")
        kernel.place(ct, asks)  # warm the jitted buckets, no faults

        set_forced_open(True)
        try:
            reference = _rows(kernel.place(ct, asks))
        finally:
            set_forced_open(False)

        rbr.reset_all()
        # long backoff: no half-open probe sneaks back mid-pass
        rbr.configure(execute_deadline=0.05, backoff_base=60.0)
        fallback_before = _counter("nomad.resilience.fallback_calls")
        trips_before = _counter("nomad.resilience.trips_total")
        # hang the first call of EVERY kernel the pass reaches (a
        # tripped kernel stops hitting the site, so occurrences land on
        # the next still-closed kernel)
        install_schedule = [
            FaultSpec("kernel.hang", i, "hang", 0.3) for i in range(8)
        ]
        from nomad_tpu.chaos import FaultPlane

        install(FaultPlane(schedule=install_schedule))
        try:
            tripped = _rows(kernel.place(ct, asks))
        finally:
            uninstall()

        assert _counter("nomad.resilience.trips_total") > trips_before
        assert _counter("nomad.resilience.fallback_calls") > fallback_before
        assert any(
            br.snapshot()["trips"] > 0 for br in rbr.all_breakers().values()
        )
        _identical(reference, tripped)

    def test_degraded_pass_counter(self):
        from nomad_tpu.device.score import PlacementKernel

        ct, asks = _tiny_workload(n_nodes=100, n_jobs=2, count=10)
        kernel = PlacementKernel("binpack")
        before = _counter("nomad.resilience.fallback_passes")
        set_forced_open(True)
        try:
            kernel.place(ct, asks)
        finally:
            set_forced_open(False)
        assert _counter("nomad.resilience.fallback_passes") == before + 1


class TestNestedKernelGuard:
    def test_inner_kernel_inlines_under_outer_trace(self, monkeypatch):
        """A traced_jit kernel called while another one is being traced
        gets tracer arguments bound to that trace's thread: it must
        inline — no breaker of its own, no second hop through the
        watchdog executor — and still trace exactly once."""
        import jax.numpy as jnp

        from nomad_tpu.resilience import watchdog
        from nomad_tpu.utils.backend import trace_counts, traced_jit

        inner_name = "tests.test_resilience.nested_inner"
        outer_name = "tests.test_resilience.nested_outer"
        seen = []

        @traced_jit(trace_name=inner_name)
        def inner(x):
            seen.append(type(x).__name__)
            return x * 2.0

        @traced_jit(trace_name=outer_name)
        def outer(x):
            return inner(x) + 1.0

        runs = []
        real_run = watchdog.global_executor.run

        def counting_run(thunk, **kw):
            runs.append(kw["name"])
            return real_run(thunk, **kw)

        monkeypatch.setattr(watchdog.global_executor, "run", counting_run)
        out = outer(jnp.arange(4, dtype=jnp.float32))
        np.testing.assert_array_equal(np.asarray(out), [1.0, 3.0, 5.0, 7.0])
        assert seen and "Tracer" in seen[0]
        assert runs == [outer_name]
        assert inner_name not in rbr.all_breakers()
        assert outer_name in rbr.all_breakers()
        assert trace_counts()[inner_name] == 1
        # called directly with concrete arrays, the inner kernel is
        # guarded like any other
        inner(jnp.ones(4, dtype=jnp.float32))
        assert runs == [outer_name, inner_name]
        assert inner_name in rbr.all_breakers()


# -- RPC retry / idempotency -------------------------------------------------


class TestRPCRetry:
    def test_dial_failure_retries_every_method(self):
        from nomad_tpu.rpc import RPCClient

        sleeps = []
        c = RPCClient(
            "127.0.0.1:1", timeout=0.5, max_attempts=3, sleep=sleeps.append
        )
        before = _counter("nomad.resilience.rpc.retries")
        with pytest.raises(ConnectionError, match="rpc dial"):
            c.call("Plan.submit", {})  # NOT idempotent — dial still retries
        assert len(sleeps) == 2  # attempts 1 and 2 backed off, 3rd raised
        assert sleeps[1] > 0
        assert _counter("nomad.resilience.rpc.retries") == before + 2

    def test_post_send_drop_retries_idempotent_method(self):
        from nomad_tpu.rpc import RPCClient, RPCServer

        srv = RPCServer()
        srv.start()
        calls = []
        srv.register("Echo.ping", lambda a: calls.append(1) or "pong")
        sleeps = []
        c = RPCClient(
            srv.address,
            timeout=2.0,
            max_attempts=3,
            idempotent=("Echo.ping",),
            sleep=sleeps.append,
        )
        install_plane = [FaultSpec("rpc.conn_drop", 0, "drop")]
        from nomad_tpu.chaos import FaultPlane

        install(FaultPlane(schedule=install_plane))
        try:
            assert c.call("Echo.ping", {}) == "pong"
        finally:
            uninstall()
            c.close()
            srv.stop()
        # the dropped attempt backed off and retried; at-least-once
        # delivery means the handler may have run on both attempts
        assert len(sleeps) == 1
        assert 1 <= len(calls) <= 2

    def test_post_send_drop_is_at_most_once_for_writes(self):
        from nomad_tpu.rpc import RPCClient, RPCServer

        srv = RPCServer()
        srv.start()
        calls = []
        srv.register("Plan.submit", lambda a: calls.append(1) or "ok")
        sleeps = []
        c = RPCClient(
            srv.address, timeout=2.0, max_attempts=3, sleep=sleeps.append
        )
        from nomad_tpu.chaos import FaultPlane

        install(FaultPlane(schedule=[FaultSpec("rpc.conn_drop", 0, "drop")]))
        try:
            with pytest.raises(ConnectionError):
                c.call("Plan.submit", {})
        finally:
            uninstall()
            c.close()
            srv.stop()
        assert sleeps == []  # no transport-level retry for a write
        assert len(calls) <= 1

    def test_default_idempotent_set_and_mark(self):
        from nomad_tpu.rpc import RPCClient
        from nomad_tpu.rpc.client import DEFAULT_IDEMPOTENT

        c = RPCClient("127.0.0.1:1")
        assert "Nomad.heartbeat" in DEFAULT_IDEMPOTENT
        assert c.is_idempotent("Nomad.heartbeat")
        assert not c.is_idempotent("Plan.submit")
        c.mark_idempotent("Custom.read")
        assert c.is_idempotent("Custom.read")


# -- eval-lifecycle deadlines ------------------------------------------------


class TestEvalDeadline:
    def test_broker_redelivery_delay_escalates_per_attempt(self):
        """nack #1 waits initial_nack_delay, each further one doubles,
        capped at nack_delay — inspected on the delay heap directly."""
        from nomad_tpu.broker.eval_broker import EvalBroker
        from nomad_tpu.structs import Evaluation

        clk = FakeClock()
        b = EvalBroker(
            nack_delay=4.0,
            initial_nack_delay=1.0,
            delivery_limit=10,
            unack_timeout=None,
            clock=clk.time,
        )
        b.set_enabled(True)
        e = Evaluation(job_id="j1")
        b.enqueue(e)
        before = _counter("nomad.broker.nack_redelivery_delayed")
        expected = [1.0, 2.0, 4.0, 4.0]  # doubling, then the cap
        for want in expected:
            # non-blocking poll: with a frozen clock a blocking dequeue
            # would spin real-time waits instead of failing fast
            got, token = b.dequeue(["service"], timeout=0)
            assert got is e
            b.nack(e.id, token)
            fire_at = b._delayed[0][0]
            assert fire_at - clk.t == pytest.approx(want)
            clk.t = fire_at + 0.001
        assert _counter("nomad.broker.nack_redelivery_delayed") == (
            before + len(expected)
        )

    def test_deadline_expiry_escalates_to_failed(self):
        """An eval whose processing blows the deadline is nacked with
        attempt accounting and, at the attempt cap, parked as failed
        with a structured reason — the hot loop ends."""
        from nomad_tpu import mock
        from nomad_tpu.server import Server, ServerConfig
        from nomad_tpu.structs.evaluation import EVAL_STATUS_FAILED

        server = Server(
            ServerConfig(
                num_workers=1,
                eval_deadline=1e-9,  # everything instantly overdue
                eval_attempt_limit=2,
            )
        )
        # fast redelivery so the escalation finishes inside the test
        server.eval_broker.initial_nack_delay = 0.02
        server.eval_broker.nack_delay = 0.05
        nacks_before = _counter("nomad.resilience.eval.deadline_nacks")
        server.establish_leadership()
        try:
            node = mock.node()
            node.compute_class()
            server.store.upsert_node(1, node)
            job = mock.job()
            job.task_groups[0].count = 1
            server.register_job(job)

            def _failed():
                evs = [
                    ev for ev in server.store.evals()
                    if ev.job_id == job.id
                ]
                return evs and all(
                    ev.status == EVAL_STATUS_FAILED for ev in evs
                )

            assert wait_until(_failed, timeout=20.0), [
                (ev.id, ev.status) for ev in server.store.evals()
            ]
            failed = [
                ev for ev in server.store.evals() if ev.job_id == job.id
            ][0]
            assert failed.attempts == 2
            assert "eval-deadline-exceeded" in failed.status_description
            assert "limit=2" in failed.status_description
            assert _counter("nomad.resilience.eval.deadline_nacks") >= (
                nacks_before + 2
            )
            assert _counter("nomad.resilience.eval.deadline_failed") >= 1
        finally:
            server.shutdown()

    def test_deadline_disabled_when_nonpositive(self):
        from nomad_tpu.server import Server, ServerConfig

        server = Server(ServerConfig(num_workers=1, eval_deadline=0))
        server.establish_leadership()
        try:
            assert server.workers[0]._eval_deadline is None
        finally:
            server.shutdown()

    def test_error_types_carry_structured_fields(self):
        e = EvalDeadlineExceeded("ev-1", 60.0, attempts=2)
        assert e.eval_id == "ev-1" and e.attempts == 2
        k = KernelDeadlineExceeded("score.place", 5.0, phase="compile")
        assert k.kernel == "score.place" and k.phase == "compile"


# -- chaos integration -------------------------------------------------------


class TestChaosResilience:
    def test_kernel_hang_trips_and_converges_clean(self):
        """A kernel.hang fault mid-run trips the breaker, the pass
        finishes degraded, and the cluster still converges with zero
        invariant violations (run_chaos shortens the execute deadline
        below the injected hang's floor, so the FIRST hang trips)."""
        run = run_chaos(
            seed=23,
            steps=40,
            schedule=[FaultSpec("kernel.hang", 0, "hang", 0.3)],
            quiesce_timeout=60.0,
        )
        assert run.ok, run.render()
        hangs = [t for t in run.triggered if t[2] == "hang"]
        assert hangs, "the hang never fired: scenario missed the seam"
        assert run.report.info["counters"].get(
            "nomad.resilience.trips_total", 0
        ) >= 1
        # breaker states were captured live in the invariant report
        assert any(
            b["trips"] >= 1 for b in run.report.info["breakers"].values()
        )

    def test_hang_rate_run_places_everything(self):
        run = run_chaos(seed=31, steps=60, faults=("hang",), rate=0.10)
        assert run.ok, run.render()


@pytest.mark.slow
class TestDegradedSoak:
    def test_ten_seed_hang_soak(self):
        """The acceptance matrix slice: kernel hangs at 10% over 200
        steps, ten seeds — zero invariant violations, full placement."""
        for seed in range(1, 11):
            run = run_chaos(seed=seed, steps=200, faults=("hang",), rate=0.10)
            assert run.ok, f"seed {seed}:\n" + run.render()
