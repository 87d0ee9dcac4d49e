"""NodeDrainer — wave-by-wave migration of allocs off draining nodes.

Reference: nomad/drainer/ (drainer.go NodeDrainer, watch_jobs.go
DrainingJobWatcher, watch_nodes.go, drain_heap.go deadline notifier).
Semantics kept:

- A draining node's allocs are NOT all stopped at once. The drainer marks
  batches of allocs with ``DesiredTransition.Migrate`` respecting each
  task group's ``migrate.max_parallel`` (watch_jobs.go handleTaskGroup:
  in-flight = allocs already marked whose replacement isn't healthy yet;
  mark at most max_parallel − in_flight more).
- System (and sysbatch) jobs stay until everything else has left the
  node; skipped entirely with ``ignore_system_jobs``
  (watch_nodes.go deadlineReached / IsDone).
- When the drain deadline passes, all remaining allocs are force-marked
  (drain_heap.go + drainer.go handleDeadlinedNodes).
- When nothing migratable remains, the node's DrainStrategy is cleared
  but the node stays ineligible (drainer.go handleDoneNodeDrains,
  NodeDrainEventComplete).

The reference's three watchers are blocking queries: they wake on the
commit that gives them work. Here the one thread sleeps on an event that
those commits set (``note_drain``: a strategy set or cleared by
``Node.UpdateDrain``; ``note_client_update``: the clients' alloc sync,
which is where a replacement turns healthy and frees its group's budget;
``note_health``: the deployment watcher's verdicts; ``note_stops``: a plan
that stopped allocations on a draining node, which may have emptied it)
and then looks at the draining nodes only, whose ids it keeps. With no
wake for ``interval`` it walks every node as before: the fallback that
finds a drain nobody told it about.

A drain is one operation to the operator who ordered it, and many evals
and commits to the program. The drainer keeps a record of each drain it
was told of (``_DrainRecord``) and hands it to the tracer where the drain
ends: the background span ``drain``, from the commit of the strategy to
the commit that cleared it, its time split among who it waited for.
"""

from __future__ import annotations

import logging

import threading
import time
from typing import Optional

from ..obs.trace import global_tracer as tracer
from ..structs import Evaluation
from ..structs.alloc import DesiredTransition
from ..structs.evaluation import EVAL_STATUS_PENDING, TRIGGER_NODE_DRAIN
from ..utils.metrics import global_metrics as metrics
from .fsm import MsgType

log = logging.getLogger("nomad_tpu.drainer")


class _DrainRecord:
    """One drain under way and where its time has gone so far. At any
    moment the drain waits for one of three, and the drainer, which looks
    at the node after every commit that can change that, keeps the time:

    ``sched``    an allocation of the node is marked to migrate and not
                 yet stopped: its eval queues, runs, commits;
    ``client``   nothing is marked and unstopped, allocations remain and
                 no group's budget lets one be marked: a replacement has
                 to turn healthy on its client first;
    ``drainer``  the rest: from the commit that gave the drainer its turn
                 (the strategy's, the one that freed a budget, the last
                 stop) over its wake and its look to the commit of its
                 own answer (the transition message, the strategy cleared).

    A state lasts from ``turn`` to ``turn``; the three sum to the time
    from ``start`` to the last turn."""

    __slots__ = ("start", "at", "state", "spent", "allocs", "waves",
                 "evals", "migrated")

    def __init__(self, start: float):
        self.start = self.at = start  # ``perf_counter`` stamps
        self.state = "drainer"
        self.spent = {"sched": 0.0, "client": 0.0, "drainer": 0.0}
        self.allocs: Optional[int] = None  # held at the first look
        self.waves = self.evals = self.migrated = 0

    def turn(self, at: float, state: str) -> None:
        self.spent[self.state] += at - self.at
        self.at, self.state = at, state


class NodeDrainer:
    """The drainer bound to a Server (the reference's watcher trio
    collapsed into one scan of the draining nodes, woken by the commits
    the trio's blocking queries would return on)."""

    def __init__(self, server, interval: float = 0.25):
        self.server = server
        self.interval = interval
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._lock = threading.Lock()
        # ids of the nodes that hold a strategy, as the commits told it
        self._draining: set[str] = set()
        self._woken_by: set[str] = set()
        # ``perf_counter`` stamps of the commits a wave answers: the
        # strategy's (node id; its first wave) and the oldest client
        # update of a job no eval has answered yet ((namespace, job id))
        self._drain_at: dict[str, float] = {}
        self._freed_at: dict[tuple[str, str], float] = {}
        # node id -> the record of its drain, from ``note_drain`` to
        # ``_complete``; a drain found by a walk or inherited by a new
        # leader has none and writes no span
        self._drains: dict[str, _DrainRecord] = {}
        # stamp of the oldest commit noted since the last scan began:
        # where a state the next look finds changed, it changed there
        self._noted_at: Optional[float] = None

    def start(self) -> None:
        self._stop.clear()
        # a new leader inherits the drains under way
        with self._lock:
            self._draining = {
                n.id for n in self.server.store.nodes() if n.drain is not None
            }
        self._thread = threading.Thread(
            target=self._run, name="node-drainer", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread:
            self._thread.join(timeout=2)
            self._thread = None

    def _run(self) -> None:
        while True:
            woken = self._wake.wait(self.interval)
            if self._stop.is_set():
                return
            self._wake.clear()
            try:
                self.scan(full=not woken)
            except Exception:  # noqa: BLE001
                log.exception("drainer scan failed")

    # -- the commits that give it work --------------------------------------
    def _wake_for(self, reason: str, at: float) -> None:
        with self._lock:
            self._woken_by.add(reason)
            if self._noted_at is None:
                self._noted_at = at
        self._wake.set()

    def note_drain(self, applied_at: float, node_id: str, drain) -> None:
        """``Node.UpdateDrain`` committed at ``applied_at``."""
        with self._lock:
            if drain is not None:
                self._draining.add(node_id)
                self._drain_at[node_id] = applied_at
                if node_id not in self._drains:  # not a new deadline
                    self._drains[node_id] = _DrainRecord(applied_at)
            else:
                self._draining.discard(node_id)
                self._drain_at.pop(node_id, None)
                self._drains.pop(node_id, None)  # cancelled: no span
        if drain is not None:
            metrics.incr("nomad.drain.started")
            self._wake_for("node_drain", applied_at)

    def note_client_update(self, applied_at: float, updates) -> None:
        """The clients' alloc sync committed at ``applied_at``: a
        replacement that reads ``running`` counts as healthy from here on
        (``_alloc_healthy``) and frees its group's budget."""
        with self._lock:
            if not self._draining:
                return
            for u in updates:
                if u.client_status == "running":
                    self._freed_at.setdefault(
                        (u.namespace, u.job_id), applied_at
                    )
        self._wake_for("client_update", applied_at)

    def note_health(self, applied_at: float, namespace: str,
                    job_id: str) -> None:
        """The deployment watcher committed healthy verdicts for
        allocations of this job."""
        with self._lock:
            if not self._draining:
                return
            self._freed_at.setdefault((namespace, job_id), applied_at)
        self._wake_for("health", applied_at)

    def note_stops(self, node_ids) -> None:
        """A plan's stops landed on these nodes: a draining one among
        them may be empty now."""
        with self._lock:
            hit = not self._draining.isdisjoint(node_ids)
        if hit:
            self._wake_for("plan_stops", time.perf_counter())

    # -- one pass ----------------------------------------------------------
    def scan(self, full: bool = True) -> None:
        """One look at the draining nodes. ``full`` walks every node of
        the store to find them (the interval's fallback, and what a test
        that calls this gets); a wake looks up the ids it keeps."""
        store = self.server.store
        with tracer.background("drain.scan") as sp:
            with self._lock:
                woken_by = ",".join(sorted(self._woken_by)) or "interval"
                self._woken_by.clear()
                noted_at, self._noted_at = self._noted_at, None
                ids = sorted(self._draining)
            if full:
                nodes = store.nodes()
                walked = len(nodes)
                draining = [n for n in nodes if n.drain is not None]
                with self._lock:
                    # commits noted since the walk began stay noted
                    self._draining |= {n.id for n in draining}
            else:
                walked = len(ids)
                draining = [
                    n for n in map(store.node_by_id, ids)
                    if n is not None and n.drain is not None
                ]
                if len(draining) < len(ids):  # cancelled, or gone
                    with self._lock:
                        for gone in set(ids) - {n.id for n in draining}:
                            self._draining.discard(gone)
                            self._drains.pop(gone, None)
            seen = {
                "draining": len(draining), "walked": walked, "marked": 0,
                "evals": 0, "completed": 0, "woken_by": woken_by,
            }
            for node in draining:
                self._drain_node(node, seen, noted_at)
            if sp is not None:
                sp.tags.update(seen)
        if not draining:
            with self._lock:
                if not self._draining:
                    self._freed_at.clear()

    @staticmethod
    def _alloc_healthy(a) -> bool:
        """Counts toward the group's serving capacity: an explicitly
        healthy deployment/migration status, or a running task set
        (watch_jobs.go handleTaskGroup uses DeploymentStatus.IsHealthy;
        outside deployments the client's alloc-health watcher reports
        migration health the same way — client_status is our analog)."""
        if a.deployment_status is not None and a.deployment_status.healthy:
            return True
        return a.client_status == "running"

    def _drain_node(self, node, seen: dict,
                    noted_at: Optional[float] = None) -> None:
        store = self.server.store
        drain = node.drain
        now = time.time()
        deadlined = 0 < drain.force_deadline_unix <= now or drain.deadline_s < 0
        rec = self._drains.get(node.id)
        t_look = time.perf_counter()

        def found(state: str) -> None:
            # what this look finds the drain waiting for; where that is
            # news, it has been so since the commit that woke the drainer
            if rec is not None and state != rec.state:
                turned = t_look if noted_at is None else noted_at
                rec.turn(min(max(turned, rec.at), t_look), state)

        allocs = [
            a for a in store.allocs_by_node(node.id) if not a.terminal_status()
        ]
        if rec is not None and rec.allocs is None:
            rec.allocs = len(allocs)
        system, normal = [], []
        for a in allocs:
            job = store.job_by_id(a.namespace, a.job_id)
            if job is not None and job.type in ("system", "sysbatch"):
                system.append((a, job))
            else:
                normal.append((a, job))

        remaining = list(normal)
        if not drain.ignore_system_jobs:
            # system allocs drain only after all others are gone, or at
            # the deadline (watch_nodes.go IsDone / deadlineReached)
            if not normal or deadlined:
                remaining += system

        if not remaining:
            found("drainer")
            self._complete(node, deadlined, rec)
            seen["completed"] += 1
            return

        transitions: dict[str, DesiredTransition] = {}
        jobs_touched: dict[tuple[str, str], object] = {}
        if deadlined:
            for a, job in remaining:
                if not a.desired_transition.migrate:
                    transitions[a.id] = DesiredTransition(migrate=True)
                    # deadline expiry is a forced exit, not a graceful
                    # wave — the SLO surface tracks the ratio
                    metrics.incr("nomad.drain.force_stops")
                jobs_touched[(a.namespace, a.job_id)] = job
        else:
            # Wave scheduling per (job, group) — watch_jobs.go
            # handleTaskGroup: numToDrain = healthy − (count − max_parallel)
            # where healthy counts serving allocs (incl. unmarked ones on
            # draining nodes) but NOT yet-unhealthy replacements, so a new
            # wave starts only as replacements come up.
            by_group: dict[tuple[str, str, str], list] = {}
            for a, job in remaining:
                by_group.setdefault((a.namespace, a.job_id, a.task_group), []).append(
                    (a, job)
                )
            for (ns, job_id, tg_name), pairs in by_group.items():
                job = pairs[0][1]
                if job is None:
                    # purged job: nothing reconciles these allocs via
                    # normal paths; drain them in one wave (the eval's
                    # job-is-None branch stops everything)
                    for a, _ in pairs:
                        if not a.desired_transition.migrate:
                            transitions[a.id] = DesiredTransition(migrate=True)
                            metrics.incr("nomad.drain.migrated")
                    jobs_touched[(ns, job_id)] = None
                    continue
                tg = job.lookup_task_group(tg_name)
                max_parallel = (
                    tg.migrate.max_parallel
                    if tg is not None and tg.migrate is not None
                    else 1
                )
                count = tg.count if tg is not None else len(pairs)
                healthy = 0
                for ja in store.allocs_by_job(ns, job_id):
                    if ja.task_group != tg_name or ja.terminal_status():
                        continue
                    if ja.desired_transition.migrate:
                        continue  # marked: on its way out
                    if ja.node_id == node.id or self._alloc_healthy(ja):
                        healthy += 1
                num_to_mark = healthy - (count - max_parallel)
                for a, _ in pairs:
                    if num_to_mark <= 0:
                        break
                    if a.desired_transition.migrate:
                        continue
                    transitions[a.id] = DesiredTransition(migrate=True)
                    metrics.incr("nomad.drain.migrated")
                    jobs_touched[(ns, job_id)] = job
                    num_to_mark -= 1

        if any(a.desired_transition.migrate for a in allocs):
            found("sched")
        else:
            found("drainer" if transitions else "client")
        if not transitions:
            return
        evals = [
            Evaluation(
                namespace=ns,
                priority=job.priority if job is not None else 50,
                type=job.type if job is not None else "service",
                triggered_by=TRIGGER_NODE_DRAIN,
                job_id=job_id,
                node_id=node.id,
                status=EVAL_STATUS_PENDING,
            )
            for (ns, job_id), job in jobs_touched.items()
        ]

        self.server.raft_apply(
            MsgType.ALLOC_DESIRED_TRANSITION,
            {"transitions": transitions, "evals": evals},
        )
        if rec is not None:
            rec.turn(time.perf_counter(), "sched")
            rec.waves += 1
            rec.migrated += len(transitions)
            rec.evals += len(evals)
        metrics.incr("nomad.drain.waves")
        seen["marked"] += len(transitions)
        if evals:
            metrics.incr("nomad.drain.evals_created", len(evals))
            seen["evals"] += len(evals)
            evals = self.server._fresh_evals(evals)
            self.server.eval_broker.enqueue_all(
                evals, trace_tags=self._wave_lags(node.id, evals)
            )

    def _wave_lags(self, node_id: str, evals) -> dict:
        """eval id -> the root tags of its trace: the commit that freed
        the job's budget (the oldest client update no eval has answered;
        for a node's first wave the strategy's own commit) -> now, the
        eval about to be enqueued."""
        now = time.perf_counter()
        tags = {}
        with self._lock:
            first = self._drain_at.pop(node_id, None)
            for ev in evals:
                freed = self._freed_at.pop((ev.namespace, ev.job_id), None)
                start = first if first is not None else freed
                if start is not None:
                    tags[ev.id] = {
                        "wave_lag_ms": round((now - start) * 1000.0, 3),
                    }
        return tags

    def _complete(self, node, deadlined: bool,
                  rec: Optional[_DrainRecord] = None) -> None:
        """Drain finished: clear the strategy, stay ineligible
        (drainer.go handleDoneNodeDrains → Node.UpdateDrain with nil),
        and hand the tracer the operation whole."""
        from ..structs import NODE_SCHED_INELIGIBLE

        self.server.raft_apply(
            MsgType.NODE_DRAIN,
            {"node_id": node.id, "drain": None,
             "eligibility": NODE_SCHED_INELIGIBLE},
        )
        cleared_at = time.perf_counter()
        with self._lock:
            # a drain cancelled while this look was under way is gone
            ended = self._drains.pop(node.id, None)
        self.note_drain(cleared_at, node.id, None)
        if rec is not None and ended is rec:
            rec.turn(cleared_at, "ended")
            tracer.add_background(
                "drain", cleared_at - rec.start, start=rec.start,
                tags={
                    "node_id": node.id, "allocs": rec.allocs,
                    "waves": rec.waves, "evals": rec.evals,
                    "migrated": rec.migrated, "deadlined": deadlined,
                    **{f"{k}_ms": round(v * 1000.0, 4)
                       for k, v in rec.spent.items()},
                },
            )
        metrics.incr("nomad.drain.completed")
        self.server._publish(
            "Node",
            "NodeDrainComplete",
            node.id,
            "default",
            {"deadline_reached": deadlined},
        )
        log.info("node %s drain complete (deadlined=%s)", node.id, deadlined)
        # a freed node is prime repacking space — nudge the defrag
        # controller (no-op unless continuous defrag is enabled)
        defrag = getattr(self.server, "defrag", None)
        if defrag is not None:
            defrag.notify_drain_complete()
