"""SystemScheduler — one allocation of each task group on every eligible
node (system / sysbatch jobs).

Reference: scheduler/scheduler_system.go (Process, computeJobAllocs,
computePlacements) and scheduler/util.go (diffSystemAllocs /
diffSystemAllocsForNode, evictAndPlace, inplaceUpdate, tasksUpdated). A
pass writes the phases the generic pass writes:

- ``prepare``: the tensors (``flatten``), a group ask each, then the diff
  (``system.diff``) of every allocation against the job and the eligible
  nodes: *place* on an eligible node without the group; *update* an
  allocation of an older job version, destructive or in place as
  ``reconcile.tasks_updated`` says (destructive: stopped here and placed
  anew on its node in the same plan; in place: ``updated_in_place``);
  *ignore* one that is current; *stop* where the job is stopped or the
  node is no longer eligible; *migrate* an allocation marked to leave;
  *lost* on a down node. Then the plan's stops come off the pass's usage
  (``plan_stops``): a replacement is fitted and scored on its node with
  the allocation it replaces gone.
- ``invoke_scheduler``: one ``score_matrix_kernel`` call a group over the
  whole fleet — every eligible node that fits gets its placement, no
  greedy scan (allocations of a system job never stack on one node) —
  and ``explain``'s two steps (the group's candidates, then the rows to
  place).
- ``build_plan``: the walk over the rows to place (``system.place``):
  preemption where a node does not fit, devices, the allocations.

``evictAndPlace``'s limit: a group whose ``update`` block is rolling
replaces at most ``max_parallel`` allocations destructively a pass, and
the eval leaves a follow-up after the stagger
(``Evaluation.next_rolling_eval``); without one every replacement is in
the one eval. The departure: the limit is the group's ``update`` (this
program's job has no job-level block) and counts destructive updates only,
as ``evictAndPlace`` does for updates; migrations and lost allocations keep
their own branches, outside it.
"""

from __future__ import annotations

import copy
import time

import numpy as np

from ..device import flatten_group_ask
from ..device.cache import DeviceStateCache
from ..device.flatten import proposed_job_counts
from ..obs.trace import global_tracer as tracer
from ..utils.metrics import global_metrics
from .algorithms import score_group
from ..structs import (
    ALLOC_DESIRED_RUN,
    Allocation,
    AllocMetric,
    ComparableResources,
    EVAL_STATUS_COMPLETE,
    Evaluation,
    new_id,
)
from .generic import free_plan_stops, tainted_nodes
from .reconcile import REASON_ALLOC_NOT_NEEDED, tasks_updated, updated_in_place
from .scheduler import Planner, register_scheduler

MAX_SYSTEM_SCHEDULE_ATTEMPTS = 5  # scheduler_system.go:12-21
ALLOC_UPDATING = "alloc is being updated due to job update"  # util.go
DIFF_CATEGORIES = (
    "place", "destructive", "inplace", "ignore", "stop", "migrate", "lost",
)


@register_scheduler("system")
@register_scheduler("sysbatch")
class SystemScheduler:
    def __init__(
        self,
        snapshot,
        planner: Planner,
        *,
        sysbatch: bool = False,
        cache=None,
        overlay=None,  # accepted for factory uniformity; system placement
        # is per-node (no greedy packing), so the overlay isn't consulted
        node_filter=None,  # likewise unused: a system job runs on EVERY
        # eligible node, so lane restriction would be semantically wrong
        clock=None,
    ):
        self.snapshot = snapshot
        # injectable clock, as the generic scheduler's: the follow-up
        # eval's wait is stamped from it
        self.clock = clock if clock is not None else time.time
        self.planner = planner
        self.sysbatch = sysbatch
        self.cache = cache if cache is not None else DeviceStateCache()
        self.eval = None
        self.job = None
        self.plan = None
        self.failed_tg_allocs: dict[str, AllocMetric] = {}
        self.explanations: dict[str, object] = {}  # tg → PlacementExplanation
        self.next_eval = None
        # seconds the follow-up waits where a rolling limit was reached
        self._stagger_s = None

    def process(self, evaluation: Evaluation) -> None:
        self.eval = evaluation
        self.sysbatch = self.sysbatch or evaluation.type == "sysbatch"
        self._explain = bool(
            getattr(
                self.snapshot.scheduler_config(),
                "placement_explanations",
                True,
            )
        )
        for _ in range(MAX_SYSTEM_SCHEDULE_ATTEMPTS):
            if self._process_once():
                break
        if self._stagger_s is not None and self.next_eval is None:
            # the rolling limit was reached: the eval that goes on after
            # the stagger (scheduler_system.go Process: NextRollingEval)
            self.next_eval = evaluation.next_rolling_eval(
                self._stagger_s, self.clock()
            )
            self.planner.create_eval(self.next_eval)
        if self.explanations and not evaluation.annotate_plan:
            from ..obs.explain import explanation_to_dict
            from ..obs.recorder import flight_recorder

            flight_recorder.record_explanation(
                evaluation.id,
                {
                    "eval_id": evaluation.id,
                    "job_id": evaluation.job_id,
                    "namespace": evaluation.namespace,
                    "groups": {
                        tg: explanation_to_dict(ex)
                        for tg, ex in self.explanations.items()
                    },
                },
            )
        updated = copy.copy(evaluation)
        updated.status = EVAL_STATUS_COMPLETE
        updated.failed_tg_allocs = dict(self.failed_tg_allocs)
        if self.next_eval is not None:
            updated.next_eval = self.next_eval.id
        self.planner.update_eval(updated)

    def _process_once(self) -> bool:
        ev = self.eval
        snap = self.snapshot
        self.job = snap.job_by_id(ev.namespace, ev.job_id)
        self.plan = ev.make_plan(self.job)
        self.plan.snapshot_index = getattr(snap, "index", 0)
        self._replaced: set = set()  # ids of the allocations replaced
        self._inplace: set = set()  # ids updated in place
        groups: list = []  # (task group, its ask)
        with tracer.phase("prepare"):
            existing = snap.allocs_by_job(ev.namespace, ev.job_id)
            ct = None
            if self.job is not None and not self.job.stopped():
                ct = self.cache.tensors(snap)
                groups = [
                    (tg, flatten_group_ask(
                        ct, snap, self.job, tg, 1, nodes_sorted=ct.nodes
                    ))
                    for tg in self.job.task_groups
                ]
            with tracer.span("system.diff") as sp:
                counts, to_place = self._diff(existing, ct, groups)
                if sp is not None:
                    sp.tags.update(counts)
            if any(rows.size for rows in to_place.values()):
                free_plan_stops(ct, self.plan)
                # the job's own counts as the plan proposes them: the
                # allocations it replaces are gone from their nodes
                job_counts = proposed_job_counts(ct, snap, self.job, self.plan)
                for _tg, ga in groups:
                    ga.job_counts = job_counts
        groups = [(tg, ga) for tg, ga in groups if to_place[tg.name].size]
        if not groups:
            return self._submit()

        scored = []
        with tracer.phase(
            "invoke_scheduler",
            timer="nomad.worker.invoke_scheduler",
            tags={"lanes": len(groups), "explain": self._explain},
        ):
            # breakdowns are derived against the usage the finals were
            # scored with, not the usage the walk below adds to
            used_at_score = np.asarray(ct.used).copy() if self._explain else None
            for tg, ga in groups:
                out = score_group(
                    ct, ga, float(max(tg.count, 1)), explain=self._explain
                )
                finals, fits = out[0], out[1]
                ex = out[2] if self._explain else None
                metas = {}
                if ex is not None:
                    self.explanations[tg.name] = ex
                    metas = self._score_metas(
                        ct, ga, used_at_score, to_place[tg.name], tg
                    )
                scored.append((tg, ga, finals, fits, ex, metas))
        with tracer.phase("build_plan"):
            for tg, ga, finals, fits, ex, metas in scored:
                self._place(ct, tg, ga, finals, fits, ex, metas,
                            to_place[tg.name])
        return self._submit()

    # -- the diff ------------------------------------------------------------
    def _diff(self, existing, ct, groups) -> tuple[dict, dict]:
        """diffSystemAllocs over the job's allocations: every stop, in-place
        update and destructive eviction goes into the plan here. Returns the
        counts by category and, per group, the node rows to place on (an
        eligible node that holds no live allocation of the group, or whose
        allocation this plan replaces)."""
        plan, job = self.plan, self.job
        counts = dict.fromkeys(DIFF_CATEGORIES, 0)
        tainted = tainted_nodes(self.snapshot, existing)
        stopped_job = job is None or job.stopped()
        eligible = {
            tg.name: np.nonzero(ga.eligible[: ct.num_nodes])[0]
            for tg, ga in groups
        }
        eligible_ids = {
            name: {ct.node_ids[r] for r in rows}
            for name, rows in eligible.items()
        }
        held: set = set()  # (node id, group) that keep what they hold
        destructive: dict = {}  # group -> old allocations to replace
        changed_by_version: dict = {}  # (group, version) -> tasks_updated
        for a in existing:
            key = (a.node_id, a.task_group)
            if a.terminal_status():
                # a completed sysbatch alloc satisfies its node permanently
                # (the batch don't-rerun rule, scheduler_system.go sysbatch)
                if self.sysbatch and a.client_status == "complete":
                    held.add(key)
                continue
            node = tainted.get(a.node_id)
            if node is not None and node.terminal_status():
                plan.append_lost_alloc(a)
                counts["lost"] += 1
            elif node is not None and a.desired_transition.migrate:
                # draining: wait for the NodeDrainer's wave mark
                # (reconcile_util.go filterByTainted — system allocs
                # leave a draining node only when marked migrating)
                plan.append_stopped_alloc(
                    a, "alloc stopped because node is draining"
                )
                counts["migrate"] += 1
            elif stopped_job:
                plan.append_stopped_alloc(a, REASON_ALLOC_NOT_NEEDED)
                counts["stop"] += 1
            elif node is not None:
                # a draining node keeps what it holds until marked
                held.add(key)
                counts["ignore"] += 1
            elif a.desired_transition.migrate:
                # migrate mark on a HEALTHY node: `alloc stop` — the node
                # is still a target, so the alloc is replaced where it is
                plan.append_stopped_alloc(a, "alloc is stopped by user")
                counts["migrate"] += 1
            elif a.node_id not in eligible_ids.get(a.task_group, ()):
                # the node is no longer a target (a constraint changed,
                # the group is gone)
                plan.append_stopped_alloc(a, REASON_ALLOC_NOT_NEEDED)
                counts["stop"] += 1
            elif a.job_version == job.version:
                held.add(key)
                counts["ignore"] += 1
            else:
                vkey = (a.task_group, a.job_version)
                if vkey not in changed_by_version:
                    changed_by_version[vkey] = tasks_updated(
                        a.job if a.job is not None else job, job,
                        a.task_group,
                    )
                held.add(key)
                if changed_by_version[vkey]:
                    destructive.setdefault(a.task_group, []).append(a)
                else:
                    plan.append_alloc(updated_in_place(a, job))
                    self._inplace.add(a.id)
                    counts["inplace"] += 1
        for name, olds in destructive.items():
            # evictAndPlace: by node, at most the rolling limit a pass
            olds.sort(key=lambda a: a.node_id)
            u = job.lookup_task_group(name).update
            limit = len(olds)
            if u is not None and u.rolling():
                limit = u.max_parallel
                if len(olds) > limit:
                    self._stagger_s = min(
                        u.stagger_s, self._stagger_s or u.stagger_s
                    )
            for a in olds[:limit]:
                plan.append_stopped_alloc(a, ALLOC_UPDATING)
                held.discard((a.node_id, a.task_group))
                self._replaced.add(a.id)
            counts["destructive"] += min(limit, len(olds))
            counts["ignore"] += max(len(olds) - limit, 0)
        to_place = {}
        for name, rows in eligible.items():
            keep = np.fromiter(
                ((ct.node_ids[r], name) not in held for r in rows),
                dtype=bool, count=rows.size,
            )
            to_place[name] = rows[keep]
            counts["place"] += int(keep.sum())
        counts["place"] -= counts["destructive"]
        return counts, to_place

    # -- placement -------------------------------------------------------------
    def _score_metas(self, ct, ga, used_at_score, rows, tg) -> dict:
        """``explain``'s second step: each row to place's breakdown, on the
        usage the finals were scored with."""
        from ..obs.explain import score_meta_for_row

        with tracer.span(
            "explain", tags={"step": "final", "instances": int(rows.size)}
        ):
            return {
                int(row): score_meta_for_row(
                    ct, ga, used_at_score, int(row),
                    desired_total=float(max(tg.count, 1)),
                )
                for row in rows
            }

    def _place(self, ct, tg, ga, finals, fits_np, ex, metas, rows) -> None:
        """The walk over the rows to place (``system.place``): one
        allocation a node, a victim set where it does not fit."""
        job, ev = self.job, self.eval
        ask_res = tg.combined_resources()
        comparable = ComparableResources(
            cpu=ask_res.cpu,
            memory_mb=ask_res.memory_mb,
            disk_mb=ask_res.disk_mb,
            bandwidth_mbits=ask_res.bandwidth_mbits(),
        )
        placed = failed = 0
        with tracer.span("system.place") as sp:
            for row in rows:
                node_id = ct.node_ids[row]
                preempted_ids: list[str] = []
                if not fits_np[row]:
                    preempted_ids = self._try_preempt_node(ct, tg, row, ga.ask)
                    if not preempted_ids:
                        m = self._fail_metric(node_id, "resources", ex)
                        self._record_failure(tg.name, m)
                        failed += 1
                        continue
                if (
                    not preempted_ids
                    and ga.slot_caps is not None
                    and ga.slot_caps[row] < 1
                ):
                    # device instances exist but are all held — system
                    # preemption may free them (PreemptForDevice)
                    preempted_ids = self._try_preempt_node(ct, tg, row, ga.ask)
                    if not preempted_ids:
                        m = self._fail_metric(node_id, "devices", ex)
                        self._record_failure(tg.name, m)
                        failed += 1
                        continue
                alloc_id = new_id()
                # victims enter the plan BEFORE device assignment so
                # collect_in_use sees their instances as freed; a failed
                # assignment rolls the eviction back (the generic path's
                # dev_ok contract, generic.py _preempt_on_row)
                victim_total = None
                for vid in preempted_ids:
                    victim = self.snapshot.alloc_by_id(vid)
                    if victim is not None:
                        self.plan.append_preempted_alloc(victim, alloc_id)
                        vec = victim.comparable_resources().to_vector()
                        victim_total = (
                            vec if victim_total is None else victim_total + vec
                        )
                devices, dev_ok = self._assign_devices(tg, node_id)
                if not dev_ok:
                    from .device import rollback_plan_preemptions

                    rollback_plan_preemptions(
                        self.plan, node_id, preempted_ids
                    )
                    m = self._fail_metric(node_id, "devices", ex)
                    self._record_failure(tg.name, m)
                    failed += 1
                    continue
                metric = AllocMetric(nodes_evaluated=1)
                metric.scores[f"{node_id}.score"] = float(finals[row])
                if ex is not None:
                    metric.score_meta = [metas[int(row)]]
                    ex.placed_nodes.append(node_id)
                alloc = Allocation(
                    id=alloc_id,
                    namespace=job.namespace,
                    eval_id=ev.id,
                    name=f"{job.id}.{tg.name}[0]",
                    node_id=node_id,
                    job_id=job.id,
                    job=job,
                    job_version=job.version,
                    task_group=tg.name,
                    resources=comparable.copy(),
                    desired_status=ALLOC_DESIRED_RUN,
                    client_status="pending",
                    metrics=metric,
                    allocated_devices=devices or [],
                )
                if preempted_ids:
                    alloc.preempted_allocations = list(preempted_ids)
                    if victim_total is not None:
                        ct.used[row] -= victim_total
                # every placement debits the (private) usage overlay so
                # later task groups' fit checks and victim selection see
                # this plan's own load
                ct.used[row] += ga.ask
                self.plan.append_alloc(alloc)
                placed += 1
            if sp is not None:
                sp.tags.update(nodes=int(rows.size), placed=placed,
                               failed=failed)

    def _try_preempt_node(self, ct, tg, row, ask_vec) -> list[str]:
        """System-job preemption on one node (the node IS the target for
        system placements — no search needed). Enabled by default per
        SchedulerConfiguration.PreemptionConfig.SystemSchedulerEnabled
        (nomad/structs/operator.go:164-169, scheduler_system.go:27);
        victim selection is the reference-exact host greedy
        (preempt_host.select_victims: maxParallel, ports, devices)."""
        cfg = self.snapshot.scheduler_config()
        if not cfg.preemption_system_enabled or self.job is None:
            return []
        from ..device.preempt import PREEMPTION_PRIORITY_DELTA
        from .preempt_host import select_victims

        if self.job.priority < PREEMPTION_PRIORITY_DELTA:
            return []
        already = {
            a.id
            for allocs in self.plan.node_preemptions.values()
            for a in allocs
        }
        ids = select_victims(
            ct,
            self.snapshot,
            self.job,
            tg,
            ask_vec,
            row,
            plan=self.plan,
            exclude_ids=already,
        )
        return ids or []

    def _assign_devices(self, tg, node_id):
        from .device import assign_devices_for_plan

        return assign_devices_for_plan(self.snapshot, self.plan, tg, node_id)

    @staticmethod
    def _fail_metric(node_id: str, dim: str, ex) -> AllocMetric:
        m = AllocMetric(nodes_evaluated=1)
        m.exhausted_node(node_id, dim)
        if ex is not None:
            # fleet-wide rejection histogram rides the (coalesced) failed
            # metric so `eval status` explains the whole group, not just
            # the first failing node
            m.rejections = dict(ex.rejections)
        return m

    def _record_failure(self, tg_name: str, metric: AllocMetric) -> None:
        existing = self.failed_tg_allocs.get(tg_name)
        if existing is not None:
            existing.coalesced_failures += 1
        else:
            self.failed_tg_allocs[tg_name] = metric

    def _submit(self) -> bool:
        if self.plan.is_no_op():
            return True
        result, new_snap = self.planner.submit_plan(self.plan)
        if new_snap is not None:
            self.snapshot = new_snap
        self._count_committed(result)
        full, _, _ = result.full_commit(self.plan)
        return full

    def _count_committed(self, result) -> None:
        """What of the plan landed: fresh placements, replacements,
        in-place updates, other stops."""
        placed = inplace = replaced = stopped = 0
        for allocs in result.node_allocation.values():
            for a in allocs:
                if a.id in self._inplace:
                    inplace += 1
                else:
                    placed += 1
        for allocs in result.node_update.values():
            for a in allocs:
                if a.id in self._replaced:
                    replaced += 1
                else:
                    stopped += 1
        for name, n in (
            ("placed", placed), ("replaced", replaced),
            ("inplace", inplace), ("stopped", stopped),
        ):
            if n:
                global_metrics.incr(f"nomad.system.{name}", n)
