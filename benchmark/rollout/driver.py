"""The client of the deployment: ``benchmark/driver.py``'s open loop and
stamps, with another request and a second role.

The request: each arrival registers the next version of the live job that
was updated longest ago (``fifo``: the pre-fill's order, then the order in
which rollouts ended). The registration carries the latency as ``run.py``
takes it: due -> the registration's eval complete and every allocation that
eval placed in the store (a service: the first ``max_parallel`` replaced
and a deployment running; a batch job: all of them replaced). Nothing is
deregistered, and a job whose rollout has not ended is not in ``fifo``: a
job's next rollout cannot start before its last one ended.

The second role: the driver plays the nodes' clients. Blocked on the
store's index as before, it looks at a rolling job when one of the job's
evals has completed, and acknowledges the new version's allocations it has
not yet acknowledged, ``running`` and (inside a deployment) healthy, in one
``update_allocs_from_client`` batch: ``Node.UpdateAlloc`` with the client's
health verdict (``client/allochealth``), ``min_healthy_time`` cut to 0. The
deployment watcher's evals for the later rounds are background work. A
rollout has ended when every allocation of the job is acknowledged on the
new version and, for a service, its deployment reads ``successful``.
"""

from __future__ import annotations

import collections
import copy
import time

from benchmark import driver as base
from benchmark.driver import Request, clock
from benchmark.gpu_preempt.driver import settle
from benchmark.rollout.jobs import versioned

NAMESPACE = "default"


class Rollout:
    __slots__ = ("spec", "request", "version", "acked", "evals_done",
                 "finished")

    def __init__(self, spec: dict, request: Request, version: int):
        self.spec = spec
        self.request = request
        self.version = version  # the program's number for this version
        self.acked: set = set()
        self.evals_done = -1  # terminal evals of the job at the last look
        self.finished = None

    def first_round(self) -> int:
        """Allocations the registration's own eval replaces."""
        update = self.spec.get("update")
        count = self.spec["count"]
        return min(int(update["max_parallel"]), count) if update else count


class Driver(base.Driver):
    def __init__(self, server, specs, make_job, live_jobs, steady_jobs,
                 patient: bool = False, traffic=None, seed=None):
        super().__init__(
            server, specs, make_job, [], steady_jobs, patient=patient
        )
        # plain specs of the live jobs, the one updated longest ago first
        self.fifo = collections.deque(live_jobs)
        self._live_allocs = sum(s["count"] for s in self.fifo)
        self.rolling: dict = {}  # job id -> Rollout
        self.finished: list = []
        # the longest ``drain`` waits; set-up lowers it: a program whose
        # rollouts never end (one that ignores the clients' verdicts)
        # should fail there soon
        self.patience_s = 60.0

    # -- the request -------------------------------------------------------
    def send_register(self, due: float) -> Request:
        if not self.fifo:
            raise RuntimeError(
                "every live job is mid-rollout: no job's next rollout "
                "starts before its last one ended"
            )
        spec = self.fifo.popleft()
        assert spec["id"] not in self.rolling
        nxt = versioned(spec, spec["version"] + 1)
        req = Request("register", nxt["id"], nxt["count"], due)
        self._send(req, self.make_job(nxt))
        if req.eval_id is None:  # refused: the job keeps its version
            self.fifo.appendleft(spec)
            return req
        job = self.server.store.job_by_id(NAMESPACE, nxt["id"])
        self.rolling[nxt["id"]] = Rollout(nxt, req, job.version)
        return req

    def send_deregister(self, due: float):
        return None  # a rollout takes nothing away

    # -- watching, and the clients' part -------------------------------------
    def collect(self) -> list:
        store = self.server.store
        done = []
        for eval_id, req in list(self.pending.items()):
            ev = store.eval_by_id(eval_id)
            if ev is None or ev.status not in base._TERMINAL:
                continue
            live = [
                a for a in store.allocs_by_job(NAMESPACE, req.job_id)
                if not a.terminal_status()
            ]
            req.done = clock()
            req.placed = sum(1 for a in live if a.eval_id == eval_id)
            want = self.rolling[req.job_id].first_round()
            req.ok = (
                ev.status == "complete" and len(live) == req.count
                and req.placed == want
            )
            if not req.ok:
                req.note = (
                    f"eval {ev.status}, {len(live)} live allocs, "
                    f"{req.placed} of {want} replaced"
                )
            self._live_allocs += len(live) - req.count
            del self.pending[eval_id]
            done.append(req)
        if done:
            self.live_alloc_track.append((done[-1].done, self._live_allocs))
        self._play_clients()
        return done

    def _play_clients(self) -> None:
        from nomad_tpu.structs.deployment import AllocDeploymentStatus

        store = self.server.store
        for job_id, r in list(self.rolling.items()):
            count = r.spec["count"]
            evals_done = sum(
                1 for e in store.evals_by_job(NAMESPACE, job_id)
                if e.status in base._TERMINAL
            )
            if evals_done != r.evals_done:
                r.evals_done = evals_done
                updates = []
                for a in store.allocs_by_job(NAMESPACE, job_id):
                    if (
                        a.terminal_status() or a.id in r.acked
                        or a.job_version != r.version
                    ):
                        continue
                    u = copy.copy(a)
                    u.client_status = "running"
                    if a.deployment_id:
                        u.deployment_status = AllocDeploymentStatus(
                            healthy=True, timestamp_unix=time.time()
                        )
                    updates.append(u)
                    r.acked.add(a.id)
                if updates:
                    self.server.update_allocs_from_client(updates)
            if len(r.acked) < count:
                continue
            if r.spec.get("update"):
                d = store.latest_deployment_by_job(NAMESPACE, job_id)
                if (
                    d is None or d.job_version != r.version
                    or d.status != "successful"
                ):
                    continue
            r.finished = clock()
            del self.rolling[job_id]
            self.finished.append(r)
            self.fifo.append(r.spec)

    def drain(self, timeout: float) -> None:
        """Wait for the requests in flight and for the rollouts under way
        (the clients go on acknowledging), then for a quiet broker."""
        store = self.server.store
        deadline = clock() + min(timeout, self.patience_s)
        while (self.pending or self.rolling) and clock() < deadline:
            seen = store.latest_index
            if not self.collect():
                self._wait(seen, 0.25)
        for req in self.pending.values():
            req.ok, req.note = False, "never completed"
        self.pending.clear()
        settle(self.server, timeout=max(1.0, deadline - clock()))
