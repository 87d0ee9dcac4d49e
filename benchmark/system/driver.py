"""The client of the deployment: ``benchmark/driver.py``'s open loop and
stamps, with another request.

The request: each arrival registers the next version of the node agent
that was updated longest ago (``fifo``: set-up's order, then the order in
which updates ended). One task ``env`` value differs: a destructive
update at the same ask, with no ``update`` block, so the one eval stops the
old allocation and places the new one on every node. The registration
carries the latency as ``run.py`` takes it: due -> the eval complete and
an allocation of the new version live on every node of the fleet, as a
client blocked on the store's index sees it. An update not done
``give_up_s`` after it was due is given up. Nothing is deregistered, and an
agent whose update has not ended is not in ``fifo``: an arrival due while
every agent is mid-update waits, its latency counting from its due time,
until an update ends and frees an agent; one still waiting when the run
ends is a failed request.
"""

from __future__ import annotations

import collections

from benchmark import driver as base
from benchmark.driver import Request, clock
from benchmark.system.jobs import versioned

NAMESPACE = "default"


class Update(Request):
    """A registration with what the judge needs besides: the version sent
    and the store's index at which the client saw it done."""

    __slots__ = ("version", "done_index")

    def __init__(self, kind, job_id, count, due, version=0):
        super().__init__(kind, job_id, count, due)
        self.version = version
        self.done_index = None


class Driver(base.Driver):
    def __init__(self, server, specs, make_job, live_agents, steady_jobs,
                 patient: bool = False, traffic=None, seed=None):
        super().__init__(
            server, specs, make_job, [], steady_jobs, patient=patient
        )
        # plain specs of the agents, the one updated longest ago first
        self.fifo = collections.deque(live_agents)
        self.nodes = len(server.store.nodes())
        self.give_up_s = float((traffic or {}).get("give_up_s", 30.0))
        self.updating: dict = {}  # job id -> the spec it is moving to
        self.waiting = collections.deque()  # due times no agent was free for
        self._live_allocs = sum(
            1 for a in server.store.allocs() if not a.terminal_status()
        )

    # -- the request -------------------------------------------------------
    def send_spec(self, spec: dict, due: float) -> Update:
        """Register ``spec`` (an agent at some version) as one request."""
        req = Update("register", spec["id"], self.nodes, due, spec["version"])
        self._send(req, self.make_job(spec))
        if req.eval_id is not None:
            self.updating[spec["id"]] = spec
        return req

    def send_register(self, due: float):
        if not self.fifo:
            self.waiting.append(due)
            return None
        spec = self.fifo.popleft()
        req = self.send_spec(versioned(spec, spec["version"] + 1), due)
        if req.eval_id is None:  # refused: the agent keeps its version
            self.fifo.appendleft(spec)
        return req

    def send_deregister(self, due: float):
        return None  # an update takes nothing away

    # -- watching ------------------------------------------------------------
    def collect(self) -> list:
        store = self.server.store
        done = []
        for eval_id, req in list(self.pending.items()):
            ev = store.eval_by_id(eval_id)
            if ev is None or ev.status not in base._TERMINAL:
                if clock() - req.due > self.give_up_s:
                    req.done, req.ok = clock(), False
                    req.note = f"given up after {self.give_up_s:g} s"
                    del self.pending[eval_id]
                    done.append(req)
                continue
            index = store.latest_index
            live = [
                a for a in store.allocs_by_job(NAMESPACE, req.job_id)
                if not a.terminal_status()
            ]
            req.done, req.done_index = clock(), index
            req.placed = sum(1 for a in live if a.job_version == req.version)
            req.ok = ev.status == "complete" and req.placed == self.nodes
            if not req.ok:
                req.note = (
                    f"eval {ev.status}, {req.placed} of {self.nodes} nodes "
                    "on the new version"
                )
            spec = self.updating.pop(req.job_id)
            if req.ok:
                self.fifo.append(spec)
            self._live_allocs += len(live) - (
                self.nodes if spec["version"] else 0
            )
            del self.pending[eval_id]
            done.append(req)
        if done:
            self.live_alloc_track.append((done[-1].done, self._live_allocs))
        while self.waiting and self.fifo:
            self.send_register(self.waiting.popleft())
        return done

    def drain(self, timeout: float) -> None:
        super().drain(timeout)
        while self.waiting:
            req = Update("register", "", self.nodes, self.waiting.popleft())
            req.done, req.ok = base.clock(), False
            req.note = "no agent free: every agent mid-update"
            self.requests.append(req)
