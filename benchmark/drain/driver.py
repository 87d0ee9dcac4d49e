"""The client of the deployment: ``benchmark/driver.py``'s open loop and
stamps, with another request and two more roles.

The request: the k-th arrival calls ``Server.update_node_drain(node,
DrainStrategy(deadline_s=3600))`` on the node of index ``(phase + stride *
k) mod n`` (the traffic file's ``drain`` block; the seed picks the phase;
set-up's warm drains take the first ``k``, so no node is drained twice in a
run), whatever the node holds. Its record carries the latency as ``run.py``
takes it, under the harness's word for the request that is timed (``kind``
"register"): due ->
the drainer has cleared the node's strategy and every allocation the node
held at the call is stopped with its replacement in the store. ``job_id``
holds the node's id and ``count`` the allocations it held. A drain that has
not ended ``give_up_s`` after it was sent is given up and fails.

The second role: the driver plays the nodes' clients. Blocked on the
store's index as before, it looks at the allocations a draining node held:
one the server has stopped is acknowledged ``complete`` and its replacement
``running`` (which is what ``health_check = "task_states"`` with
``min_healthy_time`` 0 calls healthy), in one ``update_allocs_from_client``
batch a wave. The third: ``eligible_after_s`` after a drain ended, the
operator's upgrade done, it sets the node eligible again
(``Server.update_node_eligibility``, Nomad's ``Node.UpdateEligibility``: a
program from before that call cannot run the deployment, and
``drain/warm.py`` says so where ``run.py`` imports it).

What the judge cannot read from the store once the run is over the driver
reads when it happens and keeps on the request: the index at which the
store says the node was last written, after the drain was set, when the
strategy was seen cleared and after the node was set eligible; the index at
which each held allocation was stopped (read before the ``complete``
acknowledgement moves its modify index) and at which each replacement was
acknowledged.
"""

from __future__ import annotations

import collections
import copy

from benchmark import driver as base
from benchmark.driver import Request, clock
from benchmark.gen import start_phase
from benchmark.gpu_preempt.driver import settle

ELIGIBLE = "eligible"


class DrainRequest(Request):
    __slots__ = ("node_row", "held", "drain_index", "clear_index",
                 "eligible_index", "stops", "acks")

    def __init__(self, node_id: str, node_row: int, held: list, due: float):
        super().__init__("register", node_id, len(held), due)
        self.node_row = node_row
        self.held = held  # ids of the allocations the node held at the call
        self.drain_index = 0
        self.clear_index = 0
        self.eligible_index = 0
        self.stops: dict = {}  # held allocation -> index it was stopped at
        self.acks: dict = {}  # replacement -> index it was acknowledged at


def node_order(n_nodes: int, rule: dict, seed: int):
    """Endless node rows in the traffic file's order: ``phase_step *
    phase + stride * k`` modulo the fleet."""
    step, stride = int(rule.get("phase_step", 1)), int(rule["stride"])
    phase = step * start_phase(seed)
    k = 0
    while True:
        yield (phase + stride * k) % n_nodes
        k += 1


class Driver(base.Driver):
    def __init__(self, server, specs, make_job, live, steady_jobs,
                 patient: bool = False, traffic=None, seed=None):
        super().__init__(server, specs, make_job, [], steady_jobs,
                         patient=patient)
        self.rule = traffic["drain"]
        self.deadline_s = float(self.rule["deadline_s"])
        self.eligible_after_s = float(self.rule["eligible_after_s"])
        self.give_up_s = float(self.rule["give_up_s"])
        # the fleet's nodes by row (``gen.fleet.fleet_node_id``)
        self.node_ids = sorted(n.id for n in server.store.nodes())
        self.order = node_order(len(self.node_ids), self.rule, seed)
        for _ in range(int(live["drains_sent"])):
            next(self.order)  # set-up's warm drains
        self._live_allocs = int(live["live_allocs"])
        # node id -> [request, ids still waited for, ``away`` when sent]
        self.in_flight: dict = {}
        # seconds this thread spent in the harness's ``on_open`` and
        # ``on_close`` (starting and stopping the profiler takes seconds),
        # during which no client acknowledged anything: they do not count
        # against a drain's ``give_up_s``. Nothing else is forgiven
        self.away = 0.0
        self.returning = collections.deque()  # (when, request)

    # -- the request -------------------------------------------------------
    def send_register(self, due: float) -> DrainRequest:
        from nomad_tpu.structs import DrainStrategy

        store = self.server.store
        row = next(self.order)
        node_id = self.node_ids[row]
        held = [
            a.id for a in store.allocs_by_node(node_id)
            if not a.terminal_status()
        ]
        req = DrainRequest(node_id, row, held, due)
        req.sent = clock()
        self.requests.append(req)
        self.server.update_node_drain(
            node_id, DrainStrategy(deadline_s=self.deadline_s)
        )
        req.drain_index = store.node_by_id(node_id).modify_index
        self.in_flight[node_id] = [req, set(held), self.away]
        return req

    def send_deregister(self, due: float):
        return None  # a drain takes nothing away

    def run_open(self, due_times: list, lead_in_s: float, seconds: float,
                 on_open, on_close) -> dict:
        def timed(callback):
            def call():
                t0 = clock()
                callback()
                self.away += clock() - t0
            return call

        return super().run_open(
            due_times, lead_in_s, seconds, timed(on_open), timed(on_close)
        )

    # -- watching, the clients' part and the operator's ----------------------
    def collect(self) -> list:
        store = self.server.store
        done = []
        for node_id, (req, waiting, away) in list(self.in_flight.items()):
            self._play_clients(req, waiting)
            node = store.node_by_id(node_id)
            now = clock()
            if node.drain is None and not waiting:
                req.clear_index = node.modify_index
                req.done, req.ok, req.placed = now, True, len(req.acks)
                self.returning.append((now + self.eligible_after_s, req))
            elif now - req.sent - (self.away - away) > self.give_up_s:
                req.done, req.ok = now, False
                req.note = (
                    f"given up after {self.give_up_s} s: "
                    f"{len(waiting)} of {req.count} allocations not "
                    f"replaced, strategy "
                    f"{'cleared' if node.drain is None else 'set'}"
                )
            else:
                continue
            del self.in_flight[node_id]
            self._live_allocs += len(req.acks) - len(req.stops)
            done.append(req)
        if done:
            self.live_alloc_track.append((done[-1].done, self._live_allocs))
        while self.returning and self.returning[0][0] <= clock():
            self._return(self.returning.popleft()[1])
        return done

    def _play_clients(self, req: DrainRequest, waiting: set) -> None:
        store = self.server.store
        updates, replaced = [], []
        for alloc_id in list(waiting):
            a = store.alloc_by_id(alloc_id)
            if a is None or a.desired_status == "run":
                continue  # waits for its wave
            if alloc_id not in req.stops:
                # the plan's commit: nothing else has written it since
                req.stops[alloc_id] = a.modify_index
                u = copy.copy(a)
                u.client_status = "complete"
                updates.append(u)
            r = store.alloc_by_id(a.next_allocation) if a.next_allocation else None
            if r is None:
                continue  # stopped, its replacement not in the store
            waiting.discard(alloc_id)
            if r.client_status == "pending" and not r.terminal_status():
                u = copy.copy(r)
                u.client_status = "running"
                updates.append(u)
                replaced.append(r.id)
        if updates:
            self.server.update_allocs_from_client(updates)
            for rid in replaced:
                req.acks[rid] = store.alloc_by_id(rid).modify_index

    def _return(self, req: DrainRequest) -> None:
        self.server.update_node_eligibility(req.job_id, ELIGIBLE)
        req.eligible_index = self.server.store.node_by_id(
            req.job_id).modify_index

    def drain(self, timeout: float) -> None:
        """Wait for the drains in flight (each is given up in time), set
        the drained nodes eligible again, then wait for a quiet broker."""
        store = self.server.store
        deadline = clock() + timeout
        while (self.in_flight or self.returning) and clock() < deadline:
            seen = store.latest_index
            if not self.collect():
                self._wait(seen, 0.25)
        for req, _waiting, _away in self.in_flight.values():
            req.ok, req.note = False, "never completed"
        self.in_flight.clear()
        settle(self.server, timeout=max(1.0, deadline - clock()))
