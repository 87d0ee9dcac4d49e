"""The client of the deployment: ``benchmark/driver.py``'s open loop and
stamps, with another request and the operator's part.

The arrival: the k-th arrival takes rack ``(phase + stride * k) mod
racks`` down (the traffic file's ``failure`` block; the seed picks the
phase; set-up's warm failures take the first ``k``, so no rack fails twice
in a run): the rack's timers run out in one sweep, ``NodeHeartbeater.expire``
on its nodes in node order. The request: one a job that held a live
allocation on the rack at the arrival, under the harness's word for the
request that is timed (``kind`` "register"): due -> every allocation the
job held there is stopped with client status ``lost`` and the job is back
at its count with each replacement in the store, as a client blocked on the
store's index sees it. ``job_id`` is the job's id and ``count`` the
allocations it held there. A request not done ``give_up_s`` after it was
sent is given up and fails.

The operator's part: ``ready_after_s`` after a failure's last request ended,
the rack's nodes come back (``Server.update_node_status`` ready), empty.

What the judge cannot read from the store once the run is over the driver
reads when it happens and keeps on the failure: the index at which each
node was marked down and at which it was marked ready again; on a request,
the store index its client had seen when it found the job done.
"""

from __future__ import annotations

import collections

from benchmark import driver as base
from benchmark.driver import Request, clock
from benchmark.gen import start_phase
from benchmark.gpu_preempt.driver import settle

READY = "ready"


class Failure:
    """One arrival: a rack down, its nodes, their down and ready indices."""

    __slots__ = ("k", "rack", "rows", "node_ids", "due", "down_index",
                 "ready_index", "requests")

    def __init__(self, k: int, rack: int, rows: list, node_ids: list,
                 due: float):
        self.k = k
        self.rack = rack
        self.rows = rows
        self.node_ids = node_ids
        self.due = due
        self.down_index: dict = {}  # node row -> index it was marked down at
        self.ready_index: dict = {}  # node row -> index it was marked ready at
        self.requests: list = []


class LossRequest(Request):
    __slots__ = ("failure", "held", "done_index")

    def __init__(self, job_id: str, failure: Failure, held: list,
                 due: float):
        super().__init__("register", job_id, len(held), due)
        self.failure = failure
        self.held = held  # ids of the job's allocations on the rack
        self.done_index = 0


def rack_order(racks: int, rule: dict, seed: int):
    """Endless racks in the traffic file's order: ``phase + stride * k``
    modulo the racks."""
    stride = int(rule["stride"])
    phase = start_phase(seed)
    k = 0
    while True:
        yield (phase + stride * k) % racks
        k += 1


class Driver(base.Driver):
    def __init__(self, server, specs, make_job, live, steady_jobs,
                 patient: bool = False, traffic=None, seed=None):
        super().__init__(server, specs, make_job, [], steady_jobs,
                         patient=patient)
        self.rule = traffic["failure"]
        self.ready_after_s = float(self.rule["ready_after_s"])
        self.give_up_s = float(self.rule["give_up_s"])
        # the fleet's nodes by row (``gen.fleet.fleet_node_id``) and rack
        self.node_ids = sorted(n.id for n in server.store.nodes())
        self.racks = int(live["racks"])
        self.order = rack_order(self.racks, self.rule, seed)
        for _ in range(int(live["failures_sent"])):
            next(self.order)  # set-up's warm failures
        self._live_allocs = int(live["live_allocs"])
        self.count_of = {
            j.id: sum(tg.count for tg in j.task_groups)
            for j in server.store.jobs()
        }
        self.failures: list = []
        # (job id, failure) -> [request, held allocation ids still waited
        # for, ``away`` when sent]; a job hit again before it recovered has
        # a request for each failure
        self.in_flight: dict = {}
        # seconds this thread spent in the harness's ``on_open`` and
        # ``on_close`` (starting and stopping the profiler takes seconds):
        # they do not count against a request's ``give_up_s``
        self.away = 0.0
        self.returning = collections.deque()  # (when, failure)

    # -- the arrival ---------------------------------------------------------
    def send_register(self, due: float) -> Failure:
        store = self.server.store
        rack = next(self.order)
        rows = list(range(rack, len(self.node_ids), self.racks))
        node_ids = [self.node_ids[r] for r in rows]
        failure = Failure(len(self.failures), rack, rows, node_ids, due)
        held: dict = {}
        for node_id in node_ids:
            for a in store.allocs_by_node(node_id):
                if not a.terminal_status():
                    held.setdefault(a.job_id, []).append(a.id)
        sent = clock()
        for job_id, ids in held.items():
            req = LossRequest(job_id, failure, ids, due)
            req.sent = sent
            self.requests.append(req)
            failure.requests.append(req)
            self.in_flight[(job_id, failure.k)] = [req, list(ids), self.away]
        self.failures.append(failure)
        # the fleet's live allocations by accounting: the rack's go now,
        # each job's replacements come back as its request is done
        self._live_allocs -= sum(map(len, held.values()))
        self.live_alloc_track.append((sent, self._live_allocs))
        self.server.heartbeater.expire(node_ids)
        for row, node_id in zip(rows, node_ids):
            failure.down_index[row] = store.node_by_id(node_id).modify_index
        if not failure.requests:
            self.returning.append((clock() + self.ready_after_s, failure))
        return failure

    def send_deregister(self, due: float):
        return None  # a failure takes nothing away

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

    # -- watching, and the operator's part -----------------------------------
    def _recovered(self, waiting: list) -> bool:
        """Drop from ``waiting`` each held allocation that is stopped lost
        with its replacement in the store; one plan of the job stops a
        node's share together, so the first still standing ends the look."""
        store = self.server.store
        while waiting:
            a = store.alloc_by_id(waiting[-1])
            if (
                a is None or not a.terminal_status()
                or a.client_status != "lost" or not a.next_allocation
                or store.alloc_by_id(a.next_allocation) is None
            ):
                return False
            waiting.pop()
        return True

    def collect(self) -> list:
        store = self.server.store
        done = []
        for key, (req, waiting, away) in list(self.in_flight.items()):
            now = clock()
            if self._recovered(waiting) and sum(
                1 for a in store.allocs_by_job("default", req.job_id)
                if not a.terminal_status()
            ) == self.count_of[req.job_id]:
                req.done, req.ok, req.placed = now, True, req.count
                req.done_index = store.latest_index
            elif now - req.sent - (self.away - away) > self.give_up_s:
                req.done, req.ok = now, False
                req.note = (
                    f"given up after {self.give_up_s} s: "
                    f"{len(waiting)} of {req.count} allocations not "
                    "replaced"
                )
            else:
                continue
            del self.in_flight[key]
            # a held allocation is replaced once it is out of ``waiting``
            self._live_allocs += req.count - len(waiting)
            done.append(req)
            failure = req.failure
            if all(r.done is not None for r in failure.requests):
                self.returning.append(
                    (now + self.ready_after_s, failure))
        if done:
            self.live_alloc_track.append((done[-1].done, self._live_allocs))
        while self.returning and self.returning[0][0] <= clock():
            self._return(self.returning.popleft()[1])
        return done

    def _return(self, failure: Failure) -> None:
        store = self.server.store
        for row, node_id in zip(failure.rows, failure.node_ids):
            self.server.update_node_status(node_id, READY)
            failure.ready_index[row] = store.node_by_id(node_id).modify_index

    def drain(self, timeout: float) -> None:
        """Wait for the requests in flight (each is given up in time),
        bring every failed rack back, then wait for a quiet broker."""
        store = self.server.store
        deadline = clock() + timeout
        while (self.in_flight or self.returning) and clock() < deadline:
            seen = store.latest_index
            if not self.collect():
                self._wait(seen, 0.25)
        for req, _waiting, _away in self.in_flight.values():
            req.ok, req.note = False, "never completed"
        self.in_flight.clear()
        for _when, failure in self.returning:
            self._return(failure)
        self.returning.clear()
        settle(self.server, timeout=max(1.0, deadline - clock()))
