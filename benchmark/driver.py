"""The client side of a run: one thread that sends, watches and stamps.

It drives ``Server.register_job`` / ``Server.deregister_job`` (the entry
points the HTTP handlers call) and observes completion the way a client of
a blocking query does: it blocks on the store's index
(``store.wait_for_index``) and, on every commit, looks up the evals it is
waiting for. No polling finer than the commit cadence, no
``wait_for_evals`` (which takes the broker lock every 10 ms), no thread per
client: the one thread stands for all clients of a closed loop and for the
generator of an open loop, so the worker shares the interpreter with one
client thread and not with thirty-two.

Two loop kinds, chosen by the traffic file:

- ``closed``: ``in_flight`` clients, each ``register → wait → deregister
  the oldest live job → wait → …``. Clients start alternately with a
  register and a deregister, so every pass carries both kinds from the
  first lead-in second on.
- ``open``: arrivals due at times fixed by ``gen/arrivals.py``; each
  arrival registers a job and, once the configuration's jobs are live,
  deregisters the oldest. Latency counts from the due time.
"""

from __future__ import annotations

import collections
import time

clock = time.perf_counter

_TERMINAL = ("complete", "failed", "canceled")
ADMISSION_PATIENCE_S = 120.0  # how long set-up keeps re-sending


class Request:
    __slots__ = (
        "kind", "job_id", "count", "due", "sent", "done", "eval_id", "ok",
        "placed", "note",
    )

    def __init__(self, kind, job_id, count, due):
        self.kind = kind  # "register" | "deregister"
        self.job_id = job_id
        self.count = count  # allocs asked (register) / to stop (deregister)
        self.due = due
        self.sent = None
        self.done = None
        self.eval_id = None
        self.ok = None
        self.placed = 0
        self.note = ""


class Driver:
    """Sends the cell's traffic to ``server`` and records every request."""

    def __init__(self, server, specs, make_job, live_jobs,
                 steady_jobs: int, patient: bool = False,
                 traffic=None, seed=None):
        # ``traffic`` and ``seed`` are the harness's to every driver; this
        # one's rule (register, deregister the oldest) needs neither
        self.server = server
        self.specs = specs  # iterator of plain job specs
        self.make_job = make_job
        # FIFO of (job_id, count) whose registration completed
        self.live = collections.deque(live_jobs)
        self.steady_jobs = steady_jobs
        self.pending: dict = {}  # eval_id -> Request
        self.requests: list = []
        self.live_alloc_track: list = []  # (t, live allocs by accounting)
        self._live_allocs = sum(c for _j, c in self.live)
        # set-up re-sends a request the admission controller defers
        # (as ``bench.send`` does: a cold compile inside a warm-up pass
        # is a latency spike the controller answers with 429); in the
        # window a refused request is a failed request, not a fast one
        self.patient = patient

    # -- sending -----------------------------------------------------------
    def _send(self, req: Request, job=None) -> None:
        from nomad_tpu.server.admission import AdmissionRejected

        req.sent = clock()
        self.requests.append(req)
        give_up = req.sent + ADMISSION_PATIENCE_S
        while True:
            try:
                if req.kind == "register":
                    ev = self.server.register_job(job)
                else:
                    ev = self.server.deregister_job("default", req.job_id)
                break
            except AdmissionRejected as e:
                if self.patient and clock() < give_up:
                    time.sleep(e.retry_after)
                    continue
                req.done, req.ok = clock(), False
                req.note = f"refused: {e}"
                return
        if ev is None:
            req.done, req.ok, req.note = clock(), False, "no eval returned"
            return
        req.eval_id = ev.id
        self.pending[ev.id] = req

    def send_register(self, due: float) -> Request:
        spec = next(self.specs)
        req = Request("register", spec["id"], spec["count"], due)
        self._send(req, self.make_job(spec))
        return req

    def send_deregister(self, due: float):
        if not self.live:
            return None
        job_id, count = self.live.popleft()
        req = Request("deregister", job_id, count, due)
        self._send(req)
        return req

    # -- watching ----------------------------------------------------------
    def collect(self) -> list:
        """Stamp every pending request whose eval reached a terminal
        status; returns the requests completed by this call."""
        store = self.server.store
        done = []
        for eval_id, req in list(self.pending.items()):
            ev = store.eval_by_id(eval_id)
            if ev is None or ev.status not in _TERMINAL:
                continue
            now = clock()
            live = sum(
                1 for a in store.allocs_by_job("default", req.job_id)
                if not a.terminal_status()
            )
            req.done = now
            if req.kind == "register":
                req.placed = live
                req.ok = ev.status == "complete" and live == req.count
                if req.ok:
                    self.live.append((req.job_id, req.count))
                self._live_allocs += live
            else:
                req.ok = ev.status == "complete" and live == 0
                self._live_allocs -= req.count - live
            if not req.ok:
                req.note = f"eval {ev.status}, {live} live allocs"
            del self.pending[eval_id]
            done.append(req)
        if done:
            self.live_alloc_track.append((done[-1].done, self._live_allocs))
        return done

    def _wait(self, seen_index: int, timeout: float) -> None:
        if timeout > 0:
            self.server.store.wait_for_index(seen_index + 1, timeout=timeout)

    # -- the two loops -----------------------------------------------------
    def run_closed(self, in_flight: int, lead_in_s: float, seconds: float,
                   on_open, on_close) -> dict:
        """Closed loop. The window opens at the first completion at or
        after the lead-in and closes at the first completion at or after
        ``seconds`` later: both edges sit on a commit, so the count of
        allocs and the time between them are taken over the same
        interval, not over a whole number of passes cut by a clock."""
        store = self.server.store
        next_kind = collections.deque()  # what each freed client sends next
        t_begin = clock()
        for i in range(in_flight):
            if i % 2 == 0 or not self.live:
                self.send_register(clock())
            else:
                self.send_deregister(clock())
        t_open = t_close = None
        armed = False  # on_open has run; the next completion opens
        deadline = t_begin + lead_in_s + seconds + 120.0
        while True:
            seen = store.latest_index
            done = self.collect()
            now = clock()
            if not armed and now - t_begin >= lead_in_s:
                on_open()
                armed = True
            elif armed and done:
                if t_open is None:
                    t_open = done[-1].done
                elif now - t_open >= seconds:
                    t_close = done[-1].done
            if t_close is not None or now > deadline:
                break
            for req in done:
                next_kind.append(
                    "deregister" if req.kind == "register" else "register"
                )
            while next_kind:
                kind = next_kind.popleft()
                if kind == "deregister" and self.send_deregister(clock()):
                    continue
                self.send_register(clock())
            self._wait(seen, 0.25)
        on_close()
        self.drain(60.0)
        return {"t_open": t_open, "t_close": t_close, "t_begin": t_begin}

    def run_open(self, due_times: list, lead_in_s: float, seconds: float,
                 on_open, on_close) -> dict:
        """Open loop: ``due_times`` are offsets from the start of the
        lead-in and reach past the window's end. The window is the clock
        interval ``[t_open, t_open + seconds)`` and holds every request
        due in it; the run then waits (bounded) for those still in
        flight."""
        store = self.server.store
        t_begin = clock()
        t_open = t_close = None
        i = 0
        while True:
            seen = store.latest_index
            self.collect()
            now = clock()
            if t_open is None and now - t_begin >= lead_in_s:
                on_open()
                # arrivals keep their due times; the window opens now
                t_open = now = clock()
                t_close = t_open + seconds
            # every arrival due before the close is sent, however late
            horizon = now if t_close is None else min(now, t_close)
            while i < len(due_times) and t_begin + due_times[i] <= horizon:
                due = t_begin + due_times[i]
                self.send_register(due)
                # registered + registering never exceeds the steady count
                # by more than this arrival: occupancy stays at the
                # configuration's live allocations, less what is in flight
                registering = sum(
                    r.kind == "register" for r in self.pending.values()
                )
                if len(self.live) + registering > self.steady_jobs:
                    self.send_deregister(due)
                i += 1
            if t_close is not None and now >= t_close:
                break
            if i >= len(due_times):
                raise RuntimeError("the arrival schedule ended in the window")
            next_due = t_begin + due_times[i]
            self._wait(seen, min(0.25, max(0.0, next_due - clock())))
        on_close()
        self.drain(60.0)
        return {"t_open": t_open, "t_close": t_close, "t_begin": t_begin}

    def drain(self, timeout: float) -> None:
        """Wait for what is in flight: a late answer is late, not wrong."""
        store = self.server.store
        deadline = clock() + timeout
        while self.pending and clock() < deadline:
            seen = store.latest_index
            if not self.collect():
                self._wait(seen, 0.25)
        for req in self.pending.values():
            req.ok, req.note = False, "never completed"
        self.pending.clear()
