"""The client of the deployment: ``benchmark/driver.py``'s loops and
stamps, with the occupancy read from the store. An eviction takes a live
allocation away and a victim's blocked eval brings one back without any
request of the driver's, so adding and subtracting ``count`` says nothing
here; the store is counted at every deregistration's completion (one per
arrival: the moment the room an arrival took has been handed back)."""

from __future__ import annotations

import time

from benchmark import driver as base


def live_allocations(store) -> int:
    return sum(1 for a in store.allocs() if not a.terminal_status())


def settle(server, timeout: float = 120.0) -> bool:
    """Wait until the broker holds nothing: every follow-up eval of a
    victim has run and is complete or blocked."""
    deadline = time.monotonic() + timeout
    quiet = 0
    while time.monotonic() < deadline:
        depths = server.eval_broker.queue_depths()
        busy = sum(v for k, v in depths.items() if k != "failed")
        quiet = quiet + 1 if not busy else 0
        if quiet >= 3:
            return True
        time.sleep(0.02)
    return False


class Driver(base.Driver):
    def __init__(self, server, specs, make_job, live_jobs, steady_jobs,
                 patient: bool = False, traffic=None, seed=None):
        super().__init__(
            server, specs, make_job, live_jobs, steady_jobs, patient=patient
        )

    def collect(self) -> list:
        n = len(self.live_alloc_track)
        done = super().collect()
        del self.live_alloc_track[n:]  # the sum of counts: not the occupancy
        if any(r.kind == "deregister" for r in done):
            self.live_alloc_track.append(
                (done[-1].done, live_allocations(self.server.store))
            )
        return done
