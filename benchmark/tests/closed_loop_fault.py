"""Evidence for the program fault that keeps the closed-loop cell out.

    python benchmark/tests/closed_loop_fault.py --seed <n> [--seconds 8] \
        [--without-overlay] [--real-size]

ISSUE 24's first cell (``c2m-10k.steady-spread-250``: 32 clients in a
closed loop, each registers a job and deregisters the oldest) is not in
``BENCHMARK.json``: under load that never lets the worker's pipeline go
idle, the optimistic overlay (``nomad_tpu/server/overlay.py``) is never
rebased. Its frozen base never sees a deregistration and its deltas never
drop a committed placement, so after the placements since the last idle
moment add up to the fleet's free capacity every node reads ``exhausted``
and registrations complete with no allocation, on a fleet that the store
(and the reference's replay of it) shows two thirds empty.

This drives that traffic file (at toy size on any backend, or with
``--real-size`` at the cell's own) through the same driver and the same
comparison as a benchmark run, and prints what it saw. ``--without-overlay``
is the second witness: the same program with the overlay switched off
(every pass scores on the bare snapshot, nothing is reserved) serves the
same traffic with no empty registration, so the cause is the overlay and
not the traffic, the driver or the comparison.
When the program is mended this script reports no empty registration with
the overlay on, and the cell can be added with data files alone
(``traffic/steady-spread-250.json`` is already here).
"""

import argparse
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--without-overlay", action="store_true")
    ap.add_argument("--real-size", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import check, run, warm
    from benchmark.driver import Driver
    from benchmark.gen.fleet import seed_fleet
    from benchmark.gen.jobs import job_specs, make_job
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.server.overlay import SharedOverlay

    if args.without_overlay:
        def begin_pass(self, ct):
            with self._lock:
                self._passes += 1
            return None  # score on the bare snapshot

        SharedOverlay.begin_pass = begin_pass
        SharedOverlay.add_delta = lambda self, *a, **k: None

    config = run.load_json("configs", "c2m-10k.json")
    traffic = run.load_json("traffic", "steady-spread-250.json")
    if not args.real_size:
        run.apply_rehearsal(config, traffic)
    # the cell's own 32 clients: at 8 the toy fleet's short passes let the
    # pipeline fall idle by luck, which rebases the overlay
    traffic["in_flight"] = 32
    server = Server(ServerConfig(**config["server"]))
    server.establish_leadership()
    sent: dict = {}

    def remember(spec):
        sent[len(sent)] = spec
        return make_job(spec)

    quiet = lambda _msg: None  # noqa: E731
    try:
        fleet = seed_fleet(server, config)
        setup = warm.warm_shapes(server, traffic, remember, quiet)
        specs = job_specs(traffic, args.seed, "j")
        live, more, steady = warm.prefill(
            server, config, traffic, specs, remember, args.seed, quiet
        )
        driver = Driver(server, specs, remember, live, steady)
        window = driver.run_closed(
            int(traffic["in_flight"]), float(traffic["lead_in_s"]),
            args.seconds, lambda: None, lambda: None,
        )
        answers = check.extract_answers(
            server.store, {s["id"]: j for j, s in sent.items()}
        )
    finally:
        server.shutdown()
    numbers = check.judge(
        fleet, sent, setup + more + driver.requests, answers,
        (window["t_open"] or 0.0, window["t_close"] or float("inf")),
        args.seed,
    )
    empty = [
        r for r in driver.requests
        if r.kind == "register" and r.ok is False and r.placed == 0
    ]
    live_now = int((answers["stop"] == 0).sum())
    capacity = int((fleet["cpu"] // 375).sum())  # the mix's mean ask
    registered = [
        r for r in driver.requests
        if r.kind == "register" and r.ok and r.done is not None
    ]
    span = (registered[-1].done - window["t_begin"]) if registered else 0.0
    print(json.dumps({
        "seed": args.seed,
        "overlay": not args.without_overlay,
        "allocs_per_s_while_sound": (
            sum(r.placed for r in registered) / span if span else None
        ),
        "registrations": sum(r.kind == "register" for r in driver.requests),
        "registrations_with_no_allocation": len(empty),
        "first_empty_after_s": (
            round(empty[0].done - window["t_begin"], 2) if empty else None
        ),
        "live_allocs_in_store": live_now,
        "fleet_holds_about": capacity,
        "nodes_over_capacity": numbers["nodes_over_capacity"],
        "unfinished_requests": numbers["unfinished_requests"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
