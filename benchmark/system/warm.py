"""Set-up through the served path, in order: the node agents registered one
after the other (a system eval each, every node placed), the first agent
updated once (the destructive path on the bare fleet), c2m-10k's services
registered one job at a time (``benchmark/warm.py``'s pre-fill with the
traffic file's ``prefill_in_flight`` 1: the fill is a function of the
files), and the second agent updated once on the filled fleet. The two
updates touch each agent shape of the traffic file's; the kernel of a
system pass has one shape whatever the ask. The window then goes on with
the agents in the order they were last updated.

The first update has ``first_update_s`` to place an allocation of its new
version: a program whose system scheduler leaves a live job's update
unplaced fails there, within seconds of the agents' registration, and
runs no window."""

from __future__ import annotations

from benchmark import warm as base
from benchmark.system.driver import Driver
from benchmark.system.jobs import agent_specs, versioned
from benchmark.warm import SetupFailure, settle_admission

__all__ = ["warm_shapes", "prefill", "settle_admission"]


def warm_shapes(server, traffic: dict, make_job, log) -> list:
    """Nothing apart: the agents' registrations and the two updates
    ``prefill`` makes are the window's shapes."""
    return []


def _one(driver: Driver, spec: dict, patience_s: float, what: str):
    req = driver.send_spec(spec, base.clock())
    driver.drain(patience_s)
    if req.ok is not True:
        raise SetupFailure(f"{what}: {spec['id']} v{spec['version']}: "
                           f"{req.note}")
    return req


def prefill(server, config: dict, traffic: dict, specs, make_job,
            seed: int, log) -> tuple:
    """Returns the agents' specs, the one updated longest ago first, every
    request sent, and the number of live service jobs."""
    driver = Driver(server, iter(()), make_job, [], 0, patient=True,
                    traffic=traffic, seed=seed)
    agents = agent_specs(traffic, seed)
    for spec in agents:
        _one(driver, spec, base.SETUP_PATIENCE_S, "agent registration")
    log(f"agents: {len(agents)} registered on {driver.nodes} nodes each")
    first = float(traffic["first_update_s"])
    agents[0] = versioned(agents[0], 1)
    _one(driver, agents[0], first, f"first update (within {first:g} s)")
    log("warm-up: the first agent updated")

    services = {
        **config,
        "live_allocs": int(config["live_allocs"])
        - len(agents) * driver.nodes,
    }
    live, requests, n_jobs = base.prefill(
        server, services, traffic, specs, make_job, seed, log
    )
    agents[1] = versioned(agents[1], 1)
    _one(driver, agents[1], base.SETUP_PATIENCE_S, "second update")
    log("warm-up: the second agent updated on the filled fleet")
    order = agents[2:] + agents[:2]
    return order, driver.requests + requests, n_jobs
