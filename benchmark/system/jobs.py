"""The node agents and the services of ``system-10k``.

An agent is a system job (one group, one task, the traffic file's
``agents`` entry for its ask; the job-spec default priority 50, no
``update`` block); its ``version`` is the one ``env`` value of its task, so
a spec with a higher version is the agent's next version and differs in
that value alone, which ``scheduler/util.go`` ``tasksUpdated`` reads as
destructive. The services are c2m-10k's (``gen/jobs.py``: the traffic
file's cycle, the seed picks the phase)."""

from __future__ import annotations

from benchmark.gen import jobs as base

job_specs = base.job_specs  # the services' stream


def agent_specs(traffic: dict, seed: int) -> list:
    """Version 0 of every agent, in the order they are registered."""
    return [
        {
            "id": f"agent-{seed}-{k}",
            "type": "system",
            "cpu": int(a["cpu"]),
            "memory_mb": int(a["memory_mb"]),
            "disk_mb": int(a["disk_mb"]),
            "count": 1,
            "spread": None,
            "affinity": None,
            "version": 0,
        }
        for k, a in enumerate(traffic["agents"])
    ]


def versioned(spec: dict, version: int) -> dict:
    return {**spec, "version": int(version)}


def make_job(spec: dict):
    """The program's job for one spec."""
    if spec["type"] != "system":
        return base.make_job(spec)
    from nomad_tpu.structs import Job, Resources, Task, TaskGroup
    from nomad_tpu.structs.job import EphemeralDisk

    return Job(
        id=spec["id"],
        name=spec["id"],
        type="system",
        priority=50,
        datacenters=["dc1"],
        task_groups=[
            TaskGroup(
                name="agent",
                count=1,
                ephemeral_disk=EphemeralDisk(size_mb=spec["disk_mb"]),
                tasks=[
                    Task(
                        name="agent",
                        driver="exec",
                        env={"VERSION": str(spec["version"])},
                        resources=Resources(
                            cpu=spec["cpu"], memory_mb=spec["memory_mb"]
                        ),
                    )
                ],
            )
        ],
        status="pending",
        version=0,
    )
