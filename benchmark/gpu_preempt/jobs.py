"""The jobs of the deployment as plain specs and as program jobs: the
window's services (priority, one GPU, ``distinct_hosts``, the ask by the
traffic file's cycle) and the fill's batch jobs (GPU holders, CPU fill)."""

from __future__ import annotations

import random

from benchmark.gen import start_phase


def service_spec(job_id: str, entry: dict, shape: dict) -> dict:
    return {
        "id": job_id,
        "kind": "service",
        "type": entry["type"],
        "priority": int(shape["priority"]),
        "count": int(shape["count"]),
        "cpu": int(entry["cpu"]),
        "memory_mb": int(entry["memory_mb"]),
        "disk_mb": int(shape["disk_mb"]),
        "gpus": int(shape["gpus"]),
        "distinct_hosts": bool(shape["distinct_hosts"]),
    }


def job_specs(traffic: dict, seed: int, tag: str):
    """Endless stream of the window's service jobs: the cycle's order
    inside a ``shuffle_block`` is fixed by the file, the seed picks the
    phase and names the jobs (as ``gen/jobs.py``)."""
    cycle = traffic["cycle"]
    block = int(traffic.get("shuffle_block", len(cycle)))
    if block % len(cycle):
        raise ValueError(
            f"shuffle_block {block} is not a whole number of cycles "
            f"({len(cycle)})"
        )
    order = [cycle[i % len(cycle)] for i in range(block)]
    random.Random(f"{traffic['pattern']}:pattern").shuffle(order)
    start = start_phase(seed)
    n = 0
    while True:
        yield service_spec(
            f"{tag}-{seed}-{n:07d}", order[(start + n) % block],
            traffic["job"],
        )
        n += 1


def fill_specs(fill: dict, fleet: dict, seed: int) -> list:
    """The batch jobs that fill the fleet, GPU holders first: as many
    holders as the fleet has instances, as much CPU fill as then fits
    (counted from the fleet's table: every node takes what its free cpu
    and memory hold, the last job takes the remainder)."""
    per_job = int(fill["job_count"])
    holder, cpu = fill["gpu_holder"], fill["cpu_fill"]
    gpus = fleet["gpus"]
    slots = 0
    for dim, ask in (("cpu", "cpu"), ("memory_mb", "memory_mb")):
        free = fleet[dim] - gpus * int(holder[ask])
        n = free // int(cpu[ask])
        slots = n if dim == "cpu" else slots.clip(max=n)
    out = []
    for kind, shape, total in (
        ("gpu_holder", holder, int(gpus.sum())),
        ("cpu_fill", cpu, int(slots.sum())),
    ):
        k = 0
        while total > 0:
            count = min(per_job, total)
            out.append({
                "id": f"fill-{seed}-{kind}-{k:04d}",
                "kind": kind,
                "type": "batch",
                "priority": int(shape["priority"]),
                "count": count,
                "cpu": int(shape["cpu"]),
                "memory_mb": int(shape["memory_mb"]),
                "disk_mb": int(fill["disk_mb"]),
                "gpus": int(shape.get("gpus", 0)),
                "distinct_hosts": False,
            })
            total -= count
            k += 1
    return out


def make_job(spec: dict):
    """The program's ``Job`` for one spec: one group, one exec task."""
    from nomad_tpu.structs import (
        JOB_TYPE_BATCH,
        JOB_TYPE_SERVICE,
        Job,
        Resources,
        Task,
        TaskGroup,
    )
    from nomad_tpu.structs.job import Constraint, EphemeralDisk
    from nomad_tpu.structs.resources import RequestedDevice

    from benchmark.gpu_preempt.fleet import TYPE, VENDOR

    batch = spec["type"] == "batch"
    name = "worker" if batch else "web"
    resources = Resources(cpu=spec["cpu"], memory_mb=spec["memory_mb"])
    if spec["gpus"]:
        resources.devices = [
            RequestedDevice(name=f"{VENDOR}/{TYPE}", count=spec["gpus"])
        ]
    job = Job(
        id=spec["id"],
        name=spec["id"],
        type=JOB_TYPE_BATCH if batch else JOB_TYPE_SERVICE,
        priority=spec["priority"],
        datacenters=["dc1"],
        task_groups=[
            TaskGroup(
                name=name,
                count=spec["count"],
                ephemeral_disk=EphemeralDisk(size_mb=spec["disk_mb"]),
                tasks=[Task(name=name, driver="exec", resources=resources)],
            )
        ],
        status="pending",
        version=0,
    )
    if spec["distinct_hosts"]:
        job.constraints = [Constraint(operand="distinct_hosts")]
    return job
