"""The job mix of a traffic file: the same work whatever the seed.

``bench.make_job`` drew the ask size and the job type per job from the
seed, so two seeds gave different J buckets and different work per pass
(ISSUE 24, cause 3). Here the traffic file fixes a ``cycle`` of job shapes
and, shuffled once from its constant ``pattern``, their order inside a
``shuffle_block`` (a whole number of cycles). That order repeats block after
block; the seed only picks where in the block a run starts and names the
jobs. Every block therefore holds the same multiset of shapes in the same
order for every seed, at another phase.
"""

from __future__ import annotations

import random

from benchmark.gen import start_phase


def plain_spec(job_id: str, entry: dict, shape: dict) -> dict:
    """One job as plain data: ``entry`` is a cycle entry (ask and type),
    ``shape`` the traffic file's ``job`` block."""
    return {
        "id": job_id,
        "type": entry["type"],
        "cpu": int(entry["cpu"]),
        "memory_mb": int(shape["memory_mb"]),
        "disk_mb": int(shape.get("disk_mb", 300)),
        "count": int(shape["count"]),
        "spread": shape.get("spread"),
        "affinity": shape.get("affinity"),
    }


def job_specs(traffic: dict, seed: int, tag: str):
    """Endless stream of plain job specs for ``traffic`` under ``seed``."""
    cycle = traffic["cycle"]
    block = int(traffic.get("shuffle_block", len(cycle)))
    if block % len(cycle):
        raise ValueError(
            f"shuffle_block {block} is not a whole number of cycles "
            f"({len(cycle)})"
        )
    shape = traffic["job"]
    order = [cycle[i % len(cycle)] for i in range(block)]
    random.Random(f"{traffic['pattern']}:pattern").shuffle(order)
    start = start_phase(seed)
    n = 0
    while True:
        yield plain_spec(
            f"{tag}-{seed}-{n:07d}", order[(start + n) % block], shape
        )
        n += 1


def mix_signature(traffic: dict, seed: int, n_jobs: int) -> list:
    """Sorted shapes of the first ``n_jobs`` jobs: equal for any two seeds
    when ``n_jobs`` is a whole number of blocks (the rehearsal checks)."""
    stream = job_specs(traffic, seed, "sig")
    return sorted(
        (s["type"], s["cpu"], s["memory_mb"], s["count"])
        for s in (next(stream) for _ in range(n_jobs))
    )


def make_job(spec: dict):
    """The program's ``Job`` for one plain spec (shape as ``mock.job``:
    one group, one exec task)."""
    from nomad_tpu.structs import (
        JOB_TYPE_BATCH,
        JOB_TYPE_SERVICE,
        Affinity,
        Job,
        Resources,
        Spread,
        Task,
        TaskGroup,
    )
    from nomad_tpu.structs.job import EphemeralDisk

    batch = spec["type"] == "batch"
    name = "worker" if batch else "web"
    job = Job(
        id=spec["id"],
        name=spec["id"],
        type=JOB_TYPE_BATCH if batch else JOB_TYPE_SERVICE,
        priority=50,
        datacenters=["dc1"],
        task_groups=[
            TaskGroup(
                name=name,
                count=spec["count"],
                ephemeral_disk=EphemeralDisk(size_mb=spec["disk_mb"]),
                tasks=[
                    Task(
                        name=name,
                        driver="exec",
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
    if spec["spread"]:
        job.spreads = [
            Spread(
                attribute=spec["spread"]["attribute"],
                weight=int(spec["spread"]["weight"]),
            )
        ]
    if spec["affinity"]:
        a = spec["affinity"]
        job.affinities = [
            Affinity(
                l_target=a["l_target"],
                r_target=a["r_target"],
                operand=a["operand"],
                weight=int(a["weight"]),
            )
        ]
    return job
