"""c2m-10k's job shapes (``gen/jobs.py``: the traffic file's cycle, the seed
picks the phase) as the deployment has them: services only, every group
with the traffic file's ``job.migrate`` block (Nomad's ``migrate`` stanza).
The source migrates services only; a batch allocation stays on a draining
node until it finishes or the deadline passes (PERF.md section 7)."""

from __future__ import annotations

from benchmark.gen import jobs as base


def job_specs(traffic: dict, seed: int, tag: str):
    migrate = traffic["job"]["migrate"]
    for spec in base.job_specs(traffic, seed, tag):
        yield {**spec, "migrate": migrate}


def make_job(spec: dict):
    """The program's job for one spec (the warm-up's plain specs carry no
    ``migrate`` block: the stanza's defaults)."""
    from nomad_tpu.structs.job import MigrateStrategy

    job = base.make_job(spec)
    migrate = spec.get("migrate")
    if migrate:
        job.task_groups[0].migrate = MigrateStrategy(
            max_parallel=int(migrate["max_parallel"]),
            health_check=migrate["health_check"],
            min_healthy_time_s=float(migrate["min_healthy_time_s"]),
            healthy_deadline_s=float(migrate["healthy_deadline_s"]),
        )
    return job
