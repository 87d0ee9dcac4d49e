"""c2m-10k's job mix (``gen/jobs.py``: the traffic file's cycle, the seed
picks the phase) with what a rollout needs: every service group carries the
traffic file's ``job.update`` block, batch groups none, and a spec's
``version`` is the one ``env`` value of its task. A spec with a higher
version is the same job's next version: it differs in that value alone,
which ``scheduler/util.go`` ``tasksUpdated`` reads as destructive."""

from __future__ import annotations

from benchmark.gen import jobs as base


def job_specs(traffic: dict, seed: int, tag: str):
    """The pre-fill's stream: version 0 of each job."""
    update = traffic["job"].get("update")
    for spec in base.job_specs(traffic, seed, tag):
        yield versioned(spec, 0, update)


def versioned(spec: dict, version: int, update=None) -> dict:
    """``spec`` at ``version``; a service keeps (or gets) its ``update``
    block, a batch job has none."""
    update = spec.get("update", update)
    return {
        **spec,
        "version": int(version),
        "update": None if spec["type"] == "batch" else update,
    }


def make_job(spec: dict):
    """The program's job for one spec (the warm-up's plain specs carry
    neither version nor update: version 0, no stanza)."""
    from nomad_tpu.structs.job import UpdateStrategy

    job = base.make_job(spec)
    group = job.task_groups[0]
    group.tasks[0].env = {"VERSION": str(spec.get("version", 0))}
    update = spec.get("update")
    if update:
        group.update = UpdateStrategy(
            max_parallel=int(update["max_parallel"]),
            health_check=update.get("health_check", "task_states"),
            min_healthy_time_s=float(update.get("min_healthy_time_s", 0.0)),
            canary=0,
            auto_revert=False,
        )
    return job
