"""Part ``jobs``: today's stream of specs, every job at priority 70."""

from benchmark.gen import jobs as default
from benchmark.gen.jobs import job_specs  # noqa: F401  (the part's stream)

PRIORITY = 70


def make_job(spec: dict):
    job = default.make_job(spec)
    job.priority = PRIORITY
    return job
