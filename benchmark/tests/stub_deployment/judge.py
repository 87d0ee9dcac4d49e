"""Part ``judge``: today's comparison, plus one number a mark of the other
four parts (0 where the mark is there) and one of its own."""

from benchmark import check
from benchmark.tests.stub_deployment import driver, jobs, warm


def extract_answers(store, job_ids: dict) -> dict:
    answers = check.extract_answers(store, job_ids)
    answers["jobs_not_at_stub_priority"] = sum(
        1 for job_id in job_ids
        if store.job_by_id("default", job_id).priority != jobs.PRIORITY
    )
    return answers


def judge(fleet, specs, requests, answers, window, seed) -> dict:
    numbers = check.judge(fleet, specs, requests, answers, window, seed)
    # (completions so far, steady_jobs) of every request the stub driver
    # saw succeed; set-up's requests went through today's driver
    stamps = [s for s in map(driver.read_stamp, (r.note for r in requests)) if s]
    numbers["stub_fleet_zone_missing"] = int("zone" not in fleet)
    numbers["stub_jobs_not_at_priority_70"] = answers[
        "jobs_not_at_stub_priority"
    ]
    numbers["stub_steady_jobs_not_the_warms"] = (
        sum(steady != warm.STEADY_JOBS for _n, steady in stamps)
        if stamps else None
    )
    # every request of the window that succeeded was counted as it completed
    numbers["stub_completions_uncounted"] = (
        max(0, len(stamps) - max(n for n, _steady in stamps))
        if stamps else None
    )
    numbers["stub_judge_own_number"] = 0
    return numbers
