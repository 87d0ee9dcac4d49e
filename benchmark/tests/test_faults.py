"""With the timed path broken underneath, ``correct`` comes out false."""

import pytest

from _util import cells, run_script

FAULTS = {
    "state_unchanged": "unfinished_requests",
    "half_left_out": "unfinished_requests",
    "score_altered": "score_mismatch_share",
    "rows_altered": "score_mismatch_share",
    "sampled_selection": "lone_jobs_off_best_share",
}


@pytest.mark.parametrize("cell", cells())
def test_sound_run_is_correct(cell):
    rc, result, err = run_script(
        "tests/fault_run.py", "none", "--workload", cell, "--seed", "5",
    )
    assert rc == 0 and result["correct"] is True, err[-2000:]


@pytest.mark.parametrize("cell", cells())
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_refused(cell, fault):
    rc, result, err = run_script(
        "tests/fault_run.py", fault, "--workload", cell, "--seed", "5",
    )
    assert rc == 0, err[-2000:]
    assert result["correct"] is False
    c = result["compared"][FAULTS[fault]]
    assert c["value"] is None or c["value"] > c["limit"], result["compared"]
