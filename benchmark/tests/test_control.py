"""The control comes out as not correct, the sound reference as correct."""

import pytest

from _util import cells, run_script


@pytest.mark.parametrize("cell", cells())
def test_control_fails_and_sound_reference_passes(cell):
    rc, row, err = run_script(
        "tests/control.py", "--workload", cell, "--seeds", "11", "12", "13",
        "--seconds", "6", "--rehearse",
    )
    assert rc == 0, err[-2000:]
    assert row["sound"]["correct"] is True
    assert row["capacity"]["correct"] is False
    assert "nodes_over_capacity" in row["capacity"]["failed"]
    assert row["precision"]["correct"] is False
    assert "score_mismatch_share" in row["precision"]["failed"]
    assert row["selection"]["correct"] is False
    assert row["selection"]["failed"] == [
        "jobs_off_best_share", "lone_jobs_off_best_share"]
