"""The closed loop (``Driver.run_closed``, ``traffic/steady-spread-250.json``)
driven at toy size. ISSUE 24's closed-loop cell is kept out of
``BENCHMARK.json`` by a program fault (PERF.md section 7);
``closed_loop_fault.py`` is the evidence script, and this is its second
witness: the same program and traffic with the overlay switched off serve
every registration. Without ``--without-overlay`` the script shows the
fault as long as the program has it; nothing here expects it to."""

from _util import run_script


def test_closed_loop_is_sound_without_the_overlay():
    rc, row, err = run_script(
        "tests/closed_loop_fault.py", "--seed", "21", "--seconds", "12",
        "--without-overlay",
    )
    assert rc == 0, err[-2000:]
    assert row["registrations_with_no_allocation"] == 0, row
    assert row["unfinished_requests"] == 0 and row["nodes_over_capacity"] == 0
