"""The five parts of a deployment are found by name (PERF.md section 3):
the resolver's cases, which need no server, and the witness, a stub
deployment that arrives as new files only and runs through ``run.main``."""

import json
import sys
import types

import pytest

from _util import run_script
from benchmark import run

CELL = {"config": "some-config", "traffic": "some-mix"}
STUB = "tests.stub_deployment"
MARKS = {
    "fleet": "stub_fleet_zone_missing",
    "jobs": "stub_jobs_not_at_priority_70",
    "warm": "stub_steady_jobs_not_the_warms",
    "driver": "stub_completions_uncounted",
    "judge": "stub_judge_own_number",
}


def names(parts: dict) -> dict:
    return {part: module.__name__ for part, module in parts.items()}


def test_a_part_left_out_is_todays_module():
    assert names(run.resolve_parts(CELL, {}, {})) == {
        "fleet": "benchmark.gen.fleet", "jobs": "benchmark.gen.jobs",
        "warm": "benchmark.warm", "driver": "benchmark.driver",
        "judge": "benchmark.check",
    }
    assert list(run.PARTS) == list(MARKS)


def test_the_traffic_files_parts_overlay_the_configurations():
    config = {"parts": {"driver": f"{STUB}.driver", "jobs": f"{STUB}.jobs"}}
    traffic = {"parts": {"driver": "driver"}}
    got = names(run.resolve_parts(CELL, config, traffic))
    assert got["driver"] == "benchmark.driver"
    assert got["jobs"] == f"benchmark.{STUB}.jobs"
    assert got["warm"] == "benchmark.warm"


@pytest.mark.parametrize("config, traffic, said", [
    # a module that is not there, named by the configuration
    ({"parts": {"judge": f"{STUB}.no_such"}}, {},
     ["benchmark/configs/some-config.json: parts.judge",
      f"benchmark.{STUB}.no_such does not import"]),
    # and by the traffic file, whose entry is the one that counts
    ({"parts": {"driver": "driver"}}, {"parts": {"driver": "drivers.none"}},
     ["benchmark/traffic/some-mix.json: parts.driver", "does not import"]),
    # a module that lacks a function of its part
    ({"parts": {"warm": f"{STUB}.jobs"}}, {},
     ["benchmark/configs/some-config.json: parts.warm", "lacks",
      "warm_shapes", "prefill", "settle_admission"]),
    # a name that is there and no function: the class is the part's
    ({}, {"parts": {"driver": f"{STUB}.warm"}},
     ["benchmark/traffic/some-mix.json: parts.driver", "lacks ['Driver']"]),
    # a part nobody has
    ({"parts": {"reference": "check"}}, {},
     ["benchmark/configs/some-config.json: parts.reference", "no such part"]),
])
def test_what_does_not_resolve_ends_the_run_naming_file_and_key(
        config, traffic, said):
    with pytest.raises(SystemExit) as e:
        run.resolve_parts(CELL, config, traffic)
    for words in said:
        assert words in str(e.value), e.value


def test_a_name_that_is_not_callable_does_not_pass_for_the_function(
        monkeypatch):
    module = types.ModuleType("benchmark.fake_fleet")
    module.seed_fleet = "not a function"
    monkeypatch.setitem(sys.modules, "benchmark.fake_fleet", module)
    with pytest.raises(SystemExit, match="lacks"):
        run.resolve_parts(CELL, {"parts": {"fleet": "fake_fleet"}}, {})


def stub_run(*argv):
    return run_script("tests/stub_run.py", "--seed", "2147484001", *argv)


def test_a_deployment_of_new_files_only_runs_with_all_five_marks():
    rc, result, err = stub_run()
    assert rc == 0, err[-2000:]
    assert result["correct"] is True and result["failed"] == 0, err[-2000:]
    for number in MARKS.values():
        assert result["compared"][number] == {"value": 0, "limit": 0}
    # the stub pre-fill's own steady_jobs reached the driver: 21 jobs of 8
    # stay live where the configuration's 160 allocations make 20
    assert result["steady"]["live_allocs_min"] == 168


@pytest.mark.parametrize("part", ["fleet", "jobs", "warm", "driver"])
def test_with_a_part_left_to_todays_module_its_mark_is_missed(part):
    """The marks are the parts' own: the same files with one stub left out
    come out as not correct on that part's number alone (the pre-fill's
    ``steady_jobs`` is read from the stub driver's stamps, so without that
    driver it goes unseen too)."""
    config = {p: f"{STUB}.{p}" for p in MARKS if p != part}
    rc, result, err = stub_run(
        "--config-parts", json.dumps(config), "--traffic-parts", "{}")
    assert rc == 0, err[-2000:]
    assert result["correct"] is False
    missed = [
        n for n, c in result["compared"].items()
        if c["value"] is None or c["value"] > c["limit"]
    ]
    want = ["warm", "driver"] if part == "driver" else [part]
    assert missed == [MARKS[p] for p in want], result["compared"]


def test_a_part_that_does_not_resolve_stops_the_run_before_the_server():
    rc, result, err = stub_run(
        "--config-parts", json.dumps({"judge": f"{STUB}.no_such"}))
    assert rc != 0 and result is None
    assert "benchmark/configs/stub-1k.json: parts.judge" in err
    assert "bench [" not in err  # no line of the run: nothing had started
