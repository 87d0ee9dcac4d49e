"""``benchmark/run.py``'s resolver of a deployment's five parts, mirrored
from ``benchmark/tests/test_parts.py`` (which tier-1 does not run): the
cases that need no server and no device, as cases of one test."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

CELL = {"config": "some-config", "traffic": "some-mix"}
STUB = "tests.stub_deployment"
DEFAULTS = {
    "fleet": "benchmark.gen.fleet", "jobs": "benchmark.gen.jobs",
    "warm": "benchmark.warm", "driver": "benchmark.driver",
    "judge": "benchmark.check",
}
GPU = {p: f"benchmark.gpu_preempt.{p}" for p in DEFAULTS}


def _fake_fleet(monkeypatch):
    module = types.ModuleType("benchmark.fake_fleet")
    module.seed_fleet = "not a function"
    monkeypatch.setitem(sys.modules, "benchmark.fake_fleet", module)


@pytest.mark.parametrize("config, traffic, want", [
    # a part left out is today's module
    ({}, {}, DEFAULTS),
    # the traffic file's parts overlay the configuration's
    ({"parts": {"driver": f"{STUB}.driver", "jobs": f"{STUB}.jobs"}},
     {"parts": {"driver": "driver"}},
     {**DEFAULTS, "jobs": f"benchmark.{STUB}.jobs"}),
    # a deployment that brings all five
    ({"parts": {p: f"gpu_preempt.{p}" for p in DEFAULTS}}, {}, GPU),
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
    # a name that is not callable does not pass for the function
    ({"parts": {"fleet": "fake_fleet"}}, {}, ["lacks", "seed_fleet"]),
])
def test_resolve_parts(config, traffic, want, monkeypatch):
    _fake_fleet(monkeypatch)
    if isinstance(want, dict):
        got = run.resolve_parts(CELL, config, traffic)
        assert {p: m.__name__ for p, m in got.items()} == want
        assert list(got) == list(run.PARTS)
        return
    with pytest.raises(SystemExit) as e:
        run.resolve_parts(CELL, config, traffic)
    for words in want:
        assert words in str(e.value), e.value
