"""CPU rehearsal: every cell's files run end to end at toy size.

Run with ``python -m pytest benchmark/tests -q`` (about two minutes; not
part of tier-1, which the driver runs over ``tests/`` only).
"""

import json
import os

import pytest

from _util import ROOT, benchmark_json, cells, run_script

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", cells())
@pytest.mark.parametrize("trace", ["0", "1"])
def test_cell_runs_end_to_end(cell, trace):
    rc, result, err = run_script(
        "run.py", "--workload", cell, "--seed", "2147483999",
        "--seconds", "5", "--trace", trace, "--rehearse",
    )
    assert rc == 0, err[-2000:]
    assert RESULT_KEYS <= set(result), result
    assert result["correct"] is True, err[-2000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "compared"  # the compared numbers come last
    assert result["rehearsal"] is True
    # a rehearsal never writes a device number
    assert "busy_s" not in result["device"]
    bench = benchmark_json()
    kind = "end_to_end" if trace == "0" else "per_layer"
    wanted = {
        m["name"]: m for m in bench[kind]
        if cell in m.get("workloads", [cell])
    }
    for name, value in result["metrics"].items():
        assert name in wanted, name
        assert value["unit"] == wanted[name]["unit"]
    if trace == "0":
        assert set(result["metrics"]) == set(wanted)
        assert all(v["value"] > 0 for v in result["metrics"].values())
    else:
        device_metrics = {
            n for n, m in wanted.items() if m["source"] == "device_trace"
        }
        assert set(result["metrics"]) == set(wanted) - device_metrics
    # live allocations stayed inside the configuration's band
    assert result["compared"]["live_allocs_out_of_band"]["value"] == 0
    assert result["steady"]["admission_level_at_open"] == "normal"


@pytest.mark.parametrize("cell", cells())
def test_cell_names_no_part_and_runs_todays_modules(cell):
    from benchmark import run

    entry, _bench, config, traffic = run.load_cell(cell, rehearse=False)
    assert "parts" not in config and "parts" not in traffic
    parts = run.resolve_parts(entry, config, traffic)
    assert [m.__name__ for m in parts.values()] == [
        "benchmark.gen.fleet", "benchmark.gen.jobs", "benchmark.warm",
        "benchmark.driver", "benchmark.check",
    ]


def test_no_tpu_no_result():
    rc, result, err = run_script(
        "run.py", "--workload", cells()[0], "--seed", "1", "--seconds", "1",
        "--trace", "0",
    )
    assert rc != 0 and result is None
    assert "needs 1 TPU chip" in err


def _traffic_files():
    d = os.path.join(ROOT, "benchmark", "traffic")
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


@pytest.mark.parametrize("mix", _traffic_files())
def test_mix_identical_for_two_seeds(mix):
    from benchmark.gen.jobs import job_specs, mix_signature

    with open(os.path.join(ROOT, "benchmark", "traffic", f"{mix}.json")) as f:
        traffic = json.load(f)
    n = 4 * int(traffic.get("shuffle_block", len(traffic["cycle"])))
    assert mix_signature(traffic, 7, n) == mix_signature(traffic, 2**31 + 5, n)
    if len(traffic["cycle"]) > 1:
        order = lambda seed: [  # noqa: E731
            s["cpu"] for s, _ in zip(job_specs(traffic, seed, "t"), range(n))
        ]
        assert order(7) != order(2**31 + 5)  # the seed does change the order


def test_arrivals_same_gaps_for_two_seeds():
    from benchmark.gen.arrivals import arrival_times

    traffic = {"arrivals": {"rate_per_s": 10, "block": 16}, "pattern": 0}
    a = arrival_times(traffic, 7, 32.0)
    b = arrival_times(traffic, 2**31 + 5, 32.0)
    assert len(a) == len(b) == 319  # the one due at 32.0 s is left out
    gaps = lambda t: sorted(  # noqa: E731
        round(y - x, 9) for x, y in zip([0.0] + t[:-1], t)
    )
    assert gaps(a[:304]) == gaps(b[:304]) and a != b
    # every block of 16 lasts exactly 1.6 s: the load offered never drifts
    assert abs(a[15] - 1.6) < 1e-9 and abs(b[303] - 30.4) < 1e-9


def test_trace_reduction_on_recorded_excerpt():
    """``trace_excerpt.json`` was cut from a chip run's trace (PR 24,
    grid-1k cell): two calls of the closed-form kernel and the small
    convert program before each. The expected numbers are worked out by
    hand in the file's ``expected`` block."""
    from benchmark import trace_reduce

    with open(os.path.join(ROOT, "benchmark", "tests", "trace_excerpt.json")) as f:
        rec = json.load(f)
    profile = {
        plane: {line: [tuple(e) for e in events]
                for line, events in lines.items()}
        for plane, lines in rec["profile"].items()
    }
    reduced = trace_reduce.reduce_profile(profile)
    exp = rec["expected"]
    assert reduced["n_devices"] == 1
    assert reduced["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    calls, seconds = trace_reduce.kernel_seconds(
        reduced, ["place_closed_form_kernel"]
    )
    assert calls == exp["kernel_calls"]
    assert seconds == pytest.approx(exp["kernel_s"], rel=1e-9)
    idle = 1.0 - reduced["busy_s"] / exp["window_s"]
    assert idle == pytest.approx(exp["idle_share"], rel=1e-9)
    # no device plane, no busy time: the harness then reports none
    assert trace_reduce.reduce_profile({"/host:CPU": {}})["busy_s"] is None


def test_kernel_cost_by_hand():
    """One 100-alloc job of 250 MHz on 1,000 nodes of 3,900 MHz: J = 16
    candidates per node, 22 operations each; the bytes are the job's mask,
    its results and the fleet's capacity and usage once."""
    import numpy as np

    from benchmark.kernel_cost import job_cost, least_seconds

    ops, nbytes = job_cost(1000, 100, 250, 3900, False)
    assert ops == 1000 * 16 * 22 and nbytes == 125 + 800
    peaks = {"devices": {"X": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}}
    fleet = {"n": 1000, "cpu": np.full(1000, 3900)}
    traffic = {"job": {"count": 100}, "cycle": [{"cpu": 250}]}
    least = least_seconds(peaks, "X", fleet, traffic, 10, 5)
    assert least["bytes"] == 10 * 925 + 5 * 2 * 4 * 1000 * 4
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(least["bytes"] / 1e9)
    with pytest.raises(KeyError):
        least_seconds(peaks, "unknown chip", fleet, traffic, 1, 1)


def test_idle_gaps_go_to_the_innermost_span():
    """Device busy in [10,20] and [50,60] ns of a 120 ns window; the idle
    rest goes to the span that started last among those covering it."""
    from benchmark.trace_reduce import idle_gaps_by_span

    spans = [(0, 100, "eval (between spans)"), (5, 30, "snapshot"),
             (25, 70, "invoke_scheduler"), (40, 55, "kernel.place")]
    gaps = idle_gaps_by_span([[10, 20], [50, 60]], spans, 0, 120)
    assert {k: round(v * 1e9) for k, v in gaps.items()} == {
        "eval (between spans)": 35,  # [0,5) and [70,100)
        "snapshot": 10,  # [5,10) and [20,25)
        "invoke_scheduler": 25,  # [25,40) and [60,70)
        "kernel.place": 10,  # [40,50)
        "no eval in flight": 20,  # [100,120)
    }
