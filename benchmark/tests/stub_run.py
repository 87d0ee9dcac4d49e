"""Run the stub deployment (``stub_deployment/``) through ``run.main``.

    python benchmark/tests/stub_run.py --seed <n> \
        [--config-parts <json>] [--traffic-parts <json>]

Lays out a ``BENCHMARK.json``, ``benchmark/configs/stub-1k.json`` and
``benchmark/traffic/stub-arrivals.json`` in a temporary directory, points
``run.ROOT`` / ``run.HERE`` there and drives one rehearsal run of the cell
``stub-1k.stub-arrivals``. The configuration is ``grid-1k``'s and the mix
``arrivals-binpack-100``'s, read from the tree as they are, plus a
``parts`` block each and the stub's five limits: the deployment is these
files and the modules they name, and no file that was there changes.

By default the configuration names four stubs and, explicitly, today's
driver; the traffic file names the stub driver, which has to win.
"""

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

STUB = "tests.stub_deployment"
CONFIG_PARTS = {
    "fleet": f"{STUB}.fleet", "jobs": f"{STUB}.jobs", "warm": f"{STUB}.warm",
    "driver": "driver", "judge": f"{STUB}.judge",
}
TRAFFIC_PARTS = {"driver": f"{STUB}.driver"}
STUB_LIMITS = {
    "stub_fleet_zone_missing": 0,
    "stub_jobs_not_at_priority_70": 0,
    "stub_steady_jobs_not_the_warms": 0,
    "stub_completions_uncounted": 0,
    "stub_judge_own_number": 0,
}
CELL = "stub-1k.stub-arrivals"


def lay_out(tmp: str, config_parts: dict, traffic_parts: dict) -> None:
    def tree(*parts):
        with open(os.path.join(ROOT, *parts)) as f:
            return json.load(f)

    def write(obj, *parts):
        path = os.path.join(tmp, *parts)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)

    config = tree("benchmark", "configs", "grid-1k.json")
    config["parts"] = config_parts
    config["limits"].update(STUB_LIMITS)
    traffic = tree("benchmark", "traffic", "arrivals-binpack-100.json")
    traffic["parts"] = traffic_parts
    bench = tree("BENCHMARK.json")
    write(config, "benchmark", "configs", "stub-1k.json")
    write(traffic, "benchmark", "traffic", "stub-arrivals.json")
    write({
        "workloads": [{"name": CELL, "config": "stub-1k",
                       "traffic": "stub-arrivals", "chips": 1}],
        "end_to_end": [
            {**m, "workloads": [CELL]} for m in bench["end_to_end"]
        ],
        "per_layer": [],
    }, "BENCHMARK.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--config-parts", type=json.loads, default=CONFIG_PARTS)
    ap.add_argument("--traffic-parts", type=json.loads, default=TRAFFIC_PARTS)
    args = ap.parse_args(argv)

    from benchmark import run

    with tempfile.TemporaryDirectory() as tmp:
        lay_out(tmp, args.config_parts, args.traffic_parts)
        run.ROOT, run.HERE = tmp, os.path.join(tmp, "benchmark")
        return run.main([
            "--workload", CELL, "--seed", str(args.seed), "--seconds", "4",
            "--trace", "0", "--rehearse",
        ])


if __name__ == "__main__":
    sys.exit(main())
