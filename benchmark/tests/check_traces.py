"""One traced run of a cell with every recorded trace checked and kept.

    python benchmark/tests/check_traces.py --workload <cell> --seed <n> \
        --seconds <s> [--rehearse]

Runs ``run.py --trace 1`` in this process with a second listener beside the
harness's own, then checks what a pass record promises (PERF.md section 3):

- every trace carries ``pass_id`` and ``path``; over the whole run, set-up
  included, the distinct ``pass_id``s equal ``passes_solo +
  passes_batched`` (the window's own two counts are reported beside them:
  a pass that straddles an edge of the window is in one and not the
  other);
- a pass that holds a ``kernel:<name>`` span holds exactly one
  ``kernel.place``;
- no child starts before its parent or ends after it (``register`` and
  ``dequeue`` precede the root by design), no two children of
  ``plan_apply`` overlap;
- where the time between spans goes: the gaps between consecutive children
  of each parent, summed by ``parent: before -> after``.

Writes ``chiprun_out/trace_check.<cell>.<seed>.json`` (the report, the
stage table and the traces) and prints the report to stderr; the run's
result line stays the last line of stdout. Exit code 1 if a check fails.
"""

import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

from benchmark.readers.pass_wall import end, top_level  # noqa: E402
from benchmark.spans import quantile  # noqa: E402

SLACK_S = 2e-6  # durations are rounded to 0.1 us; starts are doubles
BEFORE_ROOT = ("register", "dequeue")


def children_of(trace) -> dict:
    kids: dict = {}
    for s in trace["spans"]:
        kids.setdefault(s["parent_id"], []).append(s)
    return kids


def nesting_faults(trace) -> list:
    """Children outside their parent, and overlapping children of
    ``plan_apply``, as short strings."""
    by_id = {s["span_id"]: s for s in trace["spans"]}
    faults = []
    for s in trace["spans"]:
        parent = by_id.get(s["parent_id"])
        if parent is None:
            continue
        if parent["parent_id"] is None and s["name"] in BEFORE_ROOT:
            continue
        if s["start_unix"] < parent["start_unix"] - SLACK_S:
            faults.append(f"{s['name']} starts before {parent['name']}")
        if end(s) > end(parent) + SLACK_S:
            faults.append(f"{s['name']} ends after {parent['name']}")
    for parent_id, kids in children_of(trace).items():
        if by_id.get(parent_id, {}).get("name") != "plan_apply":
            continue
        kids = sorted(kids, key=lambda s: s["start_unix"])
        for a, b in zip(kids, kids[1:]):
            if b["start_unix"] < end(a) - SLACK_S:
                faults.append(f"{a['name']} overlaps {b['name']}")
    return faults


def gaps(traces: list) -> dict:
    """Seconds between consecutive children, by where they lie."""
    out: dict = {}
    for t in traces:
        by_id = {s["span_id"]: s for s in t["spans"]}
        for parent_id, kids in children_of(t).items():
            parent = by_id.get(parent_id)
            if parent is None:
                continue
            top = parent["parent_id"] is None
            pname = "eval" if top else parent["name"]
            kids = sorted(kids, key=lambda s: s["start_unix"])
            edges = [(f"{pname}: {a['name']} -> {b['name']}",
                      b["start_unix"] - end(a)) for a, b in zip(kids, kids[1:])]
            if not top or kids[0]["name"] not in BEFORE_ROOT:
                edges.append((f"{pname}: (start) -> {kids[0]['name']}",
                              kids[0]["start_unix"] - parent["start_unix"]))
            edges.append((f"{pname}: {kids[-1]['name']} -> (end)",
                          end(parent) - max(end(k) for k in kids)))
            for key, dt in edges:
                if dt > 0:
                    out[key] = out.get(key, 0.0) + dt
    return out


def pass_ids(trace) -> set:
    """Every pass a trace took part in: a retry on the solo path is a pass
    of its own, named on the phases it wrote."""
    ids = {s["tags"].get("pass_id") for s in trace["spans"]}
    return (ids | {trace["tags"].get("pass_id")}) - {None}


def pass_faults(traces: list) -> tuple:
    """(distinct pass ids, passes whose kernel spans lack exactly one
    ``kernel.place``)."""
    per_pass: dict = {}
    for t in traces:
        by_id = {s["span_id"]: s for s in t["spans"]}
        for s in t["spans"]:
            top = top_level(s, by_id)
            pid = top.get("tags", {}).get("pass_id")
            if pid is None or "leader_eval" in top.get("tags", {}):
                continue
            p = per_pass.setdefault(pid, {"place": 0, "kernels": 0})
            if s["name"] == "kernel.place":
                p["place"] += 1
            elif s["name"].startswith("kernel:"):
                p["kernels"] += 1
    bad = [pid for pid, p in per_pass.items()
           if p["kernels"] and p["place"] != 1]
    return set(per_pass), bad


def stage_table(traces: list) -> dict:
    """name -> count, total seconds, nearest-rank median ms, of every span
    that is no copy from a pass's leader."""
    by_name: dict = {}
    for t in traces:
        for s in t["spans"]:
            if s["parent_id"] is not None and "leader_eval" not in s["tags"]:
                by_name.setdefault(s["name"], []).append(
                    s.get("duration_ms") or 0.0)
    return {
        name: {"count": len(v), "total_s": sum(v) / 1000.0,
               "p50_ms": quantile(v, 0.5)}
        for name, v in sorted(by_name.items())
    }


def check(traces: list, result: dict, whole_run=None) -> dict:
    untagged = [t["eval_id"] for t in traces
                if "pass_id" not in t["tags"] or "path" not in t["tags"]]
    pass_ids, bad_passes = pass_faults(traces)
    faults: dict = {}
    for t in traces:
        for f in nesting_faults(t):
            faults[f] = faults.get(f, 0) + 1
    m = result.get("metrics", {})
    counted = sum(m.get(k, {}).get("value", 0.0)
                  for k in ("passes_solo", "passes_batched"))
    top_gaps = sorted(gaps(traces).items(), key=lambda kv: -kv[1])[:20]
    return {
        "traces": len(traces),
        "traces_without_pass_id_or_path": len(untagged),
        "distinct_pass_ids": len(pass_ids),
        "passes_counted_in_window": counted,
        "whole_run": whole_run,
        "passes_with_kernels_but_not_one_kernel_place": len(bad_passes),
        "nesting_faults": faults,
        "largest_gaps_s": top_gaps,
        "ok": not untagged and not bad_passes and not faults and (
            whole_run is None
            or whole_run["distinct_pass_ids"] == whole_run["passes_counted"]
        ),
    }


def main(argv) -> int:
    from benchmark import run
    from nomad_tpu.obs.recorder import flight_recorder
    from nomad_tpu.utils.metrics import global_metrics

    kept: list = []
    seen: set = set()  # pass ids of the whole run, set-up included
    flight_recorder.add_listener(lambda t: seen.update(pass_ids(t)))
    add, remove = flight_recorder.add_listener, flight_recorder.remove_listener

    def add_both(fn):  # ride the harness's own window, its last listening
        del kept[:]
        add(fn)
        add(kept.append)

    def remove_both(fn):
        remove(fn)
        remove(kept.append)

    flight_recorder.add_listener = add_both
    flight_recorder.remove_listener = remove_both
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(argv + ["--trace", "1"])
    finally:
        flight_recorder.add_listener = add
        flight_recorder.remove_listener = remove
    line = out.getvalue().strip().splitlines()[-1]
    result = json.loads(line)
    counters = global_metrics.snapshot()["counters"]
    report = check(kept, result, {
        "distinct_pass_ids": len(seen),
        "passes_counted": counters.get("nomad.worker.passes_solo", 0)
        + counters.get("nomad.worker.passes_batched", 0),
    })
    print(json.dumps(report, indent=1), file=sys.stderr)
    args = dict(zip(argv[::2], argv[1::2]))
    path = os.path.join(
        ROOT, "chiprun_out",
        f"trace_check.{args['--workload']}.{args['--seed']}.json",
    )
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"report": report, "stages": stage_table(kept),
                   "result": result, "traces": kept}, f)
    print(line, flush=True)
    return rc or (0 if report["ok"] else 1)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
