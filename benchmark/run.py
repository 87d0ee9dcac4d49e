"""One run of one benchmark cell.

    python benchmark/run.py --workload <config>.<mix> --seed <n> \
        --seconds <s> --trace <0|1> [--rehearse]

One process. It demands a TPU (``--rehearse`` runs a toy fleet on whatever
backend is there and prints no device metric), builds one ``Server``,
seeds the configuration's fleet, warms the cell's shapes and fills the
fleet through the served path (all of that is ``setup_s``), measures for
``--seconds``, reads the peak memory, shuts the server down, checks the
answers against the plain reference, and prints the result line last.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<mix>.json``, ``metrics/<name>.json``
naming a reader module under ``readers/``.

So is the code that knows what a deployment is. A configuration file may
hold a ``parts`` block and a traffic file one that overlays it; each entry
names a module under ``benchmark/`` (``PARTS`` has the module an entry
left out stands for). This file imports none of the five by name, reads no
key of ``traffic["job"]`` or ``traffic["cycle"]`` and no field of a node or
an allocation. The calls it makes are the contract of a part:

``fleet``   ``seed_fleet(server, config) -> fleet``: upserts the nodes (and
            sets what of the scheduler's configuration the deployment
            states); returns the plain table the judge and the readers get,
            a dict that holds ``n``.
``jobs``    ``job_specs(traffic, seed, tag)``: endless iterator of plain
            specs, dicts that hold ``id`` and ``count``;
            ``make_job(spec)``: the program's job for one spec.
``warm``    ``warm_shapes(server, traffic, make_job, log) -> requests``;
            ``prefill(server, config, traffic, specs, make_job, seed, log)
            -> (live, requests, steady_jobs)``: ``live`` and ``steady_jobs``
            go to the driver unread; ``settle_admission(server, log)``.
``driver``  ``Driver(server, specs, make_job, live, steady_jobs,
            traffic=, seed=)`` with ``run_open(due, lead_in_s, seconds,
            on_open, on_close)`` and ``run_closed(in_flight, lead_in_s,
            seconds, on_open, on_close)``, both returning ``t_open`` and
            ``t_close``; ``requests``: records with ``kind`` ("register"
            carries the latency), ``job_id``, ``due``, ``sent``, ``done``,
            ``ok``, ``placed``, ``note`` (and ``eval_id``, which the
            ``latency_untraced`` reader looks up); ``live_alloc_track``:
            ``(t, live allocations)`` at each completion.
``judge``   ``extract_answers(store, job_ids) -> answers`` (arrays, one row
            an allocation, ``node`` among them);
            ``judge(fleet, specs, requests, answers, window, seed) ->
            numbers``, which ``limits`` in the configuration's file holds.

``make_job`` as the other parts get it also records the spec for the judge.
``check.program_failures`` and ``check.verdict`` belong to no deployment.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # setup_s counts from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

OUT_DIR = os.path.join(ROOT, ".bench_out")  # traces; listed in .gitignore

# part -> (the module under benchmark/ an entry left out stands for, what
# the module has to hold); the docstring above has the calls
PARTS = {
    "fleet": ("gen.fleet", ("seed_fleet",)),
    "jobs": ("gen.jobs", ("job_specs", "make_job")),
    "warm": ("warm", ("warm_shapes", "prefill", "settle_admission")),
    "driver": ("driver", ("Driver",)),
    "judge": ("check", ("extract_answers", "judge")),
}


def log(msg: str) -> None:
    print(
        f"bench [{time.perf_counter() - _T_PROCESS:7.1f}s] {msg}",
        file=sys.stderr, flush=True,
    )


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(workload: str, rehearse: bool) -> tuple:
    """(cell entry, benchmark, config, traffic) for a workload name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    config = load_json("configs", f"{cell['config']}.json")
    traffic = load_json("traffic", f"{cell['traffic']}.json")
    if rehearse:
        apply_rehearsal(config, traffic)
    return cell, bench, config, traffic


def resolve_parts(cell: dict, config: dict, traffic: dict) -> dict:
    """part -> module, the traffic file's ``parts`` over the
    configuration's over ``PARTS``. A part nobody has, a module that does
    not import or one that lacks a function of its part ends the run here,
    naming the file and the key."""
    named = {}  # part -> (module name, the file that names it)
    for kind, file_name, block in (
        ("configs", cell["config"], config),
        ("traffic", cell["traffic"], traffic),
    ):
        where = f"benchmark/{kind}/{file_name}.json"
        for part, name in block.get("parts", {}).items():
            if part not in PARTS:
                raise SystemExit(
                    f"{where}: parts.{part}: no such part; have {sorted(PARTS)}"
                )
            named[part] = (name, where)
    parts = {}
    for part, (default, needs) in PARTS.items():
        name, where = named.get(part, (default, "benchmark/run.py PARTS"))
        try:
            module = importlib.import_module(f"benchmark.{name}")
        except ImportError as e:
            raise SystemExit(
                f"{where}: parts.{part}: benchmark.{name} does not import: {e}"
            )
        lacks = [f for f in needs if not callable(getattr(module, f, None))]
        if lacks:
            raise SystemExit(
                f"{where}: parts.{part}: benchmark.{name} lacks {lacks}"
            )
        parts[part] = module
    return parts


def apply_rehearsal(*blocks: dict) -> None:
    """Overlay each file's ``rehearse`` block (the toy size) on it."""
    for block in blocks:
        for key, value in block.pop("rehearse", {}).items():
            if isinstance(value, dict) and isinstance(block.get(key), dict):
                block[key] = {**block[key], **value}
            else:
                block[key] = value


def device_block(chips: int, rehearse: bool) -> dict:
    """The device as jax reports it; no TPU or too few chips is fatal."""
    import jax

    dev = jax.devices()
    if not rehearse and (dev[0].platform != "tpu" or len(dev) < chips):
        print(
            f"benchmark: needs {chips} TPU chip(s); jax initialised "
            f"{len(dev)} {dev[0].platform} device(s)", file=sys.stderr,
        )
        raise SystemExit(2)
    return {
        "platform": dev[0].platform,
        "kind": dev[0].device_kind,
        "count": len(dev),
    }


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def counters() -> dict:
    from nomad_tpu.utils.metrics import global_metrics

    return dict(global_metrics.snapshot()["counters"])


def kernel_traces() -> dict:
    from nomad_tpu.utils.backend import kernel_profile

    return {
        name.rsplit(".", 1)[-1]: {
            "traces": p["traces"], "calls": p["calls"],
            "last_shape": p["last_trace_shape"],
        }
        for name, p in kernel_profile().items()
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy fleet on any backend; no device metric")
    args = ap.parse_args(argv)

    cell, bench, config, traffic = load_cell(args.workload, args.rehearse)
    parts = resolve_parts(cell, config, traffic)
    fleet_part, jobs, warm, driver_part, judge = (parts[p] for p in PARTS)
    device = device_block(int(cell["chips"]), args.rehearse)
    log(f"{args.workload} seed={args.seed} on {device}")

    from benchmark import check, trace_reduce
    from benchmark.gen.arrivals import arrival_times
    from benchmark.readers import latency_quantile
    from benchmark.spans import quantile
    from nomad_tpu.obs.recorder import flight_recorder
    from nomad_tpu.server import Server, ServerConfig

    # -- set-up: server, fleet, warm-up, pre-fill ---------------------------
    server = Server(ServerConfig(**config.get("server", {})))
    server.establish_leadership()
    sent_specs: dict = {}  # ordinal -> plain spec, in sending order

    def remember(spec: dict):
        sent_specs[len(sent_specs)] = spec
        return jobs.make_job(spec)

    traces: list = []
    try:
        fleet = fleet_part.seed_fleet(server, config)
        log(f"fleet of {fleet['n']} nodes seeded")
        setup_requests = warm.warm_shapes(server, traffic, remember, log)
        specs = jobs.job_specs(traffic, args.seed, "j")
        live, prefill_requests, steady_jobs = warm.prefill(
            server, config, traffic, specs, remember, args.seed, log
        )
        setup_requests += prefill_requests
        warm.settle_admission(server, log)
        # long-lived servers freeze the start-up heap; without it the
        # collector rescans the fleet and the live allocations at moments
        # of its own choosing inside the window
        gc.collect()
        gc.freeze()

        driver = driver_part.Driver(
            server, specs, remember, live, steady_jobs,
            traffic=traffic, seed=args.seed,
        )
        before: dict = {}
        after: dict = {}
        trace_dir = os.path.join(OUT_DIR, args.workload)

        def on_open() -> None:
            if args.trace:
                import jax

                shutil.rmtree(trace_dir, ignore_errors=True)
                flight_recorder.add_listener(traces.append)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                before["trace_t0"] = time.perf_counter()
                before["trace_unix0"] = time.time()
                with jax.profiler.TraceAnnotation(trace_reduce.MARKER):
                    pass
            before["counters"] = counters()
            before["kernels"] = kernel_traces()
            before["level"] = server.admission.level()
            before["setup_s"] = time.perf_counter() - _T_PROCESS
            log("window open")

        def on_close() -> None:
            after["counters"] = counters()
            after["kernels"] = kernel_traces()
            if args.trace:
                import jax

                after["trace_t1"] = time.perf_counter()
                jax.profiler.stop_trace()
                flight_recorder.remove_listener(traces.append)
            for name, k in after["kernels"].items():
                n = k["traces"] - before["kernels"].get(
                    name, {"traces": 0})["traces"]
                if n:
                    log(f"compiled in window: {n} x {name} {k['last_shape']}")
            log("window closed")

        lead_in = float(traffic["lead_in_s"])
        if traffic["loop"] == "closed":
            window = driver.run_closed(
                int(traffic["in_flight"]), lead_in, args.seconds,
                on_open, on_close,
            )
        else:
            # past the window's end: opening it (starting the profiler)
            # takes a moment after the lead-in
            due = arrival_times(
                traffic, args.seed, lead_in + args.seconds + 30.0
            )
            window = driver.run_open(
                due, lead_in, args.seconds, on_open, on_close
            )
        device["memory_peak_bytes"] = memory_peak_bytes()
        program = check.program_failures(server)
        job_ids = {s["id"]: j for j, s in sent_specs.items()}
        answers = judge.extract_answers(server.store, job_ids)
    finally:
        server.shutdown()
    log(f"answers read: {answers['node'].shape[0]} allocations")

    # -- the numbers of the window ------------------------------------------
    t_open, t_close = window["t_open"], window["t_close"]
    stalled = t_open is None or t_close is None
    if stalled:  # the loop's own deadline cut the run: nothing completed
        t_open = t_open or time.perf_counter()
        t_close = time.perf_counter()
    if traffic["loop"] == "closed":
        in_window = [
            r for r in driver.requests
            if r.done is not None and t_open < r.done <= t_close
        ]
    else:
        in_window = [r for r in driver.requests if t_open <= r.due < t_close]
    attempted = len(in_window)
    failed = sum(1 for r in in_window if r.ok is not True)
    registers = [r for r in in_window if r.kind == "register"]
    measured: dict = {"setup_s": before.get("setup_s")}
    if traffic["loop"] == "closed":
        placed = sum(r.placed for r in registers if r.ok)
        measured["allocs_per_s"] = placed / (t_close - t_open)
    else:
        # all requests due in the window; one that failed counts in
        # ``failed`` and fails ``correct``, it is not a fast one
        lat = [(r.done - r.due) * 1000.0 for r in registers if r.ok]
        measured["place_latency_p50_ms"] = quantile(lat, 0.5)
    metrics = {
        m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
        for m in bench["end_to_end"]
        if args.workload in m.get("workloads", [args.workload])
        and measured.get(m["name"]) is not None
    }

    # -- correct -------------------------------------------------------------
    numbers = judge.judge(
        fleet, sent_specs, setup_requests + driver.requests, answers,
        (t_open, t_close), args.seed,
    )
    numbers.update(program)
    band = config["live_band"]
    track = [n for t, n in driver.live_alloc_track if t_open <= t <= t_close]
    numbers["live_allocs_out_of_band"] = sum(
        1 for n in track
        if abs(n - int(config["live_allocs"])) > int(band)
    )
    numbers["window_stalled"] = int(stalled)
    if traffic["loop"] == "open":
        # observed only: a median that climbs through the window says the
        # work grows with what the run has left in the store
        halves = {"registers": registers, "t_open": t_open, "t_close": t_close}
        for half in ("first", "second"):
            numbers[f"latency_p50_{half}_half_ms"] = latency_quantile.read(
                halves, 0.5, half
            )
    correct, compared = check.verdict(numbers, config["limits"])
    notes: dict = {}
    for r in setup_requests + driver.requests:
        if r.ok is not True:
            notes[f"{r.kind}: {r.note}"] = notes.get(f"{r.kind}: {r.note}", 0) + 1
    for note, n in sorted(notes.items(), key=lambda kv: -kv[1])[:5]:
        log(f"failed requests: {n} x {note}")

    # -- per-layer metrics (traced run) --------------------------------------
    breakdown = None
    if args.trace:
        ctx = {
            "cell": cell, "config": config, "traffic": traffic,
            "fleet": fleet, "t_open": t_open, "t_close": t_close,
            "requests": in_window, "registers": registers,
            "traces": traces, "before": before, "after": after,
            "device_kind": device["kind"], "rehearse": args.rehearse,
            "profile": trace_reduce.load_profile(trace_dir),
            "peaks": load_json("peaks.json"),
        }
        reduced = trace_reduce.reduce_profile(ctx["profile"])
        ctx["reduced"] = reduced
        metrics = {}
        for entry in bench["per_layer"]:
            if args.workload not in entry.get("workloads", [args.workload]):
                continue
            spec = load_json("metrics", f"{entry['name']}.json")
            reader = importlib.import_module(
                f"benchmark.readers.{spec['reader']}"
            )
            value = reader.read(ctx, **spec.get("args", {}))
            if value is not None:
                metrics[entry["name"]] = {
                    "value": value, "unit": entry["unit"],
                }
        if "roofline" in ctx:
            log(f"roofline: {ctx['roofline']}")
        if not args.rehearse and reduced["busy_s"] is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = after["trace_t1"] - before["trace_t0"]
            breakdown = trace_reduce.breakdown(ctx)

    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["rehearsal"] = True
    result["window_s"] = t_close - t_open
    result["steady"] = {
        "admission_level_at_open": before.get("level"),
        "live_allocs_min": min(track) if track else None,
        "live_allocs_max": max(track) if track else None,
    }
    result["compared"] = compared
    for name, c in compared.items():
        print(
            f"compared {name}: {c['value']} (limit {c['limit']})",
            file=sys.stderr,
        )
    for name, value in numbers.items():
        if name not in compared:
            print(f"observed {name}: {value}", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
