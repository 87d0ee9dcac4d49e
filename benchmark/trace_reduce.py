"""From the profiler's trace to numbers: device busy time, kernel time.

``load_profile`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote for
the window into plain lists; ``reduce_profile`` works on those lists only,
so it is checked on a small recorded trace (``tests/trace_excerpt.json``)
without a chip.

A TPU's plane is named ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one
event per executed operation and ``XLA Modules`` one event per executed
program (a jitted kernel call), named after the jitted function
(``jit_place_spread_opv_kernel(...)``). Busy time is the union of the op
intervals (the modules' where a plane has no op line), averaged over the
device planes; a kernel's device time is the sum of the module events whose
name contains the kernel's name.
"""

from __future__ import annotations

import glob
import os

from benchmark.spans import spans_named

OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def load_profile(trace_dir: str):
    """``{plane name: {line name: [(event name, start_ns, dur_ns)]}}`` of
    the newest trace under ``trace_dir``; ``None`` where there is none."""
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        return None
    from jax.profiler import ProfileData

    data = ProfileData.from_file(paths[-1])
    out: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events
            )
    return out


def device_planes(profile: dict) -> dict:
    return {
        name: lines for name, lines in (profile or {}).items()
        if name.startswith("/device:TPU:")
    }


def _merge(events: list) -> list:
    """Merged ``[start_ns, end_ns]`` intervals covered by ``events``."""
    merged: list = []
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return merged


def _union_ns(events: list) -> float:
    return sum(end - start for start, end in _merge(events))


def _pick(lines: dict, names: tuple) -> list:
    for n in names:
        if lines.get(n):
            return lines[n]
    return []


def reduce_profile(profile) -> dict:
    """``busy_s`` (mean over device planes of the union of op intervals),
    ``modules`` (program name -> [count, seconds], summed over planes) and
    ``ops`` (operation name -> seconds). ``busy_s`` is ``None`` where no
    device plane recorded an operation."""
    planes = device_planes(profile)
    busy, modules, ops = [], {}, {}
    for lines in planes.values():
        op_events = _pick(lines, OP_LINES)
        mod_events = _pick(lines, MODULE_LINES)
        timeline = op_events or mod_events
        if timeline:
            busy.append(_union_ns(timeline) / 1e9)
        for name, _start, dur in mod_events:
            m = modules.setdefault(name, [0, 0.0])
            m[0] += 1
            m[1] += dur / 1e9
        for name, _start, dur in op_events:
            ops[name] = ops.get(name, 0.0) + dur / 1e9
    return {
        "busy_s": sum(busy) / len(busy) if busy else None,
        "n_devices": len(planes),
        "modules": modules,
        "ops": ops,
    }


def kernel_seconds(reduced: dict, kernels: list) -> tuple:
    """(calls, device seconds) of the programs named after ``kernels``."""
    calls, seconds = 0, 0.0
    for name, (n, s) in reduced["modules"].items():
        if any(k in name for k in kernels):
            calls += n
            seconds += s
    return calls, seconds


def traced_kernel_time(ctx: dict, kernels: list):
    """``(device seconds, scoring passes)`` of ``kernels`` in a traced chip
    run; ``None`` where there is nothing to read (a rehearsal, no device
    plane, no call of these kernels, no ``kernel.place`` span)."""
    if ctx["rehearse"] or not ctx["reduced"]["modules"]:
        return None
    calls, seconds = kernel_seconds(ctx["reduced"], kernels)
    n_passes = len(spans_named(ctx["traces"], "kernel.place"))
    if not calls or seconds <= 0 or not n_passes:
        return None
    return seconds, n_passes


MARKER = "bench_window_open"  # host annotation: ties the two clocks


def busy_intervals(profile) -> list:
    """Merged [start_ns, end_ns] intervals in which any device ran an op."""
    events = []
    for lines in device_planes(profile).values():
        events.extend(_pick(lines, OP_LINES) or _pick(lines, MODULE_LINES))
    return _merge(events)


def marker_ns(profile):
    """Trace time of the host annotation ``MARKER``; ``None`` if absent."""
    for name, lines in (profile or {}).items():
        if name.startswith("/host:"):
            for events in lines.values():
                for ev_name, start, _dur in events:
                    if ev_name == MARKER:
                        return start
    return None


def host_spans(traces: list, unix_to_ns) -> list:
    """The program's spans on the trace's clock: ``(start_ns, end_ns,
    name)``, copies of a pass's shared spans counted once, each eval's
    root as ``eval (between spans)``."""
    seen, out = set(), []
    for t in traces:
        for s in t.get("spans", ()):
            start = unix_to_ns(s.get("start_unix", 0.0))
            dur = (s.get("duration_ms") or 0.0) * 1e6
            name = s.get("name", "?")
            if s.get("parent_id") is None:
                name = "eval (between spans)"
            key = (name, round(start / 1e3), round(dur / 1e3))
            if key in seen:
                continue
            seen.add(key)
            out.append((start, start + dur, name))
    return out


def idle_gaps_by_span(busy: list, spans: list, t0_ns: float,
                      t1_ns: float) -> dict:
    """Seconds of device idle time in ``[t0, t1]`` by what the host was
    doing: each idle instant goes to the span that started last among those
    covering it (the innermost), or to ``no eval in flight``."""
    gaps, cursor = [], t0_ns
    for start, end in busy:
        if start > cursor:
            gaps.append((cursor, min(start, t1_ns)))
        cursor = max(cursor, end)
        if cursor >= t1_ns:
            break
    if cursor < t1_ns:
        gaps.append((cursor, t1_ns))
    # sweep over span starts/ends inside each gap
    spans = sorted(spans)
    out: dict = {}
    i, live = 0, []  # live: spans open at the sweep point
    for g0, g1 in gaps:
        while i < len(spans) and spans[i][0] <= g0:
            live.append(spans[i])
            i += 1
        live = [s for s in live if s[1] > g0]
        t = g0
        while t < g1:
            nxt = g1
            if i < len(spans):
                nxt = min(nxt, spans[i][0])
            for s in live:
                nxt = min(nxt, s[1])
            name = max(live)[2] if live else "no eval in flight"
            out[name] = out.get(name, 0.0) + (nxt - t) / 1e9
            t = nxt
            while i < len(spans) and spans[i][0] <= t:
                live.append(spans[i])
                i += 1
            live = [s for s in live if s[1] > t]
    return out


def breakdown(ctx: dict) -> dict:
    """The ten device operations that took most time, and the ten longest
    totals of device idle time by what the host was doing."""
    ops = sorted(ctx["reduced"]["ops"].items(), key=lambda kv: -kv[1])[:10]
    profile = ctx["profile"]
    mark = marker_ns(profile)
    unix0 = ctx["before"]["trace_unix0"]
    # the marker was written at unix0; without it assume the trace began then
    offset_ns = (mark if mark is not None else 0.0) - unix0 * 1e9
    spans = host_spans(ctx["traces"], lambda u: u * 1e9 + offset_ns)
    t0 = mark if mark is not None else 0.0
    t1 = t0 + (ctx["after"]["trace_t1"] - ctx["before"]["trace_t0"]) * 1e9
    gaps = idle_gaps_by_span(busy_intervals(profile), spans, t0, t1)
    top = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "device_ops": [[_short(name), s] for name, s in ops],
        "idle_gaps": [[name, s] for name, s in top],
    }


def _short(op_name: str) -> str:
    """``%sort.2 = (f32[1,16384]{...`` -> ``sort.2 (f32[1,16384]``."""
    head, _, rest = op_name.partition(" = ")
    return (head.lstrip("%") + " " + rest.split("{")[0])[:64].strip()
