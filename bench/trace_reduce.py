"""From a profiler trace to device metrics.

Input is a list of events, each ``[plane, line, name, start_s, dur_s]``
on the trace's own clock, taken from the ``.xplane.pb`` that
``jax.profiler`` writes (``load_xplane``, which needs JAX) or from a
recorded slice of one (``load_slice``, plain JSON: bench/testdata/). The
program has no named scopes yet, so operations carry the names the
profiler prints (XLA module and op names).

    busy      union of the intervals in which an operation ran on a
              device, averaged over the devices traced
    idle      the gaps of that union inside a window, each labelled by
              what the host was doing at its middle, from spans the
              benchmark draws on the same clock
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = "/device:TPU:"
# a device plane carries the same time several times over (steps,
# modules, ops); busy is counted on one line, the finest there is
OP_LINES = ("XLA Ops", "XLA Modules")
ANCHOR = "bench_anchor"


def load_xplane(trace_dir: str) -> list:
    """Events of the newest trace under ``trace_dir``: every line of the
    device planes, and from the host planes only the anchor."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name == ANCHOR:
                    events.append([plane.name, line.name, ev.name,
                                   ev.start_ns * 1e-9, ev.duration_ns * 1e-9])
    return events


def load_slice(path: str) -> list:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)["events"]


def anchor_offset(events: list, host_time: float):
    """Seconds to add to a trace time to get the host's clock: the
    benchmark opened a TraceAnnotation named ANCHOR at ``host_time``."""
    for plane, line, name, start, dur in events:
        if name == ANCHOR:
            return host_time - start
    return None


def device_ops(events: list) -> dict:
    """{device plane: [(start, end, name), ...]} on the op line."""
    planes: dict = {}
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PLANE) and name != ANCHOR:
            planes.setdefault(plane, {}).setdefault(line, []).append(
                (start, start + dur, name))
    out = {}
    for plane, lines in planes.items():
        for want in OP_LINES:
            if want in lines:
                out[plane] = sorted(lines[want])
                break
    return out


def union(intervals: list) -> list:
    """Sorted (start, end) intervals merged where they touch or overlap."""
    merged: list = []
    for a, b in sorted((x[0], x[1]) for x in intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def clip(intervals: list, t0: float, t1: float) -> list:
    return [[max(a, t0), min(b, t1)] for a, b in intervals
            if min(b, t1) > max(a, t0)]


def busy_seconds(events: list, t0: float, t1: float) -> float:
    """Seconds inside [t0, t1] in which an operation ran, averaged over
    the devices that appear in the trace."""
    ops = device_ops(events)
    if not ops:
        return 0.0
    return sum(sum(b - a for a, b in clip(union(v), t0, t1))
               for v in ops.values()) / len(ops)


def short_name(name: str) -> str:
    """An op as the profiler prints it, without layouts, cut to 120
    characters: '%fusion.573 = f32[100663296] fusion(f32[100] %copy-...'"""
    return re.sub(r"\{[^{}]*\}", "", name)[:120]


def top_ops(events: list, t0: float, t1: float, n: int = 10) -> list:
    """[[name, seconds], ...]: the operations that took most device time
    inside [t0, t1], summed over devices. A while loop is listed beside
    the ops of its body, so the entries overlap; busy time is the union,
    never their sum."""
    total: dict = {}
    for v in device_ops(events).values():
        for a, b, name in v:
            d = min(b, t1) - max(a, t0)
            if d > 0:
                name = short_name(name)
                total[name] = total.get(name, 0.0) + d
    return [[k, s] for k, s in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: list, t0: float, t1: float, spans: list,
              n: int = 10) -> list:
    """[[label, seconds], ...]: the longest gaps in which no operation
    ran on the first device inside [t0, t1]. ``spans`` are
    (label, start, end) on the trace's clock; a gap takes the label of
    the span that holds its middle, else ``ingest-only``."""
    ops = device_ops(events)
    if not ops:
        return []
    busy = clip(union(ops[sorted(ops)[0]]), t0, t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]

    def label(mid: float) -> str:
        for name, a, b in spans:
            if a <= mid < b:
                return name
        return "ingest-only"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    return [[label((a + b) / 2), b - a] for a, b in longest]


def flush_spans(tick: float, phases: dict) -> list:
    """The spans the benchmark can draw today, laid from the tick by the
    flush's phase seconds (server.last_flush_phases)."""
    spans, t = [], tick
    for label, keys in (("swap", ("swap_s", "drain_s")),
                        ("extract", ("extract_s",)),
                        ("generate+emit", ("generate_s", "sink_flush_s"))):
        d = sum(float(phases.get(k, 0.0)) for k in keys)
        spans.append((label, t, t + d))
        t += d
    return spans


def describe(events: list) -> dict:
    """Which planes and lines a trace has, and how many events on each:
    look at one by hand before trusting the reduction."""
    out: dict = {}
    for plane, line, name, start, dur in events:
        key = f"{plane} | {line}"
        e = out.setdefault(key, {"events": 0, "seconds": 0.0, "names": {}})
        e["events"] += 1
        e["seconds"] += dur
        if len(e["names"]) < 6:
            e["names"][name] = e["names"].get(name, 0) + 1
    return out


def cut_slice(events: list, t0: float, t1: float, path: str) -> None:
    """Write the events that touch [t0, t1] as a test slice."""
    keep = [e for e in events if e[3] < t1 and e[3] + e[4] > t0
            or e[2] == ANCHOR]
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump({"events": keep}, f)
