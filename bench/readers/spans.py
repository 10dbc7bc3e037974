"""The program's own span record (veneur_tpu/core/flightrec.py), as it
rides in every counted flush under ``phases["spans"]``: the spans of
that flush and of the ingest side of its epoch, each a list
``[id, name, t_start, t_end, parent, flush ordinal, attrs]`` on the
``time.time()`` clock. Where a flush carries no such key (a program
older than the record) every reading here returns None and the metric
is left out. bench/TRACING.md describes the fields and the names.

arg["what"]:

  "sum"           seconds of the spans named in arg["names"], summed per
                  flush, mean over the counted flushes (x arg["scale"])
  "lock_held"     per flush, the sum over ``micro_fold`` spans of their
                  length less their ``micro_fold.lock_wait`` child: how
                  long micro-folds held the ingest lock against the
                  readers; mean over the counted flushes (x scale)
  "wait"          per flush, the seconds of the topmost ``wait: true``
                  spans under arg["under"]: the host blocked on the
                  device; mean over the counted flushes
  "self"          arg["under"] less "wait": the host's own time there
  "counter_share" 100 x d(arg["num"]) / (d(num) + d(arg["rest"])),
                  lifetime counters carried as attrs of the span
                  arg["on"], differenced between the first and the last
                  counted flush
  "idle_host"     seconds in which the first device ran nothing, inside
                  the traced flush, whose middle falls in a span of the
                  flush that is not waiting on the device (nor inside
                  one that is); also writes the table by span name to
                  bench/out/<cell>.idle_by_span.json
  "bytes_share"   100 x (the ``bytes`` of the ``dispatch`` spans of the
                  traced flush whose op is arg["op"]) / (the device
                  seconds of arg["programs"] in that flush) / the peak
                  arg["peak"] of bench/peaks.json for the device kind
"""

import glob
import json
import os

from bench import stream, trace_reduce
from bench.readers import modules

OUT = os.path.join(stream.BENCH, "out")
KEYS = ("id", "name", "t0", "t1", "parent", "flush", "attrs")


def spans_of(flush: dict):
    """The flush's spans as dicts keyed by id, or None if it has none."""
    raw = (flush.get("phases") or {}).get("spans")
    if raw is None:
        return None
    return {s[0]: dict(zip(KEYS, s)) for s in raw}


def ancestors(spans: dict, s: dict):
    """s's parents, nearest first, as far as the record holds them."""
    seen = set()
    while s["parent"] in spans and s["parent"] not in seen:
        seen.add(s["parent"])
        s = spans[s["parent"]]
        yield s


def waits(s: dict) -> bool:
    return bool(s["attrs"].get("wait"))


def wait_seconds(spans: dict, under: str):
    """(seconds of the ``under`` spans, seconds of the topmost waiting
    spans below them); None if the flush has no span of that name."""
    roots = [s for s in spans.values() if s["name"] == under]
    if not roots:
        return None
    ids = {s["id"] for s in roots}
    wait = 0.0
    for s in spans.values():
        if not waits(s):
            continue
        up = list(ancestors(spans, s))
        below = next((i for i, a in enumerate(up) if a["id"] in ids), None)
        if below is not None and not any(waits(a) for a in up[:below]):
            wait += s["t1"] - s["t0"]
    return sum(s["t1"] - s["t0"] for s in roots), wait


def lock_held(spans: dict) -> float:
    total = 0.0
    for s in spans.values():
        if s["name"] == "micro_fold":
            total += s["t1"] - s["t0"] - sum(
                c["t1"] - c["t0"] for c in spans.values()
                if c["parent"] == s["id"]
                and c["name"] == "micro_fold.lock_wait")
    return total


def mean_over_flushes(run: dict, per_flush):
    vals = []
    for fl in run["flushes"]:
        spans = spans_of(fl)
        if spans is None:
            return None
        v = per_flush(spans)
        if v is not None:
            vals.append(v)
    return sum(vals) / len(vals) if vals else None


def counter_share(run: dict, on: str, num: str, rest: str):
    seen = []
    for fl in run["flushes"]:
        spans = spans_of(fl)
        if spans is None:
            return None
        at = next((s for s in spans.values() if s["name"] == on
                   and num in s["attrs"] and rest in s["attrs"]), None)
        if at is not None:
            seen.append((at["attrs"][num], at["attrs"][rest]))
    if len(seen) < 2:
        return None
    dn, dr = seen[-1][0] - seen[0][0], seen[-1][1] - seen[0][1]
    return 100.0 * dn / (dn + dr) if dn + dr > 0 else None


def flush_tree(spans: dict) -> list:
    """The spans of the flush itself (their outermost recorded ancestor
    is ``flush`` or one of its phases), each with its depth; the ingest
    side's spans, which only share the epoch, are left out."""
    out = []
    for s in spans.values():
        up = list(ancestors(spans, s))
        top = up[-1] if up else s
        if top["name"] == "flush" or top["name"].startswith("flush."):
            out.append((len(up), s, any(waits(a) for a in [s] + up)))
    return out


def idle_by_span(events: list, spans: dict, offset: float, t0: float,
                 t1: float) -> dict:
    """{label: [idle seconds, waiting]}: every gap of the first device
    inside [t0, t1] (trace clock), filed under the deepest span of the
    flush that holds the gap's middle (``(no span)`` if none).
    ``waiting`` says the span, or one around it, is ``wait: true``; the
    label is the span's name, with its ``op`` where it has one
    (``dispatch:staged``) and `` [wait]`` where it is waiting."""
    ops = trace_reduce.device_ops(events)
    if not ops:
        return {}
    busy = trace_reduce.clip(trace_reduce.union(ops[sorted(ops)[0]]), t0, t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    tree = flush_tree(spans)
    table: dict = {}
    for i in range(0, len(edges), 2):
        a, b = edges[i], edges[i + 1]
        if b <= a:
            continue
        mid = (a + b) / 2 + offset
        holds = [(d, s["t0"], s["id"], w) for d, s, w in tree
                 if s["t0"] <= mid < s["t1"]]
        label, waiting = "(no span)", False
        if holds:
            _, _, sid, waiting = max(holds)
            label = spans[sid]["name"]
            if "op" in spans[sid]["attrs"]:
                label += ":" + str(spans[sid]["attrs"]["op"])
            if waiting:
                label += " [wait]"
        e = table.setdefault(label, [0.0, waiting])
        e[0] += b - a
    return table


def annotation_skew_ms(trace_dir: str, spans: dict, offset: float,
                       t0: float, t1: float):
    """{"matched": n, "worst_ms": x}: the largest distance between the
    start of a TraceAnnotation that a span of the flush opened, as the
    profiler wrote it to a host plane of the .xplane.pb, and the nearest
    recorded start of a span of that name less the anchor's offset.
    Only annotations that start inside [t0, t1] (trace clock: the traced
    flush; the trace also holds the head of the next one) are matched,
    and ``dispatch`` is left out (the ingest side opens it too, in
    epochs this record does not hold). None if the trace holds none."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    mine: dict = {}
    for _, s, _ in flush_tree(spans):
        if s["name"] != "dispatch":
            mine.setdefault(s["name"], []).append(s["t0"] - offset)
    worst, matched = 0.0, 0
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                at = ev.start_ns * 1e-9
                if ev.name in mine and t0 - 0.05 <= at <= t1:
                    worst = max(worst, min(abs(at - t) for t in mine[ev.name]))
                    matched += 1
    return {"matched": matched, "worst_ms": worst * 1e3} if matched else None


def traced(run: dict):
    """(trace dict, its flush's spans) or None."""
    tr = run.get("trace")
    if not tr or not tr["events"] or tr["flush"] is None:
        return None
    spans = spans_of(tr["flush"])
    return (tr, spans) if spans else None


def idle_host(run: dict):
    got = traced(run)
    if got is None:
        return None
    tr, spans = got
    off = tr["offset"]
    t0, t1 = tr["flush"]["tick"] - off, tr["flush"]["t_seen"] - off
    if not trace_reduce.device_ops(tr["events"]):
        return None
    table = idle_by_span(tr["events"], spans, off, t0, t1)
    name = run["cell"]["name"]
    dirs = sorted(glob.glob(os.path.join(OUT, name + ".*.trace")),
                  key=os.path.getmtime)
    report = {
        "cell": name, "flush": tr["flush"]["ordinal"],
        # what a test fixture needs to lay the spans on a recorded slice
        "offset": off, "tick": tr["flush"]["tick"],
        "t_seen": tr["flush"]["t_seen"], "device_kind": device_kind(run),
        "idle_by_span": {k: {"idle_s": v[0], "waiting": v[1]}
                         for k, v in sorted(table.items(),
                                            key=lambda kv: -kv[1][0])},
        "programs_in_flush": modules.by_program(tr["events"], t0, t1),
        "annotations": annotation_skew_ms(dirs[-1], spans, off, t0, t1)
        if dirs else None}
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, name + ".idle_by_span.json"), "w") as f:
        json.dump(report, f, indent=1)
    return sum(v[0] for v in table.values() if not v[1])


def device_kind(run: dict) -> str:
    if run.get("device_kind"):
        return run["device_kind"]
    import jax

    return jax.devices()[0].device_kind


def bytes_share(run: dict, arg: dict):
    got = traced(run)
    if got is None:
        return None
    tr, spans = got
    w = modules.window(run, "flush")
    secs = modules.module_seconds(tr["events"], arg["programs"], *w)
    moved = sum(s["attrs"].get("bytes", 0) for s in spans.values()
                if s["name"] == "dispatch" and s["attrs"].get("op") == arg["op"])
    with open(os.path.join(stream.BENCH, "peaks.json")) as f:
        peaks = json.load(f)["device_kinds"]
    peak = peaks.get(device_kind(run), {}).get(arg["peak"])
    if not secs or not moved or not peak:
        return None
    return 100.0 * moved / secs / peak


def read(run: dict, arg: dict):
    what, scale = arg["what"], arg.get("scale", 1.0)
    if what == "sum":
        v = mean_over_flushes(run, lambda sp: sum(
            s["t1"] - s["t0"] for s in sp.values()
            if s["name"] in arg["names"]))
    elif what == "lock_held":
        v = mean_over_flushes(run, lock_held)
    elif what in ("wait", "self"):
        def per_flush(sp):
            got = wait_seconds(sp, arg["under"])
            if got is None:
                return None
            return got[1] if what == "wait" else got[0] - got[1]
        v = mean_over_flushes(run, per_flush)
    elif what == "counter_share":
        return counter_share(run, arg["on"], arg["num"], arg["rest"])
    elif what == "idle_host":
        return idle_host(run)
    elif what == "bytes_share":
        return bytes_share(run, arg)
    else:
        raise ValueError(f"spans reader: unknown {what!r}")
    return None if v is None else scale * v
