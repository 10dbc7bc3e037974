"""The traced interval's idle seconds, by what the host was doing.

``spans.idle_host`` files the idle gaps of the traced *flush* under the
flush's own spans; between flushes the device is idle nine tenths of the
time and the spans that say why are the ingest side's (micro-folds, the
pump's and the sweep's drains, adoption, their dispatches), which bear
the ordinal of the flush that closes their epoch and so ride in the
*next* counted flush's record, on the same clock. This reader lays the
ingest-side spans of flush (traced ordinal + 1) and the traced flush's
own tree over the traced interval by ``run["trace"]["offset"]`` and
files every idle second of the first device under the deepest span open
at that time (among spans equally deep, the one that opened last).

A gap is cut at the spans' edges, not filed whole under its middle: a
gap of a quarter second holds several micro-folds and the time between
them. The label is the span's name, with its ``op`` where it has one
(``dispatch:fold``) and `` [wait]`` where it or a span around it is
``wait: true``; ``(no span)`` is a device with nothing due.

The value is the seconds under ingest-side spans that are not waiting
on the device: the host was on its way to a dispatch, or holding a
lock, while the device had nothing to run. The whole table goes to
bench/out/<cell>.idle_interval_by_span.json; its seconds sum to the
interval's idle seconds (``device_idle_pct`` of the interval).

None without a trace, device operations, the traced flush's successor
among the counted flushes, or its span record.
"""

import json
import os

from bench import trace_reduce
from bench.readers import spans

NO_SPAN = "(no span)"


def ingest_side(sp: dict) -> list:
    """(depth, span, waiting) of the spans that are not of the flush
    itself: ``spans.flush_tree``'s complement."""
    of_flush = {s["id"] for _, s, _ in spans.flush_tree(sp)}
    out = []
    for s in sp.values():
        if s["id"] not in of_flush:
            up = list(spans.ancestors(sp, s))
            out.append((len(up), s, any(spans.waits(a) for a in [s] + up)))
    return out


def label_of(s: dict, waiting: bool) -> str:
    label = s["name"]
    if "op" in s["attrs"]:
        label += ":" + str(s["attrs"]["op"])
    return label + " [wait]" if waiting else label


def timeline(laid: list, t0: float, t1: float) -> list:
    """[(a, b, side, span or None, waiting)] covering [t0, t1]: between
    two neighbouring edges of the spans ``laid`` ((side, depth, span,
    waiting), times already on the trace's clock) the deepest span open,
    the latest to open among equals."""
    edges = sorted({t0, t1} | {t for _, _, s, _ in laid
                               for t in (s["t0"], s["t1"]) if t0 < t < t1})
    by_start = sorted(laid, key=lambda e: e[2]["t0"])
    out, open_, nxt = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while nxt < len(by_start) and by_start[nxt][2]["t0"] <= a:
            open_.append(by_start[nxt])
            nxt += 1
        open_ = [e for e in open_ if e[2]["t1"] > a]
        if open_:
            side, _, s, w = max(open_, key=lambda e: (e[1], e[2]["t0"],
                                                      e[2]["id"]))
            out.append((a, b, side, s, w))
        else:
            out.append((a, b, "none", None, False))
    return out


def idle_table(ops: dict, laid: list, t0: float, t1: float) -> dict:
    """{label: {"idle_s", "side", "waiting"}} over the gaps of the first
    device of ``ops`` (trace_reduce.device_ops) inside [t0, t1]."""
    busy = trace_reduce.clip(trace_reduce.union(ops[sorted(ops)[0]]), t0, t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    table: dict = {}
    segs, k = timeline(laid, t0, t1), 0
    for a, b in gaps:
        while segs[k][1] <= a:
            k += 1
        j = k
        while j < len(segs) and segs[j][0] < b:
            sa, sb, side, s, w = segs[j]
            d = min(b, sb) - max(a, sa)
            if d > 1e-9:  # under a nanosecond is the floats' rounding
                label = NO_SPAN if s is None else label_of(s, w)
                e = table.setdefault(label, {"idle_s": 0.0, "side": side,
                                             "waiting": w})
                e["idle_s"] += d
            j += 1
    return table


def read(run: dict, arg: dict):
    tr = run.get("trace")
    if not tr or not tr["events"] or tr["flush"] is None:
        return None
    ops = trace_reduce.device_ops(tr["events"])
    if not ops:
        return None
    nxt = next((f for f in run["flushes"]
                if f["ordinal"] == tr["flush"]["ordinal"] + 1), None)
    own = spans.spans_of(tr["flush"])
    after = spans.spans_of(nxt) if nxt is not None else None
    if not own or not after:
        return None
    off = tr["offset"]

    def lay(side, depth, s, w):
        return side, depth, {**s, "t0": s["t0"] - off, "t1": s["t1"] - off}, w

    laid = [lay("ingest", d, s, w) for d, s, w in ingest_side(after)] \
        + [lay("flush", d, s, w) for d, s, w in spans.flush_tree(own)]
    t0, t1 = tr["t0"] - off, tr["t1"] - off
    table = idle_table(ops, laid, t0, t1)
    name = run["cell"]["name"]
    report = {
        "cell": name, "flush": tr["flush"]["ordinal"],
        "ingest_side_of": nxt["ordinal"], "offset": off,
        "t0": tr["t0"], "t1": tr["t1"],
        "idle_s": sum(e["idle_s"] for e in table.values()),
        "by_side": {side: sum(e["idle_s"] for e in table.values()
                              if e["side"] == side)
                    for side in ("ingest", "flush", "none")},
        "idle_interval_by_span": dict(sorted(
            table.items(), key=lambda kv: -kv[1]["idle_s"]))}
    os.makedirs(spans.OUT, exist_ok=True)
    with open(os.path.join(spans.OUT,
                           name + ".idle_interval_by_span.json"), "w") as f:
        json.dump(report, f, indent=1)
    return sum(e["idle_s"] for e in table.values()
               if e["side"] == "ingest" and not e["waiting"])
