"""Lifetime counters that ride as attrs of a span (``flush.begin``
carries the C++ readers' clocks and the commit path's counts: PERF.md
section 3), differenced between the first and the last counted flush.

arg["what"]:

  "ratio"  arg.get("scale", 1) x d(arg["num"]) / d(arg["den"]), attrs of
           the span arg["on"]: nanoseconds of the readers' parse, lock
           wait or commit per line committed

None where a flush has no span record, fewer than two counted flushes
carry both attrs (a program older than the counter), or the denominator
did not move.
"""

from bench.readers import spans


def ratio(run: dict, on: str, num: str, den: str):
    seen = []
    for fl in run["flushes"]:
        sp = spans.spans_of(fl)
        if sp is None:
            return None
        at = next((s for s in sp.values() if s["name"] == on
                   and num in s["attrs"] and den in s["attrs"]), None)
        if at is not None:
            seen.append((at["attrs"][num], at["attrs"][den]))
    if len(seen) < 2:
        return None
    dn, dd = seen[-1][0] - seen[0][0], seen[-1][1] - seen[0][1]
    return dn / dd if dd > 0 else None


def read(run: dict, arg: dict):
    if arg["what"] != "ratio":
        raise ValueError(f"counters reader: unknown {arg['what']!r}")
    v = ratio(run, arg["on"], arg["num"], arg["den"])
    return None if v is None else arg.get("scale", 1.0) * v
