"""The profiler trace of one scheduled interval, reduced by
bench/trace_reduce.py. arg: {"what": "busy_flush_s"} is the union of
device-op intervals inside [tick, sink-seen] of the traced flush;
{"what": "idle_pct"} is 100 x (1 - busy / the traced interval)."""

from bench import trace_reduce


def read(run: dict, arg: dict):
    tr = run.get("trace")
    if not tr or not tr["events"]:
        return None
    off = tr["offset"]
    if arg["what"] == "busy_flush_s":
        fl = tr["flush"]
        if fl is None:
            return None
        return trace_reduce.busy_seconds(tr["events"], fl["tick"] - off,
                                         fl["t_seen"] - off)
    if arg["what"] == "idle_pct":
        w0, w1 = tr["t0"] - off, tr["t1"] - off
        busy = trace_reduce.busy_seconds(tr["events"], w0, w1)
        return 100.0 * (1.0 - busy / (w1 - w0))
    raise ValueError(f"trace reader: unknown {arg['what']!r}")
