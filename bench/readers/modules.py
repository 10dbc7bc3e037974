"""Device seconds per XLA program, from the ``XLA Modules`` line of the
traced interval (bench/trace_reduce.load_xplane keeps every line of the
device planes). A module event is one execution of one jitted program,
named ``jit_<function>(<fingerprint>)``; nothing in the program has to
change for this reading.

arg: {"programs": ["jit__histo_fold_staged", ...], "within": "flush" |
"interval"}: seconds of the events whose name starts with one of
``programs`` followed by ``(``, clipped to the traced flush (tick to
sink-seen) or to the traced interval, averaged over the devices traced.
None where there is no trace or no module line (bench/TRACING.md)."""

from bench import trace_reduce

LINE = "XLA Modules"


def window(run: dict, within: str):
    """(t0, t1) on the trace's clock, or None."""
    tr = run.get("trace")
    if not tr or not tr["events"]:
        return None
    off = tr["offset"]
    if within == "interval":
        return tr["t0"] - off, tr["t1"] - off
    fl = tr["flush"]
    if fl is None:
        return None
    return fl["tick"] - off, fl["t_seen"] - off


def module_seconds(events: list, programs: list, t0: float, t1: float):
    """Seconds of the named programs inside [t0, t1], averaged over the
    device planes that have a module line; None if none has one."""
    heads = tuple(p + "(" for p in programs)
    per_plane: dict = {}
    for plane, line, name, start, dur in events:
        if line != LINE or not plane.startswith(trace_reduce.DEVICE_PLANE):
            continue
        total = per_plane.setdefault(plane, 0.0)
        if name.startswith(heads):
            d = min(start + dur, t1) - max(start, t0)
            if d > 0:
                per_plane[plane] = total + d
    if not per_plane:
        return None
    return sum(per_plane.values()) / len(per_plane)


def by_program(events: list, t0: float, t1: float) -> dict:
    """{program name without its fingerprint: [executions, seconds]}
    inside [t0, t1], summed over devices: the table to read by hand."""
    out: dict = {}
    for plane, line, name, start, dur in events:
        if line != LINE or not plane.startswith(trace_reduce.DEVICE_PLANE):
            continue
        d = min(start + dur, t1) - max(start, t0)
        if d > 0:
            e = out.setdefault(name.split("(")[0], [0, 0.0])
            e[0] += 1
            e[1] += d
    return out


def read(run: dict, arg: dict):
    w = window(run, arg["within"])
    if w is None:
        return None
    return module_seconds(run["trace"]["events"], arg["programs"], *w)
