"""What a span's thread did with its time: every span carries
``attrs["cpu_s"]``, the thread's CPU seconds between the span's two
times (veneur_tpu/core/flightrec.py, ``time.thread_time()``). A span
covers its children on the same thread, so a root's ``cpu_s`` is the
whole tree's.

arg["what"]:

  "cpu"     per flush, the ``cpu_s`` of the spans named in arg["names"]
            summed (the ``micro_fold`` spans of the flush's epoch: what
            the micro-fold thread spent on a core in the interval); mean
            over the counted flushes (x arg["scale"])
  "offcpu"  per flush, over the spans named arg["under"]: length less
            ``cpu_s``, less the same of the topmost ``wait: true`` spans
            below them (those stand still for the device, and say so):
            seconds the span's thread stood still for the interpreter, a
            lock or a page; mean over the counted flushes (x scale)

None where a flush has no span record, no flush has a span of the name,
or a span read lacks ``cpu_s`` (a program older than the attr).
"""

from bench.readers import spans


def _off(s: dict):
    """Seconds of the span its thread was not on a core, or None."""
    cpu = s["attrs"].get("cpu_s")
    return None if cpu is None else s["t1"] - s["t0"] - cpu


def cpu_seconds(sp: dict, names: list):
    """Sum of ``cpu_s`` over the spans named; None if none is there or
    one lacks the attr."""
    got = [s["attrs"].get("cpu_s") for s in sp.values()
           if s["name"] in names]
    if not got or None in got:
        return None
    return sum(got)


def offcpu_seconds(sp: dict, under: str):
    roots = [s for s in sp.values() if s["name"] == under]
    if not roots:
        return None
    ids = {s["id"] for s in roots}
    parts = [_off(s) for s in roots]
    for s in sp.values():
        if not spans.waits(s):
            continue
        up = list(spans.ancestors(sp, s))
        below = next((i for i, a in enumerate(up) if a["id"] in ids), None)
        if below is not None and not any(spans.waits(a) for a in up[:below]):
            off = _off(s)
            parts.append(None if off is None else -off)
    return None if None in parts else sum(parts)


def read(run: dict, arg: dict):
    what, scale = arg["what"], arg.get("scale", 1.0)
    if what == "cpu":
        v = spans.mean_over_flushes(
            run, lambda sp: cpu_seconds(sp, arg["names"]))
    elif what == "offcpu":
        v = spans.mean_over_flushes(
            run, lambda sp: offcpu_seconds(sp, arg["under"]))
    else:
        raise ValueError(f"span_cpu reader: unknown {what!r}")
    return None if v is None else scale * v
