"""The counted flushes' own times (sink-seen minus scheduled tick).
arg: {"what": "p50"} the median of them, {"what": "max"} the worst: what
the end-to-end mean does not show."""

import statistics


def read(run: dict, arg: dict):
    vals = [f["flush_s"] for f in run["flushes"]]
    if not vals:
        return None
    if arg["what"] == "max":
        return max(vals)
    if arg["what"] == "p50":
        return statistics.median(vals)
    raise ValueError(f"flushes reader: unknown {arg['what']!r}")
