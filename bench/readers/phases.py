"""server.last_flush_phases: host-clock spans of each counted flush.
arg: {"keys": [...phase keys summed...], "scale": number}. Mean over the
counted flushes."""


def read(run: dict, arg: dict):
    vals = [sum(float(f["phases"].get(k, 0.0)) for k in arg["keys"])
            for f in run["flushes"] if f.get("phases")]
    if not vals:
        return None
    return arg.get("scale", 1.0) * sum(vals) / len(vals)
