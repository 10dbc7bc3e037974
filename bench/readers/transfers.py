"""server.last_flush_transfers (bytes moved per flush) and
server.last_micro_folds. arg: {"key": "h2d_bytes" | "d2h_bytes" |
"micro_folds", "scale": number}. Mean over the counted flushes."""


def read(run: dict, arg: dict):
    key = arg["key"]
    vals = [float(f["micro_folds"] if key == "micro_folds"
                  else f["transfers"][key])
            for f in run["flushes"]
            if key == "micro_folds" or key in f.get("transfers", {})]
    if not vals:
        return None
    return arg.get("scale", 1.0) * sum(vals) / len(vals)
