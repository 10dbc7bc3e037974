"""Arithmetic on the sender's log: what the client's side saw.

The log (bench/sender.py) holds, for every chunk written, its cycle, the
time it was due and the time ``sendall`` returned. Everything here is
taken from the sender's own due times, never from the server.
"""

from __future__ import annotations

import numpy as np


def cycles(log: dict, t_from: float, t_to: float) -> list:
    """The whole cycles that start in [t_from, t_to): for each, its
    lines, its first due time and when its last ``sendall`` returned."""
    cyc = np.asarray(log["cycle"])
    due = np.asarray(log["due"])
    done = np.asarray(log["done"])
    out = []
    for k in np.unique(cyc).tolist():
        start = log["cycle_start"][k]
        at = np.nonzero(cyc == k)[0]
        if not (t_from <= start < t_to) or len(at) != log["chunks_per_cycle"]:
            continue
        out.append({"cycle": k, "lines": log["lines_per_cycle"],
                    "first_due": float(due[at].min()),
                    "last_done": float(done[at].max()),
                    "lag": (done[at] - due[at])})
    return out


def lines_per_s(log: dict, t_from: float, t_to: float):
    """Lines of the window's cycles over the seconds the sender had
    lines due and not yet written (per cycle: first due time to the
    return of its last ``sendall``)."""
    cs = cycles(log, t_from, t_to)
    busy = sum(c["last_done"] - c["first_due"] for c in cs)
    return sum(c["lines"] for c in cs) / busy if busy > 0 else None


def burst_send_s(log: dict, t_from: float, t_to: float):
    """Median over the window's cycles of first due -> last sendall."""
    cs = cycles(log, t_from, t_to)
    if not cs:
        return None
    return float(np.median([c["last_done"] - c["first_due"] for c in cs]))


def send_lag_ms(log: dict, t_from: float, t_to: float, pct: float):
    """Percentile of (sendall returned - due) over the window's chunks."""
    cs = cycles(log, t_from, t_to)
    if not cs:
        return None
    return float(np.percentile(np.concatenate([c["lag"] for c in cs]),
                               pct) * 1e3)
