"""The HyperLogLog insert's share of the HBM roofline, over the traced
interval.

``spans.bytes_share`` divides every ``dispatch`` span of the traced
flush's record by the device seconds inside the *flush*; the dense set
tier's insert runs between flushes, at every drain. So here both sides
are taken over the traced interval (tick to tick): the ``dispatch``
spans of op arg["op"] and kernel arg["kernel"] that started inside it,
from the record of every counted flush (a drain's spans ride in the
flush that closes its epoch), and the ``XLA Modules`` seconds of
arg["programs"] inside it.

The bytes are **from the work and not from the implementation**: an
unpadded entry is a register index (4 bytes), a slot (4) and a rank (1)
read, and one register read and written (1 + 1): 11 bytes. What the
program does besides (it sorts the batch, pads it to a ladder length and
copies a pool it does not donate) counts against it.

None where there is no trace, no such dispatch in the interval (a
program older than the attrs, or a cell in which no set promotes), no
module seconds or no peak for the device kind."""

import json
import os

from bench import stream
from bench.readers import modules, spans

BYTES_PER_ENTRY = 4 + 4 + 1 + 1 + 1


def insert_bytes(entries: int) -> int:
    """Least HBM traffic of scatter-maxing ``entries`` (slot, register,
    rank) triples into a register pool."""
    return BYTES_PER_ENTRY * int(entries)


def entries_in(run: dict, op: str, kernel: str, t0: float, t1: float):
    """Unpadded entries of the matching dispatches that started in
    [t0, t1) on the ``time.time()`` clock (a span is in one flush's
    record: the one whose ordinal it bears); None if a counted flush
    has no span record."""
    total = 0
    for fl in run["flushes"]:
        rec = spans.spans_of(fl)
        if rec is None:
            return None
        for s in rec.values():
            a = s["attrs"]
            if (s["name"] == "dispatch" and a.get("op") == op
                    and a.get("kernel") == kernel and t0 <= s["t0"] < t1):
                total += int(a.get("entries", 0))
    return total


def read(run: dict, arg: dict):
    w = modules.window(run, "interval")
    if w is None:
        return None
    tr = run["trace"]
    secs = modules.module_seconds(tr["events"], arg["programs"], *w)
    entries = entries_in(run, arg["op"], arg["kernel"], tr["t0"], tr["t1"])
    with open(os.path.join(stream.BENCH, "peaks.json")) as f:
        peaks = json.load(f)["device_kinds"]
    peak = peaks.get(spans.device_kind(run), {}).get(arg["peak"])
    if not secs or not entries or not peak:
        return None
    return 100.0 * insert_bytes(entries) / secs / peak
