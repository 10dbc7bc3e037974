"""The stream a cell sends: a ring of DogStatsD lines, repeated once per
interval. NumPy only; imported by the sender child (which must never
import JAX or the program) and by the reference.

A ring is four arrays in wire order, one entry per line:

    cls   int8     0 counter, 1 gauge, 2 timer, 3 set
    sid   int32    series number inside its class
    val   float64  increment / written value / sample / member number

built from the seed by the config's line generator
(``bench/generators/<name>.py``: ``build_ring(lines, series, rng)``).
``format_lines`` turns the same arrays into the bytes on the wire, so
the sender's lines and the reference's arrays cannot disagree.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
COUNTER, GAUGE, TIMER, SET = 0, 1, 2, 3
CLASSES = ("counter", "gauge", "timer", "set")
LETTERS = "cgts"  # a series is named cs.<letter>.<sid>


@dataclass
class Ring:
    cls: np.ndarray
    sid: np.ndarray
    val: np.ndarray
    series: dict  # class name -> number of series of that class

    def __len__(self) -> int:
        return len(self.cls)


def load_json(kind: str, name: str) -> dict:
    """bench/<kind>/<name>.json, found by the name BENCHMARK.json gives."""
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def generator(name: str):
    return importlib.import_module("bench.generators." + name)


def build_ring(config: dict, seed: int) -> Ring:
    lines = config["lines"]
    rng = np.random.default_rng(int(seed))
    cls, sid, val = generator(lines["generator"]).build_ring(
        lines, config["series"], rng)
    return Ring(cls.astype(np.int8), sid.astype(np.int32),
                val.astype(np.float64), dict(config["series"]))


def format_lines(ring: Ring, tag_from: int = 0) -> list:
    """The ring's lines as bytes, in wire order. Timer values, gauge
    values: two decimals (every value is a multiple of 0.25). Timers
    numbered ``tag_from`` and up carry a ``shard`` tag, so that tag
    parsing is on the path as it is for a fleet's timers."""
    out = np.empty(len(ring), object)
    for c in range(4):
        at = np.nonzero(ring.cls == c)[0]
        s = ring.sid[at].tolist()
        v = ring.val[at]
        if c == COUNTER:
            lines = [b"cs.c.%d:%d|c" % p for p in zip(s, v.astype(np.int64).tolist())]
        elif c == GAUGE:
            lines = [b"cs.g.%d:%.2f|g" % p for p in zip(s, v.tolist())]
        elif c == TIMER:
            lines = [b"cs.t.%d:%.2f|ms|#shard:%d" % (i, x, i & 63)
                     if i >= tag_from else b"cs.t.%d:%.2f|ms" % (i, x)
                     for i, x in zip(s, v.tolist())]
        else:
            lines = [b"cs.s.%d:u%d-%d|s" % (i, i, m)
                     for i, m in zip(s, v.astype(np.int64).tolist())]
        out[at] = lines
    return out.tolist()


def chunk_lines(lines: list, chunk_bytes: int) -> tuple:
    """Newline-terminated lines packed into chunks of at most
    ``chunk_bytes``, each ending on a line boundary: (chunks, lines in
    each chunk)."""
    chunks, counts, buf, size = [], [], [], 0
    for ln in lines:
        if buf and size + len(ln) + 1 > chunk_bytes:
            chunks.append(b"\n".join(buf) + b"\n")
            counts.append(len(buf))
            buf, size = [], 0
        buf.append(ln)
        size += len(ln) + 1
    if buf:
        chunks.append(b"\n".join(buf) + b"\n")
        counts.append(len(buf))
    return chunks, counts


def due_offsets(traffic: dict, n_chunks: int, interval_s: float) -> np.ndarray:
    """Seconds after its cycle's start at which each chunk is due."""
    return np.asarray(generator(traffic["arrival"]).due_offsets(
        traffic, n_chunks, interval_s), np.float64)
