"""Reader-scaling evidence: shard-mutex contention under concurrent
readers, with hold/wait-time percentiles.

The sharded-reader design claim (native twin of the reference's
SO_REUSEPORT readers + Digest%N worker routing, networking.go:41-91,
server.go:1028-1039) is that readers never serialize: parsing is
lock-free (thread-local scratch, GIL released by ctypes) and the only
shared state is the per-shard commit mutex, held for the short
directory-upsert + SoA append. On a multi-core host the proof is
wall-clock scaling (tools/bench_ingest_scaling.py); on the 1-core
driver host wall-clock scaling is impossible, so this harness measures
the contention itself: per-shard mutex acquisitions, how many blocked,
and wait/hold-time percentiles while R readers blast the router
concurrently. Low hold p99 (sub-microsecond scale) and a small blocked
fraction IS the scaling headroom — the serial section per sample is
what bounds multi-core speedup (Amdahl), independent of core count.

Reader-sharded lane (core/worker.attach_reader_shards): the same
harness drives R readers each committing into its OWN private context
(ingest_owned — shared-nothing, no routing). There the per-context
mutex has exactly one steady-state owner, so the pinned expectation is
contended_fraction ~ 0 and wait p99 ~ 0: the serial section is gone
from the line path entirely, not merely short. Both lanes land in
INGEST_CONTENTION.json; the sharded lane additionally writes
READER_SCALING.json with the acceptance pins (on a 1-core host
wall-clock scaling is meaningless, so the committed evidence is the
contention record itself plus cpu_count for honest reading — no
extrapolated scaling claims).

Writes INGEST_CONTENTION.json + READER_SCALING.json at the repo root,
prints one JSON line.

Env: VENEUR_LOCK_SHARDS (default 4), VENEUR_LOCK_READERS (default 4),
VENEUR_LOCK_SECONDS (default 5), VENEUR_LOCK_SERIES (default 10000).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from veneur_tpu import native as native_mod  # noqa: E402


def build_datagrams(series: int, max_len: int = 4096) -> list[bytes]:
    datagrams, lines, size = [], [], 0
    for i in range(series):
        line = b"lc.m%d:%d|ms|#shard:%d" % (i, i % 997, i % 32)
        if size + len(line) + 1 > max_len:
            datagrams.append(b"\n".join(lines))
            lines, size = [], 0
        lines.append(line)
        size += len(line) + 1
    if lines:
        datagrams.append(b"\n".join(lines))
    return datagrams


def pct(xs, q):
    if not xs:
        return None
    return round(float(np.percentile(np.asarray(xs, np.float64), q)), 1)


def run(readers: int, shards: int, seconds: float,
        datagrams: list[bytes]) -> dict:
    contexts = [native_mod.NativeIngest() for _ in range(shards)]
    router = native_mod.NativeRouter(contexts)
    # pre-register the series so steady-state commits are upsert hits
    for d in datagrams:
        router.ingest(d)
    router.reset_lock_stats()

    stop = threading.Event()
    counts = [0] * readers

    def reader(idx: int) -> None:
        i = idx
        n = 0
        while not stop.is_set():
            router.ingest(datagrams[i % len(datagrams)])
            i += 1
            n += 1
        counts[idx] = n

    threads = [threading.Thread(target=reader, args=(r,), daemon=True)
               for r in range(readers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in threads:
        t.join(30)
    wall = time.perf_counter() - t0

    per_shard = []
    waits: list[int] = []
    holds: list[int] = []
    acq = blocked = wait_total = hold_total = 0
    for s in range(shards):
        st = router.lock_stats(s)
        acq += st["acquisitions"]
        blocked += st["contended"]
        wait_total += st["wait_ns_total"]
        hold_total += st["hold_ns_total"]
        waits.extend(st["wait_ns_samples"])
        holds.extend(st["hold_ns_samples"])
        per_shard.append({
            "acquisitions": st["acquisitions"],
            "contended": st["contended"],
        })
    return {
        "readers": readers,
        "wall_s": round(wall, 2),
        "samples_committed": acq,
        "samples_per_s": round(acq / wall, 1),
        "contended_fraction": round(blocked / max(acq, 1), 6),
        "wait_ns": {"p50": pct(waits, 50), "p99": pct(waits, 99),
                    "max": max(waits) if waits else None,
                    "total_ms": round(wait_total / 1e6, 2)},
        "hold_ns": {"p50": pct(holds, 50), "p99": pct(holds, 99),
                    "max": max(holds) if holds else None,
                    "total_ms": round(hold_total / 1e6, 2)},
        # the Amdahl bound: fraction of total reader wall time that was
        # inside any shard mutex — the serial ceiling on reader scaling
        "hold_fraction_of_wall": round(
            hold_total / 1e9 / (wall * readers), 6),
        # per-shard view: shards serialize independently, so the ceiling
        # on reader count is when ONE shard's mutex saturates a core
        "per_shard_hold_fraction": round(
            hold_total / 1e9 / (wall * max(1, shards)), 6),
        "per_shard": per_shard,
    }


def run_sharded(readers: int, seconds: float,
                datagrams: list[bytes]) -> dict:
    """Shared-nothing lane: reader r commits exclusively into its own
    context — the in-process twin of Server reader-shard mode."""
    contexts = [native_mod.NativeIngest() for _ in range(readers)]
    # pre-register the series per context (each context has a private
    # directory) so steady-state commits are upsert hits
    for ctx in contexts:
        for d in datagrams:
            ctx.ingest_owned(d)
    for ctx in contexts:
        ctx.reset_lock_stats()

    stop = threading.Event()
    counts = [0] * readers

    def reader(idx: int) -> None:
        ctx = contexts[idx]
        i, n = idx, 0
        while not stop.is_set():
            ctx.ingest_owned(datagrams[i % len(datagrams)])
            i += 1
            n += 1
        counts[idx] = n

    threads = [threading.Thread(target=reader, args=(r,), daemon=True)
               for r in range(readers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in threads:
        t.join(30)
    wall = time.perf_counter() - t0

    per_reader = []
    waits: list[int] = []
    holds: list[int] = []
    acq = blocked = wait_total = hold_total = 0
    for ctx in contexts:
        st = ctx.lock_stats()
        acq += st["acquisitions"]
        blocked += st["contended"]
        wait_total += st["wait_ns_total"]
        hold_total += st["hold_ns_total"]
        waits.extend(st["wait_ns_samples"])
        holds.extend(st["hold_ns_samples"])
        per_reader.append({
            "acquisitions": st["acquisitions"],
            "contended": st["contended"],
        })
    return {
        "readers": readers,
        "wall_s": round(wall, 2),
        "samples_committed": acq,
        "samples_per_s": round(acq / wall, 1),
        "contended_fraction": round(blocked / max(acq, 1), 6),
        "wait_ns": {"p50": pct(waits, 50), "p99": pct(waits, 99),
                    "max": max(waits) if waits else None,
                    "total_ms": round(wait_total / 1e6, 2)},
        "hold_ns": {"p50": pct(holds, 50), "p99": pct(holds, 99),
                    "max": max(holds) if holds else None,
                    "total_ms": round(hold_total / 1e6, 2)},
        "per_reader": per_reader,
    }


def main() -> None:
    if not native_mod.available():
        sys.exit("native library unavailable")
    shards = int(os.environ.get("VENEUR_LOCK_SHARDS", 4))
    max_readers = int(os.environ.get("VENEUR_LOCK_READERS", 4))
    seconds = float(os.environ.get("VENEUR_LOCK_SECONDS", 5))
    series = int(os.environ.get("VENEUR_LOCK_SERIES", 10_000))
    datagrams = build_datagrams(series)

    out = {
        "cpu_count": os.cpu_count(),
        "shards": shards,
        "series": series,
        "note": ("hold_fraction_of_wall is the serial ceiling: reader "
                 "scaling flattens only when readers*hold_fraction "
                 "approaches 1 (Amdahl); measured per-sample hold times "
                 "bound it far below that for any realistic core count"),
        "runs": [run(r, shards, seconds, datagrams)
                 for r in (1, 2, max_readers)],
    }
    hold = out["runs"][-1]["hold_ns"]["p99"]
    frac = out["runs"][-1]["hold_fraction_of_wall"]
    # scaling headroom estimate from the measured serial section: with
    # hold_fraction h per reader-second, N readers serialize on a shard
    # only when their combined committed time saturates it
    out["verdict"] = {
        "hold_p99_ns_at_max_readers": hold,
        "hold_fraction_of_wall": frac,
        "contended_fraction": out["runs"][-1]["contended_fraction"],
        "supports_reader_scaling": bool(
            frac is not None and frac < 0.25),
    }

    # shared-nothing lane: private per-reader contexts, no routing
    sharded_runs = [run_sharded(r, seconds, datagrams)
                    for r in (1, 2, max_readers)]
    at_max = sharded_runs[-1]
    out["reader_sharded"] = {
        "note": ("each reader commits into a PRIVATE context "
                 "(ingest_owned); the mutex has one steady-state owner "
                 "so the expected contention is zero, not merely low"),
        "runs": sharded_runs,
        "contended_fraction": at_max["contended_fraction"],
        "wait_p99_ns": at_max["wait_ns"]["p99"],
    }

    single_core = (os.cpu_count() or 1) == 1
    scaling = {
        "cpu_count": os.cpu_count(),
        "readers": max_readers,
        "series": series,
        "seconds": seconds,
        "mode": "contention-pin" if single_core else "throughput-scaling",
        "runs": sharded_runs,
        "legacy_routed_at_max_readers": out["runs"][-1],
    }
    if single_core:
        scaling["note"] = (
            "1-core host: wall-clock reader scaling is not measurable "
            "here, and no scaling efficiency is claimed or "
            "extrapolated. The committed evidence is the shared-nothing "
            "contention record under %d concurrent readers — the line "
            "path takes no contended lock, so added cores add readers "
            "without a serial section." % max_readers)
        scaling["verdict"] = {
            "contended_fraction": at_max["contended_fraction"],
            "wait_p99_ns": at_max["wait_ns"]["p99"],
            "contended_fraction_le_1pct": bool(
                at_max["contended_fraction"] <= 0.01),
            "wait_p99_approx_zero": bool(
                (at_max["wait_ns"]["p99"] or 0) < 1000),
        }
    else:
        base = sharded_runs[0]["samples_per_s"]
        eff = (at_max["samples_per_s"] / (max_readers * base)
               if base else 0.0)
        scaling["verdict"] = {
            "samples_per_s_1_reader": base,
            "samples_per_s_max_readers": at_max["samples_per_s"],
            "scaling_efficiency": round(eff, 4),
            "near_linear_ge_0_75": bool(eff >= 0.75),
        }

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "INGEST_CONTENTION.json"), "w") as f:
        json.dump(out, f, indent=1)
    with open(os.path.join(root, "READER_SCALING.json"), "w") as f:
        json.dump(scaling, f, indent=1)
    print(json.dumps({"legacy": out["verdict"],
                      "reader_sharded": scaling["verdict"]}))


if __name__ == "__main__":
    main()
