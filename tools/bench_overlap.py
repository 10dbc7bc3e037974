"""Measures flush/ingest overlap at high series cardinality.

The server flush is two-phase (core/server.py flush): worker.swap() under
the per-worker ingest lock, extract_snapshot() outside it. This harness
reproduces the server's locking structure — an ingest thread taking the
lock per batch, a flusher doing swap-then-extract — and measures how long
ingest is actually locked out during a full-pool percentile extraction,
in both designs:

  locked_extract:   extraction runs under the lock (the round-1 design)
  overlapped:       swap under the lock, extraction outside (current)

Reference intent: the map-swap of worker.go:498-517 exists precisely so
ProcessMetric never waits on a flush; SURVEY §7 "Latency budget" calls out
the same requirement at 1M series on TPU.

Writes OVERLAP.json at the repo root and prints one JSON line.

Env: VENEUR_OVERLAP_SERIES (default 2^20 on accelerator, 2^16 on CPU),
VENEUR_OVERLAP_BATCH (default 2^20 samples), VENEUR_OVERLAP_SECONDS
(ingest window per phase, default 6).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def register_series(w, series: int) -> float:
    """Fill the (fresh) epoch's directory with `series` histogram rows and
    seed the device pool so extraction touches the full pool. Returns the
    host-side directory build time."""
    from veneur_tpu.core.directory import ScopeClass
    from veneur_tpu.core.metrics import MetricKey

    t0 = time.perf_counter()
    for i in range(series):
        w.directory.upsert_histo(
            MetricKey(name=f"s{i}", type="histogram", joined_tags=""),
            ScopeClass.MIXED, [])
    directory_s = time.perf_counter() - t0
    w._ensure_histo(series)
    return directory_s


def build_worker(series: int):
    from veneur_tpu.core.worker import DeviceWorker

    w = DeviceWorker(initial_histo_rows=series)
    directory_s = register_series(w, series)
    rng = np.random.default_rng(7)
    batch = int(os.environ.get("VENEUR_OVERLAP_BATCH",
                               min(series * 4, 1 << 22)))
    rows = ((np.arange(batch, dtype=np.int64) * 2654435761) % series).astype(
        np.int32)
    vals = rng.gamma(2.0, 50.0, batch).astype(np.float32)
    wts = np.ones(batch, np.float32)
    w._device_histo_step(rows, vals, wts)
    return w, directory_s, (rows, vals, wts)


def run_phase(w, lock, batch_arrays, qs, seconds: float, overlapped: bool,
              series: int):
    """One flush against a continuously ingesting thread. Returns ingest
    batch wall-times (lock wait + dispatch) partitioned into before/during
    the extraction window, plus swap/extract durations."""
    rows, vals, wts = batch_arrays
    stop = threading.Event()
    spans: list[tuple[float, float, float]] = []

    def ingester():
        i = 0
        while not stop.is_set():
            t0 = time.perf_counter()
            with lock:
                t_acq = time.perf_counter()
                # the swap resets the pool; real ingest recreates it on
                # first use (_upsert_histo -> _ensure_histo)
                w._ensure_histo(series)
                # jitter values so no two dispatches are the same work
                w._device_histo_step(rows, vals + np.float32(i * 1e-6), wts)
            spans.append((t0, t_acq, time.perf_counter()))
            i += 1
            # paced like real traffic (a batch every ~20ms), not a busy
            # loop — on a 1-core host a spinning ingester fights the
            # extraction compute for the core and the contention would
            # masquerade as lock stalls
            stop.wait(0.02)

    t = threading.Thread(target=ingester, daemon=True)
    t.start()
    time.sleep(seconds / 2)  # baseline window

    if overlapped:
        t0 = time.perf_counter()
        with lock:
            sw = w.swap(qs)
        swap_s = time.perf_counter() - t0
        flush_start = time.perf_counter()
        snap = w.extract_snapshot(sw, qs)
        flush_end = time.perf_counter()
    else:
        flush_start = time.perf_counter()
        with lock:
            t1 = time.perf_counter()
            sw = w.swap(qs)
            snap = w.extract_snapshot(sw, qs)
        flush_end = time.perf_counter()
        swap_s = flush_end - t1
    extract_s = flush_end - flush_start
    assert snap.quantile_values is not None
    time.sleep(max(0.0, seconds / 2 - extract_s))
    stop.set()
    # generous: on a saturated 1-core host the ingester's final fold can
    # sit behind a fresh XLA compile for minutes (observed on the dev
    # rig); on TPU it joins in ms
    t.join(300)
    if t.is_alive():
        # exiting with a thread inside XLA aborts in glibc during
        # interpreter finalization — report, then skip finalization
        print(json.dumps({"error": "ingester thread wedged (>300s device"
                                   " op); phase unreliable"}),
              flush=True)
        os._exit(3)
    # classify each ingest batch by whether its wall-time interval
    # overlaps the flush window (so a batch that blocked on the lock for
    # the whole extraction is counted against it). The LOCK WAIT is the
    # design property under test (the two-phase flush exists so ingest
    # never waits on an extraction); total batch time additionally
    # carries CPU contention on a shared-core host.
    before = [(a - s, e - s) for s, a, e in spans if e <= flush_start]
    during = [(a - s, e - s) for s, a, e in spans
              if e > flush_start and s < flush_end]
    return before, during, swap_s, extract_s


def pctile(xs: list[float], q: float):
    """Percentile rounded for the report, or None (JSON null) when no
    batch landed in the window — NaN would make the artifact invalid
    JSON."""
    if not xs:
        return None
    return round(float(np.percentile(np.asarray(xs), q)), 4)


def main() -> None:
    from veneur_tpu.core.flusher import device_quantiles
    from veneur_tpu.core.metrics import HistogramAggregates

    import jax

    backend = jax.default_backend()
    on_cpu = backend == "cpu"
    series = int(os.environ.get(
        "VENEUR_OVERLAP_SERIES", 1 << 16 if on_cpu else 1 << 20))
    seconds = float(os.environ.get("VENEUR_OVERLAP_SECONDS", 6.0))
    qs = device_quantiles(
        [0.5, 0.9, 0.99], HistogramAggregates.from_names(["min", "max"]))

    lock = threading.Lock()
    out = {"series": series, "unit": "seconds",
           "platform": backend,
           "device": str(jax.devices()[0])}
    if on_cpu:
        out["note"] = ("CPU run: the single shared core serializes the "
                       "ingest thread against extraction compute, so "
                       "during-extract batch times reflect CPU "
                       "contention, not the lock design; the TPU run is "
                       "the meaningful artifact")
    for name, overlapped in (("locked_extract", False), ("overlapped", True)):
        w, directory_s, batch_arrays = build_worker(series)
        out.setdefault("directory_build_s", round(directory_s, 3))
        # warm the extraction compile so the measured pass is steady-state,
        # then rebuild the epoch the warmup swap cleared
        w.extract_snapshot(w.swap(qs), qs)
        register_series(w, series)
        w._device_histo_step(*batch_arrays)

        before, during, swap_s, extract_s = run_phase(
            w, lock, batch_arrays, qs, seconds, overlapped, series)
        waits_b = [x[0] for x in before]
        totals_b = [x[1] for x in before]
        waits_d = [x[0] for x in during]
        totals_d = [x[1] for x in during]
        out[name] = {
            "swap_s": round(swap_s, 4),
            "extract_s": round(extract_s, 4),
            "ingest_batches_during_extract": len(during),
            "lock_wait_p99_baseline_s": pctile(waits_b, 99),
            "lock_wait_p50_during_extract_s": pctile(waits_d, 50),
            "lock_wait_max_during_extract_s": pctile(waits_d, 100),
            "ingest_batch_p50_baseline_s": pctile(totals_b, 50),
            "ingest_batch_p99_baseline_s": pctile(totals_b, 99),
            "ingest_batch_p50_during_extract_s": pctile(totals_d, 50),
            "ingest_batch_max_during_extract_s": pctile(totals_d, 100),
        }

    ov, lk = out["overlapped"], out["locked_extract"]
    out["verdict"] = {
        # the headline: with the two-phase flush, ingest's worst LOCK
        # WAIT during extraction should be far below the extraction
        # itself (total batch time additionally carries shared-core CPU
        # contention; see the lock_wait_* fields for the design property)
        "max_ingest_lock_wait_overlapped_s":
            ov["lock_wait_max_during_extract_s"],
        "max_ingest_lock_wait_locked_s":
            lk["lock_wait_max_during_extract_s"],
        "extract_s": ov["extract_s"],
        "ingest_proceeds_during_extract":
            ov["ingest_batches_during_extract"] > 0,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "OVERLAP.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["verdict"]))


if __name__ == "__main__":
    main()
