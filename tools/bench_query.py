"""Live-query latency benchmark: the read path's standing numbers.

Measures query latency (p50/p99) against the committed epoch across a
grid of series count × series shards × concurrent-ingest load, one cell
per (QueryEngine, DeviceWorker) pair, plus a sustained-rate A/B run
showing the flush/ingest side pays nothing for live queries: the same
ingest+flush workload runs once without query traffic and once with
concurrent query threads hammering the engine, and the two line rates
must agree (queries read the retained post-fold arrays and the
committed snapshot — no lock, no ledger traffic, no flush-path work).

Four query ops per cell:

  quantiles_host    flush-qs quantiles, served from snapshot host
                    arrays (zero device work — the dashboard case)
  quantiles_device  ad-hoc quantiles through the retained device
                    program (rotating qs so the per-epoch memo can't
                    serve repeats)
  scalars           min/max/sum/count for every series (limit-bounded)
  exposition        full Prometheus render of the committed epoch

Usage:
    python tools/bench_query.py                 # full grid → QUERY_BENCH.json
    python tools/bench_query.py --smoke         # bounded CI lane, /tmp artifact
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


def _cpu_grid_env() -> None:
    """The sharded grid cells need eight virtual CPU devices, and
    XLA_FLAGS must say so before the backend starts (nothing here has
    imported jax yet)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    want = "--xla_force_host_platform_device_count=8"
    if want not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            want + " " + os.environ.get("XLA_FLAGS", "")).strip()


def _percentiles(samples_s: list[float]) -> dict:
    import numpy as np

    arr = np.asarray(samples_s) * 1e3
    return {"n": len(samples_s),
            "p50_ms": round(float(np.percentile(arr, 50)), 4),
            "p99_ms": round(float(np.percentile(arr, 99)), 4),
            "mean_ms": round(float(arr.mean()), 4)}


def _build_cell(series: int, shards: int):
    import functools

    from veneur_tpu.core.flusher import device_quantiles
    from veneur_tpu.core.metrics import HistogramAggregates
    from veneur_tpu.core.worker import DeviceWorker
    from veneur_tpu.protocol.dogstatsd import parse_metric
    from veneur_tpu.query.engine import QueryEngine

    aggs = HistogramAggregates.from_names(["min", "max", "count"])
    pcts = [0.5, 0.9, 0.99]
    qs = device_quantiles(pcts, aggs)
    eng = QueryEngine(pcts, aggs, is_local=True)
    w = DeviceWorker(initial_histo_rows=min(series, 256),
                     series_shards=shards)
    w.query_publisher = functools.partial(eng.stage, 0)
    pre = [parse_metric(f"qb.s{i}:{(i * 7) % 100}|ms|#cell:a".encode())
           for i in range(series)]
    for m in pre:
        w.process_metric(m)
    w.flush(qs, interval_s=10.0)
    eng.commit(1000)
    return eng, w, qs, pre


def bench_cell(series: int, shards: int, ingest: bool, reps: int) -> dict:
    eng, w, qs, pre = _build_cell(series, shards)
    lock = threading.Lock()
    stop = threading.Event()
    threads = []
    if ingest:
        def ingest_loop():
            while not stop.is_set():
                with lock:
                    for m in pre[:200]:
                        w.process_metric(m)

        def flush_loop():
            ts = 1000
            while not stop.is_set():
                with lock:
                    sw = w.swap(qs)
                w.extract_snapshot(sw, qs, 10.0)
                ts += 1
                eng.commit(ts)
                time.sleep(0.2)

        threads = [threading.Thread(target=ingest_loop, daemon=True),
                   threading.Thread(target=flush_loop, daemon=True)]
        for t in threads:
            t.start()
        time.sleep(0.5)  # let the first concurrent epochs land

    def timed(fn, n):
        out = []
        for i in range(n):
            t0 = time.perf_counter()
            fn(i)
            out.append(time.perf_counter() - t0)
        return _percentiles(out)

    probe = "qb.s0"
    try:
        ops = {
            "quantiles_host": timed(
                lambda i: eng.query_quantiles(name=probe), reps),
            # rotate qs so the per-epoch memo can't serve a repeat; the
            # padded shape stays fixed so there is exactly one compile
            "quantiles_device": timed(
                lambda i: eng.query_quantiles(
                    qs=[0.1 + 0.8 * (i % 97) / 97.0], name=probe,
                    force_device=True), reps),
            "scalars": timed(lambda i: eng.query_scalars(limit=series),
                             reps),
            "exposition": timed(
                lambda i: eng.render_exposition(), max(reps // 4, 5)),
        }
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert eng.queries_failed == 0, "queries failed during bench"
    return {"series": series, "shards": shards,
            "concurrent_ingest": ingest, "ops": ops}


def bench_sustained_ab(cycles: int, query_threads: int = 2,
                       qps: float = 40.0) -> dict:
    """Fixed ingest+flush work, without then with paced query traffic.

    Each side runs the SAME deterministic workload — `cycles` rounds of
    (ingest the full ring, swap, extract, commit) on one thread — so the
    two line rates are directly comparable; the only difference is the
    query threads polling the engine at dashboard rate (`qps` split
    across the threads). Two designs were tried and rejected: a
    free-running flusher thread measures nothing but lock-acquisition
    chaos (16x run-to-run spread on a loaded rig), and unpaced query
    spin-loops measure GIL timesharing (any tight Python loop costs a
    1-core rig 1/N, query subsystem or not). Paced load is the claim
    the subsystem makes: live dashboards polling at a few Hz leave the
    flush contract untouched — no shared lock, no transfer-ledger
    traffic, no flush-path device work."""

    def run(with_queries: bool) -> float:
        eng, w, qs, pre = _build_cell(series=512, shards=0)
        stop = threading.Event()
        served = {"queries": 0}
        tick = query_threads / qps

        def query_loop():
            i = 0
            while not stop.is_set():
                eng.query_scalars(limit=64)
                eng.query_quantiles(name="qb.s1")
                if i % 10 == 0:
                    eng.render_exposition()
                i += 1
                served["queries"] += 2
                time.sleep(tick)

        threads = [threading.Thread(target=query_loop, daemon=True)
                   for _ in range(query_threads if with_queries else 0)]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        for cycle in range(cycles):
            for m in pre:
                w.process_metric(m)
            sw = w.swap(qs)
            w.extract_snapshot(sw, qs, 10.0)
            eng.commit(1001 + cycle)
        elapsed = time.perf_counter() - t0
        stop.set()
        for t in threads:
            t.join()
        assert eng.queries_failed == 0
        if with_queries:
            assert served["queries"] > 0, "query threads never ran"
        return cycles * len(pre) / elapsed

    run(with_queries=True)  # warmup: absorb one-time jit compile stalls
    base = run(with_queries=False)
    loaded = run(with_queries=True)
    return {"cycles_per_side": cycles, "query_threads": query_threads,
            "query_qps": qps,
            "baseline_lps": round(base, 1),
            "with_queries_lps": round(loaded, 1),
            "ratio": round(loaded / base, 4)}


def validate_schema(doc: dict) -> list[str]:
    """Shape-check the artifact (the CI lane gates on this)."""
    errs = []
    for key in ("grid", "sustained_ab", "smoke", "rev", "ts_utc"):
        if key not in doc:
            errs.append(f"missing key {key}")
    for cell in doc.get("grid", []):
        for key in ("series", "shards", "concurrent_ingest", "ops"):
            if key not in cell:
                errs.append(f"grid cell missing {key}: {cell}")
        for op, stats in cell.get("ops", {}).items():
            if not (stats.get("n", 0) > 0 and stats.get("p50_ms", 0) > 0
                    and stats.get("p99_ms", 0) >= stats.get("p50_ms", 0)):
                errs.append(f"bad stats for {op}: {stats}")
    ab = doc.get("sustained_ab", {})
    if not (ab.get("baseline_lps", 0) > 0 and ab.get("ratio", 0) > 0):
        errs.append(f"bad sustained_ab: {ab}")
    if not doc.get("grid"):
        errs.append("empty grid")
    return errs


def main() -> None:
    _cpu_grid_env()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="bounded grid + short A/B (CI lane)")
    ap.add_argument("--reps", type=int, default=0,
                    help="timed queries per op (default: 200 full, "
                         "30 smoke)")
    ap.add_argument("--ab-cycles", type=int, default=0,
                    help="ingest+flush cycles per A/B side (default: "
                         "40 full, 8 smoke)")
    ap.add_argument("--min-ab-ratio", type=float, default=0.5,
                    help="gate: loaded/baseline ingest rate floor "
                         "(1-core CI rigs timeshare the query threads "
                         "onto the ingest core, so the smoke floor is "
                         "scheduling slack, not the zero-regression "
                         "claim — the committed full run owns that)")
    ap.add_argument("--out", default=os.path.join(REPO, "QUERY_BENCH.json"))
    args = ap.parse_args()
    reps = args.reps or (30 if args.smoke else 200)
    ab_cycles = args.ab_cycles or (8 if args.smoke else 40)
    if args.smoke:
        grid_spec = [(128, 0, True), (128, 4, True)]
    else:
        grid_spec = [(s, sh, ing) for s in (256, 1024, 4096)
                     for sh in (0, 4) for ing in (False, True)]

    grid = []
    for series, shards, ingest in grid_spec:
        print(f"cell series={series} shards={shards} ingest={ingest}",
              flush=True)
        cell = bench_cell(series, shards, ingest, reps)
        grid.append(cell)
        host = cell["ops"]["quantiles_host"]
        dev = cell["ops"]["quantiles_device"]
        print(f"  host p50={host['p50_ms']}ms p99={host['p99_ms']}ms | "
              f"device p50={dev['p50_ms']}ms p99={dev['p99_ms']}ms",
              flush=True)

    print(f"sustained A/B ({ab_cycles} cycles/side)", flush=True)
    ab = bench_sustained_ab(ab_cycles)
    print(f"  baseline={ab['baseline_lps']:.0f} l/s "
          f"with-queries={ab['with_queries_lps']:.0f} l/s "
          f"ratio={ab['ratio']}", flush=True)

    import subprocess
    try:
        rev = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=REPO, capture_output=True, text=True,
                             timeout=10).stdout.strip()
    except Exception:
        rev = "unknown"
    doc = {"ts_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "rev": rev, "smoke": args.smoke,
           "platform": os.environ.get("JAX_PLATFORMS", ""),
           "grid": grid, "sustained_ab": ab}
    errs = validate_schema(doc)
    if errs:
        print("SCHEMA INVALID:\n  " + "\n  ".join(errs))
        sys.exit(1)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}")
    if ab["ratio"] < args.min_ab_ratio:
        print(f"FAIL: ingest rate regressed under query load "
              f"(ratio {ab['ratio']} < {args.min_ab_ratio})")
        sys.exit(1)
    print("QUERY BENCH OK")


if __name__ == "__main__":
    main()
