"""End-to-end Server.flush() latency, with phase breakdown.

This harness times a real Server with native
C++ ingest, S unique histogram series driven through the DogStatsD
packet path (parse -> directory -> device pool), then one full
Server.flush() — swap, device extraction, InterMetric generation, sink
fan-out to a blackhole sink — against the reference's 10s interval
budget (flusher.go:28-131; the north-star latency metric of
BASELINE.md).

Default: one size, written to E2E_FLUSH.json. With --scaling: a curve
of sizes up to 1M series (on TPU), written to E2E_SCALING.json.

With --chunked: the flush runs under the deadline governor
(flush_chunk_target_ms, default 500ms here) and each row reports
`bounded_degradation` — chunk count, max/mean per-chunk latency, and
whether the worst chunk stayed near the sub-interval target. This is
the CPU story for sizes past the cardinality knee: the flush exceeds
the 10s budget, but in bounded, watchdog-visible steps.

With --shards N: the device-sharded series axis (ops/series_shard.py,
`series_shards` in config). Single-size mode runs the flush over an
N-way shard mesh; with --scaling it ALSO appends a sharded row set
where the series count grows proportionally with the shard count
(base, 1x) -> (2*base, 2x) -> ... (N*base, Nx) — the capacity claim in
one curve: per-flush device fold time should stay ~flat as series and
shards scale together. On hosts with fewer than N devices the process
re-execs itself with --xla_force_host_platform_device_count=N (the CPU
mesh CI and this bench share that trick); a real TPU with enough chips
runs as-is.

Env: VENEUR_E2E_SERIES (default 2^20 on TPU, 2^16 elsewhere),
VENEUR_E2E_SAMPLES_PER_SERIES (default 4),
VENEUR_E2E_SCALING_SIZES (comma-separated override),
VENEUR_E2E_CHUNK_TARGET_MS (with --chunked, default 500).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_datagrams(series: int, samples_per_series: int,
                    max_len: int) -> list[bytes]:
    """Multi-line DogStatsD datagrams covering `series` unique timer
    series (name + one tag varied), each series hit
    `samples_per_series` times."""
    datagrams = []
    lines = []
    size = 0
    for rep in range(samples_per_series):
        for i in range(series):
            line = b"e2e.m%d:%d|ms|#shard:%d" % (i, (i * 7 + rep) % 1000,
                                                 i % 64)
            if size + len(line) + 1 > max_len:
                datagrams.append(b"\n".join(lines))
                lines, size = [], 0
            lines.append(line)
            size += len(line) + 1
    if lines:
        datagrams.append(b"\n".join(lines))
    return datagrams


def _backend() -> str:
    import jax

    return jax.default_backend()


def run_one(series: int, per: int, persist_partial: bool = False,
            chunk_target_ms: int = 0, shards: int = 0) -> dict:
    """Cold pass (pool growth + XLA compile) then one steady-state
    ingest+flush round — the reference's world, where every 10s interval
    sees the same series again and reuses everything (metrics expire at
    flush, README.md:135-137, so each round re-registers all series in a
    fresh epoch). Returns the steady-state measurements."""
    from veneur_tpu.core.config import Config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.blackhole import BlackholeMetricSink

    cfg = Config(interval="10s", percentiles=[0.5, 0.9, 0.99],
                 aggregates=["min", "max", "count"],
                 tpu_native_ingest=True, num_workers=1, num_readers=1,
                 flush_chunk_target_ms=chunk_target_ms,
                 series_shards=shards)
    srv = Server(cfg, metric_sinks=[BlackholeMetricSink()])
    if not srv.native_mode:
        print("warning: native ingest unavailable; using Python parser",
              file=sys.stderr)
    if shards > 1 and srv.workers[0].series_shards != shards:
        print(f"warning: series_shards={shards} did not engage "
              f"(have {srv.workers[0].series_shards}); measuring the "
              "single-device path", file=sys.stderr)

    t0 = time.perf_counter()
    datagrams = build_datagrams(series, per, cfg.metric_max_length)
    gen_s = time.perf_counter() - t0

    rounds = []
    # model the production cadence: Server.start spawns a series-sync
    # thread that adopts new-series registrations during the interval;
    # this harness drives flush() by hand, so sweep at the equivalent
    # cadence inside the ingest loop (the cost lands in ingest_s, where
    # it lands in production — and off the swap phase's ingest lock)
    sync_every = max(1, len(datagrams) // 8)
    # chunked runs need one extra warmup round: the governor's rate EWMA
    # re-sizes chunks after the cold flush, and each new chunk shape is
    # an XLA compile that would otherwise land in the measured round
    n_rounds = 3 if chunk_target_ms else 2
    for rnd in range(n_rounds):
        t0 = time.perf_counter()
        for i, d in enumerate(datagrams):
            srv.process_metric_packet(d)
            if i % sync_every == sync_every - 1:
                srv.sync_native_series_once()
        ingest_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        final = srv.flush()
        flush_s = time.perf_counter() - t0
        rounds.append((ingest_s, flush_s, dict(srv.last_flush_phases),
                       len(final), dict(srv.last_flush_chunks),
                       dict(srv.last_flush_transfers)))
        if rnd == 0 and persist_partial:
            # persist the cold round immediately: a cold-marked partial
            # beats losing the evidence if the run is cut
            root = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))
            partial = {
                "platform": _backend(), "series": series,
                "PARTIAL": "cold round only; steady-state round was "
                           "still running when this was written",
                "cold_ingest_s": round(rounds[0][0], 3),
                "cold_flush_s": round(rounds[0][1], 3),
                "cold_flush_phases": {k: round(v, 3)
                                      for k, v in rounds[0][2].items()},
            }
            tmp = os.path.join(root, "E2E_FLUSH.json.tmp")
            with open(tmp, "w") as f:
                json.dump(partial, f, indent=1)
            os.replace(tmp, os.path.join(root, "E2E_FLUSH.json"))
    srv.shutdown()
    cold_ingest_s, cold_flush_s, _, _, _, _ = rounds[0]
    ingest_s, flush_s, phases, n_final, chunks, transfers = rounds[-1]

    n_samples = series * per
    bounded = {}
    if chunk_target_ms and chunks:
        # the degraded-mode contract: the flush may exceed the interval,
        # but every CHUNK must land near the sub-interval target — that
        # is what keeps the watchdog deferral honest
        bounded = {
            "chunk_target_ms": chunks["chunk_target_ms"],
            "chunks": chunks["chunks"],
            "chunk_rows_max": chunks["chunk_rows_max"],
            "chunk_max_s": round(chunks["chunk_max_s"], 3),
            "chunk_mean_s": round(chunks["chunk_mean_s"], 3),
            # steady-state verdict: max chunk within 2x target (the
            # schedule converges to the target, it does not clamp at it)
            "chunk_under_target": (chunks["chunk_max_s"]
                                   < 2 * chunks["chunk_target_ms"] / 1000.0),
        }
    return {
        "series": series,
        **({"series_shards": shards} if shards > 1 else {}),
        "samples": n_samples,
        "datagram_gen_s": round(gen_s, 3),
        "cold_ingest_s": round(cold_ingest_s, 3),
        "cold_flush_s": round(cold_flush_s, 3),
        "ingest_s": round(ingest_s, 3),
        "ingest_samples_per_s": round(n_samples / ingest_s, 1),
        "flush_total_s": round(flush_s, 3),
        "flush_phases": {k: round(v, 3) for k, v in phases.items()},
        "inter_metrics": n_final,
        "inter_metrics_per_series": round(n_final / series, 2),
        "budget_s": 10.0,
        "fits_interval": flush_s < 10.0,
        "vs_baseline": round(10.0 / flush_s, 2),
        **({"bounded_degradation": bounded} if bounded else {}),
        **({"transfer_bytes": transfers} if transfers else {}),
    }


def _shards_arg(argv: list) -> int:
    for i, a in enumerate(argv):
        if a == "--shards" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith("--shards="):
            return int(a.split("=", 1)[1])
    return 0


def _ensure_devices(shards: int) -> None:
    """Re-exec with a forced host-device count when the backend cannot
    give `shards` devices (the CPU case — same trick as the CI sharding
    lane). A real TPU with enough chips passes through untouched. Must
    run before any jax computation so the flag lands at backend init;
    _backend() above only reads the platform name, which is safe."""
    import jax

    if jax.device_count() >= shards:
        return
    if os.environ.get("_VENEUR_E2E_SHARDS_REEXEC"):
        print(f"error: {jax.device_count()} devices even after forcing "
              f"{shards}; cannot run sharded", file=sys.stderr)
        sys.exit(2)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={shards} "
                        + env.get("XLA_FLAGS", "")).strip()
    env["_VENEUR_E2E_SHARDS_REEXEC"] = "1"
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def main() -> None:
    shards = _shards_arg(sys.argv[1:])
    if shards > 1:
        _ensure_devices(shards)
    backend = _backend()
    on_tpu = backend == "tpu"
    per = int(os.environ.get("VENEUR_E2E_SAMPLES_PER_SERIES", 4))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # --chunked: run the flush under the deadline governor so sizes past
    # the host's cardinality knee report bounded_degradation (per-chunk
    # latency vs flush_chunk_target_ms) instead of one unbounded program
    chunk_ms = (int(os.environ.get("VENEUR_E2E_CHUNK_TARGET_MS", 500))
                if "--chunked" in sys.argv[1:] else 0)

    if "--scaling" in sys.argv[1:]:
        env_sizes = os.environ.get("VENEUR_E2E_SCALING_SIZES")
        if env_sizes:
            sizes = tuple(int(s) for s in env_sizes.split(","))
        else:
            sizes = ((1 << 16, 1 << 18, 1 << 20) if on_tpu
                     else (1 << 14, 1 << 16, 1 << 17))
        rows = []
        for s in sizes:
            row = run_one(s, per, chunk_target_ms=chunk_ms)
            rows.append(row)
            print(json.dumps({"series": s,
                              "flush_total_s": row["flush_total_s"],
                              "fits_interval": row["fits_interval"],
                              **({"bounded_degradation":
                                  row["bounded_degradation"]}
                                 if "bounded_degradation" in row else {})}),
                  flush=True)
        row_keys = ("series", "series_shards", "ingest_samples_per_s",
                    "flush_total_s", "flush_phases", "fits_interval",
                    "bounded_degradation", "transfer_bytes")
        out = {
            "platform": backend,
            "note": ("end-to-end Server.flush latency vs series count; "
                     "the flush programs are O(series)"),
            "samples_per_series": per,
            "budget_s": 10.0,
            **({"flush_chunk_target_ms": chunk_ms} if chunk_ms else {}),
            "rows": [{k: r[k] for k in row_keys if k in r} for r in rows],
            "scaling_largest_vs_smallest": round(
                rows[-1]["flush_total_s"] / max(rows[0]["flush_total_s"],
                                                1e-9), 2),
        }
        if shards > 1:
            # the capacity curve: series grow WITH the shard count from
            # the smallest size, so per-flush device fold (extract) time
            # flat-ish across the set is the evidence that sharding buys
            # proportional series capacity per host
            srows = []
            d = 1
            while d <= shards:
                r = run_one(sizes[0] * d, per, chunk_target_ms=chunk_ms,
                            shards=d)
                srows.append({k: r[k] for k in row_keys if k in r})
                print(json.dumps({"series": sizes[0] * d,
                                  "series_shards": d,
                                  "extract_s":
                                      r["flush_phases"].get("extract_s"),
                                  "flush_total_s": r["flush_total_s"]}),
                      flush=True)
                d *= 2
            ex = [r["flush_phases"].get("extract_s", 0.0) for r in srows]
            out["sharded_rows"] = srows
            # per-shard normalization is the honest readout on a
            # shared-silicon rig: the forced host devices all run on the
            # same CPU cores, so wall-clock extract still grows with
            # TOTAL series even though each shard's rows, fold program,
            # and readback bytes are constant by construction. The flat
            # curve the layout buys shows up here as d2h_bytes_per_shard
            # and device_chunk_s_per_shard; wall-clock flatness needs
            # real per-shard silicon.
            out["sharded_per_shard"] = [
                {"series_shards": max(int(r.get("series_shards", 1)), 1),
                 "d2h_bytes_per_shard":
                     r["transfer_bytes"]["d2h_bytes"]
                     // max(int(r.get("series_shards", 1)), 1),
                 "device_chunk_s_per_shard": round(
                     r["bounded_degradation"]["chunk_max_s"]
                     / max(int(r.get("series_shards", 1)), 1), 4)}
                for r in srows]
            out["sharded_note"] = (
                "series scale proportionally with series_shards from the "
                "base size; per-shard rows and d2h readback bytes are "
                "constant by construction (see sharded_per_shard). On "
                "this rig the forced host devices share the CPU cores, "
                "so wall-clock extract_s still grows with total series "
                "(sharded_extract_max_over_min); flat wall clock "
                "requires real per-shard silicon.")
            out["sharded_extract_max_over_min"] = round(
                max(ex) / max(min(ex), 1e-9), 3)
        with open(os.path.join(root, "E2E_SCALING.json"), "w") as f:
            json.dump(out, f, indent=1)
        return

    series = int(os.environ.get("VENEUR_E2E_SERIES",
                                1 << 20 if on_tpu else 1 << 16))
    out = {"platform": backend,
       **run_one(series, per, persist_partial=True,
                 chunk_target_ms=chunk_ms, shards=shards)}
    with open(os.path.join(root, "E2E_FLUSH.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"metric": "e2e_flush_latency_s",
                      "value": out["flush_total_s"], "unit": "s",
                      "vs_baseline": out["vs_baseline"],
                      "platform": backend}))


if __name__ == "__main__":
    main()
