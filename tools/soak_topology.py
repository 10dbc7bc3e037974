"""Cluster-topology soak: local -> proxy -> N globals with ring churn.

The reference's multi-node story is tested in-process (SURVEY.md §4:
real servers on loopback, no cluster fixture); this soak does the same
at soak length for the TPU build's distributed tier: one local Server
forwards every interval through a ProxyServer (consistent ring) to
global Servers ingesting over real gRPC, while the ring membership
CHURNS mid-run (a global joins, another leaves — the discovery-refresh
path of reference proxy.go:491-515 / proxysrv SetDestinations
:148-176).

Conservation is the pass criterion, checked with exactly-summable
metrics: every veneurglobalonly counter increment and every histogram
sample sent by the local must be accounted for in the final cross-
global flush — a series may migrate between globals at a churn point,
but its pieces must add up, and a clean membership change must drop
nothing (proxy.drops == 0).

Writes TOPOLOGY_SOAK.json at the repo root and prints one JSON line.

Env knobs: VENEUR_SOAK_INTERVALS (default 30; 60 under mesh — the
shard_map path's leak window needs the longer run to separate compile-
cache warmup from steady-state growth), VENEUR_SOAK_HISTO_SERIES
(default 1500), VENEUR_SOAK_COUNTER_SERIES (default 500).

RSS-plateau confirmation: --min-intervals N and/or --min-duration D
("90m", "3h") extend the run for a multi-hour leak hunt. Post-warmup,
RSS is sampled in fixed interval windows and the artifact records the
per-window rss_growth_per_interval_mb series; a healthy process
plateaus, i.e. the series falls monotonically (within a noise floor —
classify_rss_plateau). When an extended run was requested the plateau
is a PASS CRITERION: a flat-or-rising growth series exits nonzero. The
short default run records the series without gating on it (too few
windows to judge).

VENEUR_SOAK_MESH=1 (VERDICT r4 item 7): the global tier runs
mesh-sharded — each global Server gets `tpu_mesh_devices: 8` over a
virtual 8-device CPU mesh (xla_force_host_platform_device_count), so
the imported digests merge through the shard_map collective path
(distributed/mesh.py build_sharded_staged_fold) instead of the
single-device pools, under the same ring churn and with the same exact
conservation criterion. The artifact records `mesh_global: true`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import tracemalloc

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _soak_common import rss_mb, write_artifact  # noqa: E402

# Below this, window-to-window RSS-growth jitter is allocator noise
# (arena reuse, page-cache rounding), not signal: a "rise" smaller than
# the floor never fails the plateau check.
RSS_NOISE_MB_PER_INTERVAL = 0.05


def churn_rebound_windows(rss_windows: list[dict],
                          churn_intervals: list[int]) -> list[int]:
    """Window indices whose growth a membership change can legitimately
    elevate: the window whose span contains the churn interval, plus
    the one after it (a join/leave re-plumbs destinations and triggers
    fresh XLA compiles whose allocations can trail past the containing
    window). classify_rss_plateau restarts its monotone chain at these
    indices instead of calling the expected rebound a leak."""
    out: set[int] = set()
    for k, w in enumerate(rss_windows):
        lo = w["upto_interval"] - w["intervals"]
        for c in churn_intervals:
            if lo <= c < w["upto_interval"]:
                out.add(k)
                out.add(k + 1)
    return sorted(i for i in out if i < len(rss_windows))


def classify_rss_plateau(growth_series: list[float],
                         tol: float = RSS_NOISE_MB_PER_INTERVAL,
                         rebound_windows: list[int] = ()) -> dict:
    """Judge a post-warmup rss_growth_per_interval_mb window series.

    A plateauing process leaks less per interval as caches fill, so the
    series must be monotonically falling: each window's growth at most
    the previous window's plus the noise floor. Windows listed in
    `rebound_windows` (from churn_rebound_windows) are excused: a
    membership change recompiles the forward path, so the window
    straddling it rises for a real, bounded reason — the chain restarts
    there, and the TAIL after the last excused window must still fall.
    Returns the verdict, the first offending window index (None when
    ok), how many rises were excused as churn rebounds, and whether
    there were enough windows to judge at all (fewer than 3 judges
    nothing — one comparison can't distinguish a trend from jitter).

    Pure — no clocks, no I/O — so the tier-1 suite pins it against
    synthetic series while the multi-hour soak consumes it live.
    """
    excused = set(rebound_windows)
    judgeable = len(growth_series) >= 3
    rising_at = None
    excused_rebounds = 0
    for k in range(1, len(growth_series)):
        if growth_series[k] > growth_series[k - 1] + tol:
            if k in excused:
                excused_rebounds += 1
                continue
            rising_at = k
            break
    return {
        "judgeable": judgeable,
        "monotonic_falling": rising_at is None,
        "rising_at_window": rising_at,
        "excused_rebounds": excused_rebounds,
        "plateau_ok": (rising_at is None) if judgeable else True,
    }


def attribute_tail_growth(rss_windows: list[dict],
                          tail_windows: int = 3) -> dict:
    """Attribute the plateau TAIL's residual growth (the carried
    ROADMAP item: ~0.06 MB/interval over the final windows) between
    the Python heap (tracemalloc delta, recorded per window as
    py_heap_growth_per_interval_mb) and the native remainder — XLA
    caches, gRPC, malloc arenas — which is everything RSS gained that
    the Python allocator never saw.

    Averages the final `tail_windows` windows and names the dominant
    side ("python_heap" / "native" / "none" when the tail is flat or
    shrinking). Pure — the tier-1 suite pins it on synthetic windows,
    the soak records it in the artifact verdict."""
    tail = [w for w in rss_windows
            if "py_heap_growth_per_interval_mb" in w][-tail_windows:]
    if not tail:
        return {"judgeable": False, "windows": 0}
    rss = sum(w["growth_per_interval_mb"] for w in tail) / len(tail)
    py = sum(w["py_heap_growth_per_interval_mb"] for w in tail) / len(tail)
    native = rss - py
    if rss > 0:
        # clamp: a shrinking python heap inside growing RSS means the
        # growth is all native (and vice versa) — fractions stay [0,1]
        py_frac = min(1.0, max(0.0, py / rss))
        dominant = "python_heap" if py_frac >= 0.5 else "native"
    else:
        py_frac = 0.0
        dominant = "none"
    return {
        "judgeable": True,
        "windows": len(tail),
        "rss_growth_per_interval_mb": round(rss, 3),
        "py_heap_growth_per_interval_mb": round(py, 3),
        "native_growth_per_interval_mb": round(native, 3),
        "py_heap_fraction": round(py_frac, 3),
        "dominant": dominant,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--min-intervals", type=int, default=0,
                    help="run at least this many flush intervals "
                         "(floors VENEUR_SOAK_INTERVALS; turns the "
                         "plateau series into a pass criterion)")
    ap.add_argument("--min-duration", default=None,
                    help="run until at least this much wall time has "
                         "passed, e.g. 90m or 3h (extends the interval "
                         "loop; turns the plateau series into a pass "
                         "criterion)")
    ap.add_argument("--rss-window", type=int, default=0,
                    help="intervals per RSS-growth window (default: "
                         "post-warmup span / 6, floored at 5)")
    args = ap.parse_args()
    mesh_global = os.environ.get("VENEUR_SOAK_MESH") == "1"
    if mesh_global:
        # the mesh globals shard over 8 virtual CPU devices, the same
        # rig the multichip dryrun uses; XLA_FLAGS must say so before
        # the backend starts (nothing above has imported jax)
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append("--xla_force_host_platform_device_count=8")
        os.environ["XLA_FLAGS"] = " ".join(flags)

    from veneur_tpu.core.config import Config
    from veneur_tpu.core.flusher import device_quantiles, \
        generate_inter_metrics
    from veneur_tpu.core.metrics import HistogramAggregates, MetricType
    from veneur_tpu.core.server import Server
    from veneur_tpu.distributed.forward import install_forwarder
    from veneur_tpu.distributed.import_server import ImportServer
    from veneur_tpu.distributed.proxy import ProxyServer

    from veneur_tpu.core.config import parse_duration

    intervals = max(int(os.environ.get("VENEUR_SOAK_INTERVALS",
                                       60 if mesh_global else 30)),
                    args.min_intervals)
    min_duration_s = (parse_duration(args.min_duration)
                      if args.min_duration else 0.0)
    # an extended run was explicitly requested: the plateau series has
    # enough windows to be a pass criterion, not just a recording
    plateau_gates = bool(args.min_intervals or args.min_duration)
    s_histo = int(os.environ.get("VENEUR_SOAK_HISTO_SERIES", 1500))
    s_counter = int(os.environ.get("VENEUR_SOAK_COUNTER_SERIES", 500))
    pcts = [0.5, 0.99]
    aggs = ["min", "max", "count"]

    rss0 = rss_mb()
    t_start = time.perf_counter()

    globals_ = []
    for _ in range(3):
        if mesh_global:
            # mesh sharding requires one worker (the mesh IS the
            # parallelism; config.py validation)
            cfg = Config(interval="10s", percentiles=pcts,
                         aggregates=aggs, num_workers=1,
                         tpu_mesh_devices=8)
        else:
            cfg = Config(interval="10s", percentiles=pcts,
                         aggregates=aggs, num_workers=2)
        srv = Server(cfg)
        imp = ImportServer(srv)
        port = imp.start_grpc()
        globals_.append((srv, imp, port))

    def dests(idxs):
        return [f"127.0.0.1:{globals_[i][2]}" for i in idxs]

    # start with globals 0+1 in the ring; 2 joins mid-run, 1 leaves later
    proxy = ProxyServer(dests([0, 1]), max_idle_conns=8)
    pport = proxy.start_grpc()

    lcfg = Config(interval="10s", percentiles=pcts, aggregates=aggs,
                  forward_address=f"127.0.0.1:{pport}",
                  forward_use_grpc=True)
    local = Server(lcfg)
    install_forwarder(local)

    def received_total() -> int:
        return sum(imp.received_metrics for _, imp, _ in globals_)

    join_at = intervals // 3
    leave_at = 2 * intervals // 3
    churn_events = []
    forward_waits = []
    per_interval = s_histo + s_counter
    stalled_intervals = 0
    # RSS snapshot once the compile caches have filled: the early
    # intervals trace+compile every shard_map/flush specialization (the
    # 166->553MB growth of the first mesh capture was front-loaded
    # here), so the leak signal is rss_end - rss_after_warmup, not
    # rss_end - rss_start
    warmup_intervals = min(10, intervals)
    rss_warm = None
    # fixed-size post-warmup windows for the plateau series: each
    # closes with its growth-per-interval, the judgment the multi-hour
    # confirmation runs on
    rss_win_len = args.rss_window or max(
        5, (intervals - warmup_intervals) // 6)
    rss_windows: list[dict] = []
    rss_win_prev = None
    rss_win_prev_traced = None
    rss_win_start = warmup_intervals

    def close_rss_window(upto: int) -> None:
        nonlocal rss_win_prev, rss_win_prev_traced, rss_win_start
        if rss_win_prev is None or upto <= rss_win_start:
            return
        cur = rss_mb()
        cur_traced = tracemalloc.get_traced_memory()[0] / 1048576.0
        n = upto - rss_win_start
        # per-window python-heap delta alongside the RSS delta: the
        # pair is what attribute_tail_growth splits into python-heap vs
        # native growth for the artifact verdict
        rss_windows.append({
            "upto_interval": upto,
            "rss_mb": round(cur, 1),
            "intervals": n,
            "growth_per_interval_mb": round(
                (cur - rss_win_prev) / n, 3),
            "py_heap_growth_per_interval_mb": round(
                (cur_traced - (rss_win_prev_traced or 0.0)) / n, 3),
        })
        rss_win_prev, rss_win_prev_traced = cur, cur_traced
        rss_win_start = upto
    # Python-heap attribution for the post-warmup accrual: the RSS
    # delta alone can't name a retainer. Snapshot the traced heap at
    # the warmup boundary and diff it against the end — the top
    # growers (by file:line) go into the artifact as tracemalloc_top.
    tracemalloc.start(10)
    tm_warm = None
    stall_events = []

    def forward_path_stats() -> dict:
        """Who's wedged: the local's forward client vs the proxy's
        downstream clients (rpc.ForwardClient.stats on both hops)."""
        out = {"proxy": proxy.forward_stats()}
        fwd = getattr(local, "forwarder", None)
        client = getattr(fwd, "client", None)
        if client is not None:
            out["local_forward"] = client.stats()
        return out

    it = 0
    while (it < intervals
           or (min_duration_s
               and time.perf_counter() - t_start < min_duration_s)):
        if it == warmup_intervals:
            rss_warm = rss_mb()
            rss_win_prev = rss_warm
            rss_win_prev_traced = \
                tracemalloc.get_traced_memory()[0] / 1048576.0
            tm_warm = tracemalloc.take_snapshot()
        elif (it > warmup_intervals
              and (it - warmup_intervals) % rss_win_len == 0):
            close_rss_window(it)
        if it == join_at:
            proxy.set_destinations(dests([0, 1, 2]))
            churn_events.append({"interval": it, "event": "join",
                                 "members": 3})
        elif it == leave_at:
            proxy.set_destinations(dests([0, 2]))
            churn_events.append({"interval": it, "event": "leave",
                                 "members": 2})
        # the packet path end to end: multi-metric datagrams through the
        # parser, not direct worker injection
        # veneurglobalonly so the GLOBAL side emits the .count aggregate
        # (mixed scope would emit it locally — flusher.go:61-74's
        # double-count avoidance — leaving nothing exactly-summable on
        # the global end of the pipeline)
        lines = []
        for i in range(s_histo):
            lines.append(b"soak.h%d:%d|ms|#shard:%d,veneurglobalonly"
                         % (i, (i * 31 + it) % 997, i % 16))
        for i in range(s_counter):
            lines.append(b"soak.c%d:2|c|#veneurglobalonly" % i)
        max_len = lcfg.metric_max_length
        batch, size = [], 0
        for line in lines:
            if size + len(line) + 1 > max_len and batch:
                local.process_metric_packet(b"\n".join(batch))
                batch, size = [], 0
            batch.append(line)
            size += len(line) + 1
        if batch:
            local.process_metric_packet(b"\n".join(batch))
        before = received_total()
        t0 = time.perf_counter()
        local.flush()
        flush_s = time.perf_counter() - t0
        ok = False
        deadline = time.time() + 30.0
        while time.time() < deadline:
            if received_total() - before >= per_interval:
                ok = True
                break
            time.sleep(0.02)
        forward_waits.append(round(time.perf_counter() - t0, 3))
        # stream progress unbuffered: the artifact only lands at the
        # END of the run, so a wedge that outlives the harness timeout
        # (the 120-interval repro died at its 50-min cap with an empty
        # log) must leave its last-known-good interval and the wedged
        # side on stderr as it happens
        if not ok or flush_s > 15.0 or it % 10 == 0:
            print(json.dumps({
                "interval": it, "flush_s": round(flush_s, 2),
                "received_delta": received_total() - before,
                "expected": per_interval, "ok": ok,
                "rss_mb": round(rss_mb(), 1),
                **({} if ok else forward_path_stats()),
            }), file=sys.stderr, flush=True)
        if not ok:
            stalled_intervals += 1
            # name the wedged side instead of timing out silently:
            # record both hops' client stats at the stall (per-attempt
            # durations, error classes, consecutive failures,
            # reconnects) — ROADMAP's 120-interval mesh stall item
            stall_events.append({
                "interval": it,
                "received_delta": received_total() - before,
                "expected": per_interval,
                **forward_path_stats(),
            })
        it += 1

    intervals = it  # actual count (a --min-duration run overshoots the plan)
    close_rss_window(it)
    rss_plateau = classify_rss_plateau(
        [w["growth_per_interval_mb"] for w in rss_windows],
        rebound_windows=churn_rebound_windows(
            rss_windows, [e["interval"] for e in churn_events]))
    # the carried ROADMAP attribution: who owns the tail's residual
    # growth — recorded inside the verdict the soak is judged on
    rss_plateau["tail_attribution"] = attribute_tail_growth(rss_windows)

    # end-of-loop heap snapshot BEFORE the final accounting flushes
    # below allocate their own transient state: the diff should show
    # steady-state growth, not teardown noise
    rss_end = rss_mb()
    tracemalloc_top = []
    if tm_warm is not None:
        tm_end = tracemalloc.take_snapshot()
        growth = [s for s in tm_end.compare_to(tm_warm, "lineno")
                  if s.size_diff > 0]
        traced_growth = sum(s.size_diff for s in growth)
        for s in growth[:12]:
            frame = s.traceback[0]
            tracemalloc_top.append({
                "where": f"{frame.filename}:{frame.lineno}",
                "size_diff_kb": round(s.size_diff / 1024.0, 1),
                "count_diff": s.count_diff,
            })
    else:
        traced_growth = 0
    tracemalloc.stop()
    forward_path_final = forward_path_stats()

    # final accounting: flush every global (including the one that left
    # the ring — its accumulated state still exists) and sum
    qs = device_quantiles(pcts, HistogramAggregates.from_names(aggs))
    counter_total = 0.0
    histo_count_total = 0.0
    for srv, _, _ in globals_:
        metrics = []
        for w, lock in zip(srv.workers, srv._worker_locks):
            with lock:
                snap = w.flush(qs, 10.0)
            metrics.extend(generate_inter_metrics(snap, False, pcts,
                                                  HistogramAggregates
                                                  .from_names(aggs)))
        for m in metrics:
            if m.type == MetricType.COUNTER and m.name.startswith("soak.c"):
                counter_total += m.value
            if m.name.endswith(".count") and m.name.startswith("soak.h"):
                histo_count_total += m.value

    expected_counter = 2.0 * s_counter * intervals
    expected_histo = float(s_histo * intervals)
    wall_s = time.perf_counter() - t_start

    out = {
        "mesh_global": mesh_global,
        "intervals": intervals,
        "histo_series": s_histo,
        "counter_series": s_counter,
        "churn_events": churn_events,
        "samples_sent": per_interval * intervals,
        "counter_total_expected": expected_counter,
        "counter_total_observed": counter_total,
        "histo_count_expected": expected_histo,
        "histo_count_observed": histo_count_total,
        "conservation_ok": (counter_total == expected_counter
                            and histo_count_total == expected_histo),
        "proxy_drops": proxy.drops,
        "stalled_intervals": stalled_intervals,
        "stall_events": stall_events,
        "forward_path": forward_path_final,
        "forward_wait_p50_s": sorted(forward_waits)[len(forward_waits) // 2],
        "forward_wait_max_s": max(forward_waits),
        "wall_s": round(wall_s, 1),
        "rss_start_mb": round(rss0, 1),
        "rss_after_warmup_mb": (round(rss_warm, 1)
                                if rss_warm is not None else None),
        "rss_end_mb": round(rss_end, 1),
        # post-warmup accrual, decomposed: how much of the RSS growth
        # the Python allocator can even see (the remainder is native —
        # XLA buffers, gRPC, malloc arenas — or tracemalloc's own
        # bookkeeping overhead inflating RSS but not the diff)
        "rss_growth_post_warmup_mb": (
            round(rss_end - rss_warm, 1) if rss_warm is not None else None),
        "rss_growth_per_interval_mb": (
            round((rss_end - rss_warm)
                  / max(1, intervals - warmup_intervals), 3)
            if rss_warm is not None else None),
        # the plateau series: post-warmup RSS growth per interval, per
        # window — falling means caches are filling, flat-or-rising
        # means a leak (the multi-hour confirmation's pass criterion)
        "rss_window_intervals": rss_win_len,
        "rss_windows": rss_windows,
        "rss_plateau": rss_plateau,
        "rss_plateau_gates": plateau_gates,
        "traced_py_growth_mb": round(traced_growth / 1048576.0, 2),
        "tracemalloc_top": tracemalloc_top,
    }

    local.shutdown()
    proxy.stop()
    for srv, imp, _ in globals_:
        imp.stop()
        srv.shutdown()

    write_artifact("TOPOLOGY_SOAK_MESH.json" if mesh_global
                   else "TOPOLOGY_SOAK.json", out)
    print(json.dumps({"metric": "topology_soak_conservation",
                      "value": 1.0 if out["conservation_ok"] else 0.0,
                      "unit": "bool",
                      "drops": out["proxy_drops"],
                      "stalled_intervals": out["stalled_intervals"],
                      "rss_plateau_ok": rss_plateau["plateau_ok"]}))
    if not out["conservation_ok"] or out["proxy_drops"]:
        sys.exit(1)
    if plateau_gates and rss_plateau["judgeable"] \
            and not rss_plateau["plateau_ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
