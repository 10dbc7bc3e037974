"""Seeded fault-injection chaos soak for the sink delivery layer.

A native-reader server under steady within-capacity load,
flushing into three real HTTP sinks whose openers are wrapped in
seeded FaultyOpeners (utils/faults.py): datadog rides a deterministic
outage window (down_ranges) that forces a full breaker
open → half-open → closed cycle; signalfx takes probabilistic 5xx /
resets / slow responses / payload rejections; prometheus takes
connection refusals. The soak proves the delivery contract under
sustained fault pressure:

1. CONSERVATION — for every sink, exactly:
   accepted == delivered + declared-dropped + still-spilled.
   Nothing is silently lost, at any fault mix.
2. DEADLINES HELD — no flush tick's sink_flush_s exceeds the interval
   (+ scheduling slack): retry budgets clip to the tick, a sick sink
   never stalls the emit stage.
3. BREAKER CYCLE — the datadog manager records at least one full
   open → half_open → closed transition sequence.

Writes FAULT_SOAK.json at the repo root and prints one JSON line;
exits nonzero on any violated invariant.

Usage: python tools/soak_faults.py [--duration 45] [--quick]
       [--seed 42] [--pps 3000]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _soak_common import (  # noqa: E402
    drain_tail, make_blaster, write_artifact)

PORT = 19127
INTERVAL_S = 1.0
# scheduler slack on a busy CPU host: the join timeout itself is the
# interval, so anything past interval + slack means a sink thread held
# the emit stage — exactly what the per-flush delivery deadline forbids
DEADLINE_SLACK_S = 0.3


def has_breaker_cycle(transitions: list[str]) -> bool:
    """Ordered subsequence open → half_open → closed."""
    i = 0
    for want in ("open", "half_open", "closed"):
        while i < len(transitions) and transitions[i] != want:
            i += 1
        if i == len(transitions):
            return False
        i += 1
    return True


def build_faulty_sinks(seed: int):
    """Three HTTP sinks over seeded FaultyOpeners, each with a fast
    delivery policy sized to the 1s soak interval."""
    from veneur_tpu.sinks.datadog import DatadogMetricSink
    from veneur_tpu.sinks.delivery import DeliveryManager, DeliveryPolicy
    from veneur_tpu.sinks.prometheus import PrometheusExpositionSink
    from veneur_tpu.sinks.signalfx import SignalFxMetricSink
    from veneur_tpu.utils.faults import FaultPlan, FaultyOpener

    def policy(**kw):
        base = dict(retry_max=1, breaker_threshold=2,
                    spill_max_bytes=1 << 20, spill_max_payloads=64,
                    timeout_s=0.5, deadline_s=0.8,
                    backoff_base_s=0.02, backoff_max_s=0.1)
        base.update(kw)
        return DeliveryPolicy(**base)

    def manager(name, i, **kw):
        return DeliveryManager(name, policy(**kw),
                               rng=random.Random(seed * 1000 + i))

    # datadog: clean except a deterministic outage window in opener-call
    # indices — long enough that the breaker (threshold 2, retry_max 1)
    # must open, probe-fail across intervals, and close on recovery
    dd_opener = FaultyOpener(FaultPlan(seed=seed, down_ranges=[(6, 14)]))
    dd = DatadogMetricSink(
        interval=INTERVAL_S, flush_max_per_body=50_000, hostname="soak",
        tags=[], dd_hostname="https://dd.invalid", api_key="k",
        opener=dd_opener, delivery=manager("datadog", 1))

    # signalfx: the probabilistic mixed-fault diet (5xx, mid-body reset,
    # sub-timeout slow responses, permanent payload rejections)
    sfx_opener = FaultyOpener(FaultPlan(
        seed=seed + 1, p_5xx=0.15, p_reset=0.10, p_slow=0.10,
        p_reject=0.05, slow_s=0.05))
    sfx = SignalFxMetricSink(
        api_key="k", hostname="soak", endpoint_base="https://sfx.invalid",
        opener=sfx_opener, delivery=manager("signalfx", 2))

    # prometheus pushgateway: connection refusals (the cheapest fault —
    # exercises pure retry/backoff without HTTP semantics)
    prom_opener = FaultyOpener(FaultPlan(seed=seed + 2, p_refuse=0.25))
    prom = PrometheusExpositionSink(
        "https://prom.invalid/metrics/job/soak", opener=prom_opener,
        delivery=manager("prometheus", 3))

    openers = {"datadog": dd_opener, "signalfx": sfx_opener,
               "prometheus": prom_opener}
    return [dd, sfx, prom], openers


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=int, default=45)
    ap.add_argument("--quick", action="store_true",
                    help="CI lane: ~18s of load, whole run under 60s")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--pps", type=int, default=3000)
    args = ap.parse_args()
    duration = 18 if args.quick else args.duration
    pps = min(args.pps, 2000) if args.quick else args.pps

    from veneur_tpu.core.config import Config
    from veneur_tpu.core.server import Server

    cfg = Config(interval="1s", percentiles=[0.5, 0.99],
                 aggregates=["min", "max", "count"],
                 statsd_listen_addresses=[f"udp://127.0.0.1:{PORT}"],
                 tpu_native_ingest=True, tpu_native_readers=True,
                 num_workers=2, num_readers=2)
    sinks, openers = build_faulty_sinks(args.seed)
    srv = Server(cfg, metric_sinks=sinks)
    srv.start()

    stop = threading.Event()
    sent = {"packets": 0, "lines": 0, "garbage": 0}
    lock = threading.Lock()
    blasters = [make_blaster(PORT, t, stop, sent, lock,
                             pps=max(1, pps // 2)) for t in range(2)]
    for t in blasters:
        t.start()

    # monitor: per-completed-flush sink_flush_s (the deadline invariant
    # is per tick, so sample faster than the tick)
    max_sink_flush = {"s": 0.0, "ticks": 0}
    mon_stop = threading.Event()

    def monitor() -> None:
        last_count = -1
        while not mon_stop.is_set():
            count = srv.flush_count
            if count != last_count:
                last_count = count
                s = srv.last_flush_phases.get("sink_flush_s")
                if s is not None:
                    max_sink_flush["ticks"] += 1
                    if s > max_sink_flush["s"]:
                        max_sink_flush["s"] = s
            time.sleep(0.1)

    mon = threading.Thread(target=monitor, daemon=True)
    mon.start()

    time.sleep(duration)
    stop.set()
    for t in blasters:
        t.join(timeout=10)
    # two more ticks: the last interval's data flushes and spill retries
    # get their probe intervals
    time.sleep(2.5)
    drain_tail(srv)
    srv.shutdown()
    mon_stop.set()
    mon.join(timeout=5)

    managers = {rname: man for rname, man in srv._delivery_managers()}
    failures: list[str] = []
    delivery = {}
    for rname, man in managers.items():
        st = man.stats()
        delivery[rname] = st
        if not man.conserved():
            failures.append(
                f"{rname}: conservation violated (accepted="
                f"{st['accepted_payloads']} delivered="
                f"{st['delivered_payloads']} dropped="
                f"{st['dropped_payloads']} spilled="
                f"{st['spilled_payloads']})")
        if st["accepted_payloads"] == 0:
            failures.append(f"{rname}: no payloads offered (dead soak)")

    if max_sink_flush["s"] > INTERVAL_S + DEADLINE_SLACK_S:
        failures.append(
            f"flush deadline violated: sink_flush_s "
            f"{max_sink_flush['s']:.2f}s > "
            f"{INTERVAL_S + DEADLINE_SLACK_S:.2f}s")
    if max_sink_flush["ticks"] < 5:
        failures.append(
            f"too few observed flush ticks ({max_sink_flush['ticks']})")

    dd_trans = delivery["datadog"]["breaker_transitions"]
    if not has_breaker_cycle(dd_trans):
        failures.append(
            f"datadog breaker never completed a full "
            f"open→half_open→closed cycle: {dd_trans}")

    injected = {name: {"calls": op.calls, **op.injected}
                for name, op in openers.items()}
    out = {
        "platform": "cpu",
        "seed": args.seed,
        "duration_s": duration,
        "interval": "1s",
        "pps": pps,
        "packets": sent["packets"],
        "lines": sent["lines"],
        "flush_ticks_observed": max_sink_flush["ticks"],
        "max_sink_flush_s": round(max_sink_flush["s"], 4),
        "deadline_budget_s": INTERVAL_S + DEADLINE_SLACK_S,
        "injected_faults": injected,
        "delivery": delivery,
        "conserved": {r: m.conserved() for r, m in managers.items()},
        "breaker_cycle_datadog": has_breaker_cycle(dd_trans),
        "failures": failures,
        "ok": not failures,
    }
    write_artifact("FAULT_SOAK.json", out)
    print(json.dumps({
        "metric": "fault_soak_ok", "value": out["ok"],
        "conserved": out["conserved"],
        "breaker_cycle": out["breaker_cycle_datadog"],
        "max_sink_flush_s": out["max_sink_flush_s"],
        "dropped": {r: delivery[r]["dropped_payloads"] for r in delivery},
        "delivered": {r: delivery[r]["delivered_payloads"]
                      for r in delivery},
        "failures": failures,
    }))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
