"""Sustained-rate measurement for the WHOLE forward ring.

Drives senders -> proxy tier -> N global ImportServers over real gRPC
and searches for the maximum offered metric rate the ring holds without
loss: multiplicative growth to bracket the cliff, bisection inside the
bracket, then a longer confirmation run. The paced senders are either
ForwardClients (the single-proxy topology RING_SUSTAINED.json pins) or
SpreadForwarders (the sharded proxy tier: client-side p2c spreading
over M proxies, distributed/spread.py), so the measured hop chain is
the production one either way:
client -> proxy ingest -> consistent-hash routing -> per-destination
DeliveryManager -> forward RPC -> import merge.

Every trial settles to quiescence and then asserts the PR-11/15
exactness contract before it may pass:

    conservation exact   ingested == proxied + dropped (spill drained)
    duplicates == 0      received never exceeds what delivery delivered
                         (max(0, received - (proxied - drops)))

Multi-proxy cells additionally record, per proxy and per interval, the
fan-in deltas (batches routed, sheds, admission timeouts) and the CPU
service demand of the proxy's own worker threads
(ProxyServer.cpu_seconds, /proc schedstat). From those the artifact
derives `proxy_tier_capacity_metrics_per_s` = sum over proxies of
(metrics proxied / proxy CPU-second): the tier capacity the fleet
offers when each proxy owns a core. On this 1-core rig every cell is
co-scheduled on the same core, so co-scheduled throughput is ~flat by
construction (the chain is CPU-bound: the PR 15 A/B measured CPU
fraction 0.89 at saturation) — the scaling claim rides on the
measured per-proxy service demand staying flat as M grows, which the
capacity metric makes exact. RING_PROXY_SCALING.json carries both
numbers plus the rig note.

--ab runs the full search twice on identical topologies — unary first,
then streaming — and writes one artifact with both modes plus the
speedup; the headline fields come from the streaming run.
--ab --ab-axis stream-window adds a third cell: the PR 15 fixed ack
window (forward_stream_adaptive off) searched at saturation, plus one
calm fixed-rate trial per streaming cell at --start-rate, so the
artifact pins adaptive >= fixed at BOTH operating points
(stream_window_ab block; "streaming" stays the adaptive cell so the
parsed keys are unchanged). --smoke is
the bounded CI lane: one fixed-rate pass/fail trial on the streaming
path (exit 1 on failure), same invariants. --scaling runs the
multi-proxy cells (M=1/2/4 spread senders) plus a chaos cell: a
scripted mid-run proxy kill (survivors absorb the respread share) and
one ElasticController autoscale event promoting a standby through the
shared fleet file every sender watches.

Usage:
    python tools/bench_ring_sustained.py --ab          # full A/B search
    python tools/bench_ring_sustained.py --smoke --rate 2e4
    python tools/bench_ring_sustained.py --smoke --proxies 2 --rate 2e4
    python tools/bench_ring_sustained.py --scaling     # sharded tier
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


class _ClientSender:
    """One paced sender over a bare ForwardClient — the single-proxy
    sender the committed RING_SUSTAINED.json numbers were measured
    with, kept bit-for-bit so --ab stays comparable."""

    def __init__(self, addr: str, rpc, streaming: bool,
                 window: int, adaptive: bool = True,
                 window_min: int = 1, window_max: int = 128) -> None:
        self._rpc = rpc
        self.client = rpc.ForwardClient(addr, timeout_s=2.0,
                                        streaming=streaming,
                                        stream_window=window,
                                        stream_adaptive=adaptive,
                                        stream_window_min=window_min,
                                        stream_window_max=window_max)
        self.offered = 0

    def maintain(self) -> None:
        pass

    def send(self, blob: bytes, n: int) -> None:
        try:
            self.client.send_raw_or_raise(blob, n)
        except self._rpc.ForwardError:
            pass  # counted: offered but not ingested

    def ingested(self) -> int:
        return self.client.sent_metrics

    def spill_payloads(self) -> int:
        return 0

    def drain(self, deadline_s: float) -> int:
        return 0

    def breaker_states(self) -> dict:
        return {}

    def spread_stats(self) -> dict:
        return {"respread_total": 0, "respread_ambiguous_total": 0,
                "dropped_metrics": 0, "picks_p2c": 0, "picks_rr": 0}

    def stream_stats(self) -> list[dict]:
        s = self.client.stats().get("stream")
        return [s] if s else []

    def conserved(self) -> bool:
        return True

    def close(self) -> None:
        self.client.close()


class _SpreadSender:
    """One paced sender over a SpreadForwarder lane set — the sharded
    proxy tier's local-tier sender (power-of-two-choices spreading,
    per-lane DeliveryManager failover)."""

    def __init__(self, fleet: list[str], streaming: bool, window: int,
                 timeout_s: float = 5.0, adaptive: bool = True,
                 window_min: int = 1, window_max: int = 128) -> None:
        from veneur_tpu.distributed.spread import SpreadForwarder
        from veneur_tpu.sinks.delivery import DeliveryPolicy

        # breaker_threshold low so a killed proxy's lane opens within a
        # handful of sends; timeout comfortably above the proxy's 1s
        # streamed-admission wait so busy-acks (safe) arrive before the
        # deadline classifies the attempt ambiguous
        self.fwd = SpreadForwarder(
            fleet, timeout_s=timeout_s, streaming=streaming,
            stream_window=window, stream_adaptive=adaptive,
            stream_window_min=window_min, stream_window_max=window_max,
            policy=DeliveryPolicy(retry_max=1, breaker_threshold=3,
                                  spill_max_bytes=16 << 20,
                                  spill_max_payloads=1024,
                                  timeout_s=timeout_s,
                                  deadline_s=2.0 * timeout_s,
                                  backoff_base_s=0.02,
                                  backoff_max_s=0.1))
        self.offered = 0

    def maintain(self) -> None:
        # retry parked payloads + sweep breaker-open lanes' spills onto
        # survivors — what install_forwarder's flush entry does per flush
        self.fwd.begin_flush()

    def send(self, blob: bytes, n: int) -> None:
        self.fwd.send_wire(blob, n)

    def ingested(self) -> int:
        return self.fwd.ingested_metrics()

    def spill_payloads(self) -> int:
        with self.fwd._lock:
            lanes = list(self.fwd._lanes.values())
        return sum(len(ln.manager.spill) for ln in lanes)

    def drain(self, deadline_s: float) -> int:
        return self.fwd.drain(deadline_s)

    def breaker_states(self) -> dict:
        return self.fwd.breaker_states()

    def spread_stats(self) -> dict:
        return {
            "respread_total": self.fwd.respread_total,
            "respread_ambiguous_total": self.fwd.respread_ambiguous_total,
            "dropped_metrics": self.fwd.dropped_metrics,
            "picks_p2c": self.fwd.picks_p2c,
            "picks_rr": self.fwd.picks_rr,
        }

    def stream_stats(self) -> list[dict]:
        per = self.fwd.forward_stats()["destinations"]
        return [d["stream"] for d in per.values()
                if d.get("live") and d.get("stream")]

    def conserved(self) -> bool:
        return self.fwd.conserved()

    def close(self) -> None:
        self.fwd.close()


class RingHarness:
    """One live ring (senders + M proxies [+ standby] + globals) in one
    forward mode.

    Owns every process-local piece; close() tears it all down. The
    sender side is `senders` threads, each with its own client
    (mirroring N independent local servers), paced against a shared
    metrics/s budget. With n_proxies + standby > 1 (or use_spread)
    each sender is a SpreadForwarder over the live fleet.
    """

    def __init__(self, n_globals: int, senders: int, batch: int,
                 series: int, streaming: bool, window: int,
                 interval_s: float = 1.0, n_proxies: int = 1,
                 standby: int = 0, use_spread: bool | None = None,
                 routing_workers: int = 4,
                 routing_queue_max: int | None = None,
                 adaptive: bool = True, window_min: int = 1,
                 window_max: int = 128) -> None:
        from veneur_tpu.core.config import Config
        from veneur_tpu.core.server import Server
        from veneur_tpu.distributed import rpc
        from veneur_tpu.distributed.import_server import ImportServer
        from veneur_tpu.distributed.proxy import (
            ROUTING_QUEUE_MAX,
            ProxyServer,
        )
        from veneur_tpu.gen import veneur_tpu_pb2 as pb
        from veneur_tpu.sinks.delivery import DeliveryPolicy

        self.streaming = streaming
        self.window = window
        self.adaptive = bool(adaptive)
        self.window_min = window_min
        self.window_max = window_max
        self.batch = batch
        self.interval_s = interval_s
        self.senders = senders
        self._rpc = rpc
        self.globals_ = []
        for _ in range(n_globals):
            cfg = Config(interval="10s", percentiles=[0.5], num_workers=2)
            srv = Server(cfg)
            imp = ImportServer(srv)
            imp.start_grpc()
            self.globals_.append((srv, imp))
        policy = DeliveryPolicy(retry_max=2, breaker_threshold=8,
                                spill_max_bytes=16 << 20,
                                spill_max_payloads=1024,
                                timeout_s=1.0, deadline_s=2.0,
                                backoff_base_s=0.02, backoff_max_s=0.1)
        gaddrs = [imp.address for _, imp in self.globals_]
        self.proxies = []
        self.proxy_addrs: list[str] = []
        for _ in range(max(1, n_proxies) + max(0, standby)):
            p = ProxyServer(
                gaddrs, timeout_s=2.0, delivery=policy,
                handoff_window_s=0.5, dedup=True, streaming=streaming,
                stream_window=window, stream_adaptive=adaptive,
                stream_window_min=window_min,
                stream_window_max=window_max,
                routing_workers=routing_workers,
                routing_queue_max=(routing_queue_max
                                   or ROUTING_QUEUE_MAX))
            port = p.start_grpc()
            self.proxies.append(p)
            self.proxy_addrs.append(f"127.0.0.1:{port}")
        self.fleet = self.proxy_addrs[:max(1, n_proxies)]
        self.standby = self.proxy_addrs[max(1, n_proxies):]
        if use_spread is None:
            use_spread = len(self.proxy_addrs) > 1
        self.use_spread = bool(use_spread)
        if self.use_spread:
            self.sender_objs = [
                _SpreadSender(self.fleet, streaming, window,
                              adaptive=adaptive, window_min=window_min,
                              window_max=window_max)
                for _ in range(senders)]
        else:
            self.sender_objs = [
                _ClientSender(self.fleet[0], rpc, streaming, window,
                              adaptive=adaptive, window_min=window_min,
                              window_max=window_max)
                for _ in range(senders)]
        # the series universe, pre-serialized into cycling wire blobs of
        # `batch` global counters each — routing splits every blob
        # across the ring by metric key, so each payload exercises the
        # fan-out, not one arc
        self._blobs: list[bytes] = []
        for base in range(0, max(series, batch), batch):
            b = pb.MetricBatch()
            for i in range(base, base + batch):
                m = b.metrics.add()
                m.name = f"ring.c{i % series}"
                m.tags.append(f"shard:{i % 16}")
                m.kind = pb.KIND_COUNTER
                m.scope = pb.SCOPE_GLOBAL
                m.counter.value = 1
            self._blobs.append(b.SerializeToString())

    # -- bookkeeping ---------------------------------------------------------

    def received_total(self) -> int:
        return sum(imp.received_metrics for _, imp in self.globals_)

    def ingested_total(self) -> int:
        return sum(s.ingested() for s in self.sender_objs)

    def snapshot(self) -> dict:
        per_proxy: dict[str, dict] = {}
        tot = {"proxied": 0, "drops": 0, "shed": 0, "spilled": 0,
               "queue_depth": 0}
        stream_tot = {"opened": 0, "reconnects": 0, "acked_total": 0,
                      "window_stalls": 0, "unacked_frames": 0,
                      "downgraded": 0, "shrink_events": 0,
                      "window_current": 0, "window_min_seen": 0,
                      "window_max_seen": 0}
        # window gauges fold in BOTH streaming hops (sender->proxy and
        # proxy->global): window_current/max_seen are worst-case maxima,
        # window_min_seen the deepest collapse anywhere in the chain
        gauge_blocks: list[dict] = []
        for s in self.sender_objs:
            for blk in s.stream_stats():
                gauge_blocks.append(blk)
                stream_tot["shrink_events"] += blk.get(
                    "shrink_events", 0)
        for addr, p in zip(self.proxy_addrs, self.proxies):
            fs = p.forward_stats()
            per_proxy[addr] = {
                "routed": fs["routing"]["routed"],
                "submitted": fs["routing"]["submitted"],
                "shed_batches": fs["routing"]["shed_batches"],
                "admission_timeouts": fs["routing"]["admission_timeouts"],
                "queue_depth": fs["routing"]["queue_depth"],
                "window_stalls": fs["stream"]["window_stalls"],
                "proxied": fs["proxied_metrics"],
                "drops": fs["drops"],
                "spilled": fs["spilled_metrics"],
                "cpu_s": fs["cpu_seconds"],
            }
            tot["proxied"] += fs["proxied_metrics"]
            tot["drops"] += fs["drops"]
            tot["shed"] += fs["shed_metrics"]
            tot["spilled"] += fs["spilled_metrics"]
            tot["queue_depth"] += fs["routing"]["queue_depth"]
            for k in ("opened", "reconnects", "acked_total",
                      "window_stalls", "unacked_frames", "downgraded",
                      "shrink_events"):
                stream_tot[k] += fs["stream"].get(k, 0)
            gauge_blocks.append(fs["stream"])
        seen_gauge = False
        for s in gauge_blocks:
            cur = s.get("window_current", 0)
            stream_tot["window_current"] = max(
                stream_tot["window_current"], cur)
            lo = s.get("window_min_seen", cur)
            stream_tot["window_min_seen"] = (
                lo if not seen_gauge
                else min(stream_tot["window_min_seen"], lo))
            stream_tot["window_max_seen"] = max(
                stream_tot["window_max_seen"],
                s.get("window_max_seen", cur))
            seen_gauge = True
        spread = {"respread_total": 0, "respread_ambiguous_total": 0,
                  "dropped_metrics": 0, "picks_p2c": 0, "picks_rr": 0}
        for s in self.sender_objs:
            for k, v in s.spread_stats().items():
                spread[k] += v
        return {
            "t": time.time(),
            "ingested": self.ingested_total(),
            "offered": sum(s.offered for s in self.sender_objs),
            "proxied": tot["proxied"],
            "drops": tot["drops"],
            "shed": tot["shed"],
            "spilled": tot["spilled"],
            "sender_spill": sum(s.spill_payloads()
                                for s in self.sender_objs),
            "received": self.received_total(),
            "queue_depth": tot["queue_depth"],
            "stream": stream_tot,
            "per_proxy": per_proxy,
            "spread": spread,
            "coalesce": {
                "batches": sum(
                    (imp.stats()["stream"] or {}).get("batches", 0)
                    for _, imp in self.globals_),
                "frames": sum(
                    (imp.stats()["stream"] or {}).get("frames", 0)
                    for _, imp in self.globals_),
                "coalesced_frames": sum(
                    (imp.stats()["stream"] or {}).get(
                        "coalesced_frames", 0)
                    for _, imp in self.globals_),
            },
        }

    @staticmethod
    def per_proxy_delta(snap: dict, prev: dict) -> dict:
        """Per-proxy fan-in deltas between two snapshots: routed /
        shed / admission-timeout counts this interval plus the CPU
        spent — the per-proxy rows the scaling artifact carries."""
        out = {}
        for addr, cur in snap["per_proxy"].items():
            p = prev["per_proxy"].get(addr, {})
            out[addr] = {
                "routed": cur["routed"] - p.get("routed", 0),
                "shed_batches": (cur["shed_batches"]
                                 - p.get("shed_batches", 0)),
                "admission_timeouts": (cur["admission_timeouts"]
                                       - p.get("admission_timeouts", 0)),
                "proxied_metrics": cur["proxied"] - p.get("proxied", 0),
                "cpu_s": round(cur["cpu_s"] - p.get("cpu_s", 0.0), 4),
                "queue_depth": cur["queue_depth"],
            }
        return out

    # -- one paced trial -----------------------------------------------------

    def _sender_loop(self, sender, rate: float, stop: threading.Event,
                     blob_offset: int) -> None:
        # rate is this thread's metrics/s budget; each send is one blob
        # of self.batch metrics. Missed slots are skipped, not bursted:
        # a ring that can't ack fast enough shows up as offered-vs-
        # ingested gap, never as a catch-up flood after the stall.
        per_send = self.batch / rate
        k = blob_offset
        next_t = time.monotonic()
        last_maintain = 0.0
        while not stop.is_set():
            now = time.monotonic()
            if now < next_t:
                time.sleep(min(next_t - now, 0.05))
                continue
            if now - next_t > 1.0:
                next_t = now  # fell behind a full second: drop the slots
            if now - last_maintain >= 0.5:
                sender.maintain()
                last_maintain = now
            sender.offered += self.batch
            sender.send(self._blobs[k % len(self._blobs)], self.batch)
            k += 1
            next_t += per_send

    def quiesce(self, grace_s: float = 20.0) -> bool:
        """Drain to a quiescent instant: sender + proxy spills empty,
        routing queues drained, received stable. The conservation
        identities are exact only here."""
        deadline = time.time() + grace_s
        last_rx = -1
        stable_since = 0.0
        while time.time() < deadline:
            for p in self.proxies:
                if p.spilled_metrics > 0:
                    p.drain_spill()
            for s in self.sender_objs:
                if s.spill_payloads() > 0:
                    s.drain(0.2)
            snap = self.snapshot()
            rx = snap["received"]
            if (snap["spilled"] == 0 and snap["queue_depth"] == 0
                    and snap["sender_spill"] == 0 and rx == last_rx):
                if stable_since == 0.0:
                    stable_since = time.time()
                elif time.time() - stable_since >= 0.3:
                    return True
            else:
                stable_since = 0.0
            last_rx = rx
            time.sleep(0.05)
        return False

    def run_trial(self, rate: float, n_intervals: int,
                  max_loss: float = 0.005,
                  min_attain: float = 0.9) -> dict:
        start = self.snapshot()
        stop = threading.Event()
        threads = [
            threading.Thread(
                target=self._sender_loop,
                args=(s, max(1.0, rate / self.senders), stop, j * 7),
                name=f"ring-send-{j}")
            for j, s in enumerate(self.sender_objs)]
        prev = start
        intervals = []
        for t in threads:
            t.start()
        try:
            for _ in range(n_intervals):
                time.sleep(self.interval_s)
                snap = self.snapshot()
                dt = snap["t"] - prev["t"]
                ing = snap["ingested"] - prev["ingested"]
                off = snap["offered"] - prev["offered"]
                intervals.append({
                    "duration_s": round(dt, 4),
                    "offered_metrics": off,
                    "ingested_metrics": ing,
                    "received_metrics": snap["received"] - prev["received"],
                    "ingested_per_s": round(ing / dt, 1) if dt > 0 else 0.0,
                    "queue_depth": snap["queue_depth"],
                    # attainment is judged against the REQUESTED rate:
                    # the pacer skips missed slots, so sender-side
                    # "offered" self-throttles to whatever the ring
                    # acks and would vacuously pass at any rate
                    "attained": bool(dt > 0
                                     and ing >= min_attain * rate * dt),
                    "stream_acked_delta": (snap["stream"]["acked_total"]
                                           - prev["stream"]["acked_total"]),
                    "stream_stalls_delta": (
                        snap["stream"]["window_stalls"]
                        - prev["stream"]["window_stalls"]),
                    "unacked_frames": snap["stream"]["unacked_frames"],
                    "window_current": snap["stream"]["window_current"],
                    "shrink_delta": (snap["stream"]["shrink_events"]
                                     - prev["stream"]["shrink_events"]),
                    "respread_delta": (snap["spread"]["respread_total"]
                                       - prev["spread"]["respread_total"]),
                    "per_proxy": self.per_proxy_delta(snap, prev),
                })
                prev = snap
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10.0)
        send_s = prev["t"] - start["t"]
        quiesced = self.quiesce()
        end = self.snapshot()
        ingested = end["ingested"] - start["ingested"]
        offered = end["offered"] - start["offered"]
        proxied = end["proxied"] - start["proxied"]
        drops = end["drops"] - start["drops"]
        received = end["received"] - start["received"]
        delivered = proxied - 0  # proxied counts delivered fragments
        duplicates = max(0, received - delivered)
        conserved_exact = (quiesced and ingested == proxied + drops
                           and all(p.conserved() for p in self.proxies)
                           and all(s.conserved()
                                   for s in self.sender_objs))
        loss = (1.0 - received / ingested) if ingested > 0 else 1.0
        attain = (ingested / (rate * send_s)
                  if rate > 0 and send_s > 0 else 0.0)
        n_att = sum(1 for i in intervals if i["attained"])
        # per-proxy CPU service demand over the whole trial: metrics
        # proxied per CPU-second of the proxy's own worker threads.
        # Summed across the FLEET (standbys with no traffic contribute
        # 0) this is the tier capacity the fleet offers when each proxy
        # owns a core — the scaling metric on a 1-core co-scheduled rig.
        per_proxy = {}
        capacity = 0.0
        for addr in end["per_proxy"]:
            cur, first = end["per_proxy"][addr], start["per_proxy"].get(
                addr, {})
            d_m = cur["proxied"] - first.get("proxied", 0)
            d_cpu = cur["cpu_s"] - first.get("cpu_s", 0.0)
            eff = (d_m / d_cpu) if d_cpu > 1e-3 and d_m > 0 else None
            per_proxy[addr] = {
                "proxied_metrics": d_m,
                "routed": cur["routed"] - first.get("routed", 0),
                "shed_batches": (cur["shed_batches"]
                                 - first.get("shed_batches", 0)),
                "admission_timeouts": (
                    cur["admission_timeouts"]
                    - first.get("admission_timeouts", 0)),
                "cpu_s": round(d_cpu, 4),
                "metrics_per_cpu_s": round(eff, 1) if eff else None,
            }
            capacity += eff or 0.0
        trial = {
            "offered_metrics_per_s": rate,
            "intervals": intervals,
            "intervals_completed": len(intervals),
            "offered_total": offered,
            "ingested_total": ingested,
            "proxied_total": proxied,
            "drops_total": drops,
            "received_total": received,
            "duplicates_observed": duplicates,
            "quiesced": quiesced,
            "conservation_exact": conserved_exact,
            "send_duration_s": round(send_s, 3),
            "ring_metrics_per_s": round(received / send_s, 1)
            if send_s > 0 else 0.0,
            "loss_frac": round(max(0.0, loss), 5),
            "attain_frac": round(attain, 4),
            "attain_interval_frac": round(n_att / max(1, len(intervals)), 4),
            "per_proxy": per_proxy,
            "proxy_tier_capacity_metrics_per_s": round(capacity, 1),
            "respread_total": (end["spread"]["respread_total"]
                               - start["spread"]["respread_total"]),
            "respread_ambiguous_total": (
                end["spread"]["respread_ambiguous_total"]
                - start["spread"]["respread_ambiguous_total"]),
            "sender_dropped_metrics": (
                end["spread"]["dropped_metrics"]
                - start["spread"]["dropped_metrics"]),
        }
        trial["passed"] = bool(
            quiesced and conserved_exact and duplicates == 0
            and trial["loss_frac"] <= max_loss
            and attain >= min_attain)
        return trial

    def stream_telemetry(self) -> dict:
        snap = self.snapshot()
        out = dict(snap["stream"])
        out["coalesce"] = snap["coalesce"]
        return out

    def kill_proxy(self, idx: int) -> str:
        """Scripted chaos: stop one proxy in place (graceful gRPC stop,
        routing queue drained, counters stay readable)."""
        self.proxies[idx].stop()
        return self.proxy_addrs[idx]

    def close(self) -> None:
        for s in self.sender_objs:
            s.close()
        for p in self.proxies:
            p.stop()
        for srv, imp in self.globals_:
            imp.stop(grace=0.2)
            srv.shutdown()


def search_ring_sustained(h: RingHarness, *, start_rate: float,
                          max_rate: float, growth: float = 1.6,
                          trial_intervals: int = 3,
                          confirm_intervals: int = 6,
                          bisect_steps: int = 4,
                          max_loss: float = 0.005) -> dict:
    """Bracket-then-bisect over offered metric rate, then confirm."""
    trials = []
    lo, hi = 0.0, 0.0
    rate = start_rate

    def run(r: float, n: int) -> dict:
        t = h.run_trial(r, n, max_loss=max_loss)
        print(json.dumps({
            "trial": r, "ingested_per_s": round(
                t["ingested_total"] / max(t["send_duration_s"], 1e-9), 1),
            "ring_metrics_per_s": t["ring_metrics_per_s"],
            "loss": t["loss_frac"], "attain": t["attain_frac"],
            "dups": t["duplicates_observed"],
            "capacity": t["proxy_tier_capacity_metrics_per_s"],
            "passed": t["passed"]}), file=sys.stderr, flush=True)
        return t

    while rate <= max_rate:
        t = run(rate, trial_intervals)
        trials.append(t)
        if t["passed"]:
            lo = rate
            rate *= growth
        else:
            hi = rate
            break
    if lo == 0.0:
        hi = hi or start_rate
        lo = hi * 0.25
    if hi > 0.0:
        for _ in range(bisect_steps):
            mid = (lo + hi) / 2.0
            if mid <= lo * 1.05:
                break
            t = run(mid, trial_intervals)
            trials.append(t)
            if t["passed"]:
                lo = mid
            else:
                hi = mid
    confirm = None
    rate = lo
    for _ in range(3):
        confirm = run(rate, confirm_intervals)
        if confirm["passed"]:
            break
        rate *= 0.9
    return {
        "search_trials": [
            {k: t.get(k) for k in (
                "offered_metrics_per_s", "ring_metrics_per_s",
                "loss_frac", "attain_frac", "duplicates_observed",
                "conservation_exact", "passed")}
            for t in trials],
        "confirm": confirm,
        "sustained_offered_metrics_per_s": rate,
        "sustained_ring_metrics_per_s":
            confirm["ring_metrics_per_s"] if confirm else 0.0,
        "confirmed": bool(confirm and confirm["passed"]),
    }


def _mode_result(h: RingHarness, search: dict) -> dict:
    confirm = search.get("confirm") or {}
    return {
        "streaming": h.streaming,
        "stream_window": h.window,
        "stream_adaptive": h.adaptive,
        "stream_window_min": h.window_min,
        "stream_window_max": h.window_max,
        "proxies": len(h.fleet),
        "spread_senders": h.use_spread,
        "sustained_ring_metrics_per_s":
            search["sustained_ring_metrics_per_s"],
        "sustained_offered_metrics_per_s":
            search["sustained_offered_metrics_per_s"],
        "confirmed": search["confirmed"],
        "search_trials": search["search_trials"],
        "confirm": confirm,
        "duplicates_observed": confirm.get("duplicates_observed"),
        "conservation_exact": confirm.get("conservation_exact"),
        "proxy_tier_capacity_metrics_per_s":
            confirm.get("proxy_tier_capacity_metrics_per_s"),
        "per_proxy": confirm.get("per_proxy"),
        "stream": h.stream_telemetry(),
    }


def _rig_note() -> dict:
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    return {
        "cores": cores,
        "core_limited": cores == 1,
        "note": ("all proxies co-scheduled on one core: co-scheduled "
                 "throughput is CPU-bound ~flat by construction; the "
                 "scaling claim is the capacity metric (per-proxy "
                 "service demand stays flat as M grows, so the fleet "
                 "capacity = sum of per-proxy metrics/cpu-s scales "
                 "with M)" if cores == 1 else
                 "multi-core rig: co-scheduled throughput meaningful"),
    }


def run_chaos(args, mk) -> dict:
    """The scripted chaos cell: M=2 live proxies + 1 standby, paced
    spread senders discovering the fleet through a watched membership
    file, a mid-run proxy kill, and an ElasticController (driven one
    tick per interval, proxy-tier pressure signals) promoting the
    standby through the same file. Invariants: conservation exact,
    duplicates == 0, the kill's share respread to survivors, a lane
    breaker opened, the standby absorbed real traffic after scale-out.
    """
    from veneur_tpu.distributed.discovery import FileWatchDiscoverer
    from veneur_tpu.distributed.elastic import (
        ElasticController,
        HealthGate,
        ProxyTierPressureSource,
    )
    from veneur_tpu.distributed.proxy import DestinationRefresher

    h = mk(streaming=True, n_proxies=2, standby=1,
           routing_workers=args.chaos_workers,
           routing_queue_max=args.chaos_queue_max)
    tmpdir = tempfile.mkdtemp(prefix="ring_fleet_")
    fleet_file = os.path.join(tmpdir, "fleet")
    watcher = FileWatchDiscoverer(fleet_file)
    watcher.write_members(list(h.fleet), list(h.standby))

    refreshers = []
    gates = []
    try:
        # every sender discovers the fleet through the SAME
        # refresher/gate stack the proxies run for globals: probe-gated
        # admission, breaker-streak quarantine, probed re-admission
        for s in h.sender_objs:
            gate = HealthGate(s.fwd, probe_timeout_s=0.2,
                              quarantine_after=2, min_admitted=1)
            r = DestinationRefresher(
                s.fwd, FileWatchDiscoverer(fleet_file), "", 0.25,
                gate=gate)
            r.start()
            refreshers.append(r)
            gates.append(gate)

        fleet_map = dict(zip(h.proxy_addrs, h.proxies))

        def fleet_stats() -> dict:
            members, _ = watcher.desired()
            return {a: fleet_map[a].forward_stats()
                    for a in members if a in fleet_map}

        src = ProxyTierPressureSource(fleet_stats)
        # min_members pins the seed fleet size: the event under test is
        # the pressure-driven scale-OUT after the kill, not an
        # opportunistic shrink during the calm lead-in
        controller = ElasticController(
            watcher, src, hysteresis_k=2, cooldown_s=1.0,
            min_members=2, max_members=len(h.proxy_addrs),
            member_load_fn=src.member_load)

        rate = args.chaos_rate
        n_intervals = args.chaos_intervals
        kill_at = max(1, n_intervals // 3)
        start = h.snapshot()
        stop = threading.Event()
        threads = [
            threading.Thread(
                target=h._sender_loop,
                args=(s, max(1.0, rate / h.senders), stop, j * 7),
                name=f"chaos-send-{j}")
            for j, s in enumerate(h.sender_objs)]
        for t in threads:
            t.start()
        timeline = []
        killed = None
        breaker_open_seen = False
        prev = start
        last_tick = 0.0
        try:
            for i in range(n_intervals):
                events = []
                if i == kill_at:
                    killed = h.kill_proxy(0)
                    events.append({"kill": killed})
                # sample breaker states at sub-interval cadence (the
                # gate quarantines an open lane within ~2 refresh ticks,
                # so a once-per-interval peek can miss the open state)
                # and drive the controller at its own observe cadence —
                # several observations per measurement interval, as a
                # deployed controller with elastic_observe_interval_s
                # shorter than a flush interval would run
                t_end = time.monotonic() + h.interval_s
                while time.monotonic() < t_end:
                    if killed and not breaker_open_seen:
                        breaker_open_seen = any(
                            s.breaker_states().get(killed) == "open"
                            for s in h.sender_objs)
                    now = time.monotonic()
                    if killed is not None and now - last_tick >= 0.4:
                        last_tick = now
                        action = controller.tick()
                        if action:
                            events.append(
                                {"autoscale": action,
                                 "reasons": controller.last_reasons})
                    time.sleep(0.05)
                snap = h.snapshot()
                members, standby_now = watcher.desired()
                timeline.append({
                    "interval": i,
                    "events": events,
                    "members": len(members),
                    "standby": len(standby_now),
                    "ingested_delta": snap["ingested"] - prev["ingested"],
                    "respread_delta": (snap["spread"]["respread_total"]
                                       - prev["spread"]["respread_total"]),
                    "per_proxy": h.per_proxy_delta(snap, prev),
                })
                prev = snap
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10.0)
        quiesced = h.quiesce()
        end = h.snapshot()
        ingested = end["ingested"] - start["ingested"]
        proxied = end["proxied"] - start["proxied"]
        drops = end["drops"] - start["drops"]
        received = end["received"] - start["received"]
        duplicates = max(0, received - proxied)
        standby_routed = 0
        for addr in h.standby:
            standby_routed += (end["per_proxy"][addr]["routed"]
                               - start["per_proxy"][addr]["routed"])
        conserved = (quiesced and ingested == proxied + drops
                     and all(p.conserved() for p in h.proxies)
                     and all(s.conserved() for s in h.sender_objs))
        ctl_stats = controller.stats()
        result = {
            "offered_metrics_per_s": rate,
            "intervals": n_intervals,
            "kill_at_interval": kill_at,
            "killed_proxy": killed,
            "ingested_total": ingested,
            "proxied_total": proxied,
            "drops_total": drops,
            "received_total": received,
            "duplicates_observed": duplicates,
            "conservation_exact": conserved,
            "quiesced": quiesced,
            "respread_total": (end["spread"]["respread_total"]
                               - start["spread"]["respread_total"]),
            "respread_ambiguous_total": (
                end["spread"]["respread_ambiguous_total"]
                - start["spread"]["respread_ambiguous_total"]),
            "breaker_opened": breaker_open_seen,
            "gate": {
                "quarantined_total": sum(g.stats()["quarantined_total"]
                                         for g in gates),
                "probe_failures": sum(g.stats()["probe_failures"]
                                      for g in gates),
            },
            "controller": {k: ctl_stats[k] for k in (
                "ticks", "scale_out_total", "scale_in_total",
                "last_reasons")},
            "controller_events": controller.events,
            "standby_routed_batches": standby_routed,
            "timeline": timeline,
        }
        result["checks"] = {
            "conservation_exact": bool(conserved),
            "duplicates_zero": duplicates == 0,
            "respread_engaged": result["respread_total"] > 0,
            "breaker_opened": bool(breaker_open_seen),
            "scale_out_happened": ctl_stats["scale_out_total"] >= 1,
            "standby_absorbed": standby_routed > 0,
        }
        result["failures"] = sorted(
            k for k, ok in result["checks"].items() if not ok)
        return result
    finally:
        for r in refreshers:
            r.stop()
        h.close()


def run_scaling(args, mk, base: dict, platform: str, t0: float) -> dict:
    """The sharded-tier scaling cells: spread senders over M=1/2/4
    co-scheduled proxies (sustained search each), then the chaos cell.
    """
    cells: dict[str, dict] = {}
    for m in args.cell_list:
        print(f"== scaling cell: {m} prox{'y' if m == 1 else 'ies'} ==",
              file=sys.stderr, flush=True)
        h = mk(streaming=True, n_proxies=m, use_spread=True)
        try:
            search = search_ring_sustained(
                h, start_rate=args.start_rate, max_rate=args.max_rate,
                trial_intervals=args.intervals or 3,
                confirm_intervals=(args.intervals or 6),
                max_loss=args.max_loss)
            cells[str(m)] = _mode_result(h, search)
        finally:
            h.close()
    chaos = None
    if not args.no_chaos:
        print("== chaos cell: kill + autoscale ==", file=sys.stderr,
              flush=True)
        if not args.chaos_rate:
            # close enough to the measured co-scheduled sustained rate
            # that one survivor (with the chaos cell's single routing
            # worker and tiny queue) is honestly pressured after the
            # kill, while the 2-proxy lead-in stays calm
            two = cells.get("2") or next(iter(cells.values()))
            args.chaos_rate = max(
                5000.0, 0.8 * two["sustained_offered_metrics_per_s"])
        chaos = run_chaos(args, mk)

    rig = _rig_note()
    out = {
        "schema": "ring_proxy_scaling_v1",
        **base,
        "rig": rig,
        "cells": cells,
        "chaos": chaos,
        "wall_s": round(time.time() - t0, 1),
    }
    lo_m = str(min(args.cell_list))
    hi_m = str(max(args.cell_list))
    cap_lo = cells[lo_m]["proxy_tier_capacity_metrics_per_s"] or 0.0
    cap_hi = cells[hi_m]["proxy_tier_capacity_metrics_per_s"] or 0.0
    sus_lo = cells[lo_m]["sustained_ring_metrics_per_s"]
    sus_hi = cells[hi_m]["sustained_ring_metrics_per_s"]
    out["capacity_scaling"] = {
        "metric": "proxy_tier_capacity_metrics_per_s",
        "cells": {m: c["proxy_tier_capacity_metrics_per_s"]
                  for m, c in cells.items()},
        f"x{hi_m}_over_x{lo_m}": round(cap_hi / cap_lo, 3)
        if cap_lo > 0 else None,
    }
    out["co_scheduled_sustained"] = {
        "cells": {m: c["sustained_ring_metrics_per_s"]
                  for m, c in cells.items()},
        f"x{hi_m}_over_x{lo_m}": round(sus_hi / sus_lo, 3)
        if sus_lo > 0 else None,
        "core_limited": rig["core_limited"],
    }
    checks = {
        f"cell_{m}_confirmed": bool(c["confirmed"])
        for m, c in cells.items()}
    checks.update({
        f"cell_{m}_duplicates_zero": c["duplicates_observed"] == 0
        for m, c in cells.items()})
    checks.update({
        f"cell_{m}_conservation_exact": bool(c["conservation_exact"])
        for m, c in cells.items()})
    ratio = out["capacity_scaling"][f"x{hi_m}_over_x{lo_m}"]
    checks["capacity_scaling_near_linear"] = bool(
        ratio is not None and ratio >= args.min_scaling)
    if not rig["core_limited"]:
        # with real cores behind the proxies the co-scheduled number
        # must ALSO scale; on the 1-core rig it is flat by construction
        co = out["co_scheduled_sustained"][f"x{hi_m}_over_x{lo_m}"]
        checks["co_scheduled_scaling"] = bool(
            co is not None and co >= args.min_scaling)
    if chaos is not None:
        for k, ok in chaos["checks"].items():
            checks[f"chaos_{k}"] = bool(ok)
    failures = sorted(k for k, ok in checks.items() if not ok)
    out["checks"] = checks
    out["failures"] = failures
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="single fixed-rate pass/fail run (CI lane)")
    ap.add_argument("--rate", type=float, default=2e4,
                    help="offered metrics/s for --smoke")
    ap.add_argument("--intervals", type=int, default=0,
                    help="measurement windows per trial (default: 3 "
                         "smoke/bracket, 6 confirm)")
    ap.add_argument("--interval-s", type=float, default=1.0,
                    help="measurement window length")
    ap.add_argument("--globals", type=int, default=3, dest="n_globals")
    ap.add_argument("--senders", type=int, default=4,
                    help="paced sender threads (each its own client)")
    ap.add_argument("--batch", type=int, default=100,
                    help="metrics per forward payload")
    ap.add_argument("--series", type=int, default=2000,
                    help="distinct counter series in the workload")
    ap.add_argument("--window", type=int, default=32,
                    help="stream ack window (streaming mode; the AIMD "
                         "starting point when adaptive)")
    ap.add_argument("--no-adaptive", action="store_true",
                    help="pin the fixed PR 15 window (adaptive AIMD is "
                         "the default)")
    ap.add_argument("--window-min", type=int, default=1,
                    help="adaptive window floor")
    ap.add_argument("--window-max", type=int, default=128,
                    help="adaptive window ceiling")
    ap.add_argument("--proxies", type=int, default=1,
                    help="live proxy fleet size (M > 1 spreads senders)")
    ap.add_argument("--standby", type=int, default=0,
                    help="standby proxies booted but out of the fleet")
    ap.add_argument("--spread", action="store_true",
                    help="spread senders even with --proxies 1")
    ap.add_argument("--start-rate", type=float, default=2e4)
    ap.add_argument("--max-rate", type=float, default=2e6)
    ap.add_argument("--max-loss", type=float, default=0.005)
    ap.add_argument("--mode", default="streaming",
                    choices=["streaming", "unary"],
                    help="forward mode for --smoke / single-mode search")
    ap.add_argument("--ab", action="store_true",
                    help="run the search in BOTH modes (unary first) on "
                         "identical topologies; one artifact, headline "
                         "from streaming, speedup recorded")
    ap.add_argument("--ab-axis", default="mode",
                    choices=["mode", "stream-window"],
                    help="what --ab compares: forward mode (unary vs "
                         "streaming), or stream-window adds a third "
                         "fixed-window streaming cell plus calm-point "
                         "trials — adaptive vs fixed-32 at calm AND "
                         "saturated rates, same artifact")
    ap.add_argument("--scaling", action="store_true",
                    help="sharded-tier cells (--cells) + chaos cell; "
                         "artifact RING_PROXY_SCALING.json")
    ap.add_argument("--cells", default="1,2,4",
                    help="comma list of fleet sizes for --scaling")
    ap.add_argument("--min-scaling", type=float, default=2.5,
                    help="required capacity ratio biggest/smallest cell")
    ap.add_argument("--no-chaos", action="store_true",
                    help="skip the kill+autoscale cell in --scaling")
    ap.add_argument("--chaos-rate", type=float, default=0.0,
                    help="offered metrics/s for the chaos cell "
                         "(0 = derive from the 2-proxy cell)")
    ap.add_argument("--chaos-intervals", type=int, default=12)
    ap.add_argument("--chaos-workers", type=int, default=1,
                    help="routing workers per chaos proxy (small so the "
                         "survivor shows honest pressure)")
    ap.add_argument("--chaos-queue-max", type=int, default=2,
                    help="routing queue bound per chaos proxy (tiny, so "
                         "a saturated survivor's full queue is visible "
                         "to the controller's depth gauge)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    # a host-side bench: the CPU backend unless the caller says otherwise
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    args.cell_list = sorted({max(1, int(x))
                             for x in args.cells.split(",") if x.strip()})
    if args.out is None:
        args.out = ("RING_PROXY_SCALING.json" if args.scaling
                    else "RING_SUSTAINED.json")

    from _soak_common import write_artifact

    try:
        import jax
        platform = jax.default_backend()
    except Exception:
        platform = "unknown"

    def mk(streaming: bool, n_proxies: int | None = None,
           standby: int | None = None, use_spread: bool | None = None,
           routing_workers: int = 4,
           routing_queue_max: int | None = None,
           adaptive: bool | None = None) -> RingHarness:
        return RingHarness(
            args.n_globals, args.senders, args.batch, args.series,
            streaming, args.window, interval_s=args.interval_s,
            n_proxies=args.proxies if n_proxies is None else n_proxies,
            standby=args.standby if standby is None else standby,
            use_spread=(args.spread or None) if use_spread is None
            else use_spread,
            routing_workers=routing_workers,
            routing_queue_max=routing_queue_max,
            adaptive=(not args.no_adaptive) if adaptive is None
            else adaptive,
            window_min=args.window_min, window_max=args.window_max)

    base = {
        "platform": platform,
        "globals": args.n_globals,
        "senders": args.senders,
        "batch_metrics": args.batch,
        "series": args.series,
        "stream_window": args.window,
        "stream_adaptive": not args.no_adaptive,
        "stream_window_min": args.window_min,
        "stream_window_max": args.window_max,
        "interval_s": args.interval_s,
    }
    t0 = time.time()

    if args.scaling:
        out = run_scaling(args, mk, base, platform, t0)
        write_artifact(args.out, out)
        summary = {
            "metric": "proxy_tier_capacity_metrics_per_s",
            "capacity_cells": out["capacity_scaling"]["cells"],
            "co_scheduled_cells": out["co_scheduled_sustained"]["cells"],
            "capacity_ratio": [v for k, v in
                               out["capacity_scaling"].items()
                               if k.startswith("x")][0],
            "core_limited": out["rig"]["core_limited"],
            "chaos_ok": (not out["chaos"]["failures"]
                         if out.get("chaos") else None),
            "failures": out["failures"],
        }
        print(json.dumps(summary))
        if out["failures"]:
            sys.exit(1)
        return

    if args.smoke:
        h = mk(args.mode == "streaming")
        try:
            trial = h.run_trial(args.rate, args.intervals or 3,
                                max_loss=args.max_loss)
            stream = h.stream_telemetry()
        finally:
            h.close()
        engaged = (args.mode != "streaming"
                   or (stream["acked_total"] > 0
                       and stream["downgraded"] == 0))
        payload = {
            "metric": "ring_sustained_smoke_metrics_per_s",
            "value": trial["ring_metrics_per_s"],
            "unit": "metrics/s",
            "mode": args.mode,
            "adaptive": not args.no_adaptive,
            "window_current": stream.get("window_current", 0),
            "shrink_events": stream.get("shrink_events", 0),
            "proxies": len(h.fleet),
            "spread_senders": h.use_spread,
            "offered": args.rate,
            "loss_frac": trial["loss_frac"],
            "attain_frac": trial["attain_frac"],
            "duplicates_observed": trial["duplicates_observed"],
            "conservation_exact": trial["conservation_exact"],
            "proxy_tier_capacity_metrics_per_s":
                trial["proxy_tier_capacity_metrics_per_s"],
            "per_proxy": trial["per_proxy"],
            "respread_total": trial["respread_total"],
            "stream_engaged": engaged,
            "passed": bool(trial["passed"] and engaged),
            "platform": platform,
        }
        print(json.dumps(payload))
        if args.out and os.path.basename(args.out) != "RING_SUSTAINED.json":
            write_artifact(args.out, payload)
        if not payload["passed"]:
            sys.exit(1)
        return

    modes: dict[str, dict] = {}
    window_ab = args.ab and args.ab_axis == "stream-window"
    if window_ab:
        # unary baseline, the PR 15 fixed window, and the adaptive
        # window, all on identical topologies; "streaming" stays the
        # adaptive (production-default) cell so the artifact keys the
        # CI gates parse are unchanged
        mode_list = [("unary", False, None),
                     ("fixed_window", True, False),
                     ("streaming", True, True)]
    elif args.ab:
        mode_list = [("unary", False, None), ("streaming", True, None)]
    else:
        mode_list = [(args.mode, args.mode == "streaming", None)]
    calm: dict[str, dict] = {}
    for name, streaming, adaptive in mode_list:
        h = mk(streaming, adaptive=adaptive)
        try:
            if window_ab and streaming:
                # the calm point: a fixed low rate well inside capacity,
                # where adaptive must not cost anything
                print(f"== calm point ({name}) ==", file=sys.stderr,
                      flush=True)
                t = h.run_trial(args.start_rate, args.intervals or 3,
                                max_loss=args.max_loss)
                calm[name] = {k: t[k] for k in (
                    "ring_metrics_per_s", "loss_frac", "attain_frac",
                    "duplicates_observed", "conservation_exact",
                    "passed")}
                calm[name]["window_current_trace"] = [
                    i["window_current"] for i in t["intervals"]]
            search = search_ring_sustained(
                h, start_rate=args.start_rate, max_rate=args.max_rate,
                trial_intervals=args.intervals or 3,
                confirm_intervals=(args.intervals or 6),
                max_loss=args.max_loss)
            modes[name] = _mode_result(h, search)
        finally:
            h.close()

    head_name = mode_list[-1][0]
    head = modes[head_name]
    out = {
        "schema": "ring_sustained_v1",
        **base,
        "proxies": args.proxies,
        "modes": modes,
        "sustained_ring_metrics_per_s":
            head["sustained_ring_metrics_per_s"],
        "confirmed": head["confirmed"],
        "duplicates_observed": head["duplicates_observed"],
        "conservation_exact": head["conservation_exact"],
        "wall_s": round(time.time() - t0, 1),
    }
    checks = {
        "confirmed": bool(head["confirmed"]),
        "duplicates_zero": head["duplicates_observed"] == 0,
        "conservation_exact": bool(head["conservation_exact"]),
    }
    if "streaming" in modes:
        st = modes["streaming"]["stream"]
        checks["stream_engaged"] = (st["acked_total"] > 0
                                    and st["downgraded"] == 0)
        checks["coalescing_engaged"] = (
            st["coalesce"]["coalesced_frames"] > 0)
    if args.ab:
        u = modes["unary"]["sustained_ring_metrics_per_s"]
        s = modes["streaming"]["sustained_ring_metrics_per_s"]
        out["unary_metrics_per_s"] = u
        out["speedup_vs_unary"] = round(s / u, 3) if u > 0 else None
        checks["unary_confirmed"] = bool(modes["unary"]["confirmed"])
        checks["unary_duplicates_zero"] = (
            modes["unary"]["duplicates_observed"] == 0)
        checks["streaming_ge_unary"] = s >= u
        out["streaming_ge_unary"] = checks["streaming_ge_unary"]
    if window_ab:
        fx = modes["fixed_window"]
        ad = modes["streaming"]
        f_sat = fx["sustained_ring_metrics_per_s"]
        a_sat = ad["sustained_ring_metrics_per_s"]
        f_calm = calm["fixed_window"]["ring_metrics_per_s"]
        a_calm = calm["streaming"]["ring_metrics_per_s"]
        out["stream_window_ab"] = {
            "fixed_window": args.window,
            "calm_rate_metrics_per_s": args.start_rate,
            "calm": calm,
            "saturated": {
                "fixed_window_metrics_per_s": f_sat,
                "adaptive_metrics_per_s": a_sat,
                "ratio": round(a_sat / f_sat, 3) if f_sat > 0 else None,
            },
        }
        # the adaptive window must win (or tie within paced-load noise)
        # at BOTH operating points; CALM_TOL absorbs scheduler jitter on
        # a fixed offered rate both cells attain anyway
        CALM_TOL = 0.97
        checks["adaptive_ge_fixed_saturated"] = a_sat >= f_sat
        checks["adaptive_ge_fixed_calm"] = (
            f_calm <= 0 or a_calm >= CALM_TOL * f_calm)
        checks["fixed_window_confirmed"] = bool(fx["confirmed"])
        checks["fixed_window_duplicates_zero"] = (
            fx["duplicates_observed"] == 0)
        checks["fixed_window_conservation_exact"] = bool(
            fx["conservation_exact"])
        checks["calm_duplicates_zero"] = all(
            c["duplicates_observed"] == 0 for c in calm.values())
        checks["calm_conservation_exact"] = all(
            bool(c["conservation_exact"]) for c in calm.values())
    failures = sorted(k for k, ok in checks.items() if not ok)
    out["checks"] = checks
    out["failures"] = failures
    write_artifact(args.out, out)
    summary = {
        "metric": "sustained_ring_metrics_per_s",
        "value": out["sustained_ring_metrics_per_s"],
        "unit": "metrics/s",
        "confirmed": out["confirmed"],
        "duplicates_observed": out["duplicates_observed"],
        "platform": platform,
    }
    if args.ab:
        summary["unary_metrics_per_s"] = out["unary_metrics_per_s"]
        summary["speedup_vs_unary"] = out["speedup_vs_unary"]
        summary["streaming_ge_unary"] = out["streaming_ge_unary"]
    if window_ab:
        summary["stream_window_ab"] = {
            "saturated": out["stream_window_ab"]["saturated"],
            "adaptive_ge_fixed_saturated":
                checks["adaptive_ge_fixed_saturated"],
            "adaptive_ge_fixed_calm": checks["adaptive_ge_fixed_calm"],
        }
    summary["failures"] = failures
    print(json.dumps(summary))
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
