"""The Server: listeners → parser → device workers → flush loop → sinks.

Parity spec: reference server.go — NewFromConfig (:262), Start (:826),
HandleMetricPacket (:994), processMetricPacket (:1136), ReadMetricSocket
(:1123), TCP/TLS statsd (:1254-1335, networking.go:97), flush ticker with
clock alignment (:908-946, CalculateTickDelay :1517), FlushWatchdog
(:948-990), Shutdown (:1473). Ingest listeners are OS threads (socket reads
release the GIL); aggregation is batched onto the device by DeviceWorker.

The reference shards series across N workers by Digest%N (server.go:1028,
1039) so each series lives in exactly one sampler; we keep the same routing
(it also keeps every series in exactly one device-pool row).
"""

from __future__ import annotations

import contextlib
import gc
import logging
import os
import socket
import ssl
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from veneur_tpu import __version__
from veneur_tpu.core import crash, flightrec
from veneur_tpu.core.config import Config, parse_duration
from veneur_tpu.core.flusher import device_quantiles, generate_inter_metrics
from veneur_tpu.core.metrics import HistogramAggregates, InterMetric
from veneur_tpu.core.spans import MetricExtractionSink, SpanWorker
from veneur_tpu.spans import ColumnarSpanPipeline, columnar_enabled
from veneur_tpu.core.worker import DeviceWorker, FlushSnapshot
from veneur_tpu.protocol import dogstatsd, ssf_wire
from veneur_tpu.sinks import (
    DELIVERY_STAT_COUNTERS,
    MetricSink,
    SpanSink,
    filter_routed,
    strip_excluded_tags,
)
from veneur_tpu.ssf import SSFSample
from veneur_tpu.utils.proc import current_rss_bytes as _current_rss_bytes

log = logging.getLogger("veneur_tpu.server")

# ssf.error_total tag sets, verbatim from the reference
# (server.go:1052-1072, 1238-1246); one definition so the five emit
# sites cannot drift from dashboard parity
_SSF_ERR_ZEROLENGTH = ["ssf_format:packet", "packet_type:unknown",
                       "reason:zerolength"]
_SSF_ERR_UNMARSHAL = ["ssf_format:packet", "packet_type:ssf_metric",
                      "reason:unmarshal"]
_SSF_ERR_EMPTY_ID = ["ssf_format:packet", "packet_type:ssf_metric",
                     "reason:empty_id"]
_SSF_ERR_PROCESSING = ["ssf_format:framed", "packet_type:unknown",
                       "reason:processing"]
_SSF_ERR_FRAMING = ["ssf_format:framed", "packet_type:unknown",
                    "reason:framing"]


def _abandon_swapped(swapped) -> None:
    """Give back the native staging planes of swapped epochs nobody
    will extract (SwappedEpoch.release: a second call frees nothing)."""
    for sw in swapped:
        sw.release()


@dataclass
class FlushJob:
    """One flush's state, handed from phase to phase. `ts` is frozen
    when the flush begins, so generation stamps every InterMetric of
    the interval with one clock."""

    # Server.flush_count of this flush: what its spans are filed under
    ordinal: int = 0
    ts: int = 0
    flush_start: float = 0.0
    qs: Any = None
    swapped: list = field(default_factory=list)
    # the flush.begin span: closed by then, it still takes the attrs
    # that only the end of the flush knows (_flush_publish)
    begin: Any = None
    span_counts: dict = field(default_factory=dict)
    phases: dict = field(default_factory=dict)
    snaps: list = field(default_factory=list)
    batch: Any = None
    final: list = field(default_factory=list)
    n_flushed: int = 0


class EventWorker:
    """Accumulates DogStatsD events (as SSF samples) until flush
    (reference EventWorker, worker.go:527-572)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._samples: list[SSFSample] = []

    def ingest(self, sample: SSFSample) -> None:
        with self._lock:
            self._samples.append(sample)

    def flush(self) -> list[SSFSample]:
        with self._lock:
            out = self._samples
            self._samples = []
        return out


def calculate_tick_delay(interval_s: float, now: float) -> float:
    """Seconds until the next interval-aligned tick
    (reference CalculateTickDelay, server.go:1517)."""
    return interval_s - (now % interval_s)


class _SpanPipelineClient:
    """Trace-client adapter: finished internal spans re-enter the owning
    server's span pipeline (sinks + ssfmetrics extraction)."""

    def __init__(self, server: "Server") -> None:
        self._server = server

    def record(self, span) -> None:
        self._server.ingest_internal_span(span)


class Server:
    """One veneur_tpu instance (local or global)."""

    def __init__(self, cfg: Config,
                 metric_sinks: Optional[list[MetricSink]] = None,
                 span_sinks: Optional[list[SpanSink]] = None,
                 inherited_fds: Optional[dict[str, list[int]]] = None
                 ) -> None:
        self.config = cfg
        self.interval = cfg.interval_seconds()
        # restarts (watchdog, fd-handoff upgrade) reuse compiled
        # flush/fold programs instead of re-paying the first compile per
        # shape; the environment places the cache before the config does
        from veneur_tpu.utils.backend import place_compilation_cache

        self.compilation_cache_dir = place_compilation_cache(
            cfg.tpu_compilation_cache_dir)
        self.hostname = cfg.hostname or (
            "" if cfg.omit_empty_hostname else socket.gethostname())
        self.tags = list(cfg.tags)
        self.percentiles = list(cfg.percentiles)
        self.aggregates = HistogramAggregates.from_names(cfg.aggregates)

        self.workers = [
            DeviceWorker(
                batch_size=cfg.tpu_batch_size,
                stage_depth=cfg.tpu_stage_depth,
                compression=cfg.tpu_compression,
                hll_precision=cfg.tpu_hll_precision,
                initial_histo_rows=cfg.tpu_initial_histo_rows,
                initial_set_rows=cfg.tpu_initial_set_rows,
                count_unique_timeseries=cfg.count_unique_timeseries,
                is_local=self.is_local,
                set_hash=cfg.set_hash,
                set_store=cfg.tpu_set_store,
                spill_cap=cfg.tpu_spill_cap,
                micro_fold=cfg.micro_fold,
                micro_fold_rows=cfg.micro_fold_rows,
                micro_fold_max_age_s=cfg.micro_fold_max_age_s,
                series_shards=cfg.series_shards,
                device_guard=cfg.device_guard,
                device_fault_streak=cfg.device_fault_streak,
                device_probe_interval_s=cfg.device_probe_interval_s,
            )
            for _ in range(cfg.num_workers)
        ]
        self._worker_locks = [threading.Lock() for _ in self.workers]
        # the one span record of this server (core/flightrec.py): flush
        # phases and their children, micro-folds, series adoption and
        # every guarded device dispatch, on the time.time() clock.
        # last_flush_phases is derived from it at the end of each flush
        self.rec = flightrec.Recorder()
        for w in self.workers:
            w.set_recorder(self.rec)
        # when the ticker meant to fire the tick it is firing (late_s of
        # flush.begin); None for a flush() called by hand
        self._tick_due: Optional[float] = None
        # device fault domain bookkeeping: last guard fault seen per
        # worker (so each new classified fault reaches the governor's
        # watchdog verdict exactly once) and the lifetime guard-counter
        # totals already emitted (telemetry reports deltas)
        self._guard_last_fault: dict = {}
        self._guard_counters_reported: dict = {}
        self._host_fallbacks_reported = 0
        # lifetime series whose objects gc.freeze() has taken out of the
        # collector's walk (_freeze_series)
        self._frozen_series = 0
        # adaptive overload shedding starts at the configured ceiling and
        # tightens when flushes overrun the interval (_adapt_spill_caps);
        # each flush may inherit at most half an interval of spill-fold
        # work (worker.swap sheds the excess, counted)
        self._spill_cap_now = cfg.tpu_spill_cap
        self.compute_threads_joined = True  # set by shutdown()
        # flush-deadline governor (health/): chunked degraded-mode
        # extraction + the progress signal the watchdog's deferral rule
        # reads. Shared across workers — extraction is sequential within
        # one flush, so one rate EWMA and one progress clock describe it.
        from veneur_tpu.health import FlushDeadlineGovernor

        self.flush_governor = FlushDeadlineGovernor(
            chunk_target_ms=cfg.flush_chunk_target_ms,
            interval_s=self.interval)
        for w in self.workers:
            w.fold_budget_s = 0.5 * self.interval
            w.governor = self.flush_governor
        # per-tenant QoS (core/tenancy.py): one shared series-budget
        # ledger across workers (a tenant's budget is global, not
        # per-shard) plus a per-worker heavy-hitter sketch folded over
        # the columnar batch at extract time. Disabled entirely (zero
        # overhead, bitwise-identical flushes) unless a budget is set.
        self.tenant_ledger = None
        self._tenant_reported: dict = {}
        if cfg.tenant_default_budget > 0 or cfg.tenant_budgets:
            from veneur_tpu.core.tenancy import TenantLedger, TenantSketch

            self.tenant_ledger = TenantLedger(
                default_budget=cfg.tenant_default_budget,
                budgets=cfg.tenant_budgets,
                tag_key=cfg.tenant_tag_key)
            for w in self.workers:
                w.tenancy = self.tenant_ledger
                w.tenant_sketch = TenantSketch(
                    depth=cfg.tenant_sketch_depth,
                    width=cfg.tenant_sketch_width,
                    topk=cfg.tenant_topk)
        # live query subsystem (veneur_tpu/query/): dormant unless
        # addresses are configured. Each worker's extract fence publishes
        # its epoch view into the engine (stage); _flush_extract commits
        # all workers' views as one epoch after the loop — the two-phase
        # publish that makes cross-worker reads tear-free.
        self.query_engine = None
        self._query_servers: list = []
        self._query_reported = (0, 0)  # (served, failed) at last report
        if cfg.query_listen_addrs:
            import functools

            from veneur_tpu.query import QueryEngine

            self.query_engine = QueryEngine(
                percentiles=self.percentiles,
                aggregates=self.aggregates,
                is_local=self.is_local,
                topk=cfg.tenant_topk)
            for i, w in enumerate(self.workers):
                w.query_publisher = functools.partial(
                    self.query_engine.stage, i)
        if cfg.tpu_mesh_devices > 1:
            # config-driven mesh sharding for the aggregation state (the
            # global tier's import merge rides ICI collectives; see
            # distributed/mesh.py)
            from veneur_tpu.distributed.mesh import MeshHistoPool, make_mesh

            mesh = make_mesh(cfg.tpu_mesh_devices,
                             cfg.tpu_mesh_hosts or None)
            self.mesh = mesh
            self.workers[0].attach_mesh_pool(MeshHistoPool(
                mesh, compression=cfg.tpu_compression,
                batch_size=cfg.tpu_batch_size))
            log.info("mesh aggregation enabled: %s", dict(mesh.shape))
        else:
            self.mesh = None
        self.event_worker = EventWorker()

        self.metric_sinks: list[MetricSink] = list(metric_sinks or [])
        self.span_sinks: list[SpanSink] = list(span_sinks or [])
        self.sink_excluded_tags: dict[str, set[str]] = {}

        # the span→metric bridge is always wired in, like the reference's
        # ssfmetrics sink (server.go:407-415)
        self._extraction_sink = MetricExtractionSink(
            route_metric=self._route,
            indicator_timer_name=cfg.indicator_span_timer_name,
            objective_timer_name=cfg.objective_span_timer_name,
            uniqueness_rate=cfg.ssf_span_uniqueness_rate,
        )
        common_tags = dict(
            t.split(":", 1) for t in self.tags if ":" in t)
        self.span_worker = SpanWorker(
            [self._extraction_sink] + self.span_sinks,
            common_tags=common_tags,
            capacity=cfg.span_channel_capacity,
            workers=cfg.num_span_workers,
            flush_drain_s=cfg.span_flush_drain_s,
        )
        # columnar span pipeline (veneur_tpu/spans/): on when configured
        # and every span sink takes sealed batches; one per-span-only
        # sink keeps the whole path on the SpanWorker lanes — a span must
        # flow through exactly one of the two or it derives twice
        self.span_pipeline: Optional[ColumnarSpanPipeline] = None
        if columnar_enabled(cfg.span_columnar) and all(
                hasattr(s, "ingest_batch") for s in self.span_sinks):
            self.span_pipeline = ColumnarSpanPipeline(
                route_many=self._route_many,
                batch_sinks=self.span_sinks,
                common_tags=common_tags,
                indicator_timer_name=cfg.indicator_span_timer_name,
                objective_timer_name=cfg.objective_span_timer_name,
                uniqueness_rate=cfg.ssf_span_uniqueness_rate,
                batch_rows=cfg.span_batch_rows,
                pending_cap=cfg.span_pending_cap,
            )
        # handle_ssf's columnar fast path stands down the moment the
        # span worker is customized at runtime (a sink appended to
        # span_worker.span_sinks, or ingest itself tapped/replaced —
        # established patterns for observing the span stream); the
        # baseline length is what "uncustomized" means
        self._span_worker_sink_count = len(self.span_worker.span_sinks)
        # per-service span ingest counters (reference server.go:1088-1101)
        self.ssf_spans_received: dict[str, int] = {}
        # lifetime tallies for span conservation (the per-service dict
        # swaps every flush; ingress_stats needs monotonic counts)
        self.ssf_spans_received_total = 0
        self._spans_native_total = 0
        self._ssf_stats_lock = threading.Lock()

        # installed by distributed/forward.py on local instances
        self.forwarder: Optional[Callable[[list[FlushSnapshot]], None]] = None
        # flush-time archival plugins (reference plugins/plugins.go)
        self.plugins: list = []
        # attached by core/factory.py when grpc/http addresses are set
        self.import_server = None
        self.import_http = None
        # installed by protocol/ssf_server.py for span ingest
        self.span_handler = None

        self._threads: list[threading.Thread] = []
        self._compute_threads: list[threading.Thread] = []
        self._sockets: list[socket.socket] = []
        self._socket_locks: list[int] = []
        # zero-downtime restart (einhorn-style fd handoff): listener fds
        # inherited from the previous process image, keyed by listener
        # spec; datagrams queue in the kernel socket buffers across the
        # re-exec instead of being dropped (reference server.go:1401-1429)
        self._inherited: dict[str, list[int]] = dict(inherited_fds or {})
        self._listener_fds: dict[str, list[int]] = {}
        self._adopt: list[int] = []
        self._handoff = False
        self._quiesce = threading.Event()
        self._shutdown = threading.Event()
        self._shutdown_once_lock = threading.Lock()
        self._shutdown_done = False
        # set once the WINNING shutdown() caller finishes its bounded
        # join + teardown; losing callers wait on it so they report the
        # real join outcome instead of the stale initial True
        self._shutdown_complete = threading.Event()
        self.last_flush_unix = time.time()
        # when the most recent flush finished sink emission
        self.last_emit_unix = 0.0
        # the last COMPLETED flush: its phase seconds (sums of the span
        # record's spans) and, under "spans", the spans themselves
        # (flightrec.Recorder.of_flush); read by bench/readers/
        self.last_flush_phases: dict = {}
        # per-flush transfer-ledger totals and chunk report (health/),
        # read by bench/readers/ alongside the phase times
        self.last_flush_transfers: dict[str, int] = {}
        self.last_flush_chunks: dict = {}
        self.flush_count = 0
        # native emit tier (native/emit.cpp): sinks serialize their wire
        # payloads GIL-free straight from the flush arrays; off = always
        # use the Python columnar formatters
        self.flush_emit_native = bool(
            getattr(cfg, "flush_emit_native", True))

        # ingest counters (self-telemetry). Incremented from every reader
        # thread: a bare `self.x += 1` loses increments at GIL switches
        # (LOAD/ADD/STORE interleave), so each thread gets its own cell
        # and the public counters are sums over the cells — single-writer
        # per cell, so no increment can be lost. Cells of dead threads
        # (per-connection stream readers exit constantly) are folded into
        # _ctr_base on read so the cell list stays bounded by the number
        # of LIVE threads.
        self._ctr_lock = threading.Lock()
        self._ctr_base = [0, 0]
        self._ctr_cells: list[tuple[threading.Thread, list[int]]] = []
        self._ctr_local = threading.local()
        self._errors_reported = 0
        self._span_sink_reported: dict[tuple[str, str], int] = {}
        # delivery.* interval-delta bookkeeping
        self._delivery_reported: dict[tuple[str, str], int] = {}
        # plugins.* interval-delta bookkeeping (plugin flush failures
        # ride the self-telemetry stream, not just the logs)
        self._plugin_reported: dict[tuple[str, str], int] = {}
        # forward.* interval-delta bookkeeping: per-proxy sender-side
        # forwarder counters, keyed (proxy_addr, stat)
        self._forward_reported: dict[tuple[str, str], int] = {}
        # write-ahead spill journals (utils/journal.py), one per
        # journalable delivery manager, attached in start() when
        # spill_journal_dir is set; shutdown_stats is filled by
        # graceful_drain (the SIGTERM path)
        self._journals: dict = {}
        self.shutdown_stats: dict = {}

        # scoped self-telemetry statsd client (reference server.go:298-308
        # builds a datadog-go client with namespace "veneur." wrapped by
        # scopedstatsd per veneur_metrics_scopes)
        from veneur_tpu import scopedstatsd
        if cfg.stats_address:
            sender: scopedstatsd.Sender = scopedstatsd.UDPSender(
                cfg.stats_address)
        else:
            sender = scopedstatsd.NullSender()
        self.stats = scopedstatsd.ScopedClient(
            sender,
            # self-telemetry carries the common tags plus the dedicated
            # veneur_metrics_additional_tags (reference server.go:300-307)
            add_tags=self.tags + list(cfg.veneur_metrics_additional_tags),
            scopes=cfg.veneur_metrics_scopes,
            namespace="veneur.",
        )
        if cfg.block_profile_rate or cfg.mutex_profile_fraction:
            # accepted for config compatibility (server.go:334-347); these
            # tune the Go runtime's profilers, which have no analog here —
            # enable_profiling drives the XLA profiler instead
            log.info("block_profile_rate/mutex_profile_fraction have no "
                     "effect in veneur-tpu (Go runtime knobs); see "
                     "enable_profiling for the XLA profiler")

        # native C++ ingest path: each worker gets its own parser context;
        # readers parse lock-free and commit to shard digest % N under
        # per-shard C++ mutexes (contention-free like the reference's
        # Digest%N channel routing, server.go:1028-1039)
        self.native_mode = False
        self._native_router = None
        self._native_ingest_tick = 0
        # C++ reader-thread handles (vn_reader_start) + their retained
        # packet counts after stop (the handle dies with the thread)
        self._native_readers: list = []
        self._native_ssf_readers: list = []
        self._native_stream_readers: list = []
        self._native_reader_packets_stopped = 0
        self._native_reader_lock = threading.Lock()
        if cfg.tpu_native_ingest:
            self.native_mode = all(w.attach_native() for w in self.workers)
            if self.native_mode:
                from veneur_tpu.native import NativeRouter

                self._native_router = NativeRouter(
                    [w._native for w in self.workers])
                log.info("native C++ ingest pipeline enabled"
                         " (%d shards)", len(self.workers))
        # shared-nothing reader shards: each C++ reader thread commits
        # into a PRIVATE context (no shared mutex on the line path); the
        # flush folds the per-reader planes on-device as one stacked
        # batch (core/worker.attach_reader_shards, ops/reader_stack.py).
        # resolve_reader_shards gates on single-worker native-reader
        # mode and honors the VENEUR_READER_SHARDS=0 legacy hatch.
        self._reader_shards = 0
        if self.native_mode:
            from veneur_tpu.core.config import resolve_reader_shards

            n_rs = resolve_reader_shards(cfg)
            if n_rs and self.workers[0].attach_reader_shards(n_rs):
                self._reader_shards = n_rs
                log.info("reader-sharded ingest enabled"
                         " (%d shared-nothing reader shards)", n_rs)

        # native SSF span fast path: only when the extraction sink is the
        # sole span consumer (other span sinks need the Python span
        # object), and single-shard only — the C++ extractor commits into
        # one context, so with several workers the Python path (which
        # routes each derived metric by digest) keeps series on their
        # home shard
        self._native_ssf = (self.native_mode and not self.span_sinks
                            and len(self.workers) == 1)

        # OpenTracing tracer for cross-hop propagation: spans it finishes
        # rejoin this server's own span pipeline (the reference's internal
        # spans flow through SpanChan the same way, server.go:310-317)
        from veneur_tpu.trace.opentracing import Tracer as _OTTracer

        self.tracer = _OTTracer(client=_SpanPipelineClient(self),
                                service="veneur-tpu")
        self._native_ssf_indicator = (
            cfg.indicator_span_timer_name.encode())
        self._native_ssf_objective = (
            cfg.objective_span_timer_name.encode())
        if self._native_ssf:
            log.info("native SSF span extraction enabled")

    @property
    def is_local(self) -> bool:
        return self.config.is_local()

    # -- packet handling ----------------------------------------------------

    def handle_metric_packet(self, packet: bytes) -> None:
        """Dispatch one line: event / service check / metric
        (reference HandleMetricPacket, server.go:994-1046)."""
        if not packet:
            return
        try:
            if packet.startswith(b"_e{"):
                sample = dogstatsd.parse_event(packet)
                self.event_worker.ingest(sample)
            elif packet.startswith(b"_sc"):
                metric = dogstatsd.parse_service_check(packet)
                self._route(metric)
            else:
                metric = dogstatsd.parse_metric(packet)
                self._route(metric)
        except dogstatsd.ParseError as e:
            self._bump_errors()
            log.debug("bad metric packet %r: %s", packet[:128], e)

    def _ctr_cell(self) -> list:
        """This thread's [packets, errors] counter cell."""
        c = getattr(self._ctr_local, "cell", None)
        if c is None:
            c = self._ctr_local.cell = [0, 0]
            with self._ctr_lock:
                self._ctr_cells.append((threading.current_thread(), c))
        return c

    def _ctr_sum(self, i: int) -> int:
        """Sum counter column i, reclaiming dead threads' cells. A dead
        thread can never increment again, so folding its cell into the
        base is exact; a live thread racing an increment is at worst off
        by the in-flight bump, same as any snapshot read."""
        with self._ctr_lock:
            if any(not t.is_alive() for t, _ in self._ctr_cells):
                live = []
                for t, c in self._ctr_cells:
                    if t.is_alive():
                        live.append((t, c))
                    else:
                        self._ctr_base[0] += c[0]
                        self._ctr_base[1] += c[1]
                self._ctr_cells = live
            return self._ctr_base[i] + sum(c[i] for _, c in self._ctr_cells)

    @property
    def packets_received(self) -> int:
        n = self._ctr_sum(0) + self._native_reader_packets_stopped
        router = self._native_router
        if router is not None:
            with self._native_reader_lock:
                for h in self._native_readers:
                    n += router.reader_packets(h)
        return n

    def ingress_stats(self) -> dict:
        """Cumulative ingress counters for the loadgen controller
        (veneur_tpu/loadgen): lifetime tallies that survive epoch swaps,
        so sent-vs-accepted loss over a load run is a subtraction of two
        snapshots. Every field is monotonic for the life of the process.

        samples_processed sums each worker's swap-accumulated
        processed_total plus its live in-epoch count; overload_dropped
        likewise folds in the not-yet-drained native delta."""
        processed = 0
        dropped = 0
        for i, w in enumerate(self.workers):
            # per-worker lock: a swap moves `processed` into
            # processed_total; reading the pair unlocked could miss a
            # whole epoch mid-swap
            with self._worker_locks[i]:
                processed += getattr(w, "processed_total", 0) + w.processed
                dropped += getattr(w, "overload_dropped_total", 0)
                native = getattr(w, "_native", None)
                if native is not None:
                    dropped += (int(native.overload_dropped)
                                - getattr(w, "_native_drop_seen", 0))
                    for j, ctx in enumerate(
                            getattr(w, "_reader_ctxs", ())):
                        dropped += (int(ctx.overload_dropped)
                                    - w._reader_drop_seen[j])
        out = {
            "packets_received": self.packets_received,
            "parse_errors": self.parse_errors,
            "samples_processed": processed,
            "overload_dropped": dropped,
            "flush_count": self.flush_count,
            "last_flush_unix": self.last_flush_unix,
            "last_emit_unix": self.last_emit_unix,
            # the phase seconds alone: the spans ride in
            # last_flush_phases["spans"] for the benchmark's readers
            "last_flush_phases": {k: v for k, v in
                                  self.last_flush_phases.items()
                                  if k != "spans"},
            # how long the last flush held the ticker thread: the
            # ingest-stall component of the cadence decomposition the
            # loadgen controller reports per interval
            "last_tick_s": self._last_tick_s(),
            # always-hot flush: lifetime micro-fold drains plus the last
            # closed interval's count (the controller's per-interval
            # micro_folds is a delta of the lifetime tally)
            "micro_folds_total": sum(
                getattr(w, "micro_folds_total", 0) for w in self.workers),
            "last_micro_folds": getattr(self, "last_micro_folds", 0),
        }
        w0 = self.workers[0]
        if getattr(w0, "_reader_ctxs", None):
            # shared-nothing ingest: per-context lifetime attribution
            # (index 0 = home context, 1.. = reader shards) plus the
            # commit-mutex contention record — contended_fraction ~ 0 is
            # the shared-nothing proof
            out["reader_shards"] = w0.reader_stats()
        out["spans"] = self._span_stats()
        delivery = {rname: man.stats()
                    for rname, man in self._delivery_managers()}
        if delivery:
            out["delivery"] = delivery
        if self._journals:
            out["journal"] = {rname: j.stats()
                              for rname, j in self._journals.items()}
        if self.shutdown_stats:
            out["shutdown"] = dict(self.shutdown_stats)
        return out

    def _last_tick_s(self) -> float:
        sp = self.rec.last("flush")
        return sp.seconds if sp is not None else 0.0

    def _span_stats(self) -> dict:
        """Span conservation for the loadgen controller. On the columnar
        path the books balance exactly:
        received == derived + dropped + pending (received counts every
        handle_ssf plus native-extracted spans; derived counts spans
        whose metrics reached the workers — on device for the native
        rows). The legacy SpanWorker path reports the same fields from
        its channel/lane tallies; its pending is a point-in-time queue
        depth, so the balance there is an eventual one, not an exact
        invariant."""
        with self._ssf_stats_lock:
            received = self.ssf_spans_received_total
        native = self._spans_native_total
        received += native
        if self.span_pipeline is not None:
            ps = self.span_pipeline.stats()
            # legacy-worker tallies are zero in pure columnar operation,
            # but a runtime customization (see handle_ssf) reroutes the
            # stream through the lanes — fold those books in so the
            # conservation invariant survives the mixed case too
            ext = self._extraction_sink
            sw = self.span_worker
            with ext._stats_lock:
                lderived = ext.spans_seen
                lrows = ext.derived_rows
                linvalid = ext.invalid_samples
            with sw._stats_lock:
                ldropped = (sw.spans_dropped
                            + sw.lane_drops.get(ext.name(), 0)
                            + sw.ingest_timeouts.get(ext.name(), 0))
            return {
                "received": received,
                "derived": ps["spans_derived"] + native + lderived,
                "derived_rows": ps["derived_rows"] + lrows,
                "dropped": ps["spans_dropped"] + ldropped,
                "pending": ps["pending"] + sw.pending(),
                "invalid_samples": ps["invalid_samples"] + linvalid,
                "columnar": True,
            }
        ext = self._extraction_sink
        sw = self.span_worker
        with ext._stats_lock:
            derived = ext.spans_seen
            rows = ext.derived_rows
            invalid = ext.invalid_samples
        with sw._stats_lock:
            dropped = (sw.spans_dropped
                       + sw.lane_drops.get(ext.name(), 0)
                       + sw.ingest_timeouts.get(ext.name(), 0))
        return {
            "received": received,
            "derived": derived + native,
            "derived_rows": rows,
            "dropped": dropped,
            "pending": sw.pending(),
            "invalid_samples": invalid,
            "columnar": False,
        }

    def _delivery_managers(self):
        """(report name, DeliveryManager) for every sink that carries
        one; span sinks report under <name>_spans so a metric/span sink
        pair sharing a vendor name stays distinguishable."""
        out = []
        for sink in self.metric_sinks:
            man = getattr(sink, "delivery", None)
            if man is not None:
                out.append((sink.name(), man))
        for sink in self.span_sinks:
            man = getattr(sink, "delivery", None)
            if man is not None:
                out.append((sink.name() + "_spans", man))
        for plugin in self.plugins:
            man = getattr(plugin, "delivery", None)
            if man is not None:
                out.append((plugin.name(), man))
        return out

    @property
    def parse_errors(self) -> int:
        """Total parse/overlong errors: Python-side cells, each worker's
        drained-and-attributed count, and the not-yet-drained native
        delta. Monotonic — a drain only MOVES the native delta into the
        worker's cumulative count (reset per process, not per epoch)."""
        n = self._ctr_sum(1)
        for w in self.workers:
            n += getattr(w, "parse_errors", 0)
            native = getattr(w, "_native", None)
            if native is not None:
                n += int(native.errors) - w._native_errs_seen
                for j, ctx in enumerate(getattr(w, "_reader_ctxs", ())):
                    n += int(ctx.errors) - w._reader_errs_seen[j]
        return n

    def _bump_errors(self, n: int = 1) -> None:
        self._ctr_cell()[1] += n

    def _route(self, metric) -> None:
        i = metric.digest % len(self.workers)
        with self._worker_locks[i]:
            self.workers[i].process_metric(metric)

    def _route_many(self, metrics: list) -> None:
        """Route a burst of metrics taking each worker lock once per
        group instead of once per metric (the columnar span pipeline
        derives thousands of rows at the flush edge). Per-worker order is
        exactly what per-metric _route would produce — grouping is a
        stable partition of one FIFO stream — so sketch state stays
        bit-identical to the per-span path."""
        nw = len(self.workers)
        if nw == 1:
            with self._worker_locks[0]:
                process = self.workers[0].process_metric
                for m in metrics:
                    process(m)
            return
        groups: dict[int, list] = {}
        for m in metrics:
            groups.setdefault(m.digest % nw, []).append(m)
        for i, group in groups.items():
            with self._worker_locks[i]:
                process = self.workers[i].process_metric
                for m in group:
                    process(m)

    def process_metric_packet(self, datagram: bytes) -> None:
        """Split a datagram on newlines and handle each line
        (reference processMetricPacket, server.go:1136)."""
        self._ctr_cell()[0] += 1
        if len(datagram) > self.config.metric_max_length:
            self._bump_errors()
            log.debug("overlong metric datagram (%d bytes)", len(datagram))
            return
        if self.native_mode:
            # no Python lock here: the C++ router parses lock-free and
            # commits under per-shard mutexes, so concurrent readers scale
            self._native_router.ingest(datagram)
            # pending-drain check is strided: each check is a ctypes call
            # per shard, which at line rate would rival the parse cost.
            # The counter is racy across readers — that only skews WHICH
            # packet triggers the check; buffers are bounded by
            # batch_size + stride·lines_per_packet and always drain at
            # flush.
            self._native_ingest_tick += 1
            if self._native_ingest_tick % 64 == 0:
                self._drain_native_thresholds()
            # events and service checks come back for the Python parser
            if b"_e{" in datagram or b"_sc" in datagram:
                self._drain_native_events()
            return
        for line in datagram.split(b"\n"):
            if line:
                self.handle_metric_packet(line)

    def _drain_native_thresholds(self) -> None:
        """Drain any worker whose native SoA spill/set/scalar batches
        crossed batch_size (shared by the strided ingest check and the
        native-reader pump). A drain that is due is a ``pump`` span:
        how long it waited for the worker's ingest lock, then the
        drain's own spans (worker.drain_native)."""
        for i, w in enumerate(self.workers):
            ctxs = [w._native] + list(getattr(w, "_reader_ctxs", ()))
            if any(c.pending_histo >= w.batch_size
                   or c.pending_set >= w.batch_size for c in ctxs):
                with self._locked_span("pump", i, w):
                    w.drain_native()

    @contextlib.contextmanager
    def _locked_span(self, name: str, i: int, worker):
        """``name`` > ``name``.lock_wait, then the body under worker i's
        ingest lock: who stood in line for that lock, and for how long.
        Both bear the epoch read under the lock (a swap may have closed
        one while this thread waited), as _micro_fold's do."""
        lock = self._worker_locks[i]
        with self.rec.span(name, worker=i) as sp:
            with self.rec.span(name + ".lock_wait") as lw:
                lock.acquire()
            try:
                sp.flush = lw.flush = worker.flight_epoch
                yield sp
            finally:
                lock.release()

    def _drain_native_events(self) -> None:
        """Pull buffered event/service-check lines out of the C++ context
        and parse them on the Python path. MUST NOT be called while
        holding a worker lock — the parsed lines re-enter _route, which
        takes them. Deliberately lock-free on the Python side: the drain
        serializes on the C++ ctx mutex (per-thread scratch in native.py),
        so reader threads no longer funnel through worker 0's ingest
        lock; each parsed line then routes to its digest owner."""
        for w in self.workers:
            for ctx in [w._native] + list(getattr(w, "_reader_ctxs", ())):
                for line in ctx.drain_other():
                    self.handle_metric_packet(line)

    def _drain_native_ssf_fallbacks(self) -> None:
        """Raw SSF payloads the C++ SSF reader handed back (STATUS spans
        need the Python pipeline). Same no-lock-held, no-funnel rule as
        events."""
        if not self._native_ssf_readers:
            return
        for pkt in self.workers[0]._native.drain_ssf_fallback():
            self.handle_trace_packet(pkt)

    # -- SSF ingest ---------------------------------------------------------

    def handle_trace_packet(self, packet: bytes) -> None:
        """One unframed SSF datagram → span pipeline
        (reference HandleTracePacket, server.go:1046)."""
        if not packet:
            self._bump_errors()
            # reference tag set verbatim (server.go:1052)
            self.stats.count("ssf.error_total", 1,
                             tags=_SSF_ERR_ZEROLENGTH)
            return
        if self._native_ssf:
            # native decode + span→metric extraction in one C++ pass;
            # rc -1 = span carries STATUS samples → Python path below
            with self._worker_locks[0]:
                rc = self.workers[0].ingest_ssf_packet(
                    packet, self._native_ssf_indicator,
                    self._native_ssf_objective,
                    self.config.ssf_span_uniqueness_rate)
            if rc == 1:
                return
            if rc == 0:
                self._bump_errors()
                self.stats.count("ssf.error_total", 1,
                                 tags=_SSF_ERR_UNMARSHAL)
                return
        try:
            span = ssf_wire.parse_ssf(packet)
        except ssf_wire.FramingError as e:
            self._bump_errors()
            self.stats.count("ssf.error_total", 1,
                             tags=_SSF_ERR_UNMARSHAL)
            log.debug("bad SSF packet: %s", e)
            return
        if span.id == 0:
            # client problem, counted but the span is still handled
            # (reference server.go:1067-1072)
            self.stats.count("ssf.error_total", 1,
                             tags=_SSF_ERR_EMPTY_ID)
            log.debug("trace packet has zero span id")
        self.handle_ssf(span)

    def ingest_internal_span(self, span) -> None:
        """Self-tracing entry: a finished internal span enters the same
        pipeline external SSF spans do."""
        self.handle_ssf(span)

    def handle_trace_packets_native(self, packets: list[bytes]) -> None:
        """Batched twin of handle_trace_packet for the native SSF fast
        path: one C call decodes+extracts the whole burst; STATUS-bearing
        spans come back for the Python pipeline."""
        worker = self.workers[0]
        with self._worker_locks[0]:
            ok, errs, fallbacks = worker._native.ingest_ssf_many(
                packets, self._native_ssf_indicator,
                self._native_ssf_objective,
                self.config.ssf_span_uniqueness_rate)
            worker.processed += ok
            if (worker._native.pending_histo >= worker.batch_size
                    or worker._native.pending_set >= worker.batch_size):
                worker.drain_native()
        self._bump_errors(errs)
        if errs:
            self.stats.count("ssf.error_total", errs,
                             tags=_SSF_ERR_UNMARSHAL)
        for pkt in fallbacks:
            try:
                span = ssf_wire.parse_ssf(pkt)
            except ssf_wire.FramingError as e:
                self._bump_errors()
                self.stats.count("ssf.error_total", 1,
                                 tags=_SSF_ERR_UNMARSHAL)
                log.debug("bad SSF packet: %s", e)
                continue
            if span.id == 0:
                # same client-problem counter as the single-packet path
                self.stats.count("ssf.error_total", 1,
                                 tags=_SSF_ERR_EMPTY_ID)
            self.handle_ssf(span)

    def handle_ssf(self, span) -> None:
        """reference handleSSF (server.go:1077): per-service counters,
        then into the span worker."""
        service = span.service or "unknown"
        with self._ssf_stats_lock:
            self.ssf_spans_received[service] = (
                self.ssf_spans_received.get(service, 0) + 1)
            self.ssf_spans_received_total += 1
        sw = self.span_worker
        # columnar only while the worker is pristine: a runtime-appended
        # per-span sink or a tapped/replaced ingest (both long-standing
        # observation patterns) must keep seeing every span, so either
        # customization routes the whole stream back through the lanes
        if (self.span_pipeline is not None
                and getattr(sw.ingest, "__func__", None) is SpanWorker.ingest
                and len(sw.span_sinks) == self._span_worker_sink_count):
            self.span_pipeline.ingest(span)
        else:
            sw.ingest(span)

    def start_ssf_udp(self, addr: str, port: int) -> int:
        sock = self._adopt_fd()
        if sock is None:
            sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind((addr, port))
        bound_port = sock.getsockname()[1]
        self._sockets.append(sock)

        if (self._native_ssf and self.config.tpu_native_readers
                and self._native_router is not None):
            # C++ SSF reader: datagram -> proto decode -> span->metric
            # extraction with no Python on the path; STATUS spans buffer
            # for the pump's fallback drain
            try:
                sock.setblocking(True)
                h = self._native_router.start_ssf_reader(
                    self.workers[0]._native, sock.fileno(),
                    min(self.config.trace_max_length_bytes, 65536),
                    self._native_ssf_indicator, self._native_ssf_objective,
                    self.config.ssf_span_uniqueness_rate)
                self._native_ssf_readers.append(h)
                self._start_native_pump()
                return bound_port
            except (AttributeError, RuntimeError) as e:
                log.warning("native SSF reader unavailable (%s); using the"
                            " Python reader", e)

        def loop():
            sock.settimeout(0.5)  # quiesce-able without closing (handoff)
            # per-datagram read buffer sized from trace_max_length_bytes,
            # matching the reference's tracePool (server.go:859-863) — NOT
            # ssf_buffer_size, which upstream is a deprecated span-count
            # alias (config_parse.go:172-176). Inet UDP datagrams cap at
            # 65507B, so clamp there; a datagram larger than the buffer is
            # truncated by recv and fails proto parse -> parse error, as
            # in the reference.
            max_len = min(self.config.trace_max_length_bytes, 65536)
            buf = bytearray(max_len)
            while not (self._shutdown.is_set() or self._quiesce.is_set()):
                try:
                    n = sock.recv_into(buf, max_len)
                    data = bytes(buf[:n])
                except socket.timeout:
                    continue
                except OSError:
                    return
                if not self._native_ssf:
                    self.handle_trace_packet(data)
                    continue
                # native fast path: greedily drain whatever else is
                # already queued and decode the whole burst in one C call
                # (the per-call overhead is ~1/3 of per-span cost)
                batch = [data]
                sock.setblocking(False)
                try:
                    while len(batch) < 512:
                        batch.append(sock.recv(max_len))
                except (BlockingIOError, OSError):
                    pass
                finally:
                    sock.settimeout(0.5)
                self.handle_trace_packets_native(batch)

        self._spawn(loop, "ssf-udp")
        return bound_port

    def start_ssf_unix(self, path: str) -> None:
        """Framed SSF over a unix stream socket
        (reference startSSFUnix, networking.go:222-285)."""
        sock = self._bind_unix_socket(path, socket.SOCK_STREAM)
        sock.listen(64)

        def accept_loop():
            while not self._shutdown.is_set():
                try:
                    conn, _ = sock.accept()
                except OSError:
                    return
                self._spawn(lambda c=conn: self._read_ssf_stream(c),
                            "ssf-unix-conn")

        self._spawn(accept_loop, "ssf-unix-accept")

    def _read_ssf_stream(self, conn: socket.socket) -> None:
        """Framed read loop; a framing error poisons the stream
        (reference ReadSSFStreamSocket, server.go:1215)."""
        f = conn.makefile("rb")
        try:
            while not self._shutdown.is_set():
                try:
                    span = ssf_wire.read_ssf(
                        f, max_length=self.config.trace_max_length_bytes)
                except ssf_wire.SSFUnmarshalError as e:
                    # the frame was consumed whole; the stream can keep
                    # reading (reference ReadSSFStreamSocket continues on
                    # non-framing errors, server.go:1243-1248)
                    self._bump_errors()
                    self.stats.count("ssf.error_total", 1,
                                     tags=_SSF_ERR_PROCESSING)
                    log.debug("bad SSF frame payload: %s", e)
                    continue
                if span is None:
                    # clean client hangup at a frame boundary
                    # (reference server.go:1229-1232)
                    self.stats.count("frames.disconnects", 1)
                    return
                self.handle_ssf(span)
        except ssf_wire.FramingError as e:
            # a framing violation poisons the stream: close it
            # (reference protocol/wire.go IsFramingError path,
            # server.go:1234-1241)
            self._bump_errors()
            self.stats.count("ssf.error_total", 1,
                             tags=_SSF_ERR_FRAMING)
            log.debug("SSF stream framing error, closing: %s", e)
        except OSError:
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def start_ssf_unixgram(self, path: str) -> None:
        """Unframed SSF datagrams over a unix datagram socket (reference
        ReadSSFPacketSocket over unixgram, networking.go:222-285)."""
        sock = self._bind_unix_socket(path, socket.SOCK_DGRAM)

        def loop():
            # unix datagrams are not bound by the inet 64KiB limit, so the
            # buffer is the full trace_max_length_bytes (reference
            # tracePool, server.go:859-863), allocated once per listener
            max_len = self.config.trace_max_length_bytes
            buf = bytearray(max_len)
            while not self._shutdown.is_set():
                try:
                    n = sock.recv_into(buf, max_len)
                except OSError:
                    return
                self.handle_trace_packet(bytes(buf[:n]))

        self._spawn(loop, "ssf-unixgram")

    def start_ssf_listeners(self) -> dict[str, int]:
        ports = {}
        for spec in self.config.ssf_listen_addresses:
            proto, _, rest = spec.partition("://")
            # fd-manifest key is namespaced: a statsd listener with the
            # IDENTICAL spec string (e.g. both "udp://127.0.0.1:0") must
            # not cross-wire its handed-off fds with this one's
            key = "ssf:" + spec
            if proto == "udp":
                self._adopt = list(
                    self._inherited.pop(key, None)
                    or self._inherited.pop(spec, []))  # pre-ns manifests
                before = len(self._sockets)
                host, _, port = rest.rpartition(":")
                ports[spec] = self.start_ssf_udp(host or "127.0.0.1",
                                                 int(port))
                self._listener_fds[key] = [
                    s.fileno() for s in self._sockets[before:]]
                self._close_unused_adopted()
            elif proto in ("unix", "unixstream"):
                self.start_ssf_unix(rest)
            elif proto == "unixgram":
                self.start_ssf_unixgram(rest)
            else:
                raise ValueError(f"unsupported SSF listener {spec!r}")
        return ports

    # -- listeners ----------------------------------------------------------

    def _spawn(self, target, name: str,
               compute: bool = False) -> threading.Thread:
        """Every long-lived server thread is wrapped in panic capture
        (reference ConsumePanic around goroutines, sentry.go:22-60,
        server.go:395-400): report to sentry_dsn, then abort so process
        supervision restarts us. Exceptions during shutdown are routine
        (sockets closed underneath readers) and are suppressed.

        compute=True marks a thread that runs device programs; shutdown
        joins those (bounded) so the interpreter never finalizes while
        one is inside XLA/C++ (see shutdown())."""
        t = threading.Thread(
            target=crash.guard(target, self.config.sentry_dsn, name,
                               suppress=self._shutdown.is_set),
            name=name, daemon=True)
        t.start()
        self._threads.append(t)
        if compute:
            self._compute_threads.append(t)
        return t

    def _adopt_fd(self) -> Optional[socket.socket]:
        """Take one inherited listener fd (if the previous process image
        handed one off for the listener being started)."""
        while self._adopt:
            fd = self._adopt.pop(0)
            try:
                return socket.socket(fileno=fd)
            except OSError:
                log.warning("inherited fd %d unusable; binding fresh", fd)
        return None

    def start_statsd_udp(self, addr: str, port: int) -> int:
        """N reader threads sharing the port via SO_REUSEPORT
        (reference networking.go:41-91, socket_linux.go)."""
        if self._adopt and len(self._adopt) != self.config.num_readers:
            # num_readers changed across the restart: a mixed
            # adopted/fresh set can't share the port (the old sockets'
            # SO_REUSEPORT state is fixed at their bind), so fall back to
            # an all-fresh bind — a brief re-bind window, logged, instead
            # of an EADDRINUSE crash
            log.warning(
                "num_readers changed across restart (%d inherited fds,"
                " %d readers); re-binding fresh", len(self._adopt),
                self.config.num_readers)
            self._close_unused_adopted()
        bound_port = port
        for i in range(self.config.num_readers):
            sock = self._adopt_fd()
            if sock is None:
                sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                if self.config.num_readers > 1:
                    sock.setsockopt(socket.SOL_SOCKET,
                                    socket.SO_REUSEPORT, 1)
                if self.config.read_buffer_size_bytes:
                    sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF,
                                    self.config.read_buffer_size_bytes)
                sock.bind((addr, bound_port))
            bound_port = sock.getsockname()[1]  # resolve port 0 once
            self._sockets.append(sock)
            if self._start_native_metric_reader(sock):
                continue
            self._spawn(
                lambda s=sock: self._read_metric_socket(s),
                f"statsd-udp-{i}",
            )
        return bound_port

    def _start_native_metric_reader(self, sock: socket.socket) -> bool:
        """Hand a bound datagram fd to a C++ reader thread: datagram →
        parse → staged sample with no Python (or GIL) on the path. The
        Python socket object stays in self._sockets so the fd outlives
        the thread (handoff keeps it open for the successor). Returns
        False when native readers are off/unavailable — the caller spawns
        the Python reader instead."""
        if not (self.native_mode and self.config.tpu_native_readers):
            return False
        try:
            sock.setblocking(True)
            with self._native_reader_lock:
                idx = len(self._native_readers)
            if self._reader_shards:
                # shared-nothing: reader idx commits exclusively into
                # reader context idx % R — no shared mutex on the line
                # path (events/errors stay on that context too)
                ctxs = self.workers[0]._reader_ctxs
                h = ctxs[idx % len(ctxs)].start_owned_reader(
                    sock.fileno(), self.config.metric_max_length)
            else:
                # digest-routed commits; `home` spreads each reader's
                # event/service-check/error buffers across the worker
                # contexts instead of funnelling them onto shard 0
                h = self._native_router.start_reader(
                    sock.fileno(), self.config.metric_max_length,
                    home=idx % len(self.workers))
            with self._native_reader_lock:
                self._native_readers.append(h)
            self._start_native_pump()
            return True
        except (AttributeError, RuntimeError) as e:
            log.warning("native reader unavailable (%s); using the"
                        " Python reader", e)
            return False

    def _start_native_pump(self) -> None:
        """With C++ readers, no Python code sees datagrams — this thread
        takes over the strided duties of process_metric_packet: threshold
        drains of the spill/set/scalar SoA batches and the event/service-
        check handback (both also run at every flush)."""
        if getattr(self, "_native_pump_started", False):
            return

        def pump() -> None:
            while not (self._shutdown.is_set() or self._quiesce.is_set()):
                time.sleep(0.1)
                try:
                    self._drain_native_thresholds()
                    self._drain_native_events()
                    self._drain_native_ssf_fallbacks()
                    self._reap_stream_readers()
                except Exception:
                    if self._shutdown.is_set():
                        return
                    raise

        self._spawn(pump, "native-pump", compute=True)
        # only after a successful spawn: a thread-creation failure must
        # leave the flag unset so the next caller retries
        self._native_pump_started = True

    def _reap_stream_readers(self) -> None:
        """Join C++ stream readers whose connection ended — an unjoined
        dead thread pins its stack for the process lifetime, and TCP
        connection churn would accumulate them."""
        with self._native_reader_lock:
            live = []
            for h in self._native_stream_readers:
                try:
                    if self._native_router.stream_reader_done(h):
                        self._native_router.stop_stream_reader(h)
                        self.stats.count("tcp.disconnects", 1)
                    else:
                        live.append(h)
                except Exception:
                    log.exception("stream reader reap failed")
            self._native_stream_readers = live

    def _stop_native_readers(self) -> None:
        """Join the C++ reader threads WITHOUT closing their fds (handoff
        leaves queued datagrams for the successor). Idempotent."""
        with self._native_reader_lock:
            readers, self._native_readers = self._native_readers, []
            for h in readers:
                try:
                    # stop_reader returns the FINAL count (post-join);
                    # reading before the join would lose the packets of
                    # the thread's last recv-timeout window
                    self._native_reader_packets_stopped += (
                        self._native_router.stop_reader(h))
                except Exception:
                    log.exception("native reader stop failed")
            ssf_readers = self._native_ssf_readers
            self._native_ssf_readers = []
            for h in ssf_readers:
                try:
                    # SSF packets are spans, not statsd packets: counted
                    # via the ssf.spans.received_total pipeline, not here
                    self._native_router.stop_ssf_reader(h)
                except Exception:
                    log.exception("native SSF reader stop failed")
            stream_readers = self._native_stream_readers
            self._native_stream_readers = []
            if stream_readers:
                self.stats.count("tcp.disconnects", len(stream_readers))
            for h in stream_readers:
                try:
                    # stream readers own their (dup'd) conn fds and close
                    # them; TCP connections don't ride the handoff
                    self._native_router.stop_stream_reader(h)
                except Exception:
                    log.exception("native stream reader stop failed")

    def _read_metric_socket(self, sock: socket.socket,
                            handoff_capable: bool = True) -> None:
        """reference ReadMetricSocket (server.go:1123): tight recv loop.
        Reads max_length+1 so overlong datagrams are detectable. The
        periodic timeout lets a handoff quiesce readers WITHOUT closing
        the socket — once quiesced, datagrams queue in the kernel buffer
        for the next process image instead of being consumed here.
        handoff_capable=False (path-based unixgram sockets, which re-bind
        instead of riding the exec) keeps consuming until shutdown —
        quiescing a socket that is about to be closed would destroy
        whatever queued behind it."""
        bufsize = self.config.metric_max_length + 1
        sock.settimeout(0.5)
        while not (self._shutdown.is_set()
                   or (handoff_capable and self._quiesce.is_set())):
            try:
                data = sock.recv(bufsize)
            except socket.timeout:
                continue
            except OSError:
                return  # socket closed during shutdown
            self.process_metric_packet(data)

    def start_statsd_tcp(self, addr: str, port: int) -> int:
        """Line-delimited TCP statsd, optional (mutual) TLS
        (reference server.go:1254-1335, TLS setup :438-472)."""
        sock = self._adopt_fd()  # inherited fds are already listening
        if sock is None:
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind((addr, port))
            sock.listen(128)
        bound_port = sock.getsockname()[1]
        self._sockets.append(sock)

        ssl_ctx = None
        if self.config.tls_key and self.config.tls_certificate:
            ssl_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ssl_ctx.load_cert_chain(self.config.tls_certificate,
                                    self.config.tls_key)
            if self.config.tls_authority_certificate:
                ssl_ctx.load_verify_locations(
                    self.config.tls_authority_certificate)
                ssl_ctx.verify_mode = ssl.CERT_REQUIRED

        def accept_loop():
            sock.settimeout(0.5)  # quiesce-able for handoff (see below)
            while not (self._shutdown.is_set() or self._quiesce.is_set()):
                try:
                    conn, peer = sock.accept()
                except socket.timeout:
                    continue
                except OSError:
                    return
                conn.settimeout(None)
                if (ssl_ctx is None and self.native_mode
                        and self.config.tpu_native_readers):
                    # plain TCP: a C++ line-stream reader owns the
                    # connection (TLS must stay Python — ssl wraps the
                    # socket object). Reader gets its own dup so the
                    # Python socket can be closed here; the pump reaps
                    # finished readers.
                    # the try covers ONLY dup+reader-start: once the C++
                    # reader owns the fd, a later failure (e.g. pump
                    # thread creation) must neither close the fd again
                    # nor fall back to the Python handler on it
                    fd = None
                    h = None
                    try:
                        fd = os.dup(conn.fileno())
                        h = self._native_router.start_stream_reader(
                            fd, self.config.metric_max_length)
                    except (AttributeError, RuntimeError) as e:
                        if fd is not None:
                            os.close(fd)
                        log.warning("native stream reader unavailable "
                                    "(%s); using the Python handler", e)
                    if h is not None:
                        self.stats.count("tcp.connects", 1)
                        with self._native_reader_lock:
                            self._native_stream_readers.append(h)
                        conn.close()
                        try:
                            self._start_native_pump()
                        except RuntimeError:
                            # thread creation failed; the reader is live
                            # and the next start attempt (UDP reader
                            # setup, next conn) retries the pump
                            log.exception("native pump start failed")
                        continue
                self._spawn(
                    lambda c=conn, p=peer: self._handle_tcp_conn(c, p, ssl_ctx),
                    "statsd-tcp-conn",
                )

        self._spawn(accept_loop, "statsd-tcp-accept")
        return bound_port

    def _handle_tcp_conn(self, conn: socket.socket, peer, ssl_ctx) -> None:
        """reference handleTCPGoroutine (server.go:1254-1335)."""
        self.stats.count("tcp.connects", 1)
        try:
            if ssl_ctx is not None:
                try:
                    conn = ssl_ctx.wrap_socket(conn, server_side=True)
                except (ssl.SSLError, OSError):
                    # a peer resetting mid-handshake raises plain
                    # ConnectionResetError, not ssl.SSLError
                    self.stats.count("tcp.tls_handshake_failures", 1)
                    raise
            conn.settimeout(10.0 * self.interval)
            buf = b""
            while not self._shutdown.is_set():
                data = conn.recv(65536)
                if not data:
                    break
                buf += data
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    if len(line) > self.config.metric_max_length:
                        self._bump_errors()
                        continue
                    if line:
                        self.handle_metric_packet(line)
            # trailing partial line without newline still counts
            if buf and len(buf) <= self.config.metric_max_length:
                self.handle_metric_packet(buf)
        except (OSError, ssl.SSLError) as e:
            log.debug("tcp statsd conn from %s error: %s", peer, e)
        finally:
            self.stats.count("tcp.disconnects", 1)
            try:
                conn.close()
            except OSError:
                pass

    def _bind_unix_socket(self, path: str, sock_type: int) -> socket.socket:
        """Bind a unix socket with flock-based exclusivity (reference
        acquireLockForSocket, networking.go:289-306): a `<path>.lock` file
        is flocked exclusively before the stale socket file is unlinked, so
        two server instances can never steal each other's socket. Abstract
        sockets (`@name`) have no filesystem presence and need no lock."""
        if path.startswith("@"):
            addr: bytes | str = "\0" + path[1:]
        else:
            import fcntl

            lock_path = path + ".lock"
            fd = os.open(lock_path, os.O_CREAT | os.O_RDWR, 0o644)
            try:
                fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                os.close(fd)
                raise RuntimeError(
                    f"socket {path!r} is locked by another veneur instance "
                    f"(flock on {lock_path!r} held)")
            self._socket_locks.append(fd)
            if os.path.exists(path):
                os.unlink(path)
            addr = path
        sock = socket.socket(socket.AF_UNIX, sock_type)
        sock.bind(addr)
        self._sockets.append(sock)
        return sock

    def start_statsd_unixgram(self, path: str) -> None:
        """Datagram unix socket statsd (reference networking.go:144-196),
        with flock exclusivity and abstract-socket (@name) support."""
        sock = self._bind_unix_socket(path, socket.SOCK_DGRAM)
        # same datagram semantics as UDP: the C++ reader works on any
        # bound datagram fd (_bind_unix_socket already registered the
        # socket in self._sockets, keeping the fd alive for the thread)
        if self._start_native_metric_reader(sock):
            return
        self._spawn(
            lambda: self._read_metric_socket(sock, handoff_capable=False),
            "statsd-unixgram")

    def start_listeners(self) -> dict[str, int]:
        """Start every configured statsd listener; returns resolved ports
        keyed by address string (reference StartStatsd, networking.go:19)."""
        ports = {}
        for spec in self.config.statsd_listen_addresses:
            proto, _, rest = spec.partition("://")
            self._adopt = list(self._inherited.pop(spec, []))
            before = len(self._sockets)
            if proto == "udp":
                host, _, port = rest.rpartition(":")
                ports[spec] = self.start_statsd_udp(host or "127.0.0.1",
                                                    int(port))
            elif proto == "tcp":
                host, _, port = rest.rpartition(":")
                ports[spec] = self.start_statsd_tcp(host or "127.0.0.1",
                                                    int(port))
            elif proto == "unixgram":
                # path-based sockets re-bind (flock exclusivity); no fd
                # handoff
                self.start_statsd_unixgram(rest)
            else:
                raise ValueError(f"unsupported statsd listener {spec!r}")
            if proto in ("udp", "tcp"):
                self._listener_fds[spec] = [
                    s.fileno() for s in self._sockets[before:]]
            self._close_unused_adopted()
        return ports

    def _close_unused_adopted(self) -> None:
        # config (e.g. num_readers) shrank across a restart: surplus
        # inherited fds must not leak
        for fd in self._adopt:
            try:
                os.close(fd)
            except OSError:
                pass
        self._adopt = []

    def prepare_handoff(self) -> dict[str, list[int]]:
        """Mark every network listener fd inheritable and return the
        spec→fds manifest for the next process image (einhorn-style
        zero-downtime restart, reference server.go:1401-1429). After this,
        shutdown() leaves those fds open so queued datagrams survive the
        re-exec."""
        self._handoff = True
        # stop the reader/accept loops first (without closing the
        # sockets) so datagrams queue in kernel buffers and TCP
        # connections wait in the listen backlog for the successor
        self._quiesce.set()
        self._stop_native_readers()  # joins; fds stay open for handoff
        deadline = time.time() + 2.0
        for t in self._threads:
            if t.name.startswith(("statsd-udp", "ssf-udp",
                                  "statsd-tcp-accept")):
                t.join(timeout=max(0.0, deadline - time.time()))
        for fds in self._listener_fds.values():
            for fd in fds:
                try:
                    os.set_inheritable(fd, True)
                except OSError:
                    log.warning("fd %d not inheritable; it will re-bind",
                                fd)
        return dict(self._listener_fds)

    # -- flush loop ---------------------------------------------------------

    def start(self) -> dict[str, int]:
        """Start listeners, sinks and the flush ticker
        (reference Server.Start, server.go:826)."""
        import jax

        devices = jax.devices()
        log.info("device: platform=%s kind=%s count=%d",
                 devices[0].platform, devices[0].device_kind, len(devices))
        if self.config.enable_profiling:
            # XLA-native analog of the reference's profile.Start()
            # (server.go:1392-1399): a JAX profiler trace capturing both
            # host Python and device (TPU) activity, viewable in
            # TensorBoard / Perfetto.
            try:
                import jax.profiler

                self._profile_dir = (self.config.profile_dir
                                     or "veneur-tpu-profile")
                jax.profiler.start_trace(self._profile_dir)
                log.info("XLA profiling enabled -> %s", self._profile_dir)
            except Exception:
                log.exception("could not start the JAX profiler")
                self._profile_dir = None
        # durable spill: attach + replay journals BEFORE sinks start, so
        # a prior incarnation's journaled payloads sit in the spill and
        # go out ahead of fresh data at the first flush (retry_spill)
        self._attach_journals()
        for sink in self.metric_sinks + self.span_sinks:
            sink.start()
        self.span_worker.start()
        ports = self.start_listeners()
        for spec, port in self.start_ssf_listeners().items():
            # identical spec on both listener lists (e.g. two ephemeral
            # "udp://127.0.0.1:0" binds): don't let the SSF port shadow
            # the statsd one in the report
            ports["ssf:" + spec if spec in ports else spec] = port
        # inherited fds whose listener spec left the config: close them,
        # or the old port stays bound with no reader and blackholes
        # traffic silently (clients get no ICMP error)
        for spec, fds in self._inherited.items():
            log.warning("closing %d inherited fds for removed listener %s",
                        len(fds), spec)
            for fd in fds:
                try:
                    os.close(fd)
                except OSError:
                    pass
        self._inherited.clear()
        for spec, port in self._start_query_listeners().items():
            ports[spec] = port
        if self.config.tpu_warmup_compile:
            self._spawn(self._warmup_compile, "warmup-compile",
                        compute=True)
        self._spawn(self._flush_loop, "flush-ticker", compute=True)
        if self.config.micro_fold:
            # always-hot flush scheduler (worker.micro_fold_once): the
            # staged ingest planes stream to the device mirrors DURING
            # the interval, so the tick's fold shrinks to a drain
            self._spawn(self._micro_fold_loop, "micro-fold", compute=True)
        if self.native_mode:
            self._spawn(self._series_sync_loop, "series-sync",
                        compute=True)
        return ports

    def _start_query_listeners(self) -> dict[str, int]:
        """Bind the live query fronts (config query_listen_addrs):
        http:// addresses serve /metrics (exposition) + /query (JSON),
        grpc:// addresses serve veneurtpu.Query/Query. Returns
        {spec: bound_port} merged into the start() port report."""
        ports: dict[str, int] = {}
        if self.query_engine is None:
            return ports
        for spec in self.config.query_listen_addrs:
            scheme, _, hostport = spec.partition("://")
            try:
                if scheme == "grpc":
                    from veneur_tpu.query.service import make_query_server

                    server, port = make_query_server(
                        self.query_engine, hostport)
                else:
                    from veneur_tpu.query.http import make_http_server

                    server, port = make_http_server(
                        self.query_engine, hostport)
            except Exception:
                # a query front failing to bind must not take down
                # ingest — the pipeline is the product, reads are a view
                log.exception("query listener %s failed to start", spec)
                continue
            self._query_servers.append((scheme, server))
            ports[spec] = port
            log.info("query listener on %s (port %d)", spec, port)
        return ports

    def _attach_journals(self) -> None:
        """Back every journalable sink's delivery spill with a
        write-ahead journal under <spill_journal_dir>/sink-<name>/ and
        replay whatever a prior incarnation left unacked. Managers that
        refuse (journal_exempt — splunk's send-once semantics) stay
        RAM-only. No spill_journal_dir = no-op, byte-identical to the
        in-RAM behaviour."""
        jdir = self.config.spill_journal_dir
        if not jdir:
            return
        from veneur_tpu.sinks.journal_codec import make_entry_codec
        from veneur_tpu.utils.journal import SpillJournal

        encode, decode = make_entry_codec()
        for rname, man in self._delivery_managers():
            if getattr(man, "journal_exempt", False):
                log.info("sink %s: spill journal skipped (send-once "
                         "semantics)", rname)
                continue
            journal = SpillJournal(
                os.path.join(jdir, f"sink-{rname}"),
                fsync=self.config.spill_journal_fsync,
                max_bytes=self.config.spill_journal_max_bytes,
                max_segments=self.config.spill_journal_max_segments,
                log=log.warning)
            if not man.attach_journal(journal, encode):
                journal.close()
                continue
            self._journals[rname] = journal
            n = man.recover(decode)
            if n:
                log.info("sink %s: %d journaled payload(s) recovered, "
                         "will retry ahead of fresh data", rname, n)

    def graceful_drain(self, deadline_s: Optional[float] = None) -> dict:
        """SIGTERM contract: final-epoch flush, then bounded delivery/
        spill-settling passes, with honest shutdown.* counters for
        whatever the deadline clips. Returns (and stores on
        self.shutdown_stats) the drain ledger; call before shutdown().

        With the journal on, clipped payloads stay durable and the next
        incarnation recovers them — the deadline bounds shutdown
        LATENCY, never silently converts spill into loss."""
        if deadline_s is None:
            deadline_s = self.config.shutdown_drain_deadline_s
        t0 = time.monotonic()
        deadline = t0 + max(0.0, float(deadline_s))
        stats: dict = {"deadline_s": float(deadline_s),
                       "final_flush": False, "drained_payloads": 0,
                       "drain_passes": 0}
        # 1) final-epoch swap + flush of whatever the last interval
        #    accumulated
        if deadline_s > 0:
            try:
                self.flush()
                stats["final_flush"] = True
            except Exception:  # noqa: BLE001 — drain anyway
                log.exception("graceful drain: final flush failed")
        # 2) bounded spill-settling passes across every manager until
        #    the spill is empty or the deadline clips
        managers = self._delivery_managers()
        while time.monotonic() < deadline:
            remaining = deadline - time.monotonic()
            spilled = 0
            for _, man in managers:
                if len(man.spill):
                    man.begin_flush(remaining)
                    stats["drained_payloads"] += man.retry_spill()
                spilled += len(man.spill)
            stats["drain_passes"] += 1
            if not spilled:
                break
            time.sleep(min(0.05, max(0.0, deadline - time.monotonic())))
        # 3) the honest remainder: what the deadline clipped
        left_payloads = left_bytes = 0
        for _, man in managers:
            s = man.stats()
            left_payloads += s["spilled_payloads"]
            left_bytes += s["spilled_bytes"]
        for journal in self._journals.values():
            journal.sync()
        stats.update({
            "clipped_payloads": left_payloads,
            "clipped_bytes": left_bytes,
            "deadline_clipped": left_payloads > 0,
            "journal_pending_records": sum(
                j.pending_records() for j in self._journals.values()),
            "duration_s": round(time.monotonic() - t0, 3),
        })
        self.shutdown_stats = stats
        self.stats.count("shutdown.drained_payloads",
                         stats["drained_payloads"])
        self.stats.count("shutdown.clipped_payloads", left_payloads)
        self.stats.count("shutdown.clipped_bytes", left_bytes)
        if left_payloads:
            log.warning(
                "graceful drain clipped by deadline: %d payload(s) / %d "
                "bytes still spilled%s", left_payloads, left_bytes,
                " (journaled for the next incarnation)"
                if self._journals else "")
        else:
            log.info("graceful drain complete in %.3fs (%d payload(s) "
                     "re-delivered)", stats["duration_s"],
                     stats["drained_payloads"])
        return stats

    def _warmup_compile(self) -> None:
        """Precompile the flush programs (staged fold + extraction) on a
        throwaway worker at the first pow2 row bucket, concurrent with
        startup. Without this the FIRST real flush pays the 20-40s
        per-shape XLA compile on TPU inside the interval — enough to trip
        a tight flush watchdog on a perfectly healthy server. Later
        growth buckets still compile lazily (and land in the persistent
        cache when tpu_compilation_cache_dir is set)."""
        try:
            from veneur_tpu.core.flusher import device_quantiles

            w = DeviceWorker(
                batch_size=self.config.tpu_batch_size,
                stage_depth=self.config.tpu_stage_depth,
                compression=self.config.tpu_compression,
                hll_precision=self.config.tpu_hll_precision,
                # must mirror the real workers' initial pool size or the
                # warmed shapes differ from the first real flush's
                initial_histo_rows=self.config.tpu_initial_histo_rows,
                is_local=self.is_local,
                series_shards=self.config.series_shards,
            )
            w.process_metric(
                dogstatsd.parse_metric(b"veneur.warmup:1|ms"))
            qs = device_quantiles(self.percentiles, self.aggregates)
            w.flush(qs, interval_s=self.interval)
            log.debug("flush programs warm (first row bucket)")
        except Exception:
            # warmup is best-effort, but not silent: the first real
            # flush compiles the same programs and meets the same error
            # where the device guard counts it
            log.warning("flush warmup failed", exc_info=True)

    def sync_native_series_once(self) -> None:
        """One locked new-series adoption sweep across all workers.

        The pending probe is a lock-free C call, so an idle sweep costs
        no worker-lock churn."""
        for i, worker in enumerate(self.workers):
            if worker._native is None or not worker.native_series_pending():
                continue
            with self._locked_span("sync", i, worker):
                worker.sync_native_series()

    def _series_sync_loop(self) -> None:
        """Adopt new-series registrations from the C++ contexts as they
        arrive instead of all at once inside flush's swap phase, so that
        the interval in which a million series hand over their strings
        for the first time (seconds of Python, once in their lifetime:
        worker._learn_series) does not spend them under the ingest lock
        at the tick. A series the context has handed over before costs
        an integer appended to its pool's book (RowBook, by id), here or
        in the micro-fold that gets to it first. Cadence is a fraction
        of the interval so the swap-time tail is small; the sweep
        early-returns when nothing is pending."""
        cadence = max(0.1, min(1.0, self.interval / 8.0))
        while not self._shutdown.wait(cadence):
            try:
                self.sync_native_series_once()
            except Exception:
                log.exception("series sync sweep failed")

    def _micro_fold_loop(self) -> None:
        """Sub-interval micro-fold scheduler (always-hot flush): poll
        each worker's staged backlog and drain it to the device mirror
        whenever the row-count or age threshold trips
        (worker.micro_fold_due / micro_fold_once). The due probe is
        lock-free (native: one C call; Python: a numpy sum); only an
        actual drain takes the worker's ingest lock, and briefly — the
        COO copy is a memcpy and the device feeds are async dispatches.
        Poll cadence tracks the age threshold so a trickle workload
        still drains within ~max_age."""
        cadence = max(0.01, min(1.0,
                                self.config.micro_fold_max_age_s / 2.0,
                                self.interval / 20.0))
        while not (self._shutdown.is_set() or self._quiesce.is_set()):
            if self._shutdown.wait(cadence):
                return
            for i, worker in enumerate(self.workers):
                try:
                    if worker.micro_fold_due():
                        self._micro_fold(i, worker)
                except Exception:
                    if self._shutdown.is_set():
                        return
                    # counted, not fatal: the staging plane retains every
                    # sample the mirror held, so the flush still folds the
                    # epoch — but a recurring drain error must be visible
                    self.stats.count("micro_fold.errors_total", 1,
                                     tags=[f"worker:{i}"])
                    log.exception("micro-fold drain failed (worker %d)", i)

    def _micro_fold(self, i: int, worker) -> None:
        """One micro-fold of one worker, as a span: how long it waited
        for the worker's ingest lock, and how long it then held it
        against the pump, the sweep and the flush's swap (micro_fold
        minus micro_fold.lock_wait; the C++ readers wait on the native
        context's lock, the ``ctx_lock`` spans below)."""
        with self._locked_span("micro_fold", i, worker) as sp:
            sp.attrs["samples"] = int(worker.micro_fold_once())
            sp.attrs["rows"] = int(getattr(worker._micro, "rows_hi", 0))

    def _flush_loop(self) -> None:
        """Interval ticker, optionally aligned to the wall clock
        (reference server.go:908-946)."""
        if self.config.synchronize_with_interval:
            time.sleep(calculate_tick_delay(self.interval, time.time()))
        next_tick = time.time()
        while not self._shutdown.is_set():
            next_tick += self.interval
            delay = next_tick - time.time()
            if delay > 0 and self._shutdown.wait(delay):
                return
            try:
                self._tick_due = next_tick
                _t0 = time.perf_counter()
                self.flush()
                self._adapt_spill_caps(time.perf_counter() - _t0)
            except Exception:
                log.exception("flush failed")
            finally:
                self._tick_due = None

    def _adapt_spill_caps(self, flush_dur: float) -> None:
        """Closed-loop overload shedding: bound the backlog one flush can
        inherit so the flush fits the interval. The C++ spill caps bound
        the direct-fold work a swap hands to extraction; when a flush
        overruns most of the interval, halve them (shed earlier at the
        parse boundary — cheap, counted — and keep the cadence); when
        flushes run comfortably fast, grow back toward the configured
        ceiling. The reference's equivalents are fixed-size worker
        channels (worker.go:31-48) plus a watchdog that kills a stalled
        flush (server.go:948-990); adapting the cap keeps the flush from
        being the thing that stalls."""
        ceiling = self.config.tpu_spill_cap
        floor = min(1 << 16, ceiling)
        cur = self._spill_cap_now
        if flush_dur > 0.9 * self.interval:
            new = max(floor, cur >> 1)
        elif flush_dur < 0.3 * self.interval:
            new = min(ceiling, cur << 1)
        else:
            return
        if new == cur:
            return
        self._spill_cap_now = new
        self.stats.gauge("ingest.spill_cap", new)
        for i, w in enumerate(self.workers):
            # under the worker's ingest lock (ADVICE item 3): _native is
            # published by attach paths and read by every ingest call;
            # the lock also orders the cap write against a concurrent
            # swap's drain/reset critical section
            with self._worker_locks[i]:
                w.spill_cap = new
                if w._native is not None:
                    w._native.set_spill_cap(new)
                    for ctx in getattr(w, "_reader_ctxs", ()):
                        ctx.set_spill_cap(new)

    def flush(self, now: float | None = None):
        """One flush pass (reference Server.Flush, flusher.go:28-134).

        Returns list[InterMetric] on the object path, or a
        ColumnarMetrics batch (len() works; call .materialize() for
        objects) when every sink consumed columns.

        `now` pins the interval's timestamp (tests compare two servers'
        output bit-for-bit by flushing both at one clock).

        Self-traced: every flush is a span (reference
        tracer.StartSpan("flush"), flusher.go:29) that rejoins this
        server's own span pipeline and surfaces as derived metrics on
        the NEXT interval."""
        # bracket the whole flush for the governor: in_flight + progress
        # beats are what the watchdog's deferral rule reads, so end_flush
        # must run even when a phase raises
        self.flush_governor.begin_flush()
        try:
            # the tracer's span is the operator's export and rejoins the
            # span pipeline; the recorder's, of the same name, is the
            # root of this flush in the span record and rejoins nothing
            with self.tracer.start_span("flush"):
                with self.rec.span("flush", flush=self.flush_count + 1):
                    job = self._flush_inner(now=now)
                # after the root has closed, so that the record handed
                # on (last_flush_phases["spans"]) holds it
                self._flush_publish(job)
            if job.batch is not None:
                # columnar flush: the batch supports len(); callers
                # needing objects use .materialize()
                return job.batch
            return job.final
        finally:
            self.flush_governor.end_flush()

    def _flush_inner(self, now: float | None = None):
        job = self._flush_begin(now=now)
        try:
            self._flush_extract(job)
        finally:
            # an epoch that was swapped and never reached its
            # extraction (a shutdown or an interrupt between the two)
            # still holds its detached C++ staging planes; an extracted
            # one holds none, and this frees nothing
            _abandon_swapped(job.swapped)
        self._flush_generate(job)
        self._flush_emit(job)
        return job

    def _flush_begin(self, now: float | None = None):
        """Tick-side flush phase: epoch close + device dispatches under
        the per-worker ingest locks (the map-swap analog of
        worker.go:498-517), no device readback. Freezes the interval's
        timestamp in job.ts: generation stamps InterMetrics from it."""
        flush_start = time.time() if now is None else float(now)
        self.last_flush_unix = flush_start
        self.flush_count += 1
        ordinal = self.flush_count
        self.stats.gauge("flush.flush_timestamp_ns", flush_start * 1e9)
        # per-phase wall times of this flush (reference tallyMetrics/
        # generateInterMetrics timing samples, flusher.go:169-298), each
        # the length of a span of the record (core/flightrec.py).
        # last_flush_phases rebinds only when the flush COMPLETES
        # (_flush_publish): observers polling mid-flush (the loadgen
        # cadence decomposition) must see the last finished flush, not a
        # half-filled dict
        phases: dict = {}
        begin = self.rec.span("flush.begin", flush=ordinal)
        if self._tick_due is not None and now is None:
            # fired minus scheduled: the ticker was held by the flush
            # before, and that flush owes this one the time
            begin.attrs["late_s"] = flush_start - self._tick_due
        with begin:
            qs, swapped, span_counts = self._flush_begin_swap(ordinal)
        phases["swap_s"] = begin.seconds
        # always-hot flush decomposition: how many micro-folds streamed
        # the closed epoch to the device mirrors, and how much of the
        # swap above was the final residual drain + mirror handoff (the
        # loadgen controller reports both per interval as micro_folds /
        # drain_ms)
        micro_folds = sum(getattr(w, "micro_folds_swapped", 0)
                          for w in self.workers)
        phases["drain_s"] = sum(
            sp.seconds for sp in self.rec.closed()
            if sp.flush == ordinal and sp.name in (
                "swap.drain.residual", "swap.mirror_handoff"))
        self.last_micro_folds = micro_folds
        if micro_folds:
            self.stats.count("worker.micro_folds_total", micro_folds)
        self.flush_governor.beat()  # swap complete: flush is live
        return FlushJob(ordinal=ordinal, ts=int(flush_start),
                        flush_start=flush_start, qs=qs, swapped=swapped,
                        begin=begin, span_counts=span_counts, phases=phases)

    def _flush_begin_swap(self, ordinal: int):
        """The body of flush.begin: drain what is buffered beside the
        workers, then close every worker's epoch under its ingest lock.
        Returns (quantiles, swapped epochs, per-service span counts)."""
        if self.native_mode:
            # events/service checks buffered in C++ (native readers have
            # no Python on the datagram path; the pump drains every 100ms
            # but this flush must see everything received before it).
            # Lines landing AFTER this drain are caught at epoch close —
            # worker.swap drains other_lines in the same critical section
            # as the context reset — and parsed into the next epoch below.
            self._drain_native_events()
            self._drain_native_ssf_fallbacks()

        other_samples = self.event_worker.flush()
        if other_samples:
            self.stats.count("worker.other_samples_flushed_total",
                             len(other_samples))
        for sink in self.metric_sinks:
            try:
                sink.flush_other_samples(other_samples)
            except Exception:
                log.exception("sink %s FlushOtherSamples failed", sink.name())

        _t_span = time.perf_counter()
        if self.span_pipeline is not None:
            # derive the interval's span batches into the workers BEFORE
            # the epoch swap below, so a span's metrics land in the same
            # epoch as the statsd samples that arrived beside it
            self.span_pipeline.flush()
        self.span_worker.flush()
        self.stats.time_in_nanoseconds(
            "worker.span.flush_duration_ns",
            (time.perf_counter() - _t_span) * 1e9)

        # per-service span counters (reference handleSSF sync.Map counters
        # reported at flush, server.go:1088-1101)
        with self._ssf_stats_lock:
            span_counts = self.ssf_spans_received
            self.ssf_spans_received = {}

        qs = device_quantiles(self.percentiles, self.aggregates)
        # Two-phase flush: the per-worker ingest lock is held only across
        # swap() (epoch close + device dispatches — the map-swap analog of
        # worker.go:498-517); the device readback in extract_snapshot()
        # runs unlocked, so next-interval ingest proceeds concurrently
        # with a large extraction (SURVEY §7 "Latency budget").
        swapped = []
        for i, (worker, lock) in enumerate(
                zip(self.workers, self._worker_locks)):
            with self.rec.span("swap.lock_wait", worker=i):
                lock.acquire()
            try:
                self._swap_worker(i, worker, qs, swapped, span_counts)
                # ingest-side spans (micro-folds, adoption) from here on
                # belong to the epoch the NEXT flush closes
                worker.flight_epoch = ordinal + 1
            except BaseException:
                # the epochs closed so far die with this flush
                _abandon_swapped(swapped)
                raise
            finally:
                lock.release()
        # event lines the swap caught at epoch close (would otherwise be
        # destroyed by the context reset): parse them into the NEW epoch,
        # OUTSIDE the worker locks — parsing re-enters _route
        for worker in self.workers:
            lines = getattr(worker, "pending_other_lines", None)
            if lines:
                worker.pending_other_lines = []
                for line in lines:
                    self.handle_metric_packet(line)
            pkts = getattr(worker, "pending_ssf_fallback", None)
            if pkts:
                worker.pending_ssf_fallback = []
                for pkt in pkts:
                    self.handle_trace_packet(pkt)
        rd = self._reader_ns()
        if rd is not None:
            # the C++ readers' lifetime nanoseconds inside recv and
            # outside it (parse + commit + lock wait): a reader of the
            # record takes the difference between two flushes
            cur = self.rec.current()
            cur.attrs["reader_recv_ns"], cur.attrs["reader_busy_ns"] = rd
            # what the busy time is made of: waiting for a context's
            # lock, holding it to commit, and the rest, the parse. (The
            # lock's record also counts a Python thread that ingests
            # through the router; the readers' clock does not.)
            locks = [w.reader_lock_ns() or (0, 0) for w in self.workers]
            wait_ns = sum(a for a, _ in locks)
            commit_ns = sum(b for _, b in locks)
            cur.attrs["reader_lock_wait_ns"] = wait_ns
            cur.attrs["reader_commit_ns"] = commit_ns
            cur.attrs["reader_parse_ns"] = rd[1] - wait_ns - commit_ns
            # beside them, what the commit path met (lifetime too)
            for w in self.workers:
                for k, v in (w.commit_counters() or {}).items():
                    cur.attrs[k] = cur.attrs.get(k, 0) + v
        # where the set entries went, lifetime too (worker.set_counters)
        for w in self.workers:
            for k, v in (w.set_counters() or {}).items():
                self.rec.add(k, v)
        return qs, swapped, span_counts

    def _reader_ns(self):
        """(ns in recv, ns outside it) summed over every native context,
        lifetime; None without native ingest."""
        per_ctx = [ns for w in self.workers
                   for ns in (w.reader_ns() or ())]
        if not per_ctx:
            return None
        return (sum(r for r, _ in per_ctx), sum(b for _, b in per_ctx))

    def _swap_worker(self, i: int, worker, qs, swapped: list,
                     span_counts: dict) -> None:
        """Close one worker's epoch; the caller holds its ingest lock."""
        with self.rec.span("swap", worker=i):
            if i == 0 and self._native_ssf:
                # drained in the SAME lock hold as the worker swap —
                # the swap resets the C++ context, and a span landing
                # between a separate drain and the reset would lose
                # its service count
                for svc, n in (
                        worker._native.drain_ssf_services().items()):
                    span_counts[svc] = span_counts.get(svc, 0) + n
                    # native-extracted spans derive on device and
                    # never pass handle_ssf: fold them into the
                    # conservation tallies here (same lock hold as
                    # the context reset, so none are lost mid-swap)
                    self._spans_native_total += n
            # canonical per-worker tallies (README.md:292-294),
            # captured before flush resets the epoch counters
            self.stats.count("worker.metrics_processed_total",
                             worker.processed, tags=[f"worker:{i}"])
            self.stats.count("worker.metrics_imported_total",
                             worker.imported, tags=[f"worker:{i}"])
            dropped = worker.overload_dropped
            if dropped:
                # samples shed at the native spill caps (overload;
                # drop-don't-block) — loud in self-telemetry, since
                # sustained nonzero means the host can't keep up
                self.stats.count("ingest.overload_dropped_total",
                                 dropped, tags=[f"worker:{i}"])
                worker.overload_dropped = 0
            swapped.append(worker.swap(qs))
            n_staged = getattr(worker, "staged_samples_swapped", 0)
            if n_staged:
                self.stats.count("worker.samples_staged_total",
                                 n_staged, tags=[f"worker:{i}"])
            if getattr(worker, "_reader_ctxs", None):
                # per-reader commit attribution (swap's fence just
                # settled reader_committed) + contention record:
                # emitted as lifetime-deltas per context, stashed
                # whole for ingress_stats/bench readers
                rs = worker.reader_stats()
                prev = getattr(self, "_reader_reported", None) or {}
                for kind, stat in (
                        ("committed", "ingest.reader_committed_total"),
                        ("dropped", "ingest.reader_dropped_total")):
                    for j, total in enumerate(rs[kind]):
                        delta = total - prev.get((kind, j), 0)
                        if delta:
                            self.stats.count(
                                stat, delta, tags=[f"reader:{j}"])
                        prev[(kind, j)] = total
                self._reader_reported = prev
            if self.tenant_ledger is not None:
                # per-tenant honest-drop counters, emitted as deltas
                # of the worker's LIFETIME tallies (read post-swap:
                # swap() folds the closing epoch — including any
                # swap-time shed attribution — into the totals before
                # resetting, exactly like processed_total). Lifetime
                # deltas survive the epoch swap; a pre-swap per-epoch
                # read would miss samples shed inside swap() itself.
                life = worker.tenant_lifetime()
                for kind, stat in (
                        ("rejected", "tenant.samples_rejected_total"),
                        ("dropped", "tenant.overload_dropped_total")):
                    for t, total in life[kind].items():
                        k = (i, kind, t)
                        delta = total - self._tenant_reported.get(k, 0)
                        if delta:
                            self._tenant_reported[k] = total
                            self.stats.count(
                                stat, delta, tags=[f"tenant:{t}"])

    def _flush_extract(self, job) -> None:
        """Device-readback flush phase: runs UNLOCKED, so next-interval
        ingest proceeds concurrently with a large extraction
        (SURVEY §7 "Latency budget")."""
        with self.rec.span("flush.extract", flush=job.ordinal) as sp:
            self._flush_extract_workers(job)
        job.phases["extract_s"] = sp.seconds
        # per-flush transfer accounting (health/ledger.py): the byte
        # counts that pin the O(samples) upload/readback diet, surfaced
        # the same way the reference surfaces flush phase timings
        h2d = sum(w.ledger.flush_h2d_bytes() for w in self.workers)
        d2h = sum(w.ledger.flush_d2h_bytes() for w in self.workers)
        self.last_flush_transfers = {"h2d_bytes": h2d, "d2h_bytes": d2h}
        if h2d or d2h:
            self.stats.count("flush.transfer_h2d_bytes", h2d)
            self.stats.count("flush.transfer_d2h_bytes", d2h)
        chunk_report = self.flush_governor.last_report
        self.last_flush_chunks = chunk_report
        # a micro-folds-only report (sub-floor pool: no chunking ran)
        # carries no chunk keys — guard on the key, not truthiness
        if "chunks" in chunk_report:
            self.stats.gauge("flush.extract_chunks",
                             chunk_report["chunks"])
            self.stats.time_in_nanoseconds(
                "flush.extract_chunk_max_ns",
                chunk_report["chunk_max_s"] * 1e9)

    def _flush_extract_workers(self, job) -> None:
        snaps = job.snaps
        for i, (worker, sw) in enumerate(zip(self.workers, job.swapped)):
            try:
                snaps.append(
                    worker.extract_snapshot(sw, job.qs, self.interval))
            except Exception:
                # per-flush data is expendable by design (README.md:135-137)
                # but a readback failure on one worker must not destroy the
                # already-swapped intervals of the others
                log.exception("flush extraction failed for worker %d", i)
            self.flush_governor.beat()  # one worker's extraction done
            # guard maintenance runs with the ingest lock held — it
            # mutates LIVE state (quarantine to host / probe re-admit),
            # unlike the extraction above which only reads swapped state
            with self.rec.span("extract.guard_tick", worker=i):
                with self._worker_locks[i]:
                    worker.device_guard_tick()
            g = worker.guard
            if (g.last_fault is not None
                    and g.last_fault != self._guard_last_fault.get(i)):
                # surface each new classified fault to the governor, so
                # a watchdog panic right after names the device error
                self._guard_last_fault[i] = g.last_fault
                desc = g.last_fault + (
                    f" — {g.trip_reason}" if g.trip_reason else "")
                self.flush_governor.note_fault(desc)
        if self.query_engine is not None:
            # commit AFTER every worker extracted: the query surface
            # flips to the new epoch atomically across workers
            with self.rec.span("extract.query_publish"):
                self.query_engine.commit(job.ts)
        for snap in snaps:
            # per-type flushed-series counts (README.md:293)
            d = snap.directory
            for mtype, n in (
                ("counter", len(snap.scalars.counter_meta)),
                ("gauge", len(snap.scalars.gauge_meta)),
                ("histogram", d.num_histo_rows),
                ("set", d.num_set_rows),
            ):
                if n:
                    self.stats.count("worker.metrics_flushed_total", n,
                                     tags=[f"metric_type:{mtype}"])

    def _flush_generate(self, job) -> None:
        """InterMetric-generation flush phase (host work over the
        already-extracted snapshots). Stamps every metric with job.ts —
        the tick-time clock — on both the columnar and object paths."""
        with self.rec.span("flush.generate", flush=job.ordinal) as sp:
            self._flush_generate_batch(job)
        job.phases["generate_s"] = sp.seconds

        if self.is_local and self.forwarder is not None:
            fwd_thread = threading.Thread(
                target=self.forwarder, args=(job.snaps,), daemon=True,
                name="forward",
            )
            fwd_thread.start()

    def _flush_generate_batch(self, job) -> None:
        snaps = job.snaps
        # Columnar fast path: the flush never materializes per-metric
        # Python objects up front — at 1M series the object loop alone is
        # seconds of host time (core/columnar.py). Columnar-capable sinks
        # consume the SoA batch directly; the rest share ONE memoized
        # materialization via the base flush_columnar, so a single legacy
        # sink no longer demotes every sink to the object path. Plugins
        # ride it too: they receive the batch itself — archival plugins
        # (veneur_tpu/archive/blob.py) serialize its arrays zero-copy,
        # and legacy TSV plugins iterate it, which shares the same
        # memoized materialization the object-path sinks use.
        use_columnar = bool(self.metric_sinks or self.plugins)
        final = job.final
        batch = None
        n_flushed = 0
        if use_columnar:
            from veneur_tpu.core.flusher import generate_columnar

            for snap in snaps:
                b = generate_columnar(
                    snap, self.is_local, self.percentiles,
                    self.aggregates, now=job.ts,
                    governor=self.flush_governor)
                if batch is None:
                    batch = b
                else:
                    batch.groups.extend(b.groups)
                    batch.extras.extend(b.extras)
            n_flushed = batch.count() if batch is not None else 0
        else:
            for snap in snaps:
                final.extend(
                    generate_inter_metrics(
                        snap, self.is_local, self.percentiles,
                        self.aggregates, now=job.ts,
                        governor=self.flush_governor
                    )
                )
            n_flushed = len(final)
        job.batch = batch
        job.n_flushed = n_flushed

    def _flush_emit(self, job) -> None:
        """Sink-emission flush phase plus the flush's self-telemetry
        tail."""
        with self.rec.span("flush.emit", flush=job.ordinal):
            self._flush_emit_sinks(job)

    def _flush_publish(self, job) -> None:
        """Rebind last_flush_phases, so observers always read the phases
        of the most recently COMPLETED flush, and beside them the spans
        of this flush and of the ingest side of its epoch."""
        # what became of the flushed epoch's row books (directory.RowBook):
        # how many had their ids resolved into per-interval containers,
        # and how many frag blobs were joined, the sinks' requests during
        # this flush included. 0 and 0 where every series came by id and
        # every sink took the columns
        books = [book for sw in job.swapped
                 for book in (sw.directory.histo, sw.directory.sets,
                              sw.scalars.counters, sw.scalars.gauges)]
        job.begin.attrs["books_materialised"] = sum(
            book.materialised for book in books)
        job.begin.attrs["frag_blob_builds"] = sum(
            book.frag_blob_builds for book in books)
        job.phases["spans"] = self.rec.of_flush(job.ordinal)
        self.last_flush_phases = job.phases
        self.last_emit_unix = time.time()

    def _run_sink_threads(self, phases: dict, targets) -> None:
        """Start one thread per (name, function, args) and join them at
        the interval, as the span emit.sinks: what sink_flush_s has
        always timed. Each function is handed the span as its last
        argument, to file its own work under (emit.sink)."""
        with self.rec.span("emit.sinks") as sp:
            threads = []
            for name, fn, args in targets:
                t = threading.Thread(target=fn, args=args + (sp,),
                                     daemon=True, name=name)
                t.start()
                threads.append(t)
            for t in threads:
                t.join(timeout=self.interval)
        phases["sink_flush_s"] = sp.seconds

    def _flush_emit_sinks(self, job) -> None:
        phases = job.phases
        batch = job.batch
        final = job.final
        n_flushed = job.n_flushed
        snaps = job.snaps
        span_counts = job.span_counts
        if batch is not None and n_flushed:
            self._run_sink_threads(phases, (
                (f"flush-{sink.name()}", self._flush_sink_columnar,
                 (sink, batch, self.sink_excluded_tags.get(sink.name())))
                for sink in self.metric_sinks))
            if self.plugins:
                self._run_plugins_clipped(batch, phases)
        elif final:
            # a generator: each sink's routing runs inside the span,
            # as its thread is about to start
            self._run_sink_threads(phases, (
                (f"flush-{sink.name()}", self._flush_sink,
                 (sink, strip_excluded_tags(
                     filter_routed(final, sink.name()),
                     self.sink_excluded_tags.get(sink.name()))))
                for sink in self.metric_sinks))
            if self.plugins:
                self._run_plugins_clipped(final, phases)
        else:
            # quiet tick (nothing aggregated this interval): the sinks'
            # flush funnels never ran, but spilled payloads must keep
            # draining — an idle server would otherwise freeze its spill
            # (and an open breaker would never get its half-open probe),
            # stranding recovered-journal backlogs and post-outage
            # retries until fresh traffic happens to arrive
            def _drain(man, _span) -> None:
                man.begin_flush()
                man.retry_spill()

            draining = [(f"spill-drain-{rname}", _drain, (man,))
                        for rname, man in self._delivery_managers()
                        if len(man.spill)]
            if draining:
                self._run_sink_threads(phases, draining)

        # flush self-telemetry (reference flusher.go:38-47, worker.go:513)
        if self.config.count_unique_timeseries:
            self.stats.count(
                "flush.unique_timeseries_total", self._tally_timeseries(snaps),
                tags=[f"global_veneur:{str(not self.is_local).lower()}"])
        self.stats.count("flush.post_metrics_total", n_flushed)
        if self.query_engine is not None:
            served = self.query_engine.queries_served
            failed = self.query_engine.queries_failed
            if served - self._query_reported[0]:
                self.stats.count("query.served_total",
                                 served - self._query_reported[0])
            if failed - self._query_reported[1]:
                self.stats.count("query.errors_total",
                                 failed - self._query_reported[1])
            self._query_reported = (served, failed)
        # per-phase wall times as self-metrics (the reference samples its
        # flush phases via ssf.Timing in tallyMetrics/generateInterMetrics,
        # flusher.go:169-298; ours are exact phase boundaries)
        for phase_name, secs in phases.items():
            self.stats.time_in_nanoseconds(
                "flush.phase_duration_ns", secs * 1e9,
                tags=[f"phase:{phase_name.removesuffix('_s')}"])
        # device fault domain telemetry (ops/device_guard.py): the guard
        # counters are lifetime totals — emit deltas, same discipline as
        # the reader/tenant counters above. host_fallbacks counts flushes
        # that completed on the host engine (degraded but conserved).
        fallbacks = sum(w.host_fallback_flushes for w in self.workers)
        if fallbacks - self._host_fallbacks_reported:
            self.stats.count("flush.host_fallbacks",
                             fallbacks - self._host_fallbacks_reported)
        self._host_fallbacks_reported = fallbacks
        quarantined = 0
        for i, w in enumerate(self.workers):
            if w.guard.quarantined:
                quarantined += 1
            for key, total in w.guard.counters().items():
                k = (i, key)
                delta = total - self._guard_counters_reported.get(k, 0)
                if delta:
                    self._guard_counters_reported[k] = total
                    self.stats.count(key, delta, tags=[f"worker:{i}"])
        self.stats.gauge("device.guard.quarantined_workers", quarantined)
        for svc, n in span_counts.items():
            self.stats.count("ssf.spans.received_total", n,
                             tags=[f"service:{svc}"])
        # statsd counters are per-interval increments: report the delta
        # (the property already totals the Python cells, the workers'
        # attributed counts, and the undrained native delta). The
        # property's reads aren't atomic vs a concurrent pump drain, so a
        # snapshot can transiently run BEHIND the last report — clamp so
        # a negative increment is never emitted; the next interval's
        # delta absorbs it.
        errors_now = max(self.parse_errors, self._errors_reported)
        self.stats.count("packet.error_total",
                         errors_now - self._errors_reported)
        self._errors_reported = errors_now
        # span-pipeline counters (reference worker.go:688,716-717:
        # ingest_timeout_total per sink, hit_chan_cap for channel drops)
        for name, total in list(self.span_worker.ingest_timeouts.items()):
            key = ("__span_worker__", f"timeout:{name}")
            delta = total - self._span_sink_reported.get(key, 0)
            self._span_sink_reported[key] = total
            if delta:
                self.stats.count("worker.span.ingest_timeout_total", delta,
                                 tags=[f"sink:{name}"])
        for name, total in list(self.span_worker.lane_drops.items()):
            key = ("__span_worker__", f"lane:{name}")
            delta = total - self._span_sink_reported.get(key, 0)
            self._span_sink_reported[key] = total
            if delta:
                # burst overflow of a sink's lane (no reference analog:
                # upstream blocks per span instead; this is the
                # loss-over-stall counterpart)
                self.stats.count("worker.span.lane_drop_total", delta,
                                 tags=[f"sink:{name}"])
        key = ("__span_worker__", "chan_cap")
        delta = self.span_worker.spans_dropped - self._span_sink_reported.get(
            key, 0)
        self._span_sink_reported[key] = self.span_worker.spans_dropped
        if delta:
            self.stats.count("worker.span.hit_chan_cap", delta)
        # span→metric derivation counters (satellite of the columnar
        # pipeline: soaks assert span conservation from these plus the
        # ingress_stats "spans" block)
        if self.span_pipeline is not None:
            pstats = self.span_pipeline.stats()
            pairs = (
                ("spans_ingested", "worker.span.columnar_ingested_total"),
                ("spans_derived", "worker.span.derived_total"),
                ("derived_rows", "worker.span.derived_metric_rows_total"),
                ("spans_dropped", "worker.span.pipeline_drop_total"),
                ("invalid_samples", "worker.span.invalid_samples_total"),
            )
            for attr, metric in pairs:
                key = ("__span_pipeline__", attr)
                delta = pstats[attr] - self._span_sink_reported.get(key, 0)
                self._span_sink_reported[key] = pstats[attr]
                if delta:
                    self.stats.count(metric, delta)
        else:
            ext = self._extraction_sink
            with ext._stats_lock:
                ext_pairs = (
                    ("spans_seen", "worker.span.derived_total",
                     ext.spans_seen),
                    ("derived_rows", "worker.span.derived_metric_rows_total",
                     ext.derived_rows),
                    ("invalid_samples", "worker.span.invalid_samples_total",
                     ext.invalid_samples),
                )
            for attr, metric, total in ext_pairs:
                key = ("__extraction__", attr)
                delta = total - self._span_sink_reported.get(key, 0)
                self._span_sink_reported[key] = total
                if delta:
                    self.stats.count(metric, delta)
        # span-sink delta counters (reference sinks/sinks.go:60-78;
        # sinks track cumulative attributes, telemetry reports deltas)
        for sink in self.span_sinks:
            tags = [f"sink:{sink.name()}"]
            for attr, metric in (("spans_flushed", "sink.spans_flushed_total"),
                                 ("spans_dropped", "sink.spans_dropped_total")):
                total = getattr(sink, attr, None)
                if total is None:
                    continue
                key = (sink.name(), attr)
                delta = total - self._span_sink_reported.get(key, 0)
                self._span_sink_reported[key] = total
                if delta:
                    self.stats.count(metric, delta, tags=tags)
        # plugin delta counters: the plugins' own cumulative failure /
        # progress attributes (localfile/s3/archive_blob) reported as
        # interval deltas like the sinks above, so a silently failing
        # archiver shows up on the same dashboard as a failing sink
        for plugin in self.plugins:
            pname = plugin.name()
            ptags = [f"plugin:{pname}"]
            for attr, metric in (
                    ("flush_errors", "plugins.flush_errors_total"),
                    ("uploads", "plugins.uploads_total"),
                    ("rotations", "plugins.rotations_total")):
                total = getattr(plugin, attr, None)
                if total is None:
                    continue
                key = (pname, attr)
                delta = total - self._plugin_reported.get(key, 0)
                self._plugin_reported[key] = total
                if delta:
                    self.stats.count(metric, delta, tags=ptags)
        # delivery-reliability telemetry (sinks/delivery.py): every
        # manager's cumulative counters as interval deltas, breaker and
        # spill occupancy as gauges.
        for rname, man in self._delivery_managers():
            if (self.tenant_ledger is not None
                    and man.abusive_tenants is None):
                # tenant-aware spill eviction (sinks/delivery.py): wired
                # lazily so sinks attached after server construction
                # still get the hook by their first flush
                man.abusive_tenants = self.tenant_ledger.over_budget
            dstats = man.stats()
            tags = [f"sink:{rname}"]
            for key in DELIVERY_STAT_COUNTERS:
                total = dstats[key]
                rkey = (rname, key)
                delta = total - self._delivery_reported.get(rkey, 0)
                self._delivery_reported[rkey] = total
                if delta:
                    self.stats.count(f"delivery.{key}", delta, tags=tags)
            self.stats.gauge("delivery.circuit_state",
                             float(dstats["circuit_state_code"]), tags=tags)
            self.stats.gauge("delivery.spilled_payloads",
                             float(dstats["spilled_payloads"]), tags=tags)
            self.stats.gauge("delivery.spilled_bytes",
                             float(dstats["spilled_bytes"]), tags=tags)
        # forward-path self-telemetry: the local forwarder's cumulative
        # counters as interval deltas, per proxy destination (tagged
        # proxy:<addr>) plus the spread-level respread/pick counters.
        # GRPCForwarder and SpreadForwarder report the same shape via
        # forward_stats() (the plain `stats` attribute is their
        # telemetry sink), so a single-proxy deployment shows the same
        # dashboard with one proxy tag value.
        fwd = self.forwarder
        if fwd is not None and hasattr(fwd, "forward_stats"):
            try:
                fstats = fwd.forward_stats()
            except Exception:  # noqa: BLE001 — telemetry must not wedge
                log.exception("forwarder stats failed")
                fstats = None
            if fstats:
                for name in ("respread_total", "respread_ambiguous_total",
                             "dropped_metrics", "picks_p2c", "picks_rr"):
                    total = fstats.get(name)
                    if total is None:
                        continue
                    key = ("", name)
                    delta = total - self._forward_reported.get(key, 0)
                    self._forward_reported[key] = total
                    if delta:
                        self.stats.count(f"forward.{name}", delta)
                self.stats.gauge("forward.proxies",
                                 float(fstats.get("proxies", 0)))
                for addr, dest in fstats.get("destinations", {}).items():
                    ptags = [f"proxy:{addr}"]
                    for name in ("sent_metrics", "sent_batches",
                                 "respread_in", "respread_out"):
                        total = dest.get(name)
                        if total is None:
                            continue
                        key = (addr, name)
                        delta = total - self._forward_reported.get(key, 0)
                        self._forward_reported[key] = total
                        if delta:
                            self.stats.count(f"forward.{name}", delta,
                                             tags=ptags)
                    for cause, total in (dest.get("errors") or {}).items():
                        key = (addr, f"errors.{cause}")
                        delta = total - self._forward_reported.get(key, 0)
                        self._forward_reported[key] = total
                        if delta:
                            self.stats.count(
                                "forward.errors_total", delta,
                                tags=ptags + [f"cause:{cause}"])
                    live = bool(dest.get("live", True))
                    self.stats.gauge("forward.lane_live",
                                     1.0 if live else 0.0, tags=ptags)
                    if live and "depth" in dest:
                        self.stats.gauge("forward.lane_depth",
                                         float(dest["depth"]), tags=ptags)
        # per-tenant QoS gauges (core/tenancy.py): live/rejected series
        # per tenant from the shared ledger, plus overload-shed samples
        # attributed by the governor — the operator-facing view of which
        # tenant is spending the cardinality budget
        led = self.tenant_ledger
        if led is not None:
            for t, n in led.live_counts().items():
                self.stats.gauge("tenant.series_live", float(n),
                                 tags=[f"tenant:{t}"])
            for t, n in led.series_rejected_counts().items():
                self.stats.gauge("tenant.series_rejected", float(n),
                                 tags=[f"tenant:{t}"])
            for t, n in self.flush_governor.tenant_shed_counts().items():
                self.stats.gauge("tenant.shed_samples", float(n),
                                 tags=[f"tenant:{t}"])
        # runtime gauges (analog of the Go runtime stats, flusher.go:32-47;
        # gc.number is cumulative completed collections, mem.rss_bytes is
        # CURRENT resident set from /proc — not the misleading peak)
        self.stats.gauge("gc.number", float(
            sum(s["collections"] for s in gc.get_stats())))
        interned = sum(w.interned_series for w in self.workers)
        self.stats.gauge("directory.interned", float(interned))
        self._freeze_series(interned)
        rss = _current_rss_bytes()
        if rss is not None:
            self.stats.gauge("mem.rss_bytes", float(rss))
        # total duration from the tick-time clock
        self.stats.time_in_nanoseconds(
            "flush.total_duration_ns",
            (time.time() - job.flush_start) * 1e9)

    def _freeze_series(self, interned: int) -> None:
        """Take what adoption keeps out of the cycle collector's walk.

        The workers keep a RowMeta, a MetricKey, a tag list and their
        strings per lifetime series: at 2^20 series ten million objects
        that never die, which every generation-2 pass walked (0.3-0.5 s
        of CPU an interval at 394k series, and the one whole-interpreter
        stall that was caught was such a pass). Once a flush has ended
        with the table a quarter larger than what was last frozen — the
        first full interval of a start, or a wave of new series — freeze
        the heap as it stands: the collector then walks what an interval
        made. Frozen objects still die by reference count (a dropped
        table frees its series); only a cycle alive at the freeze is
        never collected."""
        if interned < self._frozen_series:  # a table was dropped
            self._frozen_series = interned
        if interned > self._frozen_series * 1.25 + 4096:
            gc.freeze()
            self._frozen_series = interned

    @staticmethod
    def _tally_timeseries(snaps: list[FlushSnapshot]) -> int:
        """Merge per-worker unique-timeseries HLLs and estimate
        (reference Server.tallyTimeseries, flusher.go:134-143)."""
        import numpy as np
        from veneur_tpu.ops import hll as hll_ops
        regs = [s.unique_timeseries_registers for s in snaps
                if s.unique_timeseries_registers is not None]
        if not regs:
            return 0
        merged = regs[0]
        for r in regs[1:]:
            merged = np.maximum(merged, r)
        import math
        precision = int(math.log2(merged.shape[-1]))
        est = hll_ops.estimate(merged[None, :], precision=precision)
        return int(float(np.asarray(est)[0]))

    def _run_plugins_clipped(self, metrics, phases: dict) -> None:
        """Run the plugin pass in a worker thread joined at the flush
        interval — the same deadline clipping sinks get — so a hung
        plugin (blocked PUT, full disk) can never stall the emit stage
        past its tick. The thread is daemon: an overrun finishes (or
        dies with the process) without wedging shutdown."""
        with self.rec.span("emit.plugins") as sp:
            t = threading.Thread(
                target=self._flush_plugins, args=(metrics,), daemon=True,
                name="flush-plugins",
            )
            t.start()
            t.join(timeout=self.interval)
            if t.is_alive():
                self.stats.count("plugins.flush_clipped_total", 1)
        phases["plugin_flush_s"] = sp.seconds

    def _flush_plugins(self, metrics) -> None:
        """reference flusher.go:117-131: plugins run after the sinks.
        ``metrics`` is the ColumnarMetrics batch on the columnar path
        (iterable via the shared materialization) or the object list.
        Failures count — an exception here rides the self-telemetry
        stream as plugins.flush_errors_total, not just the log."""
        for plugin in self.plugins:
            start = time.time()
            tags = [f"plugin:{plugin.name()}"]
            try:
                plugin.flush(metrics, self.hostname)
            except Exception:
                log.exception("plugin %s flush failed", plugin.name())
                self.stats.count("plugins.flush_errors_total", 1, tags=tags)
            finally:
                self.stats.time_in_nanoseconds(
                    "plugins.flush_total_duration_ns",
                    (time.time() - start) * 1e9, tags=tags)

    def _flush_sink_columnar(self, sink: MetricSink, batch,
                             excluded_tags, parent=None) -> None:
        with self.rec.span("emit.sink", parent=parent, sink=sink.name()):
            self._emit_sink_columnar(sink, batch, excluded_tags)

    def _emit_sink_columnar(self, sink: MetricSink, batch,
                            excluded_tags) -> None:
        start = time.time()
        tags = [f"sink:{sink.name()}"]
        try:
            # per-sink capability negotiation: try the native emit tier
            # first (native/emit.cpp serializers, GIL released); a False
            # return means the sink couldn't take this batch natively
            # and the Python columnar formatter runs instead
            handled = False
            if (self.flush_emit_native
                    and getattr(sink, "supports_native_emit", False)):
                handled = sink.flush_columnar_native(batch, excluded_tags)
            fn = getattr(sink, "flush_columnar", None)
            if handled:
                pass
            elif fn is not None:
                fn(batch, excluded_tags)
            else:
                # duck-typed sink (name()/flush() without the MetricSink
                # base): hand it the shared materialization, routed and
                # tag-stripped like the object path would
                metrics = filter_routed(batch.materialize(), sink.name())
                sink.flush(strip_excluded_tags(metrics, excluded_tags))
        except Exception:
            log.exception("sink %s columnar flush failed", sink.name())
            self.stats.count("flush.error_total", 1, tags=tags)
        else:
            self.stats.count(
                "sink.metrics_flushed_total", batch.count_for(sink.name()),
                tags=tags)
        finally:
            self.stats.time_in_nanoseconds(
                "sink.metric_flush_total_duration_ns",
                (time.time() - start) * 1e9, tags=tags)

    def _flush_sink(self, sink: MetricSink,
                    metrics: list[InterMetric], parent=None) -> None:
        with self.rec.span("emit.sink", parent=parent, sink=sink.name()):
            self._emit_sink(sink, metrics)

    def _emit_sink(self, sink: MetricSink,
                   metrics: list[InterMetric]) -> None:
        start = time.time()
        tags = [f"sink:{sink.name()}"]
        try:
            sink.flush(metrics)
        except Exception:
            log.exception("sink %s flush failed", sink.name())
            self.stats.count("flush.error_total", 1, tags=tags)
        else:
            self.stats.count(
                "sink.metrics_flushed_total", len(metrics), tags=tags)
        finally:
            # canonical per-sink telemetry (reference sinks/sinks.go:11-24);
            # duration is recorded even on failure — that's when it matters
            self.stats.time_in_nanoseconds(
                "sink.metric_flush_total_duration_ns",
                (time.time() - start) * 1e9, tags=tags)

    # -- watchdog -----------------------------------------------------------

    def flush_watchdog(self) -> None:
        """Die if flushes stop happening, so process supervision restarts us
        (reference FlushWatchdog, server.go:948-990) — with one deliberate
        departure, the progress-aware deferral contract (health/policy.py):

        An overdue flush defers the panic WHILE ITS CHUNKS ARE COMPLETING.
        Chunked degraded-mode extraction makes a slow flush legitimate —
        bounded steps at the rate the hardware allows — and killing it
        would lose both the interval and the progress; sustained overload
        is the shedding layer's job (_adapt_spill_caps), not the
        watchdog's. A STALLED flush (no progress beat within the stall
        window) panics exactly as the reference would, as does a silent
        flush loop with nothing in flight."""
        missed = self.config.flush_watchdog_missed_flushes
        if missed == 0:
            return
        from veneur_tpu.health import watchdog_should_defer

        while not self._shutdown.is_set():
            if self._shutdown.wait(self.interval):
                return
            now = time.time()
            overdue = now - self.last_flush_unix
            if overdue > missed * self.interval:
                defer, why = watchdog_should_defer(
                    now, self.flush_governor, self.interval)
                if defer:
                    log.warning(
                        "flush watchdog: flush %.1fs overdue but "
                        "deferring (%s)", overdue, why)
                    self.stats.count("flush.watchdog_deferred_total", 1)
                    continue
                log.critical(
                    "flush watchdog: no flush for %.1fs (> %d intervals;"
                    " %s); aborting", overdue, missed, why,
                )
                os._exit(2)

    def start_watchdog(self) -> None:
        self._spawn(self.flush_watchdog, "flush-watchdog")

    # -- lifecycle ----------------------------------------------------------

    def shutdown(self) -> bool:
        """reference Server.Shutdown (server.go:1473). Idempotent — the
        /quitquitquit handler thread and the main loop may both call it.

        Returns False when a compute thread is still inside XLA/C++
        after the bounded join: the caller should exit via os._exit so
        interpreter finalization can't unwind it mid-frame."""
        self._shutdown.set()
        with self._shutdown_once_lock:
            if self._shutdown_done:
                # lost the once-race: the winner is mid-teardown, and
                # compute_threads_joined still holds its INITIAL True —
                # returning it now would tell the caller the join
                # succeeded before it ran (the caller would then let
                # interpreter finalization unwind a live XLA thread).
                # Wait for the winner; on timeout report False, the
                # conservative side (callers exit via os._exit).
                if not self._shutdown_complete.wait(timeout=30.0):
                    return False
                return self.compute_threads_joined
            self._shutdown_done = True
        try:
            return self._shutdown_teardown()
        finally:
            # set even when teardown raises: a loser blocked in the
            # wait above must not hang its full timeout on an exception
            self._shutdown_complete.set()

    def _shutdown_teardown(self) -> bool:
        """The winning shutdown() caller's teardown body."""
        self._stop_native_readers()
        # join the compute threads (bounded): a daemon thread still
        # inside XLA/C++ when the interpreter finalizes is force-unwound
        # mid-frame — glibc's "FATAL: exception not rethrown" abort
        # (reproduced by the overload soak exiting during a long flush).
        # Only threads spawned with compute=True are joined; listener
        # threads block in plain C syscalls (their sockets close below)
        # and joining them here would stall every shutdown instead.
        me = threading.current_thread()
        deadline = time.time() + 10.0
        for t in self._compute_threads:
            if t is me or not t.is_alive():
                continue
            t.join(timeout=max(0.1, deadline - time.time()))
        # a compute thread that outlived the bounded join is still inside
        # XLA/C++ (e.g. a starved multi-minute compile): the caller must
        # NOT let the interpreter finalize under it (glibc "FATAL:
        # exception not rethrown" / heap-corruption aborts at exit) —
        # exit with os._exit instead. All flush data is already out.
        self.compute_threads_joined = all(
            (t is me or not t.is_alive()) for t in self._compute_threads)
        if getattr(self, "_profile_dir", None):
            try:
                import jax.profiler

                jax.profiler.stop_trace()
                log.info("XLA profile written to %s", self._profile_dir)
            except Exception:
                log.exception("could not stop the JAX profiler")
            self._profile_dir = None
        self.stats.close()
        self.span_worker.stop()
        if self.forwarder is not None and hasattr(self.forwarder, "close"):
            # the spread forwarder settles its per-proxy spills and
            # stops its discovery refresher; single-destination
            # forwarders just close their channel
            try:
                self.forwarder.close()
            except Exception:
                log.exception("forwarder failed to close")
        for sink in list(self.metric_sinks) + list(self.span_sinks):
            try:
                sink.stop()
            except Exception:
                log.exception("sink %s failed to stop", sink.name())
        if self.import_server is not None:
            self.import_server.stop()
        if self.import_http is not None:
            self.import_http.stop()
        for scheme, server in self._query_servers:
            try:
                if scheme == "grpc":
                    server.stop(grace=0.5)
                else:
                    server.shutdown()
                    server.server_close()
            except Exception:
                log.exception("query listener (%s) failed to stop", scheme)
        self._query_servers.clear()
        for journal in self._journals.values():
            # final durability point: whatever is still spilled survives
            # for the next incarnation's recovery
            try:
                journal.sync()
                journal.close()
            except Exception:  # noqa: BLE001 — teardown must not wedge
                log.exception("spill journal close failed")
        self._journals.clear()
        handoff_fds = set()
        if self._handoff:
            for fds in self._listener_fds.values():
                handoff_fds.update(fds)
        for sock in self._sockets:
            try:
                if sock.fileno() in handoff_fds:
                    # fd rides through the re-exec; the kernel keeps
                    # queuing datagrams for the next process image
                    sock.detach()
                else:
                    sock.close()
            except OSError:
                pass
        for fd in self._socket_locks:
            try:
                os.close(fd)  # releases the flock
            except OSError:
                pass
        self._socket_locks.clear()
        return self.compute_threads_joined

    @property
    def version(self) -> str:
        return __version__

    @property
    def build_date(self) -> str:
        """Analog of the reference's linker-injected BUILD_DATE."""
        return os.environ.get("VENEUR_TPU_BUILD_DATE", "dev")
