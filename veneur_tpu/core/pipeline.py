"""Stage-parallel flush executor: overlap extract, generate, emit.

SUSTAINED_PIPELINE.json's rig is cadence-bound, not packet-bound: the
C++ ingest path holds 500k lines/s at <0.03% loss, but the serial flush
runs device extraction, InterMetric generation, and sink emission
back-to-back inside the tick, all timeslicing against ingest. This
module keeps the cheap snapshot swap on the flush tick and hands the
swapped epoch to three dedicated single-worker stages, so device
fold/extract for interval N, generation for N-1, and sink emission for
N-2 proceed concurrently — the same "overlap host work with accelerator
dispatch" discipline the JAX scaling literature prescribes for step
loops, applied to the flush loop. The reference hides sink latency the
same way with per-sink goroutines (flusher.go:92-115); this extends the
overlap across whole flush phases.

Invariants:

- Bit-identical output. Each stage runs the SAME server methods the
  serial flush runs (_flush_extract/_flush_generate/_flush_emit), over
  a FlushJob that froze its timestamp at tick time, so the pipelined
  InterMetric stream for an interval is byte-for-byte the serial one
  (tests/test_pipeline.py pins this across all metric classes).
- Single-worker stages. One thread per stage, bounded queues between
  them: intervals cannot reorder, and a stage's work for interval N
  always finishes before its work for N+1 starts.
- Bounded backpressure (health/policy.py MAX_STAGE_BACKLOG). A stage
  more than `max_backlog` intervals behind sheds instead of queueing:
  an over-full extract queue defers the TICK (nothing is swapped — the
  epoch keeps aggregating and the next tick flushes two intervals'
  worth, so counters are late, not lost), an over-full downstream
  queue drops that interval's flush output (per-flush data is
  expendable by design, README.md:135-137). Both paths count loudly;
  a shed interval or a RUN of deferred ticks (two consecutive — one is
  a transient the overlap absorbs) also kicks the standing shedding
  loop (_adapt_spill_caps) so the overload is attacked at the parse
  boundary.

The governor sees one in-flight flush per admitted interval
(begin_stage_flush / end_flush refcount), so the watchdog's deferral
rule keeps working under overlap, and the extract stage owns the
per-flush chunk report (begin_report).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any

from veneur_tpu.health.policy import MAX_STAGE_BACKLOG, pipeline_should_shed

log = logging.getLogger(__name__)

STAGES = ("extract", "generate", "emit")


@dataclass
class FlushJob:
    """One interval's flush state, passed stage to stage.

    `ts` is frozen at tick time so generation stamps InterMetrics with
    the interval's own wall clock regardless of how long earlier stages
    queued — the serial path stamps the identical value (bit-identity).
    """

    seq: int = 0
    # Server.flush_count of this flush: what its spans are filed under
    ordinal: int = 0
    # a serial flush holds the record's root span open around the four
    # phases and publishes the phases itself, once the root has closed
    rooted: bool = False
    ts: int = 0
    flush_start: float = 0.0
    qs: Any = None
    swapped: list = field(default_factory=list)
    span_counts: dict = field(default_factory=dict)
    phases: dict = field(default_factory=dict)
    snaps: list = field(default_factory=list)
    batch: Any = None
    final: list = field(default_factory=list)
    n_flushed: int = 0
    span: Any = None
    stage_s: dict = field(default_factory=dict)
    failed: bool = False


class FlushPipeline:
    """Owns the stage threads and queues; the server owns the phases."""

    def __init__(self, server, max_backlog: int = MAX_STAGE_BACKLOG) -> None:
        self._server = server
        self.max_backlog = max(1, int(max_backlog))
        self._queues = [queue.Queue(maxsize=self.max_backlog)
                        for _ in STAGES]
        self._threads: list[threading.Thread] = []
        self._stop_event = threading.Event()
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._inflight = 0
        self._seq = 0
        self.completed = 0
        self.completed_seq = 0
        self.deferred_ticks = 0
        # ticker-thread only: consecutive deferrals since the last
        # admitted tick. One deferral is a transient (an XLA recompile
        # billed to one extract) and costs nothing — the epoch keeps
        # aggregating; only a RUN of them means the extract stage is
        # persistently behind and the parse boundary should shed.
        self._consec_deferred = 0
        self.shed = {name: 0 for name in STAGES}
        # slowest stage of the most recently completed interval: the
        # pipeline's throughput bound, fed to _adapt_spill_caps in
        # place of the serial flush duration
        self.last_cycle_s = 0.0
        # times the delivery layer reported a sink persistently behind
        # (server delivery reporting via note_downstream_behind)
        self.downstream_behind = 0

    def start(self) -> None:
        if self._threads:
            return
        for idx, name in enumerate(STAGES):
            # server._spawn: crash capture + (for the device-touching
            # extract stage) the bounded compute-thread join at shutdown
            t = self._server._spawn(
                lambda i=idx: self._stage_loop(i),
                f"flush-{name}", compute=(name == "extract"))
            self._threads.append(t)

    # -- tick (called by the flush ticker only: single producer) ----------

    def tick(self, now: float | None = None) -> str:
        """Admit one interval: swap under the ingest locks, enqueue the
        swapped epoch for the stage threads. Returns "ok", or
        "deferred" when the extract stage is a full interval behind
        (backpressure: nothing is swapped, the epoch keeps aggregating
        and the next successful tick flushes it — late, not lost)."""
        srv = self._server
        if self._stop_event.is_set():
            return "stopped"
        if pipeline_should_shed(self._queues[0].qsize(), self.max_backlog):
            self.deferred_ticks += 1
            self._consec_deferred += 1
            srv.stats.count("flush.pipeline_deferred_total", 1)
            if self._consec_deferred >= 2:
                # persistently behind — attack the overload at the
                # parse boundary too (a single deferral sheds nothing:
                # measured on the 1-core rig, halving the spill caps on
                # every deferral threw away ~3% of an interval's lines
                # for stalls the pipeline absorbed by itself)
                srv._pipeline_overrun()
            log.warning("flush pipeline: extract stage %d interval(s) "
                        "behind; deferring tick (epoch keeps aggregating)",
                        self._queues[0].qsize())
            return "deferred"
        self._consec_deferred = 0
        gov = srv.flush_governor
        # refcounted in-flight mark, NOT begin_flush: the tick must not
        # clobber the chunk report an overlapped extract is still filling
        gov.begin_stage_flush()
        span = srv.tracer.start_span("flush")
        try:
            job = srv._flush_begin(now=now)
        except Exception:
            try:
                span.finish()
            finally:
                gov.end_flush()
            raise
        job.span = span
        with self._lock:
            self._seq += 1
            job.seq = self._seq
            self._inflight += 1
        # cannot be Full: this is the sole producer and the queue was
        # below the backlog bound above (consumers only drain it)
        self._queues[0].put_nowait(job)
        return "ok"

    # -- stage threads -----------------------------------------------------

    def _stage_loop(self, idx: int) -> None:
        q = self._queues[idx]
        while True:
            try:
                job = q.get(timeout=0.2)
            except queue.Empty:
                if self._stop_event.is_set():
                    return
                continue
            self._run(idx, job)

    def _run(self, idx: int, job: FlushJob) -> None:
        srv = self._server
        name = STAGES[idx]
        t0 = time.perf_counter()
        try:
            if idx == 0:
                # the extract stage owns the per-flush chunk report
                # (serial flushes reset it in begin_flush instead)
                srv.flush_governor.begin_report()
                srv._flush_extract(job)
            elif idx == 1:
                srv._flush_generate(job)
            else:
                srv._flush_emit(job)
        except Exception:
            # per-flush data is expendable; the stage thread is not.
            # crash.guard would abort the process on an escape, which is
            # right for a wedged loop but wrong for one bad interval.
            job.failed = True
            log.exception("flush pipeline: %s stage failed (interval %d)",
                          name, job.seq)
        job.stage_s[name] = time.perf_counter() - t0
        if job.failed or idx == len(STAGES) - 1:
            self._finish(job)
            return
        try:
            self._queues[idx + 1].put_nowait(job)
        except queue.Full:
            nxt = STAGES[idx + 1]
            self.shed[nxt] += 1
            srv.stats.count("flush.pipeline_shed_total", 1,
                            tags=[f"stage:{nxt}"])
            srv._pipeline_overrun()
            log.warning("flush pipeline: %s stage backlog full; shedding "
                        "interval %d's flush output", nxt, job.seq)
            self._finish(job)

    def _finish(self, job: FlushJob) -> None:
        try:
            if job.span is not None:
                job.span.finish()
        except Exception:
            log.debug("flush span finish failed", exc_info=True)
        finally:
            self._server.flush_governor.end_flush()
        with self._lock:
            self._inflight -= 1
            self.completed += 1
            if job.seq > self.completed_seq:
                self.completed_seq = job.seq
            if job.stage_s:
                self.last_cycle_s = max(job.stage_s.values())
            self._idle.notify_all()

    def note_downstream_behind(self) -> None:
        """Delivery layer signal (server._flush_emit): a sink has been
        behind — open breaker or spill deferrals — for
        DELIVERY_BEHIND_INTERVALS consecutive flushes. Treated like a
        persistent stage backlog: kick the standing shedding loop so
        the overload is attacked at the parse boundary instead of
        accumulating in sink spills."""
        with self._lock:
            self.downstream_behind += 1
        self._server._pipeline_overrun()

    # -- lifecycle ---------------------------------------------------------

    def drain(self, timeout: float | None = None) -> bool:
        """Block until every admitted interval has finished (emitted,
        shed, or failed). True on drained, False on timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            while self._inflight > 0:
                if deadline is None:
                    self._idle.wait(timeout=0.5)
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._idle.wait(timeout=remaining)
            return True

    def stop(self, drain: bool = True, timeout: float = 30.0) -> bool:
        """Drain in-flight intervals (the shutdown contract: the final
        tick's data reaches the sinks), then stop the stage threads."""
        drained = self.drain(timeout) if drain else True
        self._stop_event.set()
        for t in self._threads:
            t.join(timeout=2.0)
        return drained

    def stats(self) -> dict:
        with self._lock:
            return {
                "inflight": self._inflight,
                "completed": self.completed,
                "deferred_ticks": self.deferred_ticks,
                "shed": dict(self.shed),
                "last_cycle_s": self.last_cycle_s,
                "max_backlog": self.max_backlog,
                "downstream_behind": self.downstream_behind,
            }
