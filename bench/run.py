#!/usr/bin/env python3
"""bench/run.py: one cell of the benchmark, on the served path.

    python3 bench/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

One process that holds the chip and builds the server exactly as the
binary does (YAML -> load_config -> build_server -> start()), one child
(bench/sender.py) that sends the cell's lines in an open loop which does
not know about flushes. The server's own ticker cuts the stream; a
collector sink keeps every flush; bench/reference.py locates each cut
from the flush's own output and holds the flush to a float64 reference.

Every line of standard output is one JSON object; the last is the
contract's result line. Off the chip the same path runs as a rehearsal,
prints no result line and exits non-zero. bench/README.md has the rest.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()  # before the heavy imports: set-up counts them

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import re  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from bench import cpu, reference, senderlog, stream, trace_reduce  # noqa: E402

OUT = os.path.join(ROOT, "bench", "out")
WARMUP_INTERVALS = 90   # at most this many intervals before the window
ON_SCHEDULE = 0.02      # a tick this share of an interval late is late
HOLD_AFTER = 0.1        # the sender is held once a tick is this late


class BenchFailure(Exception):
    """The run cannot give a result; it ends non-zero with this reason."""


def emit(event: str, **fields) -> None:
    print(json.dumps({"event": event, **fields}, default=float), flush=True)


# --------------------------------------------------------------------------
# what the server logs, and what JAX compiles
# --------------------------------------------------------------------------

class LogTap(logging.Handler):
    """What the program logged at WARNING and above: the flush loop, the
    warm-up and the guard report failures there and carry on."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.warnings: list = []
        self.errors: list = []

    def emit(self, record: logging.LogRecord) -> None:
        if not record.name.startswith("veneur_tpu"):
            return
        msg = f"{record.name}: {record.getMessage()}"
        if record.exc_info and record.exc_info[1] is not None:
            msg += f" [{type(record.exc_info[1]).__name__}: " \
                   f"{str(record.exc_info[1])[:800]}]"
        (self.errors if record.levelno >= logging.ERROR
         else self.warnings).append(msg)


class CompileClock(logging.Handler):
    """What JAX compiled, or loaded from its persistent cache, and for
    how long, from its own compile log. A program found in the cache is
    logged like a compilation, after a line that says it was a hit (on
    the same thread): ``loaded`` tells the two apart. Both keep a flush
    from being quiet; only a real compilation inside the window makes a
    run incorrect."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        import jax

        self._done = re.compile(
            r"Finished XLA compilation of (.+) in ([0-9.eE+-]+) sec")
        self._lock = threading.Lock()
        self._hit: set = set()  # threads whose next compilation is a load
        self.log: list = []     # (time, program, seconds, loaded)
        jax.config.update("jax_log_compiles", True)
        for name in ("jax._src.dispatch", "jax._src.compiler"):
            lg = logging.getLogger(name)
            lg.addHandler(self)
            lg.propagate = False  # one line per trace and compile is noise
        px = logging.getLogger("jax._src.interpreters.pxla")
        px.addHandler(logging.NullHandler())
        px.propagate = False

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Persistent compilation cache hit"):
            with self._lock:
                self._hit.add(record.thread)
            return
        m = self._done.search(msg)
        if m:
            with self._lock:
                loaded = record.thread in self._hit
                self._hit.discard(record.thread)
                self.log.append((time.time(), m.group(1), float(m.group(2)),
                                 loaded))

    def between(self, t0: float, t1: float) -> dict:
        """Finished in (t0, t1]: {"n": compiled or loaded, "s": seconds,
        "compiled": real compilations, "programs": {name: [n, s]}}."""
        with self._lock:
            hits = [e for e in self.log if t0 < e[0] <= t1]
        progs: dict = {}
        for _, name, secs, _loaded in hits:
            e = progs.setdefault(name, [0, 0.0])
            e[0] += 1
            e[1] += secs
        return {"n": len(hits), "s": sum(e[2] for e in hits),
                "compiled": sum(not e[3] for e in hits), "programs": progs}


# --------------------------------------------------------------------------
# the collector sink: what the server's flush hands a columnar sink
# --------------------------------------------------------------------------

def make_collector(fault: str):
    from veneur_tpu.sinks import MetricSink

    class Collector(MetricSink):
        supports_columnar = True

        def __init__(self) -> None:
            self.flushes: list = []
            self.seen = threading.Condition()
            self.server = None  # set once the server is built

        def name(self) -> str:
            return "bench"

        def flush(self, metrics) -> None:
            raise BenchFailure("the object path ran; the columnar flush "
                               "is the served path")

        def flush_columnar(self, batch, excluded_tags=None) -> None:
            # keep the batch itself until the flush has ended: its arrays
            # are this flush's own, and names are resolved outside the
            # flush's timed span (Flushes.next)
            if fault == "alter" and len(self.flushes) == 2:
                # tests only: one answer altered where it is produced
                batch.groups[0].families[0].values[0] += 1.0
            with self.seen:
                self.flushes.append({
                    "t_seen": time.time(), "batch": batch,
                    "ordinal": self.server.flush_count,
                    "tick": self.server.last_flush_unix})
                self.seen.notify_all()

        def flush_other_samples(self, samples) -> None:
            pass

    return Collector()


def view_of(batch) -> reference.FlushView:
    """A flush's batch as plain arrays: per class and family suffix the
    series numbers emitted and their values. Series are named
    cs.<c|g|t|s>.<number> (bench/stream.py); other names are foreign."""
    view = reference.FlushView()
    parts: dict = {}
    for g in batch.groups:
        names = [g.meta_at(i)[0] for i in range(g.nrows)]
        cls = np.full(g.nrows, -1, np.int8)
        sid = np.zeros(g.nrows, np.int64)
        for r, nm in enumerate(names):
            if nm[:3] == "cs.":
                cls[r] = stream.LETTERS.find(nm[3])
                sid[r] = int(nm[5:])
            elif len(view.foreign) < 8:
                view.foreign.append(nm)
        for f in g.families:
            on = cls >= 0 if f.mask is None else (cls >= 0) & f.mask
            for c in np.unique(cls[on]).tolist():
                at = on & (cls == c)
                parts.setdefault((c, f.suffix), []).append(
                    (sid[at], np.asarray(f.values, np.float64)[at]))
    view.foreign += [m.name for m in batch.extras][:8 - len(view.foreign)]
    for key, ps in parts.items():
        view.families[key] = (np.concatenate([p[0] for p in ps]),
                              np.concatenate([p[1] for p in ps]))
    return view


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def write_yaml(path: str, config: dict, chips: int) -> dict:
    """The YAML the server is built from: the config file's ``server``
    keys and its interval; every key not named keeps the program's
    default (native ingest and readers, micro-fold, device guard and
    warm-up compile are on by default)."""
    cfg = dict(config["server"])
    cfg["interval"] = f"{int(config['interval_s'])}s"
    if config.get("preset_histo_rows"):
        # without it every epoch climbs the pow2 ladder from 4,096 rows;
        # the pool keeps one scratch row above the series, hence the
        # pow2 above the count and not at it
        cfg["tpu_initial_histo_rows"] = 1 << config["series"][
            "timer"].bit_length()
    if chips > 1:
        cfg["series_shards"] = chips
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for k, v in cfg.items():
            f.write(f"{k}: {json.dumps(v)}\n")
    return cfg


class Sender:
    """The child process that sends (bench/sender.py), its commands
    and what it says back."""

    def __init__(self, argv: list) -> None:
        self.proc = subprocess.Popen(argv, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True)
        self.said: queue.Queue = queue.Queue()
        threading.Thread(target=self._listen, name="bench-sender",
                         daemon=True).start()

    def _listen(self) -> None:
        for raw in self.proc.stdout:
            try:
                self.said.put(json.loads(raw))
            except ValueError:
                pass
        self.said.put({"event": "gone"})

    def tell(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def hear(self, event: str, timeout: float):
        """The next thing the child says, which must be ``event``; None
        if nothing comes in ``timeout`` seconds."""
        try:
            ev = self.said.get(timeout=timeout) if timeout > 0 \
                else self.said.get_nowait()
        except queue.Empty:
            return None
        if ev.get("event") != event:
            raise BenchFailure(f"the sender said {ev.get('event')!r}, "
                               f"not {event!r}")
        return ev

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()  # end of input stops the sender
            except OSError:
                pass
            try:
                self.proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


class Flushes:
    """The collector's flushes, taken one by one as they reach the sink,
    each with what the server says about it once it has finished."""

    def __init__(self, server, collector, clock, tick1, interval) -> None:
        self.server, self.collector, self.clock = server, collector, clock
        self.tick1, self.interval = tick1, interval
        self.taken = 0
        self.t_last = tick1

    def scheduled(self, ordinal: int) -> float:
        """The ticker adds one interval per flush and skips none, so
        flush n is due at the first tick + (n-1) intervals however late
        it fires."""
        return self.tick1 + (ordinal - 1) * self.interval

    def observe(self) -> None:
        """A tick can fire late and never early, so the schedule's base
        is the earliest that any tick seen so far allows (the first
        tick alone may have fired late, beside a busy start-up)."""
        n = self.server.flush_count  # before the time: a torn read
        t = self.server.last_flush_unix  # then errs late, harmlessly
        if n >= 1:
            self.tick1 = min(self.tick1, t - (n - 1) * self.interval)

    def pending(self) -> bool:
        with self.collector.seen:
            return len(self.collector.flushes) > self.taken

    def next(self, deadline: float, tap: LogTap) -> dict:
        c = self.collector
        with c.seen:
            while len(c.flushes) <= self.taken:
                if tap.errors:
                    raise BenchFailure(f"server logged: {tap.errors[0]}")
                if time.time() > deadline:
                    raise BenchFailure("no flush reached the sink in time")
                c.seen.wait(0.2)
            fl = c.flushes[self.taken]
        self.taken += 1
        # _flush_emit rebinds last_flush_phases after the sinks return
        limit = time.time() + 5.0
        while self.server.last_emit_unix < fl["t_seen"]:
            if time.time() > limit:
                raise BenchFailure("a flush reached the sink and never "
                                   "finished")
            time.sleep(0.002)
        self.tick1 = min(self.tick1, fl["tick"]
                         - (fl["ordinal"] - 1) * self.interval)
        fl["scheduled"] = self.scheduled(fl["ordinal"])
        fl["late_s"] = fl["tick"] - fl["scheduled"]
        fl["flush_s"] = fl["t_seen"] - fl["scheduled"]
        fl["phases"] = dict(self.server.last_flush_phases)
        fl["transfers"] = dict(self.server.last_flush_transfers)
        fl["micro_folds"] = int(getattr(self.server, "last_micro_folds", 0))
        fl["compiled"] = self.clock.between(self.t_last, fl["t_seen"])
        # for the log alone: when lines were shed
        fl["overload_dropped"] = self.server.ingress_stats()[
            "overload_dropped"]
        self.t_last = fl["t_seen"]
        # the batch goes as soon as its flush has ended: a dozen kept
        # batches keep a dozen epochs' directories alive, ten million
        # objects that the collector of cycles then walks for seconds
        # with every thread stopped (chip call 7 of PR 24: a 7 s stall
        # in two runs of six). Resolving 400k names takes about half a
        # second of this thread, beside ingest, after the flush's own
        # clock has stopped.
        fl["view"] = view_of(fl.pop("batch"))
        emit("flush", **{k: v for k, v in fl.items() if k != "view"})
        return fl


def device_path_faults(server) -> list:
    """Counters that would show the device path was left, or a line shed."""
    bad = []
    for i, w in enumerate(server.workers):
        for key, n in w.guard.counters().items():
            if n:
                bad.append(f"worker {i}: {key}={n}")
        if w.guard.quarantined:
            bad.append(f"worker {i}: quarantined ({w.guard.trip_reason})")
        if w.host_fallback_flushes:
            bad.append(f"worker {i}: flush.host_fallbacks="
                       f"{w.host_fallback_flushes}")
    return bad


def trace_thread(trace_dir: str, t_start: float, t_stop: float, out: dict):
    """A profiler trace from t_start to t_stop (tick to tick), device
    and TraceMe events only: the Python tracer would slow the server's
    own flush code and bury the trace."""
    import jax

    def run() -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        time.sleep(max(0.0, t_start - time.time()))
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        out["t0"] = time.time()
        with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
            out["anchor"] = time.time()
        time.sleep(max(0.0, t_stop - time.time()))
        out["t1"] = time.time()
        jax.profiler.stop_trace()
        out["stopped"] = time.time()

    th = threading.Thread(target=run, name="bench-trace")
    th.start()
    return th


def cpu_record(sampler, stages, interval: float, cell: str, raw_tag) -> dict:
    """What the samples of bench/cpu.py come to: ``run["cpu"]``. Writes
    the table by thread to bench/out/<cell>.cpu_by_thread.json, and with
    ``raw_tag`` (a traced run) /proc as it was read at the first and the
    last sample to bench/out/<raw_tag>.proc_tasks.json (bench/testdata
    keeps one)."""
    table = cpu.by_thread(sampler.threads, stages.first, interval)
    if table is not None:
        with open(os.path.join(OUT, cell + ".cpu_by_thread.json"), "w") as f:
            json.dump(table, f, indent=1)
    if raw_tag and sampler.raw:
        with open(os.path.join(OUT, raw_tag + ".proc_tasks.json"), "w") as f:
            json.dump({"interval_s": interval, "stages": stages.first,
                       "samples": [{**{k: sampler.threads[i][k]
                                       for k in ("t", "process", "python")},
                                    "stat": sampler.raw[i]}
                                   for i in sorted(sampler.raw)]}, f)
    emit("cpu", ticks=sampler.ticks, sampled_late_s=sampler.late_s,
         groups=table and table["groups"],
         top=table and dict(list(table["threads"].items())[:12]))
    return {"ticks": sampler.ticks, "interval_s": interval,
            "by_thread": table}


def run_cell(args, holder: dict, fault: str = "") -> dict:
    """The whole run. Returns {"result": the contract's line or None,
    "correct", "reasons", ...}; raises BenchFailure where it cannot."""
    with open(args.benchmark_file) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        raise BenchFailure(f"no workload {args.workload!r} in "
                           f"{args.benchmark_file}")
    config = stream.load_json("configs", cell["config"])
    traffic = stream.load_json("traffic", cell["traffic"])
    interval = float(config["interval_s"])
    os.makedirs(OUT, exist_ok=True)
    tag = f"{cell['name']}.{args.seed}"
    sender_log = os.path.join(OUT, tag + ".sender.json")

    # the sender builds its ring while this process builds the server
    child = Sender(
        [sys.executable, os.path.join(ROOT, "bench", "sender.py"),
         "--config", cell["config"], "--traffic", cell["traffic"],
         "--seed", str(args.seed), "--log", sender_log]
        + (["--fault", "double"] if fault == "double" else []))
    server = None
    tracer = None
    sampler = None
    # when each thread is first seen: tells the program's C++ reader
    # from the runtime's threads in the table by thread (bench/cpu.py)
    stages = cpu.Stages()
    stages.mark("import")
    try:
        import jax

        devs = jax.devices()
        stages.mark("backend")
        dev = devs[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs)}
        on_chip = dev.platform == "tpu" and len(devs) >= cell["chips"]
        emit("device", **device, workload=cell["name"], seed=args.seed,
             seconds=args.seconds, trace=args.trace, jax=jax.__version__,
             rehearsal=not on_chip)

        from veneur_tpu.core.config import load_config
        from veneur_tpu.core.factory import build_server

        tap = LogTap()
        logging.getLogger().addHandler(tap)
        clock = CompileClock()
        written = write_yaml(os.path.join(OUT, cell["name"] + ".yaml"),
                             config, cell["chips"])
        cfg = load_config(os.path.join(OUT, cell["name"] + ".yaml"))
        collector = make_collector(fault)
        server = build_server(cfg, extra_metric_sinks=[collector])
        collector.server = holder["server"] = server
        if not server.native_mode:
            raise BenchFailure(
                "native ingest is off: the C++ library did not build or "
                "load, and the Python parser is not the served path")
        ports = server.start()
        stages.mark("server")
        port = next(iter(ports.values()))
        emit("server", config=written, port=port,
             compilation_cache_dir=server.compilation_cache_dir,
             started_s=time.time() - T_PROCESS)

        ready = child.hear("ready", 120.0)
        if ready is None:
            raise BenchFailure("the sender did not build its ring")
        if ready["longest_line"] > cfg.metric_max_length:
            raise BenchFailure("a line longer than metric_max_length")
        emit("sender", **{k: v for k, v in ready.items() if k != "event"})

        # the first tick: the ticker fires one interval after start()
        limit = time.time() + 3 * interval
        while server.flush_count < 1:
            if time.time() > limit:
                raise BenchFailure("the ticker never fired")
            time.sleep(0.001)
        tick1 = server.last_flush_unix
        # the open loop's clock: cycle k starts at s0 + k intervals,
        # s0 a fixed phase after a tick (traffic file), and from here on
        # the sender never hears from the server again
        phase = float(traffic["phase_s"])
        flushes = Flushes(server, collector, clock, tick1, interval)

        def next_start() -> float:
            """The first tick + phase that is still ahead."""
            base = flushes.tick1
            m = math.ceil((time.time() + 0.3 - base - phase) / interval)
            return base + max(0, m) * interval + phase

        s0 = next_start()
        stages.mark("first_tick")
        child.tell(port=port, s0=s0)
        # the first flush with traffic loads or compiles most of the
        # programs and outlasts its interval: the sender stands still
        # after its first cycle until that is over (below)
        child.tell(hold=True)
        emit("schedule", tick1=tick1, s0=s0, interval_s=interval)
        emit("hold", at=time.time(), flush_count=server.flush_count,
             after_first_cycle=True)
        # the sender connects as soon as it has the port, and the accept
        # loop hands the connection to a C++ reader thread of its own
        emit("reader_threads",
             tids=stages.wait_for_native(cpu.READER_STAGE, 1.0))

        # ---- warm-up, under the cell's own traffic: all of it set-up ----
        # The window opens at the first scheduled tick after two flushes
        # in a row that held the schedule's traffic, began on the
        # ticker's schedule and compiled (or loaded) nothing. A flush
        # that compiles past its interval makes the ticker late; the
        # sender is then held at its next cycle boundary (and always
        # after its first cycle), so that no backlog piles up which no
        # steady state has, neither of shapes nor against the spill
        # cap, and given a new s0 once the ticker is back on schedule
        # and idle. Each such point is kept (syncs): the chain of cuts
        # can start anew there.
        record: list = []
        t0 = None
        held, holding, syncs = None, True, []
        limit = tick1 + WARMUP_INTERVALS * interval
        while t0 is None:
            now = time.time()
            flushes.observe()
            if now > limit:
                raise BenchFailure(
                    f"no two quiet flushes on the ticker's schedule in "
                    f"{WARMUP_INTERVALS} intervals")
            if tap.errors:
                raise BenchFailure(f"server logged: {tap.errors[0]}")
            due = flushes.scheduled(server.flush_count + 1)
            behind = now > due + ON_SCHEDULE * interval
            idle = server.last_emit_unix >= server.last_flush_unix
            # a flush that outlasts its interval is compiling, and while
            # it holds the ingest lock nothing drains what the reader
            # parses: the sender is told before its cycle ends (cycles
            # end phase_s after a tick), so that at most 1.2 cycles and
            # not 2.2 pile up against the program's spill cap
            if now > due + HOLD_AFTER * interval and not holding:
                child.tell(hold=True)
                holding = True
                emit("hold", at=now, flush_count=server.flush_count)
            if holding and held is None:
                held = child.hear("held", 0)
            # resumed after a flush that began on schedule, clear of the
            # sender's last write, and has ended: it drained whatever
            # was left, so the next flush starts at the sender's count
            # whatever was shed before (reference.compare_record)
            if (holding and held is not None and idle and not behind
                    and server.last_flush_unix
                    >= held["t"] + 0.1 * interval
                    and abs(server.last_flush_unix - flushes.scheduled(
                        server.flush_count)) < ON_SCHEDULE * interval):
                s0 = next_start()
                syncs.append({
                    "ordinal": server.flush_count,
                    "lines": held["lines_written"],
                    "shed": server.ingress_stats()["overload_dropped"]})
                child.tell(s0=s0)
                holding, held = False, None
                emit("resume", s0=s0, **syncs[-1])
            if not flushes.pending():
                time.sleep(0.02)
                continue
            fl = flushes.next(now + 1.0, tap)
            record.append(fl)
            fl["quiet"] = (fl["compiled"]["n"] == 0 and not holding
                           and fl["tick"] >= s0
                           and abs(fl["late_s"]) < ON_SCHEDULE * interval)
            prev = record[-2] if len(record) > 1 else None
            nxt = flushes.scheduled(fl["ordinal"] + 1)
            if (fl["quiet"] and prev is not None and prev.get("quiet")
                    and prev["ordinal"] == fl["ordinal"] - 1
                    and fl["t_seen"] < nxt - 0.05 * interval):
                t0 = nxt
        n0 = record[-1]["ordinal"] + 1
        n_counted = math.ceil(args.seconds / interval)
        t1 = t0 + args.seconds
        setup_s = t0 - T_PROCESS
        stages.mark("warmup")
        sampler = cpu.Sampler(
            [t0 + k * interval for k in range(n_counted)]).start()
        warm = clock.between(0, time.time())
        emit("window", opens=t0, seconds=args.seconds, setup_s=setup_s,
             first_ordinal=n0, flushes_counted=n_counted,
             warmup_compiles=warm["n"], warmup_compile_s=warm["s"])

        # ---- the measured window ----
        trace_out: dict = {}
        if args.trace:
            k = 1 if n_counted > 1 else 0  # one whole flush, tick to tick
            trace_dir = os.path.join(OUT, tag + ".trace")
            tracer = trace_thread(trace_dir, t0 + k * interval,
                                  t0 + (k + 1) * interval, trace_out)
            trace_out["ordinal"] = n0 + k
        counted: list = []
        reasons: list = []
        while len(counted) < n_counted:
            want = n0 + len(counted)
            fl = flushes.next(flushes.scheduled(want) + 3 * interval, tap)
            record.append(fl)
            if fl["ordinal"] != want:
                reasons.append(f"flush {want} missing or doubled: flush "
                               f"{fl['ordinal']} reached the sink")
                break
            counted.append(fl)
        t_window_end = time.time()
        inside = clock.between(t0, max(t_window_end, t1))
        # counted, printed and read as a per-layer metric, not part of
        # `correct`: the parent specialises its spill fold per batch
        # size, so a program never met before can turn up in any window
        # (PERF.md section 7), and what it costs shows in flush_s
        emit("window_compiles", **inside)
        if tracer is not None:
            tracer.join()

        # ---- stop the sender, let one more flush drain ----
        # not before the last cycle that started in the window is written
        last_cycle = math.ceil((t1 - s0) / interval)
        time.sleep(max(0.0, s0 + last_cycle * interval + 0.05 - time.time()))
        child.tell(stop=True)
        stopped = child.hear("stopped", 3 * interval)
        if stopped is None:
            raise BenchFailure("the sender did not stop")
        # the first flush whose tick falls clear of the last write (its
        # bytes may still sit in socket buffers) holds whatever is left
        limit = time.time() + 4 * interval
        while not (server.last_flush_unix
                   >= stopped["t_stopped"] + 0.1 * interval
                   and server.last_emit_unix >= server.last_flush_unix):
            if time.time() > limit:
                raise BenchFailure("no flush finished after the sender "
                                   "stopped")
            time.sleep(0.01)
        while flushes.pending():
            record.append(flushes.next(time.time() + 1.0, tap))
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs[:cell["chips"]]]
        faults = device_path_faults(server)
        stats = server.ingress_stats()
        emit("counters", faults=faults,
             overload_dropped=stats["overload_dropped"],
             parse_errors=stats["parse_errors"], peak_bytes_in_use=peaks,
             lines_written=stopped["lines_written"])

        # ---- nothing below is timed: the reference and the comparison ----
        sampler.close()  # its last sample was due inside the window
        t_ref = time.time()
        strm = reference.Stream(stream.build_ring(config, args.seed))
        held_to = reference.compare_record(
            strm, record, stopped["lines_written"], written, syncs,
            control=bool(args.control))
        for line in held_to["flushes"]:
            emit("comparison", **line)
        numbers, control = held_to["numbers"], held_to["control"]
        reasons += held_to["reasons"]
        reasons += faults
        reasons += [f"server logged an error: {m}" for m in tap.errors[:5]]
        # lines shed in set-up, before a point from which the chain of
        # cuts is exact again (reference.compare_record), are printed
        # and are no part of the result; any other shed line fails it
        shed = (stats["overload_dropped"] - held_to["shed_in_setup"]
                + stats["parse_errors"])
        if shed:
            reasons.append(f"overload_dropped={stats['overload_dropped']} "
                           f"(in set-up {held_to['shed_in_setup']}) "
                           f"parse_errors={stats['parse_errors']}")
        if tap.warnings:
            emit("log_warnings", first=tap.warnings[:10], n=len(tap.warnings))
        emit("limits", numbers=numbers, limits=reference.LIMITS,
             reference_s=time.time() - t_ref)
        if args.control:
            emit("control", precision="bfloat16 samples and gauges, "
                 "float32 counter sums", numbers=control,
                 fails=reference.verdict(control))

        # ---- metrics ----
        with open(sender_log) as f:
            slog = json.load(f)
        run = {"cell": cell, "config": config, "traffic": traffic,
               "window": (t0, t1), "flushes": counted, "sender_log": slog,
               "memory_peaks": peaks, "trace": None,
               "window_compiles": inside,
               "cpu": cpu_record(sampler, stages, interval, cell["name"],
                                 tag if args.trace else None)}
        if args.trace and "stopped" in trace_out:
            events = trace_reduce.load_xplane(trace_dir)
            offset = trace_reduce.anchor_offset(events, trace_out["anchor"])
            run["trace"] = {
                "events": events, "anchored": offset is not None,
                "offset": offset or 0.0, "t0": trace_out["t0"],
                "t1": trace_out["t1"],
                "flush": next((f for f in counted
                               if f["ordinal"] == trace_out["ordinal"]), None)}
        metrics = read_metrics(bench, cell, run, setup_s, bool(args.trace))
        missing = max(0, numbers["lines_missing"])
        attempted = missing + sum(f["range"][1] - f["range"][0]
                                  for f in counted if "range" in f)
        if numbers["cut_not_found"]:
            # no range for the flushes after it: every line written is
            # then attempted, and none can be told from the failed
            attempted = max(attempted, int(stopped["lines_written"]))
        attempted = max(1, int(attempted))
        failed = min(attempted, int(shed + missing))
        correct = not reasons
        for r in reasons:
            emit("failure", reason=r)
        device["memory_peak_bytes"] = max((p for p in peaks if p), default=0)
        result = {"correct": correct, "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": device}
        if run["trace"] is not None:
            tr = run["trace"]
            w0, w1 = tr["t0"] - tr["offset"], tr["t1"] - tr["offset"]
            device["busy_s"] = trace_reduce.busy_seconds(tr["events"], w0, w1)
            device["window_s"] = tr["t1"] - tr["t0"]
            spans = []
            if tr["flush"] is not None:
                spans = trace_reduce.flush_spans(
                    tr["flush"]["tick"] - tr["offset"], tr["flush"]["phases"])
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(tr["events"], w0, w1),
                "idle_gaps": trace_reduce.idle_gaps(tr["events"], w0, w1,
                                                    spans)}
            emit("trace", anchored=tr["anchored"], events=len(tr["events"]),
                 lines=trace_reduce.describe(tr["events"]))
            if tr["flush"] is not None:
                # half a second from the traced flush's tick, for
                # bench/testdata: small enough to keep and to read by hand
                at = tr["flush"]["tick"] - tr["offset"]
                trace_reduce.cut_slice(
                    tr["events"], at, at + 0.5,
                    os.path.join(OUT, tag + ".slice.json.gz"))
        # each number compared beside its limit: the line's last key
        result["compared"] = {
            k: {"value": numbers[k], "limit": reference.LIMITS[k]}
            for k in reference.LIMITS if k in numbers}
        return {"result": result if on_chip else None, "correct": correct,
                "reasons": reasons, "numbers": numbers, "control": control,
                "rehearsal": result}
    finally:
        child.close()
        if sampler is not None:
            sampler.close()
        if tracer is not None:
            tracer.join()


def read_metrics(bench: dict, cell: dict, run: dict, setup_s: float,
                 traced: bool) -> dict:
    """--trace 0: the cell's end-to-end metrics. --trace 1: its
    per-layer metrics, each by the reader its own file names; a reader
    that finds nothing to read returns None and the metric is left out."""
    def applies(m: dict) -> bool:
        return "workloads" not in m or cell["name"] in m["workloads"]

    t0, t1 = run["window"]
    flush_s = [f["flush_s"] for f in run["flushes"]]
    values = {
        "lines_per_s": senderlog.lines_per_s(run["sender_log"], t0, t1),
        "flush_s.mean": statistics.fmean(flush_s) if flush_s else None,
        "setup_s": setup_s,
        "host_cpu_s.interval": cpu.per_interval(
            run["cpu"]["ticks"], run["cpu"]["interval_s"])
        if run.get("cpu") else None,
    }
    out = {}
    if not traced:
        for m in bench["end_to_end"]:
            if applies(m) and values.get(m["name"]) is not None:
                out[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        return out
    for m in bench["per_layer"]:
        if not applies(m):
            continue
        spec = stream.load_json("layer_metrics", m["name"])
        reader = importlib.import_module("bench.readers." + spec["reader"])
        value = reader.read(run, spec.get("arg", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--control", type=int, default=0, choices=(0, 1),
                    help="1: also hold the lower-precision control to the "
                         "limits, over the same ranges, and print it")
    ap.add_argument("--benchmark-file",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    done, holder = None, {}
    try:
        done = run_cell(args, holder)
    except BenchFailure as e:
        emit("failure", reason=str(e))
    except Exception as e:  # any phase that raised fails the run
        logging.getLogger("bench").exception("phase raised")
        emit("failure", reason=f"{type(e).__name__}: {e}")
    rc = 1
    if done is not None and done["result"] is not None:
        # the result goes out before shutdown: a compute thread still
        # inside XLA can force os._exit (cli/veneur_main.py does the same)
        print(json.dumps(done["result"]), flush=True)
        rc = 0
    elif done is not None:
        emit("rehearsal", note="not a TPU with the cell's chips: no result "
             "line, exit code 1", would_print=done["rehearsal"])
    clean = True
    if holder.get("server") is not None:
        clean = holder["server"].shutdown()
    if done is not None:
        # the last lines of standard error: each number compared, beside
        # its limit (the result line carries the same under `compared`)
        for k, v in done["rehearsal"]["compared"].items():
            print(f"compared {k} {v['value']} limit {v['limit']}",
                  file=sys.stderr)
    sys.stdout.flush()
    sys.stderr.flush()
    if not clean:
        os._exit(rc)
    return rc


if __name__ == "__main__":
    sys.exit(main())
