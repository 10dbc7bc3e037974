#!/usr/bin/env python3
"""chip_smoke.py: the served path on the chip, once, at a size users call real.

One process. It writes a YAML config, goes through
``core.config.load_config`` -> ``core.factory.build_server`` ->
``server.start()`` (what ``cli/veneur_main.py`` does), sends three flush
intervals of DogStatsD lines over a real TCP socket from a sender
thread, lets the server's own flush ticker cut the windows, and compares
what reaches a collector sink with a plain float64 reference built here
from the same seeded samples.

It exits 0, and prints ``"ok": true`` as its last line, only when the
device is a TPU, native ingest is on, every comparison is inside its
written tolerance, and no counter shows that the device path was left:
no ``device.fault.*``, no guard trip or quarantine, no host-fallback
flush, no Pallas demotion, no shed line, no missed window. Off the chip
it runs the same path (the rehearsal) and can only end ``"ok": false``.

Every earlier line of standard output is one JSON object with an
``event`` key; the last line is the verdict and nothing else.

    python chip_smoke.py                  # one chip, 2^20 timer series
    python chip_smoke.py --chips 4        # only the series_shards=4 path
    JAX_PLATFORMS=cpu python chip_smoke.py --series 4096   # rehearsal
"""

from __future__ import annotations

import argparse
import json
import logging
import math
import os
import socket
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# ---- the deployment under test: upstream veneur defaults ----------------
INTERVAL_S = 10.0
PERCENTILES = (0.5, 0.75, 0.99)
AGGREGATES = ("min", "max", "count")
COMPRESSION = 100.0
HLL_PRECISION = 14
WINDOWS = 3

# ---- traffic per window --------------------------------------------------
COLD_SAMPLES = 2        # samples per ordinary timer series
HOT_SERIES = 1024       # timer series that get HOT_SAMPLES each
HOT_SAMPLES = 1024
COUNTERS = 65536
GAUGES = 65536
SEND_CHUNK = 1 << 20    # bytes per sendall
SEND_MARGIN_S = 1.0     # a window's lines must be in this long before its tick


# what the server logged at WARNING and above: the flush loop, the
# warm-up and the guard report failures there and carry on
LOG_WARNINGS: list[str] = []
LOG_ERRORS: list[str] = []


class _LogTap(logging.Handler):
    def emit(self, record: logging.LogRecord) -> None:
        if not record.name.startswith("veneur_tpu"):
            return
        msg = f"{record.name}: {record.getMessage()}"
        if record.exc_info and record.exc_info[1] is not None:
            msg += f" [{type(record.exc_info[1]).__name__}: " \
                   f"{str(record.exc_info[1])[:800]}]"
        (LOG_ERRORS if record.levelno >= logging.ERROR
         else LOG_WARNINGS).append(msg)


class SmokeFailure(Exception):
    """A phase failed; the run ends non-zero with this reason."""


def emit(event: str, **fields) -> None:
    print(json.dumps({"event": event, **fields}), flush=True)


def set_cardinalities() -> np.ndarray:
    """1,024 sets: 1,000 small (1..100 members), 16 around the bound
    where a sparse host row is promoted to a dense device row (2^p/8 =
    2,048 distinct registers), 8 past it up to 50,000 members."""
    small = np.round(np.geomspace(1, 100, 1000)).astype(np.int64)
    mid = np.round(np.geomspace(128, 4096, 16)).astype(np.int64)
    large = np.round(np.geomspace(5000, 50000, 8)).astype(np.int64)
    return np.concatenate([small, mid, large])


# --------------------------------------------------------------------------
# traffic and the plain reference, both from one seed
# --------------------------------------------------------------------------

class Traffic:
    """One window's lines (sent unchanged in every window: a new interval
    sees the same series again, and state leaking across a flush would
    double a count) and the float64 per-series truth about them."""

    def __init__(self, series: int, seed: int, max_len: int) -> None:
        if series <= HOT_SERIES:
            raise SmokeFailure(f"--series must exceed {HOT_SERIES}")
        rng = np.random.default_rng(seed)
        self.series = series
        n_cold = series - HOT_SERIES
        # timer values: multiples of 0.25 below 2^17, so every sample is
        # exact in float32 and min/max can be compared exactly
        self.cold = rng.integers(4, 400000, (n_cold, COLD_SAMPLES)) / 4.0
        # hot series: a lognormal latency shape, snapped to the same grid
        hot = np.exp(rng.normal(3.0, 1.0, (HOT_SERIES, HOT_SAMPLES)))
        self.hot = np.clip(np.round(hot * 4.0), 1, 400000) / 4.0
        # counters: two increments a window, so the sum is exercised
        self.counter_incs = rng.integers(1, 1000, (COUNTERS, 2))
        # gauges: two writes a window, the last one wins
        self.gauge_vals = rng.integers(0, 1 << 20, (GAUGES, 2)) / 4.0
        self.set_cards = set_cardinalities()

        # one TCP connection: a second and a fourth did not raise the
        # rate on the chip host (every reader commits under one context
        # lock), and one keeps each gauge's writes in order
        lines: list[bytes] = []
        add = lines.append
        for s in range(COLD_SAMPLES):
            col = self.cold[:, s].tolist()
            for i in range(n_cold):
                add(b"cs.t.%d:%.2f|ms|#shard:%d" % (i, col[i], i & 63))
        # hot series interleaved sample by sample, as concurrent clients
        # would send them, not one series after another
        for s in range(HOT_SAMPLES):
            col = self.hot[:, s].tolist()
            for j in range(HOT_SERIES):
                add(b"cs.hot.%d:%.2f|ms" % (j, col[j]))
        for s in range(2):
            col = self.counter_incs[:, s].tolist()
            for i in range(COUNTERS):
                add(b"cs.c.%d:%d|c" % (i, col[i]))
        for s in range(2):
            col = self.gauge_vals[:, s].tolist()
            for i in range(GAUGES):
                add(b"cs.g.%d:%.2f|g" % (i, col[i]))
        for k, card in enumerate(self.set_cards.tolist()):
            for m in range(card):
                add(b"cs.s.%d:u%d-%d|s" % (k, k, m))
        self.n_lines = len(lines)
        longest = max(map(len, lines))
        if longest > max_len:
            raise SmokeFailure(f"line of {longest} bytes > metric_max_length")
        # newline-terminated lines on a TCP stream, written SEND_CHUNK
        # bytes at a time; the stream reader splits them again
        self.chunks: list[bytes] = []
        buf: list[bytes] = []
        size = 0
        for ln in lines:
            if size + len(ln) + 1 > SEND_CHUNK:
                self.chunks.append(b"\n".join(buf) + b"\n")
                buf, size = [], 0
            buf.append(ln)
            size += len(ln) + 1
        if buf:
            self.chunks.append(b"\n".join(buf) + b"\n")
        self.n_bytes = sum(map(len, self.chunks))


# --------------------------------------------------------------------------
# the collector sink: what the server's flush hands a columnar sink
# --------------------------------------------------------------------------

def make_collector():
    from veneur_tpu.sinks import MetricSink

    class Collector(MetricSink):
        supports_columnar = True

        def __init__(self) -> None:
            self.flushes: list[dict] = []
            self.seen = threading.Condition()
            self.server = None  # set once the server is built

        def name(self) -> str:
            return "chip_smoke"

        def flush(self, metrics) -> None:
            raise SmokeFailure("the object path ran; the columnar flush "
                               "is the served path at this size")

        def flush_columnar(self, batch, excluded_tags=None) -> None:
            # keep the batch itself: its arrays are this flush's own and
            # each epoch has a fresh directory, so names are resolved
            # after the last window, outside every timed span
            with self.seen:
                self.flushes.append({"t_seen": time.time(), "batch": batch,
                                     "tick": self.server.last_flush_unix})
                self.seen.notify_all()

        def flush_other_samples(self, samples) -> None:
            pass

        def wait_for(self, k: int, t_accepted: float, ordinal: int,
                     server) -> dict:
            """Window k's flush as the sink saw it: the first one whose
            tick came after the window was accepted (an earlier one
            cannot hold it; what such a stray flush held is checked at
            the end). It is the server's flush number `ordinal`: the
            ticker runs flushes one after another and swallows what they
            raise, so a later flush that has begun, or a logged error,
            means this one is lost. A first flush may sit in a long
            compile, hence the generous limit."""
            limit = time.time() + 60 * INTERVAL_S
            with self.seen:
                while True:
                    for fl in self.flushes:
                        if fl["tick"] >= t_accepted:
                            fl["window"] = k
                            return fl
                    if server.flush_count > ordinal or LOG_ERRORS:
                        raise SmokeFailure(
                            f"window {k}: its flush never reached the sink"
                            + (f": {LOG_ERRORS[0]}" if LOG_ERRORS else ""))
                    if time.time() > limit:
                        raise SmokeFailure(
                            f"window {k}: flush not at the sink after "
                            f"{60 * INTERVAL_S:.0f}s")
                    self.seen.wait(0.2)

    return Collector()


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def write_config(path: str, series: int, chips: int) -> dict:
    """The YAML the server is built from. Upstream defaults everywhere a
    key is not named (tpu_native_ingest, tpu_native_readers, micro_fold,
    device_guard and tpu_warmup_compile are on by default; series_shards
    is unset on one chip)."""
    # pool rows preset to the series count: without it every epoch climbs
    # the pow2 ladder from 4,096 rows, eight grow programs to compile
    # (config.py tpu_initial_histo_rows). The pool keeps one scratch row
    # above the series, so at a power-of-two count it still grows once.
    rows = 1 << (series - 1).bit_length()
    cfg = {
        "statsd_listen_addresses": ["tcp://127.0.0.1:0"],
        "interval": f"{int(INTERVAL_S)}s",
        "percentiles": list(PERCENTILES),
        "aggregates": list(AGGREGATES),
        "tpu_compression": COMPRESSION,
        "tpu_hll_precision": HLL_PRECISION,
        "tpu_initial_histo_rows": rows,
        "num_workers": 1,
        "num_readers": 1,
    }
    if chips > 1:
        cfg["series_shards"] = chips
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for k, v in cfg.items():
            f.write(f"{k}: {json.dumps(v)}\n")
    return cfg


class CompileClock(logging.Handler):
    """What JAX compiled and for how long, from its own compile log
    (jax_log_compiles), so a window can say how much of it was
    compilation and of which program."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        import re

        import jax

        self._pat = re.compile(
            r"Finished XLA compilation of (.+) in ([0-9.eE+-]+) sec")
        self._lock = threading.Lock()
        self.by_program: dict[str, list] = {}
        jax.config.update("jax_log_compiles", True)
        lg = logging.getLogger("jax._src.dispatch")
        lg.addHandler(self)
        lg.propagate = False  # one line per trace and compile is noise
        px = logging.getLogger("jax._src.interpreters.pxla")
        px.addHandler(logging.NullHandler())  # "Compiling ..." + shapes
        px.propagate = False

    def emit(self, record: logging.LogRecord) -> None:
        m = self._pat.search(record.getMessage())
        if m:
            with self._lock:
                e = self.by_program.setdefault(m.group(1), [0, 0.0])
                e[0] += 1
                e[1] += float(m.group(2))

    def read(self) -> dict[str, tuple]:
        with self._lock:
            return {k: (v[0], v[1]) for k, v in self.by_program.items()}

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        """{"n": compiles, "s": seconds, "programs": {name: [n, s]}}"""
        progs = {}
        for name, (n, secs) in after.items():
            n0, s0 = before.get(name, (0, 0.0))
            if n > n0:
                progs[name] = [n - n0, round(secs - s0, 3)]
        return {"n": sum(v[0] for v in progs.values()),
                "s": sum(v[1] for v in progs.values()),
                "programs": progs}


def await_ticker(server, last: dict) -> tuple[float, int]:
    """The time of the next window's tick, and how many quiet ticks went
    by first. After a warm flush that is one interval after its own
    tick. A flush that compiled may have run past its interval (a warm
    one that does has already failed the run); the ticker then fires the
    ticks it missed back to back, some of them slowly, and a window that
    started among them would be cut at once. So after a cold flush, wait
    for a quiet flush that began one interval after the flush before it,
    as only a tick the ticker waited for does, and has ended, and start
    behind it. (Not "the first window's tick plus whole intervals": one
    tick that the send's adoption made 0.09 s late was the phase every
    later tick missed, at 2^20 series in PR 26.)"""
    if not last["cold"]:
        return last["tick"] + INTERVAL_S, 0
    fc0 = server.flush_count
    t_in = time.time()
    before = began = server.last_flush_unix
    while True:
        latest = server.last_flush_unix
        if latest != began:
            before, began = began, latest
        if (began > t_in and abs(began - before - INTERVAL_S) < 0.05
                and server.last_emit_unix >= began):
            break
        if time.time() > t_in + 30 * INTERVAL_S:
            raise SmokeFailure(
                "the ticker did not resume its schedule: "
                f"{server.flush_count - fc0} flushes in {30 * INTERVAL_S:.0f}s,"
                f" the last began {began - before:.3f}s after the one before "
                f"it and {'ended' if server.last_emit_unix >= began else 'runs'}")
        time.sleep(0.01)
    emit("ticker_resumed", after_flush_s=last["flush_tick_to_sink_s"],
         waited_s=time.time() - t_in, quiet_flush_s=time.time() - began)
    return began + INTERVAL_S, server.flush_count - fc0


def accepted(server) -> int:
    """Samples the workers have accepted, over the process's life. Read
    without the ingest locks (ingress_stats takes them, and a micro-fold
    that compiles holds one for seconds); no epoch swap can fall between
    the two reads, because the caller checks that no tick did."""
    return sum(w.processed_total + w.processed for w in server.workers)


def run_windows(server, collector, traffic, port: int, t_start: float,
                clock: CompileClock, dev) -> list[dict]:
    """Three windows cut by the server's own ticker. Window k's send
    starts when the sink has seen flush k-1 and must be accepted a
    second before tick k."""
    sock = socket.create_connection(("127.0.0.1", port))
    windows = []
    accepted_before = accepted(server)
    next_tick = t_start + INTERVAL_S  # the ticker starts inside start()
    try:
        for k in range(1, WINDOWS + 1):
            quiet = 0
            if k > 1:
                next_tick, quiet = await_ticker(server, windows[-1])
            fc0 = server.flush_count
            comp0 = clock.read()
            t0 = time.time()
            err: list[BaseException] = []

            def _send():
                try:
                    for c in traffic.chunks:
                        sock.sendall(c)
                except BaseException as e:  # re-raised below
                    err.append(e)

            sender = threading.Thread(target=_send, name="smoke-sender")
            sender.start()
            deadline = next_tick - SEND_MARGIN_S
            sender.join(max(0.0, deadline - time.time()))
            if err:
                raise err[0]
            if sender.is_alive():
                raise SmokeFailure(
                    f"window {k}: sender too slow: {traffic.n_lines} lines "
                    f"not written {SEND_MARGIN_S}s before the tick "
                    f"({accepted(server) - accepted_before} accepted in "
                    f"{time.time() - t0:.2f}s)")
            t_sent = time.time()
            want = accepted_before + traffic.n_lines
            while (got := accepted(server)) < want:
                if time.time() > deadline:
                    raise SmokeFailure(
                        f"window {k}: server accepted "
                        f"{got - accepted_before} of {traffic.n_lines} "
                        f"lines {SEND_MARGIN_S}s before the tick (written "
                        f"in {t_sent - t0:.2f}s)")
                time.sleep(0.01)
            t_acc = time.time()
            if server.flush_count != fc0:
                raise SmokeFailure(
                    f"window {k}: a tick fell inside the send (sent in "
                    f"{t_sent - t0:.2f}s, accepted in {t_acc - t0:.2f}s)")
            accepted_before = got
            # the flush of this window: tick -> sink, on the host's clock
            seen = collector.wait_for(k, t_acc, fc0 + 1, server)
            tick = seen["tick"]
            # the flush rebinds last_flush_phases after the sinks return
            t_wait = time.time() + 5.0
            while server.last_emit_unix < seen["t_seen"]:
                if time.time() > t_wait:
                    raise SmokeFailure(f"window {k}: flush never finished")
                time.sleep(0.005)
            comp = clock.delta(comp0, clock.read())
            win = {
                "window": k,
                "tick": tick,
                # cold: JAX compiled during this window or its flush
                "cold": comp["n"] > 0,
                "quiet_ticks_before": quiet,
                "lines": traffic.n_lines,
                "send_s": t_sent - t0,
                "accept_s": t_acc - t0,
                "lines_per_s_accepted": traffic.n_lines / (t_acc - t0),
                "send_slack_s": deadline - t_acc,
                "flush_tick_to_sink_s": seen["t_seen"] - tick,
                # the phase seconds; the spans behind them are the
                # benchmark's to read (bench/TRACING.md)
                "flush_phases": {k: v for k, v in
                                 server.last_flush_phases.items()
                                 if k != "spans"},
                "flush_transfers": dict(server.last_flush_transfers),
                "micro_folds": server.last_micro_folds,
                "compile_s": comp["s"],
                "compiles": comp["n"],
                "compiled_programs": comp["programs"],
                "peak_bytes_in_use": (dev.memory_stats() or {}).get(
                    "peak_bytes_in_use"),
            }
            emit("window", **win)
            windows.append(win)
            if not win["cold"] and win["flush_tick_to_sink_s"] >= INTERVAL_S:
                raise SmokeFailure(
                    f"window {k}: a warm flush took "
                    f"{win['flush_tick_to_sink_s']:.2f}s from tick to sink, "
                    f"longer than the {INTERVAL_S:.0f}s interval, so the "
                    f"next tick fires late")
    finally:
        sock.close()
    return windows


# --------------------------------------------------------------------------
# comparison, outside every timed span
# --------------------------------------------------------------------------

def _index(names: list[str], prefix: str, n: int) -> np.ndarray:
    """Row -> series number for rows named <prefix><number>; -1 else."""
    out = np.full(len(names), -1, np.int64)
    cut = len(prefix)
    for r, nm in enumerate(names):
        if nm.startswith(prefix):
            out[r] = int(nm[cut:])
    if out.max(initial=-1) >= n:
        raise SmokeFailure(f"{prefix}: a series number past {n}")
    return out


def _scatter(idx: np.ndarray, values: np.ndarray, mask, n: int,
             what: str) -> np.ndarray:
    """values by series number; every series exactly once."""
    sel = idx >= 0
    if mask is not None:
        sel &= mask
    got = np.full(n, np.nan)
    hits = np.bincount(idx[sel], minlength=n)
    if not (hits == 1).all():
        raise SmokeFailure(
            f"{what}: {int((hits == 0).sum())} series missing, "
            f"{int((hits > 1).sum())} emitted twice")
    got[idx[sel]] = values[sel]
    return got


def hll_tolerance(card: np.ndarray) -> np.ndarray:
    """Allowed |estimate - truth| for a set of `card` members.

    The estimator (ops/hll.py) is linear counting, m*ln(m/zeros), while
    the raw estimate is below 2.5m, and the harmonic-mean estimate
    above. Linear counting is not a list of members: two members that
    hash to one register count once, so even a small set is exact only
    until its first collision (13 members: once in 200 sets). Its
    standard error is sqrt(m*(e^t - t - 1)) with t = n/m (Whang et al.
    1990); five sigma, because 1,024 sets in 3 windows are 3,072 draws,
    plus one member for the collision that sigma rounds away, plus the
    half that rounding costs. The harmonic mean's standard error is
    1.04/sqrt(m) of n; three sigma, as for any dense HLL. Near the
    switch-over either estimator may have answered: the wider applies."""
    m = float(1 << HLL_PRECISION)
    n = card.astype(np.float64)
    t = n / m
    lc = np.where(n <= 3.0 * m, 5.0 * np.sqrt(m * np.expm1(t) - n), 0.0)
    hm = np.where(n >= 2.0 * m, 3.0 * 1.04 / math.sqrt(m) * n, 0.0)
    return 1.5 + np.maximum(lc, hm)


def flush_names(batch) -> tuple[int, list[str]]:
    """How many of a flush's series are this script's, and a few names
    of those that are not."""
    own, foreign = 0, []
    for g in batch.groups:
        for i in range(g.nrows):
            nm = g.meta_at(i)[0]
            if nm.startswith("cs."):
                own += 1
            elif len(foreign) < 8:
                foreign.append(nm)
    foreign += [m.name for m in batch.extras][:8 - len(foreign)]
    return own, foreign


def compare_window(batch, traffic: Traffic) -> dict:
    """Worst error per class for one flush against the reference;
    raises SmokeFailure on the first class outside its tolerance."""
    from veneur_tpu.core.metrics import MetricType

    n_cold = traffic.series - HOT_SERIES
    worst: dict[str, float] = {}
    emitted = 0
    foreign: set[str] = set()
    seen_groups = {"timer": 0, "set": 0, "counter": 0, "gauge": 0}

    for g in batch.groups:
        names = [g.meta_at(i)[0] for i in range(g.nrows)]
        fams = {f.suffix: f for f in g.families}
        own = np.fromiter((nm.startswith("cs.") for nm in names), bool,
                          len(names))
        foreign.update(nm for nm, o in zip(names, own) if not o)
        for f in g.families:
            m = own if f.mask is None else (own & f.mask)
            emitted += int(m.sum())
        if not own.any():
            continue
        kind = names[int(np.argmax(own))].split(".")[1]
        if kind in ("t", "hot"):
            seen_groups["timer"] += 1
            ci = _index(names, "cs.t.", n_cold)
            hi = _index(names, "cs.hot.", HOT_SERIES)
            ref = {
                # exact: every sample is a float32-exact multiple of 0.25
                ".min": (traffic.cold.min(1), traffic.hot.min(1)),
                ".max": (traffic.cold.max(1), traffic.hot.max(1)),
                # exact: whole numbers below 2^24 survive the f32 readback
                ".count": (np.full(n_cold, float(COLD_SAMPLES)),
                           np.full(HOT_SERIES, float(HOT_SAMPLES))),
            }
            for suffix, (rc, rh) in ref.items():
                f = fams[suffix]
                gc = _scatter(ci, f.values, f.mask, n_cold, "timer" + suffix)
                gh = _scatter(hi, f.values, f.mask, HOT_SERIES,
                              "hot timer" + suffix)
                bad = int((gc != rc).sum() + (gh != rh).sum())
                worst["timer" + suffix + "_mismatches"] = bad
                if bad:
                    raise SmokeFailure(f"timer{suffix}: {bad} series differ "
                                       "from the reference (exact expected)")
            hot_sorted = np.sort(traffic.hot, axis=1)
            cold_sorted = np.sort(traffic.cold, axis=1)
            for q in PERCENTILES:
                suffix = ".%dpercentile" % round(q * 100)
                f = fams[suffix]
                gh = _scatter(hi, f.values, f.mask, HOT_SERIES,
                              "hot timer" + suffix)
                # rank error: how far q is from the share of the series'
                # samples at or below the reported value. Two bounds.
                # Per series, the digest's own resolution: samples whose
                # k(q) = delta*(asin(2q-1)/pi + 1/2) at their left edge
                # share an integer part are one centroid (ops/tdigest.py).
                # A bucket is pi*sqrt(q(1-q))/delta of the weight wide
                # (1.57% at the median for delta=100); a centroid starts
                # inside one bucket and may reach through the next, and
                # the samples inside a centroid need not lie the way the
                # interpolation assumes, so an answer can be off by a
                # centroid's whole span: two buckets, plus 1/n for the
                # sample grid. (One bucket was the first bound written
                # here; the CPU rehearsal of --chips 4 crossed it at p99,
                # 0.0046 against 0.0041.) Over the hot series,
                # BASELINE.md's 1% target holds for the mean.
                below = (hot_sorted < gh[:, None]).sum(1) / HOT_SAMPLES
                upto = (hot_sorted <= gh[:, None]).sum(1) / HOT_SAMPLES
                rank_err = np.maximum(0.0, np.maximum(below - q, q - upto))
                bound = (2 * math.pi * math.sqrt(q * (1 - q)) / COMPRESSION
                         + 1.0 / HOT_SAMPLES)
                worst["hot" + suffix + "_rank_err_max"] = float(
                    rank_err.max())
                worst["hot" + suffix + "_rank_err_mean"] = float(
                    rank_err.mean())
                worst["hot" + suffix + "_series_over_1pct"] = int(
                    (rank_err > 0.01).sum())
                if rank_err.max() > bound or rank_err.mean() > 0.01:
                    raise SmokeFailure(
                        f"hot timer{suffix}: rank error max "
                        f"{rank_err.max():.4f} (bound {bound:.4f}), mean "
                        f"{rank_err.mean():.4f} (bound 0.01)")
                # a series of n samples holds n unit centroids, so its
                # q-quantile must lie between the order statistics on
                # either side of rank q*n
                gc = _scatter(ci, f.values, f.mask, n_cold, "timer" + suffix)
                lo = min(max(math.floor(q * COLD_SAMPLES) - 1, 0),
                         COLD_SAMPLES - 1)
                hi_i = min(math.ceil(q * COLD_SAMPLES), COLD_SAMPLES - 1)
                out = int(((gc < cold_sorted[:, lo])
                           | (gc > cold_sorted[:, hi_i])).sum())
                worst["timer" + suffix + "_unbracketed"] = out
                if out:
                    raise SmokeFailure(
                        f"timer{suffix}: {out} series outside their "
                        "neighbouring order statistics")
        elif kind == "s":
            seen_groups["set"] += 1
            f = g.families[0]
            si = _index(names, "cs.s.", len(traffic.set_cards))
            got = _scatter(si, f.values, f.mask, len(traffic.set_cards),
                           "set")
            err = np.abs(got - traffic.set_cards)
            tol = hll_tolerance(traffic.set_cards)
            worst["set_err_over_tolerance"] = float((err / tol).max())
            worst["set_rel_err_max"] = float(
                (err / traffic.set_cards).max())
            if (err > tol).any():
                k = int(np.argmax(err / tol))
                raise SmokeFailure(
                    f"set of {traffic.set_cards[k]} members estimated "
                    f"{got[k]:.1f}, tolerance {tol[k]:.1f}")
        elif kind in ("c", "g"):
            f = g.families[0]
            is_counter = f.type == MetricType.COUNTER
            seen_groups["counter" if is_counter else "gauge"] += 1
            if is_counter:
                # exact: integer sums far below 2^53 in the host's f64 pool
                n, ref1 = COUNTERS, traffic.counter_incs.sum(1).astype(float)
                idx = _index(names, "cs.c.", n)
            else:
                # exact: the last write of the window, a multiple of 0.25
                n, ref1 = GAUGES, traffic.gauge_vals[:, -1]
                idx = _index(names, "cs.g.", n)
            what = "counter" if is_counter else "gauge"
            got = _scatter(idx, f.values, f.mask, n, what)
            bad = int((got != ref1).sum())
            worst[what + "_mismatches"] = bad
            if bad:
                raise SmokeFailure(f"{what}: {bad} series differ from the "
                                   "reference (exact expected)")
    if any(v != 1 for v in seen_groups.values()):
        raise SmokeFailure(f"metric classes emitted: {seen_groups}")
    want = (traffic.series * (len(AGGREGATES) + len(PERCENTILES))
            + COUNTERS + GAUGES + len(traffic.set_cards))
    worst["series_emitted"] = emitted
    worst["series_expected"] = want
    worst["foreign_names"] = sorted(foreign)[:8]
    if emitted != want:
        raise SmokeFailure(f"{emitted} series emitted, {want} expected")
    return worst


# --------------------------------------------------------------------------
# counters that would show the device path was left
# --------------------------------------------------------------------------

def device_path_faults(server, collector) -> list[str]:
    bad = []
    guard = {}
    for i, w in enumerate(server.workers):
        c = w.guard.counters()
        guard[f"worker{i}"] = c
        for key, n in c.items():
            if n:
                bad.append(f"worker {i}: {key}={n}")
        if w.guard.quarantined:
            bad.append(f"worker {i}: quarantined ({w.guard.trip_reason})")
        if w.host_fallback_flushes:
            bad.append(f"worker {i}: flush.host_fallbacks="
                       f"{w.host_fallback_flushes}")
    st = server.ingress_stats()
    if st["overload_dropped"]:
        bad.append(f"overload_dropped={st['overload_dropped']}")
    if st["parse_errors"]:
        bad.append(f"parse_errors={st['parse_errors']}")
    matched = sum("window" in fl for fl in collector.flushes)
    if matched != WINDOWS:
        bad.append(f"{matched} flushes matched a window, {WINDOWS} sent")
    emit("counters", guard=guard,
         host_fallbacks=sum(w.host_fallback_flushes for w in server.workers),
         overload_dropped=st["overload_dropped"],
         parse_errors=st["parse_errors"],
         samples_processed=st["samples_processed"],
         flush_count=st["flush_count"],
         native_mode=server.native_mode)
    return bad


def pool_placement(server, port: int) -> dict:
    """Where a live pool's arrays lie: devices and rows per shard. An
    epoch has no pool until its first hot-row spill or its flush, so
    after the last window this sends one series more samples than its
    staging holds and waits for the pool the spill creates, at the
    configured size. Touches no data on the device."""
    with socket.create_connection(("127.0.0.1", port)) as sk:
        sk.sendall(b"".join(b"cs.hot.0:%d|ms\n" % v for v in range(1, 130)))
    limit = time.time() + INTERVAL_S
    while (histo := server.workers[0]._histo) is None:
        if time.time() > limit:
            raise SmokeFailure("no live pool appeared to inspect")
        time.sleep(0.05)
    out = {}
    for name in ("means", "weights", "dmin", "lweight"):
        shards = getattr(histo, name).addressable_shards
        out[name] = {"devices": sorted({s.device.id for s in shards}),
                     "rows": sorted({s.data.shape[0] for s in shards})}
    return out


def placement_faults(server, chips: int, pools: dict) -> list[str]:
    """--chips N: the pools really lie on N devices, in equal parts, and
    no device carried much more than the others."""
    import jax

    w = server.workers[0]
    if w.series_shards != chips:
        return [f"series_shards is {w.series_shards}, asked for {chips}"]
    bad = []
    for name, where in pools.items():
        if len(where["devices"]) != chips or len(where["rows"]) != 1:
            bad.append(f"pool {name}: devices {where['devices']}, "
                       f"rows per shard {where['rows']}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    if all(peaks):
        # code that has only met virtual CPU devices may put everything
        # on the first one
        if max(peaks) > 2 * min(peaks):
            bad.append(f"peak HBM uneven across devices: {peaks}")
    elif jax.devices()[0].platform == "tpu":
        bad.append("memory_stats() gave no peak on some device")
    emit("placement", series_shards=w.series_shards, pools=pools,
         peak_bytes_in_use=peaks)
    return bad


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    # The north star's 2^20. A warm flush there takes 0.62 s tick -> sink
    # and the accept 3.2-3.6 s of the 9 s a window allows (PR 26's chip
    # runs; 11.4 s a flush while the k-bucket was a search, when this
    # default stood at 2^18).
    ap.add_argument("--series", type=int, default=1 << 20,
                    help="distinct timer series per chip, the 1,024 hot "
                         "ones included (default 2^20)")
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the series_shards=4 path, at 4x "
                         "--series")
    args = ap.parse_args()
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    tap = _LogTap(level=logging.WARNING)
    logging.getLogger().addHandler(tap)

    failures: list[str] = []
    # no JAX_PLATFORMS default here: the platform is whatever JAX finds
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    series = args.series * args.chips
    emit("device", **device, series=series, seed=args.seed,
         chips=args.chips, jax=jax.__version__)
    if dev.platform != "tpu":
        failures.append(f"platform is {dev.platform!r}, not 'tpu'")
    if len(jax.devices()) < args.chips:
        failures.append(f"{len(jax.devices())} devices, --chips "
                        f"{args.chips}")

    server = None
    try:
        from veneur_tpu.core.config import load_config
        from veneur_tpu.core.factory import build_server

        clock = CompileClock()
        cfg_path = os.path.join(ROOT, "chiprun_out", "chip_smoke.yaml")
        written = write_config(cfg_path, series, args.chips)
        cfg = load_config(cfg_path)
        t0 = time.time()
        traffic = Traffic(series, args.seed, cfg.metric_max_length)
        emit("traffic", lines_per_window=traffic.n_lines,
             bytes_per_window=traffic.n_bytes, build_s=time.time() - t0,
             timer_series=series, hot_series=HOT_SERIES,
             counters=COUNTERS, gauges=GAUGES, sets=len(traffic.set_cards),
             set_members=int(traffic.set_cards.sum()))

        collector = make_collector()
        t0 = time.time()
        server = build_server(cfg, extra_metric_sinks=[collector])
        t_build = time.time() - t0
        collector.server = server
        if not server.native_mode:
            raise SmokeFailure(
                "native ingest is off: the C++ library did not build or "
                "load, and the Python parser is not the served path")
        t_start = time.time()
        ports = server.start()
        emit("server", config=written, ports=ports,
             build_s=t_build, start_s=time.time() - t_start,
             native_mode=server.native_mode,
             compilation_cache_dir=server.compilation_cache_dir,
             note="tpu_initial_histo_rows is preset to the series count, "
                  "so the pow2 ladder below it is not compiled")
        port = next(iter(ports.values()))
        windows = run_windows(server, collector, traffic, port, t_start,
                              clock, dev)

        # ---- nothing below is timed ----
        for fl in collector.flushes:
            if "window" in fl:
                worst = compare_window(fl["batch"], traffic)
                emit("comparison", window=fl["window"], **worst)
                continue
            # a flush no window was waiting for: it may not hold any of
            # this script's series, or a window was split over two
            own, foreign = flush_names(fl["batch"])
            emit("stray_flush", tick=fl["tick"], series=len(fl["batch"]),
                 own_series=own, foreign_names=foreign)
            if own:
                failures.append(f"a flush no window waited for held {own} "
                                f"of this script's series")
        failures += device_path_faults(server, collector)
        if args.chips > 1:
            failures += placement_faults(
                server, args.chips, pool_placement(server, port))
        ms = dev.memory_stats() or {}
        peak = ms.get("peak_bytes_in_use")
        emit("memory", peak_bytes_in_use=peak,
             bytes_limit=ms.get("bytes_limit"),
             compile_s_total=sum(w["compile_s"] for w in windows),
             compiles=sum(w["compiles"] for w in windows),
             cold_flush_s=[w["flush_tick_to_sink_s"] for w in windows
                           if w["cold"]],
             warm_flush_s=[w["flush_tick_to_sink_s"] for w in windows
                           if not w["cold"]])
        if dev.platform == "tpu" and not peak:
            failures.append("memory_stats() gave no peak_bytes_in_use")
    except SmokeFailure as e:
        failures.append(str(e))
    except Exception as e:  # any phase that raised fails the run
        logging.getLogger("chip_smoke").exception("phase raised")
        failures.append(f"{type(e).__name__}: {e}")

    # an ERROR the server logged is a phase that raised and was caught
    failures += [f"server logged an error: {m}" for m in LOG_ERRORS[:5]]
    if LOG_WARNINGS:
        emit("log_warnings", first=LOG_WARNINGS[:10], n=len(LOG_WARNINGS))
    ok = not failures
    for f in failures:
        emit("failure", reason=f)
    # the verdict goes out before shutdown: a compute thread still inside
    # XLA can force os._exit (cli/veneur_main.py does the same)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    clean = True
    if server is not None:
        clean = server.shutdown()
    sys.stderr.flush()
    if not clean:
        os._exit(0 if ok else 1)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
