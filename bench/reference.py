"""The plain reference: NumPy float64, nothing of the program.

The sender repeats a ring of lines; the server's ticks cut that stream
wherever they fall. One connection, one reader and one worker make a
flush a contiguous range of it: flush k holds stream lines
[c(k-1), c(k)). ``Stream.locate_cut`` finds c(k) from the flush's own
output (never from a counter of the program); ``Stream.truth`` says what
any range must aggregate to; ``compare_flush`` holds a flush to it and
returns every number compared, each of which has its limit in ``LIMITS``.

A flush arrives here as plain arrays (``FlushView``), made from the
program's batch by bench/run.py.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from bench.stream import COUNTER, GAUGE, SET, TIMER, CLASSES, Ring

# each number compared, and the most it may read. Exact classes: every
# value is a float32-exact multiple of 0.25 below 2^17, counter sums are
# integers far below 2^53 and counts far below 2^24, so nothing may
# differ at all (and a run in any lower precision differs in thousands).
LIMITS = {
    "cut_not_found": 0,          # no position of the stream fits the flush
    "series_mismatch": 0,        # emitted where absent, missing, or twice
    "counter_mismatch": 0,
    "gauge_mismatch": 0,
    "timer_count_mismatch": 0,
    "timer_min_mismatch": 0,
    "timer_max_mismatch": 0,
    # more samples than stay unmerged: rank error over two t-digest
    # buckets + 1/n; twice that is the limit (PERF.md section 2: the
    # widest of 3,072 readings a flush read up to 1.18 in sound runs,
    # whose digests are merged twenty times an interval; a series that
    # holds another's samples reads over 10)
    "quantile_rank_over_bound": 2.0,
    # and BASELINE.md's 1% target on the mean over series of n >= 100
    "quantile_rank_mean": 0.01,
    # few samples: outside the neighbouring order statistics
    "quantile_unbracketed": 0,
    # |estimate - distinct| over the HyperLogLog tolerance
    "set_err_over_tolerance": 1.0,
    "lines_missing": 0,          # conservation, after the sender stopped
}
MEAN_N = 100


def unmerged_n(compression: float) -> int:
    """The most samples a series can have and still hold each as a
    centroid of its own: a centroid may span one unit of
    k(q) = delta*(asin(2q-1)/pi + 1/2), whose slope is least at the
    median, 2*delta/pi per unit of q; a sample of weight 1/n is wider
    than that while n <= 2*delta/pi (63 at delta = 100). The CPU
    rehearsal showed the first merges at the median at n = 68."""
    return int(2.0 * compression / math.pi)


class Mismatch(Exception):
    """The flush cannot be any range of the stream."""


@dataclass
class FlushView:
    """One flush as the sink saw it: per class and family suffix, the
    series numbers emitted and their values."""
    families: dict = field(default_factory=dict)  # (cls, suffix) -> (sid, values)
    foreign: list = field(default_factory=list)   # names that are not ours

    def family(self, cls: int, suffix: str):
        return self.families.get(
            (cls, suffix), (np.empty(0, np.int64), np.empty(0)))

    def suffixes(self, cls: int) -> list:
        return sorted(s for c, s in self.families if c == cls)

    def scalar(self, cls: int):
        """The one family of a counter, gauge or set group."""
        sfx = self.suffixes(cls)
        if len(sfx) > 1:
            raise Mismatch(f"{CLASSES[cls]}: families {sfx}, one expected")
        return self.family(cls, sfx[0] if sfx else "")


def hll_tolerance(card: np.ndarray, precision: int) -> np.ndarray:
    """Allowed |estimate - truth| for a set of ``card`` distinct members.

    The estimator is linear counting, m*ln(m/zeros), while the raw
    estimate is below 2.5m, and the harmonic-mean estimate above. Linear
    counting is not a list of members: two members that hash to one
    register count once, so even a small set is exact only until its
    first collision. Its standard error is sqrt(m*(e^t - t - 1)) with
    t = n/m (Whang et al. 1990); five sigma, because a run compares
    thousands of sets, plus one member for the collision that sigma
    rounds away, plus the half that rounding costs. The harmonic mean's
    standard error is 1.04/sqrt(m) of n; three sigma, as for any dense
    HLL. Near the switch-over either estimator may have answered: the
    wider applies."""
    m = float(1 << precision)
    n = card.astype(np.float64)
    t = n / m
    lc = np.where(n <= 3.0 * m,
                  5.0 * np.sqrt(np.maximum(m * np.expm1(t) - n, 0.0)), 0.0)
    hm = np.where(n >= 2.0 * m, 3.0 * 1.04 / math.sqrt(m) * n, 0.0)
    return 1.5 + np.maximum(lc, hm)


def rank_bound(q: float, n, compression: float):
    """Most by which the share of a series' samples at or below its
    reported q-quantile may miss q. A t-digest bucket is
    pi*sqrt(q(1-q))/delta of the weight wide; a centroid starts inside
    one bucket and may reach through the next, and the samples inside a
    centroid need not lie the way the interpolation assumes, so an
    answer can be off by a centroid's whole span: two buckets, plus 1/n
    for the sample grid."""
    return 2 * math.pi * math.sqrt(q * (1 - q)) / compression + 1.0 / n


@dataclass
class Truth:
    """What a range of the stream aggregates to, in float64."""
    counter_sum: np.ndarray      # per counter; nan where absent
    gauge_last: np.ndarray       # per gauge; nan where absent
    timer_n: np.ndarray          # per timer, samples in the range
    timer_start: np.ndarray      # per timer, start of its sorted samples
    timer_sorted: np.ndarray     # all samples, by (timer, value)
    set_distinct: np.ndarray     # per set, distinct members


class Stream:
    """The ring, repeated for ever, with two prefix sums over it."""

    def __init__(self, ring: Ring) -> None:
        self.ring = ring
        self.n = len(ring)
        is_t = ring.cls == TIMER
        inc = np.where(ring.cls == COUNTER, ring.val, 0).astype(np.int64)
        # prefix[r] = total over ring lines before r; prefix[n] = a cycle
        self.timers = np.concatenate([[0], np.cumsum(is_t, dtype=np.int64)])
        self.counted = np.concatenate([[0], np.cumsum(inc)])

    def _before(self, prefix: np.ndarray, p: int) -> int:
        q, r = divmod(int(p), self.n)
        return q * int(prefix[-1]) + int(prefix[r])

    def _first_at_least(self, prefix: np.ndarray, target: int) -> int:
        """Smallest stream position with at least ``target`` before it."""
        if target <= 0:
            return 0
        cycle = int(prefix[-1])
        q = (target - 1) // cycle
        r = int(np.searchsorted(prefix, target - q * cycle, "left"))
        return q * self.n + r

    def _run(self, prefix: np.ndarray, target: int):
        """Positions p with exactly ``target`` before them: (lo, hi)."""
        lo = self._first_at_least(prefix, target)
        if self._before(prefix, lo) != target:
            return None
        return lo, self._first_at_least(prefix, target + 1) - 1

    def cut_run(self, start: int, t: int, s: int, limit: int | None = None):
        """Positions c >= start such that [start, c) holds exactly t
        timer lines and counter increments summing to s: one run of
        gauge and set lines, or None."""
        a = self._run(self.timers, self._before(self.timers, start) + t)
        b = self._run(self.counted, self._before(self.counted, start) + s)
        if a is None or b is None:
            return None
        lo, hi = max(a[0], b[0], start), min(a[1], b[1])
        if limit is not None:
            hi = min(hi, limit)
        return (lo, hi) if lo <= hi else None

    def lines(self, a: int, b: int):
        """(cls, sid, val) of stream lines [a, b)."""
        at = np.arange(a, b) % self.n
        r = self.ring
        return r.cls[at], r.sid[at], r.val[at]

    def truth(self, a: int, b: int) -> Truth:
        cls, sid, val = self.lines(a, b)
        ns = self.ring.series
        m = cls == COUNTER
        hits = np.bincount(sid[m], minlength=ns["counter"])
        sums = np.bincount(sid[m], weights=val[m], minlength=ns["counter"])
        counter_sum = np.where(hits > 0, sums, np.nan)
        m = cls == GAUGE
        gauge_last = np.full(ns["gauge"], np.nan)
        gauge_last[sid[m]] = val[m]  # repeated index: the last write stays
        m = cls == TIMER
        ts, tv = sid[m], val[m]
        order = np.lexsort((tv, ts))
        timer_n = np.bincount(ts, minlength=ns["timer"])
        timer_start = np.concatenate([[0], np.cumsum(timer_n)[:-1]])
        m = cls == SET
        pairs = np.unique(sid[m].astype(np.int64) * (1 << 32)
                          + val[m].astype(np.int64))
        set_distinct = np.bincount(pairs >> 32, minlength=ns["set"])
        return Truth(counter_sum, gauge_last, timer_n, timer_start,
                     tv[order], set_distinct)

    def locate_cut(self, start: int, flush: FlushView,
                   limit: int | None = None, last: bool = False) -> int:
        """c such that the flush holds stream lines [start, c).

        t = sum of the timers' counts and s = sum of the counters'
        values are exact integers; the positions with exactly t timer
        lines and s of counter sum in [start, c) are one short run of
        gauge and set lines. Inside it the cut is the first candidate
        whose gauge last-writes, and whose sets' being there at all,
        match the flush; further set lines cannot move a result outside
        its tolerance. ``last``: take the last such candidate instead
        (the final flush, where conservation asks whether every line
        written can be inside)."""
        t = int(round(float(flush.family(TIMER, ".count")[1].sum())))
        s = int(round(float(flush.scalar(COUNTER)[1].sum())))
        run = self.cut_run(start, t, s, limit)
        if run is None:
            raise Mismatch(
                f"no cut after line {start} leaves {t} timer lines and a "
                f"counter sum of {s}: the flush lost or doubled a line")
        lo, hi = run
        if lo == hi:
            return lo
        ns = self.ring.series
        cls, sid, val = self.lines(lo, hi)
        gauge_got = np.full(ns["gauge"], np.nan)
        g_sid, g_val = flush.scalar(GAUGE)
        gauge_got[g_sid] = g_val
        set_got = np.zeros(ns["set"], bool)
        set_got[flush.scalar(SET)[0]] = True
        base = self.truth(start, lo)
        gauge_now = {int(i): base.gauge_last[i] for i in sid[cls == GAUGE]}
        set_now = {int(i): base.set_distinct[i] > 0 for i in sid[cls == SET]}

        def fits() -> bool:
            return (all(_same(gauge_got[i], v) for i, v in gauge_now.items())
                    and all(set_got[i] == v for i, v in set_now.items()))

        found = None
        for k in range(len(cls) + 1):
            if fits():
                found = lo + k
                if not last:
                    break
            if k < len(cls):
                if cls[k] == GAUGE:
                    gauge_now[int(sid[k])] = val[k]
                else:
                    set_now[int(sid[k])] = True
        if found is not None:
            return found
        raise Mismatch(
            f"timer and counter sums place the cut in lines [{lo}, {hi}], "
            f"but no cut there gives the flush's gauges and sets")


def _same(a: float, b: float) -> bool:
    return (a == b) or (a != a and b != b)


def _emitted(flush_sid: np.ndarray, present: np.ndarray) -> int:
    """Series emitted though absent from the range, missing, or twice."""
    hits = np.bincount(flush_sid, minlength=len(present))
    if len(hits) > len(present):
        return int(len(hits) - len(present)) + int(
            (hits[:len(present)] != present).sum())
    return int((hits != present).sum())


def _scatter(n: int, sid: np.ndarray, values: np.ndarray) -> np.ndarray:
    got = np.full(n, np.nan)
    ok = sid < n
    got[sid[ok]] = values[ok]
    return got


def compare_flush(truth: Truth, flush: FlushView, server: dict) -> dict:
    """Every number compared for one flush (see LIMITS)."""
    out = {k: 0 for k in LIMITS if k not in ("cut_not_found", "lines_missing")}
    out["quantile_rank_over_bound"] = 0.0
    out["quantile_rank_mean"] = 0.0
    out["set_err_over_tolerance"] = 0.0

    # counters: the exact sum. gauges: the last write.
    for cls, ref, key in ((COUNTER, truth.counter_sum, "counter_mismatch"),
                          (GAUGE, truth.gauge_last, "gauge_mismatch")):
        sid, values = flush.scalar(cls)
        present = ~np.isnan(ref)
        out["series_mismatch"] += _emitted(sid, present)
        got = _scatter(len(ref), sid, values)
        out[key] = int((got[present] != ref[present]).sum())

    # timers: count, min and max exact; quantiles inside the digest's budget
    n, start, samples = truth.timer_n, truth.timer_start, truth.timer_sorted
    present = n > 0
    last = start + np.maximum(n, 1) - 1
    want = [".count", ".max", ".min"] + [
        ".%dpercentile" % round(q * 100) for q in server["percentiles"]]
    have = flush.suffixes(TIMER)
    if present.any() and sorted(want) != have:
        raise Mismatch(f"timer families {have}, expected {sorted(want)}")
    exact = {".count": n.astype(np.float64)}
    if len(samples):
        exact[".min"] = samples[np.minimum(start, len(samples) - 1)]
        exact[".max"] = samples[np.minimum(last, len(samples) - 1)]
    for suffix, ref in exact.items():
        sid, values = flush.family(TIMER, suffix)
        out["series_mismatch"] += _emitted(sid, present)
        got = _scatter(len(n), sid, values)
        out["timer_%s_mismatch" % suffix[1:]] = int(
            (got[present] != ref[present]).sum())
    few = unmerged_n(server["tpu_compression"])
    big = np.nonzero(n > few)[0]
    small = np.nonzero(present & (n <= few))[0]
    for q in server["percentiles"]:
        sid, values = flush.family(TIMER, ".%dpercentile" % round(q * 100))
        out["series_mismatch"] += _emitted(sid, present)
        got = _scatter(len(n), sid, values)
        # few samples: the digest holds every sample as a centroid of
        # its own, so the answer lies between the order statistics on
        # either side of rank q*n
        ns_, st = n[small], start[small]
        lo = np.clip(np.floor(q * ns_).astype(np.int64) - 1, 0, ns_ - 1)
        hi = np.minimum(np.ceil(q * ns_).astype(np.int64), ns_ - 1)
        g = got[small]
        out["quantile_unbracketed"] += int(
            (~((g >= samples[st + lo]) & (g <= samples[st + hi]))).sum())
        # more: rank error, the distance of q from the share of the
        # series' samples at or below the reported value
        errs = np.empty(len(big))
        for j, i in enumerate(big.tolist()):
            seg = samples[start[i]:start[i] + n[i]]
            below = np.searchsorted(seg, got[i], "left") / n[i]
            upto = np.searchsorted(seg, got[i], "right") / n[i]
            errs[j] = max(0.0, below - q, q - upto) if got[i] == got[i] else 1.0
        if len(big):
            bound = rank_bound(q, n[big], server["tpu_compression"])
            out["quantile_rank_over_bound"] = max(
                out["quantile_rank_over_bound"], float((errs / bound).max()))
        many = n[big] >= MEAN_N
        if many.any():
            out["quantile_rank_mean"] = max(
                out["quantile_rank_mean"], float(errs[many].mean()))

    # sets: the distinct count inside the HyperLogLog budget
    sid, values = flush.scalar(SET)
    present = truth.set_distinct > 0
    out["series_mismatch"] += _emitted(sid, present)
    got = _scatter(len(present), sid, values)
    if present.any():
        err = np.abs(got[present] - truth.set_distinct[present])
        tol = hll_tolerance(truth.set_distinct[present],
                            server["tpu_hll_precision"])
        ratio = np.where(np.isnan(err), np.inf, err / tol)
        out["set_err_over_tolerance"] = float(ratio.max())
    return out


def verdict(numbers: dict) -> list:
    """The numbers over their limits, as 'name value > limit'."""
    return [f"{k} {numbers[k]} > {LIMITS[k]}" for k in LIMITS
            if k in numbers and numbers[k] > LIMITS[k]]


def compare_record(strm: Stream, record: list, lines_written: int,
                   server: dict, syncs=(), control: bool = False) -> dict:
    """Every flush of a run, in order: each one's cut located from the
    one before, its range compared, conservation at the end. Each flush
    (``ordinal``, ``view``) loses its view and gains its ``range``.

    ``syncs``: points of set-up at which the sender stood still at a
    known count of ``lines`` until a flush (``ordinal``) had drained
    everything before it, so the next flush starts at ``lines`` whatever
    came before, with ``shed``, the lines the program itself had counted
    as shed until then. A flush that fails (no cut fits, or a number is
    over its limit) is passed over only before such a point and only if
    the program counted lines shed that no earlier point has answered
    for: a cold start's compilations stall the drain for minutes, the
    spill cap sheds, and lines the program says it dropped cannot be in
    a flush. Those flushes are set-up; from the point on the chain is
    exact again, and a hole after the last point fails the run as
    before.

    Returns {"numbers", "control" (the lower-precision control over the
    same ranges, if asked for), "reasons", "flushes" (what to print for
    each), "shed_in_setup"}."""
    numbers = {k: 0 for k in LIMITS}
    low = {k: 0 for k in LIMITS}
    reasons, flushes, seen = [], [], set()
    cut, answered, skip = 0, 0, None
    for fl in record:
        if fl["ordinal"] in seen:
            reasons.append(f"flush {fl['ordinal']} reached the sink twice")
        seen.add(fl["ordinal"])
        view = fl.pop("view")
        if skip is not None:
            if fl["ordinal"] <= skip["ordinal"]:
                continue
            cut, skip = int(skip["lines"]), None
        got = None
        try:
            end = strm.locate_cut(cut, view, lines_written,
                                  last=fl is record[-1])
            truth = strm.truth(cut, end)
            got = compare_flush(truth, view, server)
            why = "; ".join(verdict(got))
        except Mismatch as e:
            why = str(e)
        if why:
            skip = next((s for s in syncs if s["ordinal"] >= fl["ordinal"]
                         and s["shed"] > answered), None)
            if skip is not None:
                answered = int(skip["shed"])
                flushes.append({"ordinal": fl["ordinal"], "failed": why,
                                "passed_over_until": skip["ordinal"],
                                "shed_in_setup": answered})
                continue
            if got is None:
                numbers["cut_not_found"] += 1
                reasons.append(f"flush {fl['ordinal']}: {why}")
                break
        fl["range"] = [cut, end]
        for k, v in got.items():
            numbers[k] = max(numbers[k], v)
        if control:
            for k, v in compare_flush(
                    truth, lower_precision_flush(truth, server),
                    server).items():
                low[k] = max(low[k], v)
        flushes.append({"ordinal": fl["ordinal"], "lines": end - cut,
                        "foreign": view.foreign, **got})
        cut = end
    if skip is not None:
        numbers["cut_not_found"] += 1
        reasons.append(f"no flush after flush {skip['ordinal']}")
    numbers["lines_missing"] = lines_written - cut
    reasons += verdict(numbers)
    if numbers["lines_missing"] < 0:
        reasons.append("more lines flushed than the sender wrote")
    return {"numbers": numbers, "control": low, "reasons": reasons,
            "flushes": flushes, "shed_in_setup": answered}


def lower_precision_flush(truth: Truth, server: dict) -> FlushView:
    """The control: the reference put in the program's place, computed
    one precision below what the deployment states. The device holds
    timer samples and gauges as float32, so the control holds them as
    bfloat16 (8 bits of mantissa, round to nearest even); counters sum
    in float32 instead of float64. Quantiles are the exact order
    statistics of the rounded samples: no digest, so only the precision
    differs."""
    def bf16(x):
        u = np.asarray(x, np.float32).view(np.uint32)
        u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
        return u.view(np.float32).astype(np.float64)

    fl = FlushView()
    at = np.nonzero(~np.isnan(truth.counter_sum))[0]
    fl.families[(COUNTER, "")] = (
        at, truth.counter_sum[at].astype(np.float32).astype(np.float64))
    at = np.nonzero(~np.isnan(truth.gauge_last))[0]
    fl.families[(GAUGE, "")] = (at, bf16(truth.gauge_last[at]))
    at = np.nonzero(truth.timer_n > 0)[0]
    n, st = truth.timer_n[at], truth.timer_start[at]
    samples = bf16(truth.timer_sorted)
    fl.families[(TIMER, ".count")] = (at, n.astype(np.float64))
    fl.families[(TIMER, ".min")] = (at, samples[st] if len(at) else st)
    fl.families[(TIMER, ".max")] = (at, samples[st + n - 1] if len(at) else st)
    for q in server["percentiles"]:
        k = np.clip(np.ceil(q * n).astype(np.int64) - 1, 0, n - 1)
        fl.families[(TIMER, ".%dpercentile" % round(q * 100))] = (
            at, samples[st + k] if len(at) else st)
    at = np.nonzero(truth.set_distinct > 0)[0]
    fl.families[(SET, "")] = (at, truth.set_distinct[at].astype(np.float64))
    return fl
