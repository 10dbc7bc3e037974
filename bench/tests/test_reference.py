"""The range reference against a brute-force dictionary implementation,
the cut arithmetic, and the two ways a flush must fail."""

import math

import numpy as np
import pytest

from bench import reference, stream
from bench.stream import COUNTER, GAUGE, SET, TIMER

SERVER = {"percentiles": [0.5, 0.75, 0.99], "tpu_compression": 100.0,
          "tpu_hll_precision": 14}
CONFIG = {
    "series": {"timer": 400, "counter": 60, "gauge": 60, "set": 12},
    "lines": {"generator": "fleet", "cold_samples": 2, "hot_series": 8,
              "hot_samples": 400, "counter_incs": 2, "gauge_writes": 2,
              "set_max_members": 300},
}


def make_stream(seed=7):
    return reference.Stream(stream.build_ring(CONFIG, seed))


def brute_flush(strm, a, b, drop=None, double=None):
    """What a correct server flushes for stream lines [a, b), worked out
    line by line with dictionaries; ``drop``/``double``: one stream
    position left out or taken twice."""
    counters, gauges, timers, sets = {}, {}, {}, {}
    r, n = strm.ring, strm.n
    positions = [p for p in range(a, b) if p != drop]
    if double is not None:
        positions.append(double)
    for p in positions:
        c, s, v = int(r.cls[p % n]), int(r.sid[p % n]), float(r.val[p % n])
        if c == COUNTER:
            counters[s] = counters.get(s, 0.0) + v
        elif c == GAUGE:
            gauges[s] = v
        elif c == TIMER:
            timers.setdefault(s, []).append(v)
        else:
            sets.setdefault(s, set()).add(v)
    view = reference.FlushView()

    def put(cls, suffix, d):
        keys = sorted(d)
        view.families[(cls, suffix)] = (
            np.array(keys, np.int64), np.array([d[k] for k in keys], float))

    put(COUNTER, "", counters)
    put(GAUGE, "", gauges)
    put(SET, "", {k: float(len(v)) for k, v in sets.items()})
    put(TIMER, ".count", {k: float(len(v)) for k, v in timers.items()})
    put(TIMER, ".min", {k: min(v) for k, v in timers.items()})
    put(TIMER, ".max", {k: max(v) for k, v in timers.items()})
    for q in SERVER["percentiles"]:
        put(TIMER, ".%dpercentile" % round(q * 100),
            {k: sorted(v)[max(0, math.ceil(q * len(v)) - 1)]
             for k, v in timers.items()})
    return view


def test_ring_is_about_5000_lines():
    assert 4500 < make_stream().n < 6000


@pytest.mark.parametrize("case", range(12))
def test_random_cuts_are_located_and_compare_equal(case):
    strm = make_stream()
    rng = np.random.default_rng(case)
    a = int(rng.integers(0, 3 * strm.n))
    b = a + int(rng.integers(1, 2 * strm.n))
    view = brute_flush(strm, a, b)
    end = strm.locate_cut(a, view)
    # the cut may stop short of b only by set lines that change nothing
    cls, _, _ = strm.lines(min(end, b), max(end, b))
    assert end <= b and (cls == SET).all()
    got = reference.compare_flush(strm.truth(a, end), view, SERVER)
    assert reference.verdict(got) == []


def test_a_range_of_several_whole_cycles():
    strm = make_stream()
    a, b = strm.n, 4 * strm.n
    view = brute_flush(strm, a, b)
    assert strm.locate_cut(a, view, last=True) == b
    got = reference.compare_flush(strm.truth(a, b), view, SERVER)
    assert reference.verdict(got) == []
    t = strm.truth(a, b)
    assert t.timer_n.sum() == 3 * strm.timers[-1]


def test_consecutive_cuts_chain_and_conserve():
    strm = make_stream()
    cuts = [0, 1234, 1235, strm.n + 77, 3 * strm.n - 1, 3 * strm.n + 2000]
    at = 0
    for a, b in zip(cuts, cuts[1:]):
        at = strm.locate_cut(at, brute_flush(strm, a, b), limit=cuts[-1],
                             last=b == cuts[-1])
    assert at == cuts[-1]


@pytest.mark.parametrize("how", ["drop", "double"])
def test_one_line_removed_or_doubled_fails(how):
    strm = make_stream()
    a, b = 500, 500 + strm.n
    failed = 0
    for p in range(a + 10, a + 60):
        view = brute_flush(strm, a, b, **{how: p})
        try:
            end = strm.locate_cut(a, view)
            got = reference.compare_flush(strm.truth(a, end), view, SERVER)
            # a cut may still fit where the line is a set member or a
            # gauge write that was overwritten later: the stream's
            # conservation check then finds the line missing
            bad = reference.verdict(got) or end != b
        except reference.Mismatch:
            bad = True
        cls = int(strm.ring.cls[p % strm.n])
        if cls in (COUNTER, TIMER):
            assert bad, f"{how} of a {stream.CLASSES[cls]} line went unseen"
        failed += bool(bad)
    assert failed >= 40


def shed_record(strm, cuts, hole):
    """Flushes 1.. over consecutive ranges, one timer line lost inside
    the second of them, as a shed line is."""
    record = []
    for k, (a, b) in enumerate(zip(cuts, cuts[1:])):
        record.append({"ordinal": k + 1, "view": brute_flush(
            strm, a, b,
            drop=hole if hole is not None and a <= hole < b else None)})
    return record


@pytest.mark.parametrize("case,ok", [
    ("no hole", True),
    ("shed before a sync", True),        # passed over: it was set-up
    ("shed, but none counted", False),   # the program says it shed nothing
    ("shed after the sync", False),      # nothing answers for this hole
    ("sync at another count", False),    # the chain does not close
])
def test_a_hole_is_passed_over_only_before_a_sync_that_counted_it(case, ok):
    strm = make_stream()
    # each cut just after a timer or counter line, where it is the one
    # candidate: a server's ranges chain, and so must these
    cuts = [0] + [next(p for p in range(c, c + 50)
                       if strm.ring.cls[(p - 1) % strm.n] in (TIMER, COUNTER))
                  for c in (900, 2100, 2500, 3900, 5200, strm.n + 1500)]
    timers = [p for p in range(950, 2000) if strm.ring.cls[p] == TIMER]
    hole = None if case == "no hole" else timers[3]
    if case == "shed after the sync":
        hole = next(p for p in range(4000, 4200) if strm.ring.cls[p] == TIMER)
    sync = {"ordinal": 3, "lines": cuts[3], "shed": 1}
    if case == "shed, but none counted":
        sync["shed"] = 0
    if case == "sync at another count":
        sync["lines"] += 1
    got = reference.compare_record(
        strm, shed_record(strm, cuts, hole), cuts[-1], SERVER, [sync])
    assert (not got["reasons"]) == ok, got["reasons"]
    if case == "shed before a sync":
        assert got["shed_in_setup"] == 1
        assert got["numbers"]["lines_missing"] == 0
        passed = [f for f in got["flushes"] if "failed" in f]
        assert [f["ordinal"] for f in passed] == [2]
    if case == "no hole":
        assert got["shed_in_setup"] == 0 and len(got["flushes"]) == 6


def test_a_ring_from_another_seed_fails():
    view = brute_flush(make_stream(7), 0, 5000)
    other = make_stream(8)
    try:
        end = other.locate_cut(0, view)
        bad = reference.verdict(reference.compare_flush(
            other.truth(0, end), view, SERVER))
    except reference.Mismatch:
        bad = True
    assert bad


def test_cut_arithmetic_by_hand():
    # timer, counter(3), gauge, set, timer, counter(2)
    ring = stream.Ring(np.array([2, 0, 1, 3, 2, 0], np.int8),
                       np.array([0, 0, 0, 0, 1, 0], np.int32),
                       np.array([1.0, 3, 5.0, 9, 2.0, 2]),
                       {"timer": 2, "counter": 1, "gauge": 1, "set": 1})
    s = reference.Stream(ring)
    assert s.timers.tolist() == [0, 1, 1, 1, 1, 2, 2]
    assert s.counted.tolist() == [0, 0, 3, 3, 3, 3, 5]
    # one timer line and a counter sum of 3 from the start: the cut may
    # sit before the gauge, before the set, or before the second timer
    assert s.cut_run(0, 1, 3) == (2, 4)
    # a whole cycle and one line more: exact, no run
    assert s.cut_run(0, 3, 5) == (7, 7)
    # a counter sum no prefix has: the flush lost or doubled a line
    assert s.cut_run(0, 1, 2) is None
    assert s.cut_run(4, 2, 5) == (8, 10)
    assert s._before(s.counted, 13) == 10
    assert s._first_at_least(s.timers, 4) == 7 + 4


def test_the_lower_precision_control_fails_the_exact_classes():
    strm = make_stream()
    truth = strm.truth(100, 100 + strm.n)
    got = reference.compare_flush(
        truth, reference.lower_precision_flush(truth, SERVER), SERVER)
    over = reference.verdict(got)
    assert any(o.startswith("timer_max_mismatch") for o in over)
    assert any(o.startswith("gauge_mismatch") for o in over)
