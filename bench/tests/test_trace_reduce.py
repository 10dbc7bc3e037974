"""bench/trace_reduce.py: busy union, idle share, top operations and gap
labelling, on events written by hand (exact arithmetic) and on a slice
cut from a real chip trace (bench/testdata/)."""

import glob
import os

import pytest

from bench import trace_reduce as tr

DEV = "/device:TPU:0"
EVENTS = [
    # two lines carry the same time twice over: only one may be counted
    [DEV, "XLA Modules", "jit_fold", 1.0, 2.0],
    [DEV, "XLA Ops", "fusion.1", 1.0, 0.5],
    [DEV, "XLA Ops", "sort.2", 1.4, 0.6],     # overlaps fusion.1
    [DEV, "XLA Ops", "fusion.1", 2.5, 0.5],
    [DEV, "XLA Ops", "copy.3", 6.0, 1.0],
    ["/host:CPU", "python", tr.ANCHOR, 0.25, 0.0],
]


def test_busy_is_the_union_on_one_line():
    assert tr.busy_seconds(EVENTS, 0.0, 10.0) == pytest.approx(2.5)
    # clipped to the window: [1.5, 2.0] + [2.5, 2.75]
    assert tr.busy_seconds(EVENTS, 1.5, 2.75) == pytest.approx(0.75)
    assert tr.busy_seconds([], 0.0, 1.0) == 0.0


def test_top_ops_sum_by_name_inside_the_window():
    assert tr.top_ops(EVENTS, 0.0, 10.0, n=2) == [
        ["fusion.1", pytest.approx(1.0)], ["copy.3", pytest.approx(1.0)]]


def test_gaps_take_the_label_of_the_span_that_holds_their_middle():
    spans = tr.flush_spans(0.5, {"swap_s": 0.4, "drain_s": 0.1,
                                 "extract_s": 2.5, "generate_s": 0.3,
                                 "sink_flush_s": 0.2})
    assert spans == [("swap", 0.5, pytest.approx(1.0)),
                     ("extract", pytest.approx(1.0), pytest.approx(3.5)),
                     ("generate+emit", pytest.approx(3.5), pytest.approx(4.0))]
    gaps = tr.idle_gaps(EVENTS, 0.0, 10.0, spans)
    assert gaps[0] == ["ingest-only", pytest.approx(3.0)]   # 3 .. 6
    assert gaps[1] == ["ingest-only", pytest.approx(3.0)]   # 7 .. 10
    assert ["extract", pytest.approx(0.5)] in gaps          # 2 .. 2.5
    assert ["swap", pytest.approx(1.0)] in gaps             # 0 .. 1
    assert sum(g[1] for g in gaps) == pytest.approx(10.0 - 2.5)


def test_the_anchor_gives_the_clock_offset():
    assert tr.anchor_offset(EVENTS, 1000.25) == pytest.approx(1000.0)
    assert tr.anchor_offset(EVENTS[:3], 5.0) is None


def brute_busy(events, t0, t1, step=1e-5):
    """Busy time by sampling the window, per device, then averaged."""
    ops = tr.device_ops(events)
    total = 0.0
    for v in ops.values():
        n = int(round((t1 - t0) / step))
        hit = bytearray(n)
        for a, b, _ in v:
            lo = max(0, int((a - t0) / step + 0.5))
            hi = min(n, int((b - t0) / step + 0.5))
            if hi > lo:
                hit[lo:hi] = b"\x01" * (hi - lo)
        total += sum(hit) * step
    return total / max(1, len(ops))


def test_a_recorded_chip_slice_reduces_the_same_both_ways():
    paths = glob.glob(os.path.join(os.path.dirname(__file__), "..",
                                   "testdata", "*.slice.json.gz"))
    if not paths:
        pytest.skip("no recorded slice under bench/testdata")
    events = tr.load_slice(paths[0])
    ops = tr.device_ops(events)
    assert ops, "the slice has no device plane"
    t0 = min(v[0][0] for v in ops.values())
    t1 = t0 + 0.3
    busy = tr.busy_seconds(events, t0, t1)
    assert 0.0 < busy <= 0.3
    assert busy == pytest.approx(brute_busy(events, t0, t1), abs=2e-3)
    gaps = tr.idle_gaps(events, t0, t1, [("extract", t0, t1)])
    one = tr.busy_seconds(
        [e for e in events if e[0] == sorted(ops)[0]], t0, t1)
    assert sum(g[1] for g in gaps) <= 0.3 - one + 1e-9
    assert tr.top_ops(events, t0, t1)[0][1] > 0
    assert tr.anchor_offset(events, 0.0) is not None
