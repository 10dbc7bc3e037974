"""bench/readers/spans.py and bench/readers/modules.py: on events and
spans written by hand (exact arithmetic), and on what one chip run of
PR 25 recorded (bench/testdata/: the half second from the traced
flush's tick, and that flush's spans with the anchor's offset)."""

import json
import os

import pytest

from bench import trace_reduce as tr
from bench.readers import modules, spans

DEV = "/device:TPU:0"
DATA = os.path.join(os.path.dirname(__file__), "..", "testdata")
OFF = 1000.0   # host clock = trace clock + OFF


def S(i, name, t0, t1, parent=None, flush=5, **attrs):
    return [i, name, OFF + t0, OFF + t1, parent, flush, attrs]


# one flush, tick at 1.0 on the trace's clock, sink-seen at 5.0
SPANS = [
    S(1, "flush", 1.0, 5.0),
    S(2, "flush.begin", 1.0, 1.2, 1, reader_recv_ns=8_000, reader_busy_ns=2_000),
    S(3, "swap", 1.05, 1.2, 2),
    S(4, "swap.adopt", 1.06, 1.10, 3, series=10),
    S(5, "flush.extract", 1.2, 4.8, 1),
    S(6, "extract.spill_fold", 1.2, 1.6, 5),
    S(7, "extract.spill_fold.wait", 1.3, 1.6, 6, wait=True),
    S(8, "dispatch", 1.3, 1.6, 7, op="spill"),          # inside a wait
    S(9, "extract.mirror_fold", 1.6, 2.0, 5),
    S(10, "dispatch", 1.7, 2.0, 9, op="staged", bytes=4_000_000),
    S(11, "extract.readback", 2.0, 4.5, 5, wait=True),
    S(12, "extract.unpack", 4.5, 4.8, 5),
    S(13, "flush.generate", 4.8, 4.9, 1),
    S(14, "flush.emit", 4.9, 5.0, 1),
    # the ingest side of the same epoch, on other threads
    S(20, "micro_fold", 0.2, 0.5, None, samples=7, rows=3),
    S(21, "micro_fold.lock_wait", 0.2, 0.3, 20),
    S(22, "micro_fold.feed", 0.3, 0.5, 20),
    S(23, "adopt", 0.6, 0.75, None, series=5),
    S(24, "micro_fold", 1.25, 1.35, None, samples=1, rows=3),  # next epoch's
]
EVENTS = [
    [DEV, "XLA Modules", "jit__histo_ingest_step(11)", 1.30, 0.20],
    [DEV, "XLA Modules", "jit__scatter_chunk(12)", 1.55, 0.02],
    [DEV, "XLA Modules", "jit__histo_fold_staged(13)", 2.0, 2.0],
    [DEV, "XLA Modules", "jit__histo_fold_staged(13)", 6.0, 1.0],   # later
    [DEV, "XLA Modules", "jit__histo_ingest_step(11)", 7.5, 0.25],
    [DEV, "XLA Ops", "%fusion.1", 1.30, 0.20],
    [DEV, "XLA Ops", "%fusion.2", 1.55, 0.02],
    [DEV, "XLA Ops", "%while.17", 2.0, 2.0],
    [DEV, "XLA Ops", "%while.17", 6.0, 1.0],
    [DEV, "XLA Ops", "%fusion.1", 7.5, 0.25],
    ["/host:CPU", "python3", tr.ANCHOR, 0.5, 0.0],
]


def flush(spans_list, ordinal=5):
    return {"ordinal": ordinal, "tick": OFF + 1.0, "t_seen": OFF + 5.0,
            "phases": {"extract_s": 3.6, "spans": spans_list}}


def run_of(*flushes, trace=True):
    run = {"cell": {"name": "test-cell"}, "flushes": list(flushes),
           "device_kind": "TPU v5 lite", "trace": None}
    if trace:
        run["trace"] = {"events": EVENTS, "offset": OFF, "t0": OFF + 1.0,
                        "t1": OFF + 11.0, "flush": flushes[0]}
    return run


def test_sums_waits_and_self_time_of_one_flush():
    run = run_of(flush(SPANS))
    assert spans.read(run, {"what": "sum", "names": ["adopt", "swap.adopt"],
                            "scale": 1000.0}) == pytest.approx(190.0)
    # both micro-folds, each less its lock wait: 0.2 + 0.1
    assert spans.read(run, {"what": "lock_held", "scale": 1000.0}) \
        == pytest.approx(300.0)
    # topmost waits under flush.extract: 0.3 + 2.5; the dispatch inside
    # the first is not counted a second time
    wait = spans.read(run, {"what": "wait", "under": "flush.extract"})
    host = spans.read(run, {"what": "self", "under": "flush.extract"})
    assert wait == pytest.approx(2.8)
    assert host == pytest.approx(0.8)
    assert wait + host == pytest.approx(3.6)


def test_means_run_over_the_counted_flushes_and_counters_are_differenced():
    later = [list(s) for s in SPANS]
    later[1] = S(2, "flush.begin", 1.0, 1.2, 1,
                 reader_recv_ns=8_000 + 70_000, reader_busy_ns=2_000 + 30_000)
    later[3] = S(4, "swap.adopt", 1.06, 1.20, 3, series=10)
    run = run_of(flush(SPANS), flush(later, 6))
    assert spans.read(run, {"what": "sum", "names": ["adopt", "swap.adopt"]}) \
        == pytest.approx((0.19 + 0.29) / 2)
    share = spans.read(run, {"what": "counter_share", "on": "flush.begin",
                             "num": "reader_busy_ns", "rest": "reader_recv_ns"})
    assert share == pytest.approx(30.0)
    # one flush alone gives no difference
    assert spans.read(run_of(flush(SPANS)), {
        "what": "counter_share", "on": "flush.begin",
        "num": "reader_busy_ns", "rest": "reader_recv_ns"}) is None


def test_a_program_without_the_record_reads_nothing():
    old = {"ordinal": 5, "tick": OFF + 1.0, "t_seen": OFF + 5.0,
           "phases": {"extract_s": 3.6}}
    run = run_of(old)
    for arg in ({"what": "sum", "names": ["adopt"]}, {"what": "lock_held"},
                {"what": "wait", "under": "flush.extract"},
                {"what": "self", "under": "flush.extract"},
                {"what": "counter_share", "on": "flush.begin",
                 "num": "a", "rest": "b"},
                {"what": "idle_host"},
                {"what": "bytes_share", "op": "staged", "peak":
                 "hbm_bytes_per_s", "programs": ["jit__histo_fold_staged"]}):
        assert spans.read(run, arg) is None, arg
    assert spans.read(run_of(flush(SPANS), trace=False),
                      {"what": "idle_host"}) is None


def test_module_seconds_by_program_inside_the_flush_and_the_interval():
    run = run_of(flush(SPANS))
    fold = {"programs": ["jit__histo_fold_staged"], "within": "flush"}
    assert modules.read(run, fold) == pytest.approx(2.0)
    assert modules.read(run, {**fold, "within": "interval"}) \
        == pytest.approx(3.0)
    spill = {"programs": ["jit__histo_ingest_step"], "within": "interval"}
    assert modules.read(run, spill) == pytest.approx(0.45)
    # a name is matched whole: jit__histo_fold is not jit__histo_fold_staged
    assert modules.read(run, {"programs": ["jit__histo_fold"],
                              "within": "interval"}) == 0.0
    micro = {"programs": ["jit__scatter_chunk", "jit__grow_mirror"],
             "within": "interval"}
    assert modules.read(run, micro) == pytest.approx(0.02)
    assert modules.read(run_of(flush(SPANS), trace=False), fold) is None
    # every program inside the flush stays within the busy time there
    table = modules.by_program(EVENTS, 1.0, 5.0)
    assert table == {"jit__histo_ingest_step": [1, pytest.approx(0.2)],
                     "jit__scatter_chunk": [1, pytest.approx(0.02)],
                     "jit__histo_fold_staged": [1, pytest.approx(2.0)]}
    assert sum(v[1] for v in table.values()) <= tr.busy_seconds(
        EVENTS, 1.0, 5.0) + 1e-9


def test_idle_gaps_fall_in_waiting_spans_or_in_the_hosts(tmp_path,
                                                         monkeypatch):
    monkeypatch.setattr(spans, "OUT", str(tmp_path))
    table = spans.idle_by_span(EVENTS, spans.spans_of(flush(SPANS)), OFF,
                               1.0, 5.0)
    # gaps: 1.0-1.3 (middle 1.15 in swap), 1.5-1.55 (in the dispatch
    # inside spill_fold.wait: waiting), 1.57-2.0 (middle 1.785 in the
    # staged dispatch: the host's), 4.0-5.0 (middle 4.5: unpack begins
    # where the readback ends)
    assert table == {
        "swap": [pytest.approx(0.3), False],
        "dispatch:spill [wait]": [pytest.approx(0.05), True],
        "dispatch:staged": [pytest.approx(0.43), False],
        "extract.unpack": [pytest.approx(1.0), False]}
    host = spans.read(run_of(flush(SPANS)), {"what": "idle_host"})
    with open(tmp_path / "test-cell.idle_by_span.json") as f:
        report = json.load(f)
    idle = report["idle_by_span"]
    assert sum(v["idle_s"] for v in idle.values()) == pytest.approx(
        4.0 - tr.busy_seconds(EVENTS, 1.0, 5.0))
    assert host == pytest.approx(sum(
        v["idle_s"] for v in idle.values() if not v["waiting"]))
    assert report["programs_in_flush"]["jit__histo_fold_staged"][1] \
        == pytest.approx(2.0)


def test_the_fold_share_of_the_hbm_peak():
    run = run_of(flush(SPANS))
    arg = {"what": "bytes_share", "op": "staged", "peak": "hbm_bytes_per_s",
           "programs": ["jit__histo_fold_staged"]}
    with open(os.path.join(DATA, "..", "peaks.json")) as f:
        peak = json.load(f)["device_kinds"]["TPU v5 lite"]["hbm_bytes_per_s"]
    assert spans.read(run, arg) == pytest.approx(
        100.0 * 4_000_000 / 2.0 / peak)
    run["device_kind"] = "a chip nobody listed"
    assert spans.read(run, arg) is None


def recorded():
    path = os.path.join(DATA, "local-timers.steady.pr25.spans.json")
    if not os.path.exists(path):
        pytest.skip("no recorded spans under bench/testdata")
    with open(path) as f:
        rec = json.load(f)
    events = tr.load_slice(os.path.join(
        DATA, "local-timers.steady.pr25.slice.json.gz"))
    return rec, events


def test_the_recorded_flush_reads_like_the_run_that_recorded_it():
    rec, events = recorded()
    fl = {"ordinal": rec["ordinal"], "tick": rec["tick"],
          "t_seen": rec["t_seen"],
          "phases": {"extract_s": rec["extract_s"], "spans": rec["spans"]}}
    run = {"cell": {"name": "local-timers.steady"}, "flushes": [fl],
           "device_kind": rec["device_kind"],
           "trace": {"events": events, "offset": rec["offset"],
                     "t0": rec["tick"], "t1": rec["tick"] + 10.0,
                     "flush": fl}}
    wait = spans.read(run, {"what": "wait", "under": "flush.extract"})
    host = spans.read(run, {"what": "self", "under": "flush.extract"})
    assert wait + host == pytest.approx(rec["extract_s"], rel=0.01)
    assert wait > host > 0
    # the slice is the first half second from the tick: the spill fold's
    # program and the head of the staged fold are both in it
    t0 = rec["tick"] - rec["offset"]
    table = modules.by_program(events, t0, t0 + 0.5)
    assert table["jit__histo_fold_staged"][1] > 0.1
    assert "jit__histo_ingest_step" in table
    idle = spans.idle_by_span(events, spans.spans_of(fl), rec["offset"],
                              t0, t0 + 0.5)
    assert any(w for _, w in idle.values()), idle      # one in a wait
    assert any(not w for _, w in idle.values()), idle  # one in the host's
    assert sum(v for v, _ in idle.values()) == pytest.approx(
        0.5 - tr.busy_seconds(
            [e for e in events if e[0] == sorted(tr.device_ops(events))[0]
             or e[2] == tr.ANCHOR], t0, t0 + 0.5), abs=1e-6)
