"""The benchmark's readers of what PR 42 added to the span record
(bench/readers/counters.py, span_cpu.py, ingest_idle.py): on spans and
device events written by hand, where the arithmetic is exact, and on
what one chip run recorded (bench/testdata/local-timers-1m.steady.pr42.*:
two counted flushes of the cell, the traced one and its successor, and
the module line of the traced interval). On PR 25's recording, which has
none of the attrs, every one of them reads nothing and raises nothing.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import stream  # noqa: E402
from bench.readers import counters, ingest_idle, span_cpu, spans  # noqa: E402

DATA = os.path.join(ROOT, "bench", "testdata")
DEV = "/device:TPU:0"
OFF = 1000.0   # host clock = trace clock + OFF


def S(i, name, t0, t1, parent=None, flush=5, **attrs):
    return [i, name, OFF + t0, OFF + t1, parent, flush, attrs]


def flush_of(ordinal, tick, span_list):
    return {"ordinal": ordinal, "tick": OFF + tick, "t_seen": OFF + tick + 1,
            "phases": {"spans": span_list}}


def begin(i, tick, ordinal, **counters_):
    return S(i, "flush.begin", tick, tick + 0.2, None, ordinal, cpu_s=0.05,
             **counters_)


# -- counters.ratio --------------------------------------------------------

def test_ratio_differences_the_first_and_the_last_counted_flush():
    run = {"flushes": [
        flush_of(5, 0, [begin(1, 0, 5, reader_parse_ns=1_000,
                              commit_lines=10)]),
        flush_of(6, 10, [begin(2, 10, 6, reader_parse_ns=9_000_000,
                               commit_lines=77)]),
        flush_of(7, 20, [begin(3, 20, 7, reader_parse_ns=4_001_000,
                               commit_lines=20_010)])]}
    arg = {"what": "ratio", "on": "flush.begin", "num": "reader_parse_ns",
           "den": "commit_lines"}
    assert counters.read(run, arg) == pytest.approx(200.0)
    assert counters.read(run, {**arg, "scale": 0.001}) == pytest.approx(0.2)


@pytest.mark.parametrize("case", ["no_record", "one_flush", "no_attr",
                                  "still"])
def test_ratio_reads_nothing_where_there_is_nothing_to_read(case):
    a = begin(1, 0, 5, reader_parse_ns=1_000, commit_lines=10)
    b = begin(2, 10, 6, reader_parse_ns=5_000, commit_lines=30)
    flushes = [flush_of(5, 0, [a]), flush_of(6, 10, [b])]
    if case == "no_record":
        flushes[1]["phases"] = {}
    elif case == "one_flush":
        flushes = flushes[:1]
    elif case == "no_attr":
        del b[6]["reader_parse_ns"]
    else:
        b[6]["commit_lines"] = 10
    assert counters.read({"flushes": flushes}, {
        "what": "ratio", "on": "flush.begin", "num": "reader_parse_ns",
        "den": "commit_lines"}) is None


def test_a_reader_refuses_a_reading_it_does_not_know():
    for reader in (counters, span_cpu):
        with pytest.raises(ValueError):
            reader.read({"flushes": []}, {"what": "nope"})


# -- span_cpu --------------------------------------------------------------

# one flush: a swap that stood still 30 ms outside its device wait, and
# three micro-folds of the epoch on their own thread
CPU_SPANS = [
    S(1, "flush", 1.0, 2.0, cpu_s=0.5),
    S(2, "flush.begin", 1.0, 1.2, 1, cpu_s=0.12),
    S(3, "swap.handoff", 1.05, 1.15, 2, cpu_s=0.07),
    S(4, "swap.fence", 1.15, 1.20, 2, wait=True, cpu_s=0.0),
    S(5, "dispatch", 1.16, 1.19, 4, op="x", wait=True, cpu_s=0.0),  # nested
    S(6, "flush.generate", 1.5, 1.6, 1, cpu_s=0.1),
    S(20, "micro_fold", 0.1, 0.3, None, cpu_s=0.15),
    S(21, "micro_fold.drain", 0.1, 0.2, 20, cpu_s=0.09),
    S(22, "micro_fold", 0.5, 0.6, None, cpu_s=0.05),
    S(23, "micro_fold", 0.8, 0.9, None, cpu_s=0.1),
]


def test_cpu_sums_the_named_spans_and_offcpu_sets_the_waits_aside():
    run = {"flushes": [flush_of(5, 1.0, CPU_SPANS),
                       flush_of(6, 11.0, [S(30, "micro_fold", 10, 10.4, None,
                                            6, cpu_s=0.1)])]}
    # (0.15 + 0.05 + 0.1) and 0.1: the children are inside their roots
    assert span_cpu.read(run, {"what": "cpu", "names": ["micro_fold"]}) \
        == pytest.approx(0.2)
    # 0.2 long, 0.12 on a core, 0.05 waiting for the device (once: the
    # dispatch below the fence is the fence's); the second flush has no
    # flush.begin and is left out of the mean
    assert span_cpu.read(run, {"what": "offcpu", "under": "flush.begin",
                               "scale": 1000.0}) == pytest.approx(30.0)
    assert span_cpu.read(run, {"what": "offcpu", "under": "flush.generate"}) \
        == pytest.approx(0.0)
    assert span_cpu.read(run, {"what": "offcpu", "under": "nope"}) is None


@pytest.mark.parametrize("what", ["cpu", "offcpu"])
def test_span_cpu_reads_nothing_from_a_record_without_the_attr(what):
    old = [[s[0], s[1], s[2], s[3], s[4], s[5],
            {k: v for k, v in s[6].items() if k != "cpu_s"}]
           for s in CPU_SPANS]
    arg = {"what": what, "names": ["micro_fold"], "under": "flush.begin"}
    assert span_cpu.read({"flushes": [flush_of(5, 1.0, old)]}, arg) is None
    assert span_cpu.read({"flushes": [{"phases": {}}]}, arg) is None
    # a wait below that lacks it spoils the span's reading too
    part = [s if s[1] != "swap.fence" else S(4, "swap.fence", 1.15, 1.20, 2,
                                             wait=True) for s in CPU_SPANS]
    assert span_cpu.read({"flushes": [flush_of(5, 1.0, part)]}, {
        "what": "offcpu", "under": "flush.begin"}) is None


# -- ingest_idle -----------------------------------------------------------

# the traced interval is [1, 11] on the trace's clock; flush 5 fires at 1
# and its tree ends at 2; the ingest side of flush 6's epoch follows
OWN = [
    S(1, "flush", 1.0, 2.0),
    S(2, "flush.extract", 1.2, 1.9, 1),
    S(3, "extract.readback", 1.4, 1.8, 2, wait=True),
    S(9, "micro_fold", 0.2, 0.4, None),            # flush 5's epoch: before
]
AFTER = [
    S(40, "flush", 11.0, 11.5, None, 6),           # flush 6 itself: after
    S(41, "micro_fold", 3.0, 4.0, None, 6),
    S(42, "micro_fold.lock_wait", 3.0, 3.1, 41, 6),
    S(43, "micro_fold.drain", 3.1, 3.6, 41, 6),
    S(44, "drain.apply", 3.2, 3.6, 43, 6),
    S(45, "dispatch", 3.3, 3.5, 44, 6, op="fold"),
    S(46, "fold.fence", 3.5, 3.6, 44, 6, wait=True),
    S(47, "pump", 3.05, 4.2, None, 6),             # another thread,
    S(48, "pump.lock_wait", 3.05, 4.0, 47, 6),     # waiting for the lock
    S(49, "sync", 10.5, 11.5, None, 6),            # runs past the interval
]
EVENTS = [
    [DEV, "XLA Ops", "%fusion.1", 1.5, 0.2],       # inside the readback
    [DEV, "XLA Ops", "%fusion.2", 3.4, 0.15],      # the fold, dispatched
    [DEV, "XLA Ops", "%fusion.3", 3.55, 0.05],     # touches the one before
    [DEV, "XLA Ops", "%fusion.9", 12.0, 1.0],      # after the interval
    [DEV, "XLA Modules", "jit_x(1)", 0.0, 20.0],   # the op line is finer
]


def traced_run(own=OWN, after=AFTER, events=EVENTS):
    fl5, fl6 = flush_of(5, 1.0, own), flush_of(6, 11.0, after)
    return {"cell": {"name": "test-cell"}, "flushes": [fl5, fl6],
            "trace": {"events": events, "offset": OFF, "t0": OFF + 1.0,
                      "t1": OFF + 11.0, "flush": fl5}}


def test_the_intervals_idle_seconds_go_to_the_deepest_span_open(monkeypatch,
                                                               tmp_path):
    monkeypatch.setattr(spans, "OUT", str(tmp_path))
    got = ingest_idle.read(traced_run(), {})
    with open(tmp_path / "test-cell.idle_interval_by_span.json") as f:
        report = json.load(f)
    table = {k: v["idle_s"] for k, v in report["idle_interval_by_span"].items()}
    want = {
        # the traced flush's own tree, [1, 2] less the op at [1.5, 1.7]
        "flush": 0.3, "flush.extract": 0.3,
        "extract.readback [wait]": 0.2,
        # the micro-fold of the next epoch, [3, 4] less [3.4, 3.6]: the
        # lock wait loses to nothing deeper, the pump's wait (as deep,
        # opened later) takes [3.05, 3.1] and all past the drain
        "micro_fold.lock_wait": 0.05, "pump.lock_wait": 0.05 + 0.4,
        "micro_fold.drain": 0.1, "drain.apply": 0.1, "dispatch:fold": 0.1,
        "pump": 0.2, "sync": 0.5,
        # nothing due: [2, 3], [4.2, 10.5]
        "(no span)": 1.0 + 6.3,
    }
    assert table == pytest.approx(want)
    assert report["idle_s"] == pytest.approx(10.0 - 0.2 - 0.2)
    assert report["by_side"] == pytest.approx(
        {"flush": 0.8, "ingest": 1.5, "none": 7.3})
    assert report["idle_interval_by_span"]["extract.readback [wait]"] == {
        "idle_s": pytest.approx(0.2), "side": "flush", "waiting": True}
    # the value: the ingest side's, none of which waits on the device here
    assert got == pytest.approx(1.5)
    assert (report["flush"], report["ingest_side_of"]) == (5, 6)


def test_a_fence_on_the_ingest_side_is_not_the_hosts_idle_time(monkeypatch,
                                                              tmp_path):
    monkeypatch.setattr(spans, "OUT", str(tmp_path))
    # the device runs nothing while the fold's fence waits
    events = [e for e in EVENTS if e[2] not in ("%fusion.2", "%fusion.3")]
    got = ingest_idle.read(traced_run(events=events), {})
    with open(tmp_path / "test-cell.idle_interval_by_span.json") as f:
        table = json.load(f)["idle_interval_by_span"]
    assert table["fold.fence [wait]"] == {
        "idle_s": pytest.approx(0.1), "side": "ingest", "waiting": True}
    assert table["dispatch:fold"]["idle_s"] == pytest.approx(0.2)
    assert got == pytest.approx(1.5 + 0.1)      # the fence's 0.1 is not in it
    assert sum(e["idle_s"] for e in table.values()) == pytest.approx(9.8)


@pytest.mark.parametrize("case", ["no_trace", "no_events", "no_successor",
                                  "no_record", "no_device"])
def test_ingest_idle_reads_nothing_where_a_piece_is_missing(case, monkeypatch,
                                                            tmp_path):
    monkeypatch.setattr(spans, "OUT", str(tmp_path))
    run = traced_run()
    if case == "no_trace":
        run["trace"] = None
    elif case == "no_events":
        run["trace"]["events"] = []
    elif case == "no_successor":
        run["flushes"] = run["flushes"][:1]
    elif case == "no_record":
        run["flushes"][1]["phases"] = {}
    else:
        run["trace"]["events"] = [["/host:CPU", "python3", "bench_anchor",
                                   0.5, 0.0]]
    assert ingest_idle.read(run, {}) is None
    assert not os.listdir(tmp_path)


# -- the benchmark's files -------------------------------------------------

NEW = ("reader_parse_ns.line", "reader_lock_wait_ns.line",
       "reader_commit_ns.line", "ctx_lock_held_ms.flush",
       "micro_fold_cpu_s.interval", "swap_offcpu_ms", "generate_offcpu_ms",
       "idle_ingest_host_s.interval")


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_appended_with_its_file_and_lists_no_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended together and in this order, after what PR 41 left and
    # before whatever a later PR appends (PR 44: three more)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert at == 27 and names[at:at + len(NEW)] == list(NEW)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert "workloads" not in entry
    spec = stream.load_json("layer_metrics", name)
    for key in ("name", "unit", "layer", "moves", "source", "better"):
        assert spec[key] == entry[key], key
    assert spec["reader"] in ("counters", "span_cpu", "ingest_idle", "spans")


# -- PR 25's recording: nothing to read, nothing raised ---------------------

def old_run():
    with open(os.path.join(DATA, "local-timers.steady.pr25.spans.json")) as f:
        rec = json.load(f)
    fl = {"ordinal": rec["ordinal"], "tick": rec["tick"],
          "t_seen": rec["t_seen"], "phases": {"spans": rec["spans"]}}
    nxt = {**fl, "ordinal": rec["ordinal"] + 1, "tick": rec["tick"] + 10.0}
    with gzip.open(os.path.join(
            DATA, "local-timers.steady.pr25.slice.json.gz"), "rt") as f:
        events = json.load(f)["events"]
    return {"cell": {"name": "old-cell"}, "flushes": [fl, nxt],
            "trace": {"events": events, "offset": rec["offset"],
                      "t0": rec["tick"], "t1": rec["tick"] + 10.0,
                      "flush": fl}}


@pytest.mark.parametrize("name", [n for n in NEW
                                  if n != "idle_ingest_host_s.interval"])
def test_the_old_recording_gives_a_new_metric_nothing_or_zero(name):
    spec = stream.load_json("layer_metrics", name)
    reader = {"counters": counters, "span_cpu": span_cpu,
              "spans": spans}[spec["reader"]]
    got = reader.read(old_run(), spec["arg"])
    if name == "ctx_lock_held_ms.flush":
        # the old program had swap.drain, and neither of the other two
        rec = old_run()["flushes"][0]["phases"]["spans"]
        assert got == pytest.approx(1e3 * sum(
            s[3] - s[2] for s in rec if s[1] == "swap.drain"))
    else:
        assert got is None


def test_the_old_recording_has_an_idle_table_with_no_ingest_side_work(
        monkeypatch, tmp_path):
    """The spans of the old program lie where they lay; the reader files
    the half second of recorded device events under them and does not
    mind that none has a cpu_s or a ctx_lock."""
    monkeypatch.setattr(spans, "OUT", str(tmp_path))
    run = old_run()
    got = ingest_idle.read(run, {})
    with open(tmp_path / "old-cell.idle_interval_by_span.json") as f:
        report = json.load(f)
    assert got is not None and got >= 0.0
    assert report["idle_s"] == pytest.approx(sum(
        e["idle_s"] for e in report["idle_interval_by_span"].values()))
    assert 0.0 < report["idle_s"] < 10.0


# -- PR 42's recording ------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """(the recording, a run built from it as bench/run.py builds one)."""
    with gzip.open(os.path.join(
            DATA, "local-timers-1m.steady.pr42.interval.json.gz"), "rt") as f:
        rec = json.load(f)
    flushes = [{"ordinal": fl["ordinal"], "tick": fl["tick"],
                "t_seen": fl["t_seen"], "phases": {"spans": fl["spans"]}}
               for fl in rec["flushes"]]
    run = {"cell": {"name": rec["cell"]}, "flushes": flushes,
           "device_kind": rec["device_kind"],
           "trace": {"events": rec["events"], "offset": rec["offset"],
                     "t0": rec["t0"], "t1": rec["t1"], "flush": flushes[0]}}
    return rec, run


def attrs_of(flush, name):
    return [s[6] for s in flush["phases"]["spans"] if s[1] == name]


@pytest.mark.parametrize("part", ["parse", "lock_wait", "commit"])
def test_the_recorded_readers_time_splits_three_ways(recorded, part):
    rec, run = recorded
    name = f"reader_{part}_ns.line"
    got = counters.read(run, stream.load_json("layer_metrics", name)["arg"])
    (a,), (b,) = (attrs_of(fl, "flush.begin") for fl in run["flushes"])
    lines = b["commit_lines"] - a["commit_lines"]
    # an interval of the cell, cut where the tick fell
    assert lines == pytest.approx(3435357, rel=0.01)
    assert got == pytest.approx(
        (b[f"reader_{part}_ns"] - a[f"reader_{part}_ns"]) / lines)
    # one interval against the run's four: the same reader, near enough
    assert got == pytest.approx(rec["metrics"][name], rel=0.35)
    # and the three are the readers' busy time, to the nanosecond
    total = sum(b[f"reader_{k}_ns"] - a[f"reader_{k}_ns"]
                for k in ("parse", "lock_wait", "commit"))
    assert total == b["reader_busy_ns"] - a["reader_busy_ns"]
    # a record a lock hold, and a hold a chunk
    assert b["commit_batches"] - a["commit_batches"] \
        == pytest.approx(1460, abs=10)


@pytest.mark.parametrize("name, rel", [("micro_fold_cpu_s.interval", 0.15),
                                       ("ctx_lock_held_ms.flush", 0.3),
                                       ("swap_offcpu_ms", None),
                                       ("generate_offcpu_ms", None)])
def test_the_recorded_spans_give_the_span_metrics(recorded, name, rel):
    rec, run = recorded
    spec = stream.load_json("layer_metrics", name)
    reader = span_cpu if spec["reader"] == "span_cpu" else spans
    got = reader.read(run, spec["arg"])
    if rel is not None:
        assert got == pytest.approx(rec["metrics"][name], rel=rel)
    else:
        # the host's thread clock ticks in 10 ms: a flush's phase stood
        # still for less than that, or for a few of them
        under = spec["arg"]["under"]
        wall = sum(s[3] - s[2] for fl in run["flushes"]
                   for s in fl["phases"]["spans"] if s[1] == under) / 2
        assert -10.0 <= got <= 1e3 * wall
    if name == "ctx_lock_held_ms.flush":
        held = {s[1] for fl in run["flushes"] for s in fl["phases"]["spans"]
                if s[6].get("ctx_lock")}
        assert held == {"drain.raw", "feed.stage_delta", "swap.drain"}


def test_the_recorded_intervals_idle_seconds_are_all_filed(recorded,
                                                           monkeypatch,
                                                           tmp_path):
    from bench import trace_reduce

    rec, run = recorded
    monkeypatch.setattr(spans, "OUT", str(tmp_path))
    got = ingest_idle.read(run, {})
    with open(tmp_path / (rec["cell"] + ".idle_interval_by_span.json")) as f:
        report = json.load(f)
    table = report["idle_interval_by_span"]
    off = rec["offset"]
    w0, w1 = rec["t0"] - off, rec["t1"] - off
    idle = (w1 - w0) - trace_reduce.busy_seconds(rec["events"], w0, w1)
    # every idle second of the interval is in the table, once
    assert sum(e["idle_s"] for e in table.values()) == pytest.approx(idle)
    assert report["idle_s"] == pytest.approx(idle)
    assert sum(report["by_side"].values()) == pytest.approx(idle)
    # the module line's edges are the op line's to a few milliseconds:
    # what the run itself filed, by side, is what the recording gives
    assert idle == pytest.approx(rec["idle_s"], abs=0.05)
    for side, secs in rec["by_side"].items():
        assert report["by_side"][side] == pytest.approx(secs, abs=0.05), side
    assert got == pytest.approx(
        rec["metrics"]["idle_ingest_host_s.interval"], abs=0.05)
    # nine tenths of the idle time nothing was due
    assert table[ingest_idle.NO_SPAN]["idle_s"] > 0.85 * idle
    assert table["feed.carry"]["side"] == "ingest"
    assert table["swap.handoff"]["side"] == "flush"
    assert all(e["waiting"] == k.endswith(" [wait]") for k, e in table.items())
