"""The configuration ``local-mixed`` (BASELINE config 2: counters,
gauges, sets and timers together under Zipf 1.1 keys) through the served
path against the benchmark's plain reference, at a small size on the
CPU, and the files of the configuration.

``tests/test_bench_parity.py`` holds the served path to
``bench/reference.py`` on uniform traffic. Here the lines come from the
configuration's own generator (``zipf_mix``, the same 20-rank pattern,
Zipf 1.1) at 20,000 series and 524,288 lines an interval, a size that
keeps the traffic's shape: the rank-1 key is a timer that takes 76,000
samples an interval (more than four ``_FOLD_CHUNK``s, through a dozen
and more successive spill folds), about 250 timer rows pass the staging
depth and three timer samples in four take the spill fold, nine hundred
rows are wide for the staged fold, and one series in thirteen is absent
from an interval (the last rank expects 1.4 lines). The CPU folds it in
seconds because the pool is 8,192 rows and an interval's spill is some
twenty steps of at most 16,384 samples. Two
intervals in a row (the second drawn anew, so its hot rows and its
absent series are not the first's), three seeds, PERF.md section 2's
limits unchanged.
The server is built as the harness builds it (``write_yaml`` ->
``load_config`` -> ``build_server``) and never started.

The rest: the comparison has teeth on this traffic (one sample of the
rank-1 timer dropped, and its samples rounded to bfloat16, each read
over a named limit), the counters and attrs that came with the cell
(``histo_spilled``, ``histo_staged``, ``hot_row_samples``,
``spill_steps``, ``sets``, ``sparse_entries``) are what the stream says
they must be, ``BENCHMARK.json`` agrees with the configuration's file,
and the ring a seed builds at the full size is pinned byte for byte.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference, run, stream  # noqa: E402
from tests.test_bench_parity import flushed, nothing_shed  # noqa: E402

CONFIG, CELL = "local-mixed", "local-mixed.steady"
# the pattern deals 8 t, 7 c, 3 g, 2 s to every 20 ranks
SMALL_SERIES = {"timer": 8000, "counter": 7000, "gauge": 3000, "set": 2000}
SMALL_LINES = {"lines_per_interval": 524288}
SEEDS = (4400000011, 4400000012, 2147483659)
MICRO_FOLD_EVERY = 8  # chunks of 64 KiB between two micro-folds
STAGE_DEPTH = 64

# (seed, series, lines by class, sha256 over (dtype, bytes) of cls, sid,
# val), as tests/test_bench_parity.py pins the other two
RING = (4400000001, 100000, [1424926, 508301, 1941273, 319804],
        "09217df94eb9e3c755a3f38ee279271941aa8682debcf0b7454a0b321dbffff1")


def small_config() -> dict:
    config = stream.load_json("configs", CONFIG)
    config["series"] = dict(SMALL_SERIES)
    config["lines"] = {**config["lines"], **SMALL_LINES}
    return config


@pytest.fixture
def served(tmp_path):
    """(config, server, collector): the harness's own server, not
    started. Skips where the native library is absent."""
    from veneur_tpu.core.config import load_config
    from veneur_tpu.core.factory import build_server

    config = small_config()
    path = str(tmp_path / "cell.yaml")
    written = run.write_yaml(path, config, chips=1)
    assert written["tpu_initial_histo_rows"] == 8192
    collector = run.make_collector("")
    srv = build_server(load_config(path), extra_metric_sinks=[collector])
    collector.server = srv
    try:
        if not srv.native_mode:
            pytest.skip("native library unavailable")
        yield config, srv, collector
    finally:
        srv.shutdown()


def hand_over(srv, lines: list) -> None:
    """The lines in the sender's 64 KiB chunks, a micro-fold every
    MICRO_FOLD_EVERY chunks (each drains the hot rows' spill into a few
    ingest steps, so the rank-1 row is folded into again and again) and
    the adoption sweep, each called here and not by a thread."""
    chunks, _ = stream.chunk_lines(lines, 65536)
    for i, chunk in enumerate(chunks, 1):
        srv._native_router.ingest(chunk)
        if i % MICRO_FOLD_EVERY == 0:
            srv._micro_fold(0, srv.workers[0])
    srv.sync_native_series_once()


def attrs_of(srv, name: str) -> dict:
    (attrs,) = [s[6] for s in srv.last_flush_phases["spans"] if s[1] == name]
    return attrs


def timer_counts(ring, a: int, b: int) -> np.ndarray:
    cls, sid, _ = reference.Stream(ring).lines(a, b)
    return np.bincount(sid[cls == stream.TIMER],
                       minlength=ring.series["timer"])


def rank1_timer(ring) -> int:
    return int(np.argmax(timer_counts(ring, 0, len(ring))))


def live_series(ring) -> set:
    """The series with a line in the ring, as class << 32 | number."""
    return set(np.unique(ring.cls.astype(np.int64) << 32
                         | ring.sid).tolist())


@pytest.mark.parametrize("seed", SEEDS)
def test_two_intervals_of_zipf_traffic_are_what_the_reference_says(served,
                                                                   seed):
    """Two intervals in a row, the second drawn anew (the next seed: the
    ranks are dealt to other series, so the hot rows move and another
    sixteenth of the series is absent), each held to the reference."""
    from veneur_tpu.core import worker as W

    config, srv, collector = served
    rings = [stream.build_ring(config, seed), stream.build_ring(config,
                                                                seed + 1)]
    assert rank1_timer(rings[0]) != rank1_timer(rings[1])
    total = sum(SMALL_SERIES.values())
    seen = []
    for ring in rings:
        n = len(ring)
        hand_over(srv, stream.format_lines(ring, config["lines"]["tag_from"]))
        view = flushed(srv, collector)
        truth = reference.Stream(ring).truth(0, n)
        numbers = reference.compare_flush(truth, view, config["server"])
        assert reference.verdict(numbers) == [], numbers
        assert view.foreign == []
        per_row = timer_counts(ring, 0, n)
        # the traffic kept its shape: a rank-1 timer deeper than two
        # chunks of the spill fold, a few hundred rows past the staging
        # depth, most timer samples on the spill path, series absent
        assert per_row.max() > 2 * W._FOLD_CHUNK
        assert 200 <= int((per_row > STAGE_DEPTH).sum()) <= 400
        spilled = int(np.maximum(per_row - STAGE_DEPTH, 0).sum())
        assert spilled > 0.6 * per_row.sum()
        live = len(live_series(ring))
        assert 0.03 * total <= total - live <= 0.10 * total
        assert live == sum(len(view.family(c, sfx)[0]) for c, sfx in (
            (stream.COUNTER, ""), (stream.GAUGE, ""), (stream.SET, ""),
            (stream.TIMER, ".count")))
        # and it took the served path: micro-folds fed the mirror, the
        # hot rows spilled while the epoch was live and at the tick, the
        # staged fold told wide rows from narrow
        spans = srv.last_flush_phases["spans"]
        ops = {s[6].get("op") for s in spans if s[1] == "dispatch"}
        assert {"fold", "spill", "micro", "staged", "extract"} <= ops
        assert {"micro_fold.feed", "extract.mirror_fold",
                "extract.spill_fold"} <= {s[1] for s in spans}
        extract = attrs_of(srv, "flush.extract")
        assert extract["fold_path"] == "split"
        assert extract["wide_rows"] == int((per_row > 16).sum())
        assert extract["rows_used"] == int((per_row > 0).sum())
        assert extract["hot_row_samples"] == int(per_row.max())
        assert extract["spill_steps"] >= -(-spilled // W._FOLD_CHUNK)
        sets = attrs_of(srv, "extract.sets")
        assert sets["sets"] == int((truth.set_distinct > 0).sum())
        assert 0 < sets["sparse_entries"] <= int(truth.set_distinct.sum())
        assert attrs_of(srv, "extract.sets.compact")["pending"] >= 0
        estimate = attrs_of(srv, "extract.sets.estimate")
        assert (estimate["sparse_rows"], estimate["dense_rows"]) == (
            sets["sets"], 0)
        seen.append(attrs_of(srv, "flush.begin"))
    # the second interval's live series are not the first's: those it
    # shares were re-stamped, the rest first seen; and the counters that
    # came with the cell moved by what the staging depth leaves each
    first, second = seen
    a, b = live_series(rings[0]), live_series(rings[1])
    assert len(b - a) > 0.03 * total and len(a - b) > 0.03 * total
    assert second["dir_first_seen"] - first["dir_first_seen"] == len(b - a)
    assert second["dir_restamped"] - first["dir_restamped"] == len(a & b)
    per_row = timer_counts(rings[1], 0, len(rings[1]))
    assert second["histo_staged"] - first["histo_staged"] == int(
        np.minimum(per_row, STAGE_DEPTH).sum())
    assert second["histo_spilled"] - first["histo_spilled"] == int(
        np.maximum(per_row - STAGE_DEPTH, 0).sum())
    nothing_shed(srv)


def test_spilled_and_staged_are_the_timer_samples_committed(served):
    """Over an interval with a swap in it: a whole interval, a flush, a
    third of the next, a flush. ``histo_spilled + histo_staged`` moved
    by the timer lines handed over, exactly, and each by what the
    staging depth leaves it (a row's depth starts anew at the swap)."""
    config, srv, collector = served
    ring = stream.build_ring(config, SEEDS[1])
    n = len(ring)
    lines = stream.format_lines(ring, config["lines"]["tag_from"])
    w = srv.workers[0]
    assert w.commit_counters()["histo_spilled"] == 0
    assert w.commit_counters()["histo_staged"] == 0
    staged = spilled = committed = 0
    for a, b in ((0, n), (n, n + n // 3)):
        hand_over(srv, (lines + lines)[a:b])
        per_row = timer_counts(ring, a, b)
        staged += int(np.minimum(per_row, STAGE_DEPTH).sum())
        spilled += int(np.maximum(per_row - STAGE_DEPTH, 0).sum())
        committed += int(per_row.sum())
        got = w.commit_counters()
        assert (got["histo_staged"], got["histo_spilled"]) == (staged, spilled)
        assert got["histo_staged"] + got["histo_spilled"] == committed
        flushed(srv, collector)
        begin = attrs_of(srv, "flush.begin")
        assert (begin["histo_staged"], begin["histo_spilled"]) == (
            staged, spilled)
    assert spilled > staged > 0
    nothing_shed(srv)


def bf16(x: np.ndarray) -> np.ndarray:
    u = np.asarray(x, np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.view(np.float32).astype(np.float64)


@pytest.mark.parametrize("fault,limit", [
    ("drop", "timer_count_mismatch"), ("bf16", "timer_max_mismatch")])
def test_the_comparison_has_teeth_on_the_rank_1_timer(served, fault, limit):
    """One sample of the rank-1 timer dropped before the reader, and its
    samples rounded to bfloat16 before the fold: the same comparison
    against the same truth reads over a limit, the one named."""
    config, srv, collector = served
    ring = stream.build_ring(config, SEEDS[0])
    truth = reference.Stream(ring).truth(0, len(ring))
    hot = rank1_timer(ring)
    at = np.nonzero((ring.cls == stream.TIMER) & (ring.sid == hot))[0]
    keep = np.ones(len(ring), bool)
    if fault == "drop":
        keep[at[len(at) // 2]] = False
    else:
        ring.val[at] = bf16(ring.val[at])
        assert (ring.val[at] * 4 == np.round(ring.val[at] * 4)).all()
    lines = stream.format_lines(ring, config["lines"]["tag_from"])
    hand_over(srv, [ln for ln, k in zip(lines, keep.tolist()) if k])
    view = flushed(srv, collector)
    failed = reference.verdict(
        reference.compare_flush(truth, view, config["server"]))
    assert [f.split()[0] for f in failed] == [limit], failed
    nothing_shed(srv)


def test_benchmark_json_agrees_with_the_configurations_file():
    """One entry for the configuration, one cell that runs it and the
    three metrics that came with it, all appended, all what the files
    say; the first two cells are where they were."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    own = stream.load_json("configs", CONFIG)
    assert own["name"] == CONFIG
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    (cell,) = [w for w in bench["workloads"] if w["config"] == CONFIG]
    # (third of each list: PR 46 appended `local-uniques` after them)
    assert entry is bench["configs"][2] and cell is bench["workloads"][2]
    assert entry["file"] == f"bench/configs/{CONFIG}.json"
    assert entry["reduced"] == own["reduced"] == []
    assert own["reduced_why"] == {}
    # the entry's source is the file's, down to the part that defines
    # the deployment
    assert entry["source"].startswith(own["source"])
    assert len(entry["source"]) <= 200 and 0 < len(entry["why"]) <= 200
    assert cell["name"] == CELL == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] == 1 and 0 < len(cell["why"]) <= 200
    assert stream.load_json("traffic", cell["traffic"])["arrival"] == "steady"
    assert [w["name"] for w in bench["workloads"][:2]] == [
        "local-timers.steady", "local-timers-1m.steady"]
    assert sum(own["series"].values()) == 100000
    assert own["lines"]["lines_per_interval"] == 4194304
    assert own["lines"]["zipf_s"] == 1.1
    assert own["lines"]["rank_pattern"] == "tctcgtcstctgctcstcgt"
    assert small_config()["lines"]["rank_pattern"] == \
        own["lines"]["rank_pattern"]
    # no metric lists the cell: each of the three new ones prints in
    # every cell, by a reader that was there
    assert not [m["name"] for m in bench["per_layer"] + bench["end_to_end"]
                if CELL in m.get("workloads", [])]
    at = [m["name"] for m in bench["per_layer"]].index("spill_share_pct")
    new = bench["per_layer"][at:at + 3]
    assert [m["name"] for m in new] == [
        "spill_share_pct", "spill_tick_ms.flush", "sets_ms.flush"]
    args = {
        "spill_share_pct": {"what": "counter_share", "on": "flush.begin",
                            "num": "histo_spilled", "rest": "histo_staged"},
        "spill_tick_ms.flush": {"what": "sum", "scale": 1000.0,
                                "names": ["extract.spill_fold"]},
        "sets_ms.flush": {"what": "sum", "scale": 1000.0,
                          "names": ["extract.sets"]}}
    for m in new:
        spec = stream.load_json("layer_metrics", m["name"])
        assert m == {k: spec[k] for k in ("name", "unit", "better", "source",
                                          "layer", "moves")}
        assert "workloads" not in m
        assert (spec["reader"], spec["arg"]) == ("spans", args[m["name"]])


def test_the_new_metrics_read_the_span_record(served):
    """The three data files through the readers that are there, on two
    flushes of the served path; and on a record without the counters,
    as a parent's is, the share reads nothing and does not raise."""
    from bench.readers import spans as reader

    config, srv, collector = served
    ring = stream.build_ring(config, SEEDS[2])
    n = len(ring)
    lines = stream.format_lines(ring, config["lines"]["tag_from"])
    flushes = []
    for a, b in ((0, n // 2), (n // 2, n)):
        hand_over(srv, lines[a:b])
        flushed(srv, collector)
        flushes.append({"phases": dict(srv.last_flush_phases)})
    got = {name: reader.read(
        {"flushes": flushes}, stream.load_json("layer_metrics", name)["arg"])
        for name in ("spill_share_pct", "spill_tick_ms.flush",
                     "sets_ms.flush")}
    per_row = timer_counts(ring, n // 2, n)
    spilled = int(np.maximum(per_row - STAGE_DEPTH, 0).sum())
    assert got["spill_share_pct"] == pytest.approx(
        100.0 * spilled / per_row.sum(), abs=1e-9)
    assert got["spill_tick_ms.flush"] > 0 and got["sets_ms.flush"] > 0
    for fl in flushes:
        for s in fl["phases"]["spans"]:
            s[6].pop("histo_spilled", None)
    assert reader.read({"flushes": flushes}, stream.load_json(
        "layer_metrics", "spill_share_pct")["arg"]) is None


def test_the_ring_is_byte_for_byte_the_pinned_one():
    seed, n_series, by_class, digest = RING
    ring = stream.build_ring(stream.load_json("configs", CONFIG), seed)
    assert np.bincount(ring.cls).tolist() == by_class
    assert sum(ring.series.values()) == n_series
    h = hashlib.sha256()
    for a in (ring.cls, ring.sid, ring.val):
        h.update(a.dtype.str.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == digest
