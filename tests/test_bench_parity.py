"""The served path against the benchmark's plain reference, at a small
size on the CPU, and the files of the configuration ``local-timers-1m``.

On the chip ``bench/run.py`` holds every flush of a cell to
``bench/reference.py`` (NumPy float64, nothing of the program) at the
full size, outside the timed window. Here the same comparison runs at
8,192 timer series: the lines of the configuration's own generator go
through native ingest, the staging plane, the micro-fold mirror, the
spill fold, the staged fold, the extract and a columnar sink, and the
flush is held to PERF.md section 2's limits unchanged. The server is
built as the harness builds it (``write_yaml`` -> ``load_config`` ->
``build_server``) and never started: no listener, no ticker, no thread;
the test hands over the bytes and calls the flush itself.

The rest pins the configuration's file: ``local-timers-1m`` is its
sibling ``local-timers`` at the source's own size and nothing else,
``BENCHMARK.json`` agrees with the file wherever it names it, and the
ring a seed builds is pinned byte for byte, so that a later edit of the
file's texts provably changes no line of the cell's traffic.
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

CONFIG, SIBLING, CELL = "local-timers-1m", "local-timers", \
    "local-timers-1m.steady"
# the keys in which the configuration may differ from its sibling
OWN_KEYS = {"name", "source", "deployment", "series", "reduced",
            "reduced_why", "assumed"}
# the configuration's shapes at a size the CPU folds in seconds: its own
# one hot timer in 256, the per-series sample counts untouched
SMALL_SERIES = {"timer": 8192, "counter": 512, "gauge": 512, "set": 64}
SMALL_LINES = {"hot_series": 32, "tag_from": 32}
SEEDS = (3900000011, 3900000012, 2147483659)

# sha256 over (dtype, bytes) of cls, sid, val, as
# bench/tests/test_benchmark_files.py takes it for the sibling
# config: (seed, series, lines by class, sha256)
RINGS = {
    CONFIG: (3900000001, 1180672, [131072, 131072, 3137536, 35677],
             "d14295fc6ca29e01cec8e0032a746e4254c11edf8dfb0258e511a7b74548b2cb"),
    SIBLING: (2800000001, 394240, [131072, 131072, 1564672, 35677],
              "9db68ac74c944ff6ddcf93fba9f1129f378e96c69678aa795903d696c55baa69"),
}


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
    assert written["tpu_initial_histo_rows"] == 16384
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
    """The lines in the sender's 64 KiB chunks, with a micro-fold half
    way (the spill fold of the hot rows runs from inside it) and the
    adoption sweep, each called here and not by a thread."""
    chunks, _ = stream.chunk_lines(lines, 65536)
    for i, chunk in enumerate(chunks):
        srv._native_router.ingest(chunk)
        if i == len(chunks) // 2:
            srv._micro_fold(0, srv.workers[0])
    srv.sync_native_series_once()


def flushed(srv, collector) -> reference.FlushView:
    before = len(collector.flushes)
    srv.flush()
    assert len(collector.flushes) == before + 1
    return run.view_of(collector.flushes[-1]["batch"])


def nothing_shed(srv) -> None:
    stats = srv.ingress_stats()
    assert stats["overload_dropped"] == 0 and stats["parse_errors"] == 0
    assert run.device_path_faults(srv) == []


@pytest.mark.parametrize("seed", SEEDS)
def test_a_flush_of_the_served_path_is_what_the_reference_says(served, seed):
    config, srv, collector = served
    ring = stream.build_ring(config, seed)
    hand_over(srv, stream.format_lines(ring, config["lines"]["tag_from"]))
    view = flushed(srv, collector)
    truth = reference.Stream(ring).truth(0, len(ring))
    numbers = reference.compare_flush(truth, view, config["server"])
    assert reference.verdict(numbers) == [], numbers
    assert view.foreign == []
    assert len(view.family(stream.TIMER, ".count")[0]) == 8192
    # and it took the served path: a micro-fold fed the mirror, the hot
    # rows (256 samples against a staging depth of 64) spilled, the
    # staged fold ran over the mirror and told wide rows from narrow
    spans = srv.last_flush_phases["spans"]
    ops = {s[6].get("op") for s in spans if s[1] == "dispatch"}
    assert {"spill", "micro", "staged", "extract"} <= ops
    assert {"micro_fold.feed", "extract.mirror_fold"} <= {s[1] for s in spans}
    (extract,) = [{k: v for k, v in s[6].items() if k != "cpu_s"}
                  for s in spans if s[1] == "flush.extract"]
    # (since PR 44 also the deepest row's samples and the epoch's ingest
    # steps: one from the micro-fold's drain, one deferred to the tick)
    assert extract == {"wide_rows": 32, "narrow_rows": 8160,
                       "fold_path": "split", "fold_rows": 8192,
                       "rows_used": 8192, "hot_row_samples": 256,
                       "spill_steps": 2}
    nothing_shed(srv)


def test_two_flushes_hold_the_two_halves_of_the_stream(served):
    config, srv, collector = served
    ring = stream.build_ring(config, SEEDS[0])
    lines = stream.format_lines(ring, config["lines"]["tag_from"])
    strm, half = reference.Stream(ring), len(ring) // 2
    for a, b in ((0, half), (half, len(ring))):
        hand_over(srv, lines[a:b])
        view = flushed(srv, collector)
        numbers = reference.compare_flush(strm.truth(a, b), view,
                                          config["server"])
        assert reference.verdict(numbers) == [], (a, b, numbers)
        # and the flush's own sums place its cut where the lines ended
        lo, hi = strm.cut_run(
            a, int(view.family(stream.TIMER, ".count")[1].sum()),
            int(view.scalar(stream.COUNTER)[1].sum()))
        assert lo <= b <= hi
    nothing_shed(srv)


def test_a_thin_flush_after_a_whole_one_holds_at_the_whole_ones_rows(served):
    """The flush after the sender's stop, which holds part of a cycle:
    fewer series than any flush before, folded and extracted at the row
    count the whole flush had (no program of its own), and held to the
    reference like any other."""
    from veneur_tpu.core import worker as W

    config, srv, collector = served
    ring = stream.build_ring(config, SEEDS[1])
    lines = stream.format_lines(ring, config["lines"]["tag_from"])
    strm, rows = reference.Stream(ring), []
    for a, b in ((0, len(ring)), (len(ring), len(ring) + len(ring) // 8)):
        programs = (W._histo_fold_staged._cache_size(),
                    W._histo_flush_extract._cache_size())
        hand_over(srv, (lines + lines)[a:b])
        view = flushed(srv, collector)
        numbers = reference.compare_flush(strm.truth(a, b), view,
                                          config["server"])
        assert reference.verdict(numbers) == [], (a, b, numbers)
        (extract,) = [s[6] for s in srv.last_flush_phases["spans"]
                      if s[1] == "flush.extract"]
        rows.append((extract["rows_used"], extract["fold_rows"]))
    (whole, at), (thin, thin_at) = rows
    assert (whole, at) == (8192, 8192) and thin_at == 8192
    assert 1024 < thin <= 2048
    assert programs == (W._histo_fold_staged._cache_size(),
                        W._histo_flush_extract._cache_size())
    nothing_shed(srv)


def test_the_control_one_precision_lower_fails_a_limit():
    """The comparison is tight enough: the reference itself, computed in
    bfloat16 samples and float32 sums, is over at least one limit."""
    config = small_config()
    ring = stream.build_ring(config, SEEDS[0])
    truth = reference.Stream(ring).truth(0, len(ring))
    low = reference.lower_precision_flush(truth, config["server"])
    failed = reference.verdict(
        reference.compare_flush(truth, low, config["server"]))
    assert failed, "the lower-precision control passed every limit"
    assert any(f.startswith(("timer_min_mismatch", "timer_max_mismatch",
                             "gauge_mismatch")) for f in failed), failed


def test_the_configuration_is_its_sibling_at_the_sources_size():
    own = stream.load_json("configs", CONFIG)
    sib = stream.load_json("configs", SIBLING)
    assert set(own) == set(sib)
    for key in set(own) - OWN_KEYS:
        assert own[key] == sib[key], key
    assert own["series"] == {**sib["series"], "timer": 1048576}
    assert own["reduced"] == [] and own["reduced_why"] == {}
    assert sib["reduced"] == ["series.timer"]
    assert set(own["assumed"]) == set(sib["assumed"])
    assert own["preset_histo_rows"] is True


def test_benchmark_json_agrees_with_the_configurations_file():
    """One entry for the configuration and one cell that runs it, both
    appended, and both what the file says."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    own = stream.load_json("configs", CONFIG)
    assert own["name"] == CONFIG and len(own["source"]) <= 200
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    (cell,) = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert entry["file"] == f"bench/configs/{CONFIG}.json"
    assert entry["reduced"] == own["reduced"] == []
    assert entry["source"] == own["source"]
    assert 0 < len(entry["why"]) <= 200
    assert cell["name"] == CELL == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] == 1 and 0 < len(cell["why"]) <= 200
    assert stream.load_json("traffic", cell["traffic"])["arrival"] == "steady"
    assert bench["configs"][0]["name"] == SIBLING
    assert bench["workloads"][0]["name"] == f"{SIBLING}.steady"
    # the one metric that lists its cells is the sibling's own
    listed = [m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])]
    assert listed == []
    # and the metric that came with the cell reads the spill fold's
    # warming spans, in every cell, with the reader that is there
    (warm,) = [m for m in bench["per_layer"]
               if m["name"] == "spill_warm_ms.flush"]
    spec = stream.load_json("layer_metrics", warm["name"])
    assert warm == {k: spec[k] for k in ("name", "unit", "better", "source",
                                         "layer", "moves")}
    assert warm["name"] == "spill_warm_ms.flush" and "workloads" not in warm
    assert (spec["reader"], spec["arg"]) == (
        "spans", {"what": "sum", "names": ["spill.warm"], "scale": 1000.0})


@pytest.mark.parametrize("name", sorted(RINGS))
def test_the_ring_is_byte_for_byte_the_pinned_one(name):
    seed, n_series, by_class, digest = RINGS[name]
    ring = stream.build_ring(stream.load_json("configs", name), seed)
    assert np.bincount(ring.cls).tolist() == by_class
    assert sum(ring.series.values()) == n_series
    h = hashlib.sha256()
    for a in (ring.cls, ring.sid, ring.val):
        h.update(a.dtype.str.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == digest
