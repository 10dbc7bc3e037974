"""The configuration ``local-uniques`` (a local agent beside a service
that counts unique users per endpoint: BASELINE config 2's Set(HLL) in
the HyperLogLog regime) through the served path against the benchmark's
plain reference, at a small size on the CPU, and the files of the
configuration.

The lines come from the configuration's own generator (``uniques``) at
128 endpoints and 262,144 requests an interval, a size that keeps the
traffic's shape: fixed Zipf 1.1 counts, a request's timer, counter and
set line adjacent, the rank-1 set above 3 m distinct user ids (the
estimator's harmonic-mean branch answers it and the reference's three
sigma judge it), a dozen and more sets promoted to dense device rows,
the tail sparse. Two intervals in a row (the ring repeats, as in the
cell), held to PERF.md section 2's limits unchanged. The server is built as the
harness builds it (``write_yaml`` -> ``load_config`` -> ``build_server``)
and never started.

The rest: the ring a seed builds at the full size is pinned (3,211,264
lines; 1,048,576 / 65,536 / 1,048,576 / 1,048,576 by class; the same
counts n_r under a second seed, on other endpoints and with other users;
44-48 sets past 2,190 distinct members; every set of a whole interval,
hashed as the program hashes, inside ``hll_tolerance``),
``BENCHMARK.json`` agrees with the configuration's file and
every entry added has its file, and the four metrics that came with the
cell read the span record, and nothing on a record without them.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference, run, stream  # noqa: E402
from bench.generators import uniques  # noqa: E402
from tests.test_bench_parity import flushed, nothing_shed  # noqa: E402

CONFIG, CELL, SIBLING = "local-uniques", "local-uniques.steady", "local-mixed"
SMALL_SERIES = {"timer": 128, "counter": 128, "gauge": 128, "set": 128}
SMALL_LINES = {"requests_per_interval": 262144}
SEEDS = (4600000011, 2147483659)
MICRO_FOLD_EVERY = 8  # chunks of 64 KiB between two micro-folds
M = 1 << 14
NEW_METRICS = ("sets_dense_share_pct", "sets_device_s.interval",
               "sets_promote_ms.flush", "sets_insert_hbm_share_pct")


def small_config() -> dict:
    config = stream.load_json("configs", CONFIG)
    config["series"] = dict(SMALL_SERIES)
    config["lines"] = {**config["lines"], **SMALL_LINES}
    # the server samples its own flush span's name into a set
    # (ssf.names_unique) one time in a hundred: a set line more, in the
    # next interval, than the counters below are held to
    config["server"] = {**config["server"], "ssf_span_uniqueness_rate": 0.0}
    return config


@pytest.fixture
def served(tmp_path):
    from veneur_tpu.core.config import load_config
    from veneur_tpu.core.factory import build_server

    config = small_config()
    path = str(tmp_path / "cell.yaml")
    written = run.write_yaml(path, config, chips=1)
    assert written["tpu_initial_histo_rows"] == 256
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
    MICRO_FOLD_EVERY chunks (its drain hands the set store a batch of
    some twenty thousand triples) and the adoption sweep, each called
    here and not by a thread."""
    chunks, _ = stream.chunk_lines(lines, 65536)
    for i, chunk in enumerate(chunks, 1):
        srv._native_router.ingest(chunk)
        if i % MICRO_FOLD_EVERY == 0:
            srv._micro_fold(0, srv.workers[0])
    srv.sync_native_series_once()


def attrs_of(srv, name: str) -> list:
    return [s[6] for s in srv.last_flush_phases["spans"] if s[1] == name]


def distinct_members(ring) -> np.ndarray:
    m = ring.cls == stream.SET
    pairs = np.unique(ring.sid[m].astype(np.int64) << 32
                      | ring.val[m].astype(np.int64))
    return np.bincount(pairs >> 32, minlength=ring.series["set"])


@pytest.mark.parametrize("seed", SEEDS)
def test_two_intervals_of_unique_users_are_what_the_reference_says(served,
                                                                   seed):
    from veneur_tpu.ops import staged_sets as st

    config, srv, collector = served
    own = stream.load_json("configs", CONFIG)
    ring = stream.build_ring(config, seed)
    n = len(ring)
    assert n == 3 * 262144 + 128 * 16
    lines = stream.format_lines(ring, config["lines"]["tag_from"])
    truth = reference.Stream(ring).truth(0, n)
    exact = truth.set_distinct
    # the shape of the cell: one set past 3 m, where the harmonic mean
    # answers and three sigma judge, a dozen and more past the promotion
    # threshold, the tail sparse
    assert exact.max() > 3 * M
    assert 12 <= int((exact >= 2190).sum()) <= 32
    assert int((exact < 2048).sum()) >= 96
    set_lines = int((ring.cls == stream.SET).sum())
    seen = []
    for _ in range(2):
        hand_over(srv, lines)
        view = flushed(srv, collector)
        numbers = reference.compare_flush(truth, view, own["server"])
        assert reference.verdict(numbers) == [], numbers
        assert 0 < numbers["set_err_over_tolerance"] < 1.0
        assert view.foreign == []
        (est,) = attrs_of(srv, "extract.sets.estimate")
        assert 8 <= est["dense_rows"] <= int((exact >= 2048).sum())
        assert est["dense_rows"] + est["sparse_rows"] == 128
        sets = [a for a in attrs_of(srv, "dispatch") if a.get("op") == "sets"]
        ins = [a for a in sets if a["kernel"] == "insert"]
        assert ins and {a["padded"] for a in ins} <= set(st.INSERT_LENGTHS)
        assert {a["pool_rows"] for a in ins} == {st.POOL_MIN_ROWS}
        assert sum(a["kernel"] == "estimate" for a in sets) == 1
        promoted = attrs_of(srv, "sets.promote")
        assert sum(p["rows"] for p in promoted) == est["dense_rows"]
        (begin,) = attrs_of(srv, "flush.begin")
        seen.append((begin["sets_dense"], begin["sets_sparse"]))
    # lifetime counters: an interval's set lines went one way or the other
    (d0, s0), (d1, s1) = seen
    assert d0 + s0 == set_lines and d1 + s1 == 2 * set_lines
    assert (d1 - d0) > 0.3 * set_lines
    nothing_shed(srv)


def test_the_ring_is_the_pinned_one():
    config = stream.load_json("configs", CONFIG)
    n_r = uniques.rank_counts(1048576, 4096, 1.1)
    assert (int(n_r[0]), int(n_r[1]), int(n_r[45]), int(n_r[-1])) == (
        170295, 78497, 2494, 17)
    assert int(n_r.sum()) == 1048576
    assert round(float(n_r[:46].sum()) / 1048576, 3) == 0.607
    holders, members = [], []
    for seed in (4600000001, 4600000002):
        ring = stream.build_ring(config, seed)
        assert len(ring) == 3211264
        # counter, gauge, timer, set
        assert np.bincount(ring.cls).tolist() == [1048576, 65536, 1048576,
                                                  1048576]
        # a request's three lines are adjacent and of one endpoint
        at = np.nonzero(ring.cls == stream.TIMER)[0]
        assert (ring.cls[at + 1] == stream.COUNTER).all()
        assert (ring.cls[at + 2] == stream.SET).all()
        assert (ring.sid[at] == ring.sid[at + 1]).all()
        assert (ring.sid[at] == ring.sid[at + 2]).all()
        assert (ring.val[at + 1] == 1.0).all()
        counts = np.bincount(ring.sid[at], minlength=4096)
        assert (np.sort(counts)[::-1] == n_r).all()
        assert (np.bincount(ring.sid[ring.cls == stream.GAUGE],
                            minlength=4096) == 16).all()
        # an endpoint's ids come from a universe of four a request: 88.5%
        # of its requests are distinct ids
        exact = distinct_members(ring)
        assert 44 <= int((exact >= 2190).sum()) <= 48
        assert abs(exact.sum() / 1048576 - 0.885) < 0.002
        top = np.sort(exact)[::-1]
        # ranks 1 and 2 past 3 m, where three sigma of the harmonic mean
        # judge alone; rank 3 between 2.5 m and 3 m; the rest under 2.5 m
        assert 150000 < top[0] < 151400 and 3 * M < top[1] < 70000
        assert 2.5 * M < top[2] < 3 * M and top[3] < 2.5 * M
        holders.append(np.argsort(counts, kind="stable"))
        members.append(ring.val[at + 2])
    # the counts are every seed's; which endpoint holds which rank and
    # whom its requests carry are the seed's
    assert (holders[0] != holders[1]).mean() > 0.9
    assert (members[0] != members[1]).mean() > 0.9


@pytest.mark.parametrize("seed", (4600000001, 4600000002))
def test_a_whole_interval_of_the_ring_is_inside_the_tolerance(seed):
    """Every set of a whole interval, hashed as the program hashes its
    members, through the estimator's host twin: three sets answered by
    the harmonic mean, 44-48 past the promotion threshold, some 364,000
    registers on the rows that stay sparse."""
    from veneur_tpu.ops import hll
    from veneur_tpu.ops import host_engine as he
    from veneur_tpu.utils.hashing import hll_hash_batch

    ring = stream.build_ring(stream.load_json("configs", CONFIG), seed)
    at = ring.cls == stream.SET
    pairs = np.unique(ring.sid[at].astype(np.int64) << 32
                      | ring.val[at].astype(np.int64))
    sid, member = pairs >> 32, pairs & 0xFFFFFFFF
    hashes = hll_hash_batch([b"u%d-%d" % p for p in zip(sid.tolist(),
                                                        member.tolist())])
    idx, rank = hll.split_hashes(hashes, 14)
    regs = np.zeros((4096, M), np.int8)
    np.maximum.at(regs, (sid, idx), rank)
    est = he.np_hll_estimate_exact(regs, 14).astype(np.float64)
    exact = np.bincount(sid, minlength=4096)
    ratio = np.abs(est - exact) / reference.hll_tolerance(exact, 14)
    assert float(ratio.max()) < 1.0
    assert int((est > 2.5 * M).sum()) == 3 and ratio[exact > 3 * M].max() > 0
    held = (regs > 0).sum(1)
    assert 44 <= int((held >= 2048).sum()) <= 48
    assert 350000 < int(held[held < 2048].sum()) < 380000


def test_benchmark_json_agrees_with_the_configurations_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    own = stream.load_json("configs", CONFIG)
    sibling = stream.load_json("configs", SIBLING)
    assert own["name"] == CONFIG
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    (cell,) = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert entry is bench["configs"][3] and cell is bench["workloads"][3]
    assert entry["file"] == f"bench/configs/{CONFIG}.json"
    assert os.path.exists(os.path.join(ROOT, entry["file"]))
    assert entry["reduced"] == own["reduced"] == []
    assert entry["source"] == own["source"]
    assert len(entry["source"]) <= 200 and 0 < len(entry["why"]) <= 200
    assert cell["name"] == CELL == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] == 1 and 0 < len(cell["why"]) <= 200
    assert "46" in cell["why"] and "60.7%" in cell["why"]
    assert stream.load_json("traffic", cell["traffic"])["arrival"] == "steady"
    # the server and the guarantees are the sibling's, letter for letter
    assert own["server"] == sibling["server"]
    assert own["guarantees"] == sibling["guarantees"]
    assert own["interval_s"] == sibling["interval_s"] == 10
    assert own["series"] == dict.fromkeys(
        ("timer", "counter", "gauge", "set"), 4096)
    assert own["lines"] == {
        "generator": "uniques", "requests_per_interval": 1048576,
        "zipf_s": 1.1, "universe_per_request": 4, "gauge_writes": 16,
        "tag_from": 0}
    assert os.path.exists(os.path.join(
        ROOT, "bench", "generators", own["lines"]["generator"] + ".py"))
    # the four metrics that came with the cell, appended; the kernel's
    # roofline share lists the cell, the three others print anywhere
    new = bench["per_layer"][-4:]
    assert tuple(m["name"] for m in new) == NEW_METRICS
    for m in new:
        spec = stream.load_json("layer_metrics", m["name"])
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            k: spec[k] for k in ("name", "unit", "better", "source",
                                 "layer", "moves")}
        assert m["moves"] == "flush_s.mean"
        assert os.path.exists(os.path.join(
            ROOT, "bench", "readers", spec["reader"] + ".py"))
        assert m.get("workloads") == (
            [CELL] if m["name"] == "sets_insert_hbm_share_pct" else None)
    assert [m["better"] for m in new] == ["lower", "lower", "lower", "higher"]


def test_the_new_metrics_read_the_span_record(served):
    """The span metrics' data files through their readers on two flushes
    of the served path, the kernel's bytes from the dispatches' attrs;
    on a record without them, as a parent's is, each reads nothing or
    0 and does not raise."""
    from bench.readers import hll as hll_reader
    from bench.readers import spans as reader

    config, srv, collector = served
    ring = stream.build_ring(config, SEEDS[0])
    lines = stream.format_lines(ring, config["lines"]["tag_from"])
    flushes = []
    for _ in range(2):
        hand_over(srv, lines)
        flushed(srv, collector)
        flushes.append({"phases": dict(srv.last_flush_phases)})
    run_ = {"flushes": flushes}

    def read(name):
        return reader.read(run_, stream.load_json("layer_metrics",
                                                  name)["arg"])

    share = read("sets_dense_share_pct")
    assert 30.0 < share < 100.0
    assert read("sets_promote_ms.flush") > 0
    # every insert dispatch of the two epochs, by its unpadded entries
    spec = stream.load_json("layer_metrics", "sets_insert_hbm_share_pct")
    assert spec["reader"] == "hll"
    arg = spec["arg"]
    entries = hll_reader.entries_in(run_, arg["op"], arg["kernel"], 0.0,
                                    float("inf"))
    ins = [s[6] for fl in flushes for s in fl["phases"]["spans"]
           if s[1] == "dispatch" and s[6].get("kernel") == "insert"]
    assert entries == sum(a["entries"] for a in ins) > 0
    assert hll_reader.insert_bytes(entries) == 11 * entries
    assert entries < sum(a["padded"] for a in ins)
    # no trace: the device's side of the share is not there
    assert hll_reader.read({**run_, "trace": None}, arg) is None
    # a parent's record: no counters, no attrs, no promote span
    for fl in flushes:
        fl["phases"]["spans"] = [
            [*s[:6], {k: v for k, v in s[6].items()
                      if k not in ("sets_dense", "sets_sparse", "kernel",
                                   "entries", "padded", "pool_rows")}]
            for s in fl["phases"]["spans"] if s[1] != "sets.promote"]
    assert read("sets_dense_share_pct") is None
    assert read("sets_promote_ms.flush") == 0.0
    assert hll_reader.entries_in(run_, arg["op"], arg["kernel"], 0.0,
                                 float("inf")) == 0
