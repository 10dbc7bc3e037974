"""Shared-nothing multi-reader ingest (core/worker.attach_reader_shards
+ ops/reader_stack.py): reader-sharded == legacy, per series, exactly.

The reader-shard contract is that giving every reader thread its own
C++ context — private directory, staging plane, SoA spill epoch, no
shared mutex on the line path — is INVISIBLE in the flush output. The
ground truth is the legacy single-context path processing the same
per-reader streams serialized in context order ([home] + readers):
the flush-edge merge concatenates per-context planes in that same
order, so every series' staged samples reach the device fold in the
identical sequence and the folded values compare EXACTLY (==, not
approx). Canonical row INDICES may permute between the two modes —
series are discovered in different orders — so parity is keyed
per-series value equality over the generated InterMetric stream, never
raw snapshot-array bytes.

Pinned here across the golden matrix — all metric classes (t-digest
timers, HLL sets, counters, gauges), micro_fold on/off (micro is
FULLY inactive in shard mode; the flag must not perturb output),
series_shards 2, tenant budgets — plus:

- conservation: committed == folded + shed, with per-context committed
  attribution (worker.reader_committed) summing to the processed total;
- the torn-epoch fence: reader threads committing concurrently with
  swaps lose no samples and double-fold none;
- the event/error funnel fix: events, service checks and parse errors
  stay on the COMMITTING reader's context instead of funnelling to
  shard 0;
- config resolution (reader_shards key, VENEUR_READER_SHARDS=0 legacy
  hatch, auto mode, single-worker gating).

CI runs the server/ingest/microfold suites twice — num_readers=4
reader-sharded and VENEUR_READER_SHARDS=0 legacy (tools/ci.sh) — the
same dual-lane shape as the micro-fold and series-shard hatches.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from veneur_tpu.core.config import Config, load_config, resolve_reader_shards
from veneur_tpu.core.flusher import device_quantiles, generate_inter_metrics
from veneur_tpu.core.metrics import HistogramAggregates, MetricType
from veneur_tpu.core.tenancy import TenantLedger
from veneur_tpu.core.worker import DeviceWorker

AGGS = HistogramAggregates.from_names(["min", "max", "count"])
PCTS = [0.5, 0.9, 0.99]
QS = device_quantiles(PCTS, AGGS)

R = 3  # reader shards under test


def _mk_worker(sharded: bool, *, micro: bool = False,
               series_shards: int = 0, budget: int = 0,
               stage_depth: int = 32) -> DeviceWorker:
    w = DeviceWorker(compression=100, stage_depth=stage_depth,
                     batch_size=8, initial_histo_rows=8,
                     initial_set_rows=8, is_local=True, micro_fold=micro,
                     micro_fold_rows=1, micro_fold_max_age_s=1e9,
                     series_shards=series_shards)
    if budget:
        w.tenancy = TenantLedger(default_budget=budget, budgets={})
    if not w.attach_native():
        pytest.skip("native ingest library unavailable")
    if sharded:
        assert w.attach_reader_shards(R)
    return w


def _interval_streams(rng, interval: int) -> list[list[bytes]]:
    """R per-reader datagram streams for one interval: overlapping
    timer/counter/set series (the reconciliation maps must fold the
    same series arriving via several readers onto one canonical row)
    and per-reader gauge series (gauge LWW between contexts is settled
    by drain order, which mid-epoch threshold drains are allowed to
    advance — cross-reader gauge races are not part of the parity
    ground truth)."""
    streams = []
    for r in range(R):
        lines = []
        for b in range(6):
            for i in range(8):
                k = (interval + b * 8 + i) % 13
                lines.append(f"h{k}:{rng.normal():.6f}|ms|#a:{k % 3}")
                lines.append(f"c{k}:{1 + k % 4}|c")
                lines.append(f"s{k}:v{rng.integers(0, 200)}|s")
                lines.append(f"g.r{r}.{k}:{rng.normal():.6f}|g")
        streams.append([ln.encode() for ln in lines])
    return streams


def _drive(sharded: bool, *, micro: bool = False, series_shards: int = 0,
           budget: int = 0, intervals: int = 3, stage_depth: int = 32,
           drain_every: int = 0):
    """Ingest identical per-reader streams either through R owned
    contexts (sharded) or serialized in context order through the one
    legacy context; flush per interval. `drain_every` > 0 inserts
    mid-epoch drains + series syncs every that-many datagrams, so
    reconciliation runs incrementally instead of all at the swap
    fence."""
    w = _mk_worker(sharded, micro=micro, series_shards=series_shards,
                   budget=budget, stage_depth=stage_depth)
    rng = np.random.default_rng(23)
    snaps = []
    for interval in range(intervals):
        streams = _interval_streams(rng, interval)
        n = 0
        if sharded:
            # interleave across readers (per-reader order preserved —
            # the only ordering a shared-nothing reader guarantees)
            for dgs in zip(*streams):
                for r, dg in enumerate(dgs):
                    w._reader_ctxs[r].ingest_owned(dg)
                    n += 1
                    if drain_every and n % drain_every == 0:
                        w.drain_native()
                        w.sync_native_series()
        else:
            for stream in streams:
                for dg in stream:
                    w.ingest_datagram(dg)
                    n += 1
                    if drain_every and n % drain_every == 0:
                        w.drain_native()
                        w.sync_native_series()
        snaps.append(w.flush(QS))
    return w, snaps


def _keyed(snap) -> dict:
    return {(m.name, m.type, tuple(m.tags)): m.value
            for m in generate_inter_metrics(snap, True, PCTS, AGGS,
                                            now=1000)
            if m.type != MetricType.STATUS}


def _assert_series_identical(a, b, path: str) -> None:
    da, db = _keyed(a), _keyed(b)
    missing = set(da) ^ set(db)
    assert not missing, (path, missing)
    diff = {k: (da[k], db[k]) for k in da if da[k] != db[k]}
    assert not diff, (path, diff)


# -- reconciliation by the batch ---------------------------------------------


def test_one_series_through_two_contexts_shares_one_canonical_row():
    """Each context names a series by a lifetime id of its own; the
    batch reconcile still folds the same series arriving through two
    readers, or through the Python path, onto one canonical row — in
    the interval the strings arrive and in the ones after, when a known
    series is three integers."""
    from veneur_tpu.protocol.dogstatsd import parse_metric

    w = _mk_worker(True)
    a, b = w._reader_ctxs[0], w._reader_ctxs[1]
    for interval in range(3):
        w.process_metric(parse_metric(b"py.t:1|ms|#k:v"))  # Python path
        a.ingest_owned(b"shared.t:1|ms\nonly.a:1|ms\nshared.c:1|c"
                       b"\npy.t:2|ms|#k:v")
        w.sync_native_series()  # mid-epoch: b's batch meets a's rows
        b.ingest_owned(b"only.b:1|ms\nshared.t:2|ms\nshared.c:2|c"
                       b"\nshared.s:x|s|#veneurlocalonly")
        a.ingest_owned(b"shared.s:y|s|#veneurlocalonly")
        w.sync_native_series()
        histo = w.directory.histo
        names = [m.key.name for m in histo.rows]
        assert sorted(names) == ["only.a", "only.b", "py.t", "shared.t"]
        maps_a, maps_b = w._ctx_maps[1], w._ctx_maps[2]
        # local rows follow each context's own first-seen order
        assert [names[r] for r in maps_a[0]] == ["shared.t", "only.a",
                                                 "py.t"]
        assert [names[r] for r in maps_b[0]] == ["only.b", "shared.t"]
        assert len(w.scalars.counters.meta) == 1
        assert list(maps_a[2]) == list(maps_b[2]) == [0]
        assert len(w.directory.sets.rows) == 1
        assert list(maps_a[1]) == list(maps_b[1]) == [0]
        assert histo.index == {(m.key, m.scope_class): r
                               for r, m in enumerate(histo.rows)}
        if interval:  # nothing learnt twice
            assert [len(k) for k in w._adopt_cache] == learnt
        learnt = [len(k) for k in w._adopt_cache]
        assert learnt == [0, 5, 4, 0]
        snap = w.flush(QS)
        got = _keyed(snap)
        assert got[("shared.t.count", MetricType.COUNTER, ())] == 2.0
        assert got[("py.t.count", MetricType.COUNTER, ("k:v",))] == 2.0
        assert got[("shared.c", MetricType.COUNTER, ())] == 3.0
        assert round(got[("shared.s", MetricType.GAUGE, ())], 2) == 2.0


# -- the golden matrix ------------------------------------------------------


@pytest.mark.parametrize("micro", [False, True], ids=["batch", "micro"])
@pytest.mark.parametrize("drain_every", [0, 17],
                         ids=["swap-drain", "mid-epoch-drains"])
def test_sharded_matches_legacy_per_series(micro, drain_every):
    _, base = _drive(False, micro=micro, drain_every=drain_every)
    w, got = _drive(True, micro=micro, drain_every=drain_every)
    assert len(w._reader_ctxs) == R
    # micro-fold must be fully inactive in shard mode
    assert w.micro_folds_total == 0
    for n, (a, b) in enumerate(zip(base, got)):
        _assert_series_identical(a, b, f"micro={micro} interval={n}")


def test_sharded_matches_legacy_with_series_shards():
    import jax

    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    _, base = _drive(False, series_shards=2)
    w, got = _drive(True, series_shards=2)
    assert w._shard is not None, "series sharding did not engage"
    for n, (a, b) in enumerate(zip(base, got)):
        _assert_series_identical(a, b, f"series-sharded interval={n}")


def test_sharded_matches_legacy_with_tenant_budgets():
    """Budget admission must bite identically: the adopt cache decides
    once per series lifetime, whichever context registered it first."""
    _, base = _drive(False, budget=7)
    _, got = _drive(True, budget=7)
    for n, (a, b) in enumerate(zip(base, got)):
        _assert_series_identical(a, b, f"budget interval={n}")


def test_sharded_matches_legacy_under_depth_pressure():
    """stage_depth 4 forces both per-context C++ spill (a reader's own
    backlog over 4) and merge-edge reconcile spill (stacked total over
    4 across readers) every interval; parity must survive both."""
    _, base = _drive(False, stage_depth=4)
    _, got = _drive(True, stage_depth=4)
    for n, (a, b) in enumerate(zip(base, got)):
        _assert_series_identical(a, b, f"depth4 interval={n}")


# -- conservation -----------------------------------------------------------


def test_conservation_committed_equals_folded_plus_shed():
    """committed (per-context fence attribution) == folded (histogram
    counts + counter totals in the snapshots) + shed (overload drops):
    exact, across intervals, with zero shed at test scale."""
    w, snaps = _drive(True, intervals=3)
    sent_h = sent_c = 0.0
    rng = np.random.default_rng(23)
    for interval in range(3):
        for stream in _interval_streams(rng, interval):
            for dg in stream:
                for ln in dg.split(b"\n"):
                    if b"|ms" in ln:
                        sent_h += 1
                    elif b"|c" in ln:
                        sent_c += float(ln.split(b":")[1].split(b"|")[0])
    got_h = got_c = 0.0
    for snap in snaps:
        for (name, mtype, _tags), v in _keyed(snap).items():
            if mtype == MetricType.COUNTER and name.endswith(".count"):
                got_h += v
            elif mtype == MetricType.COUNTER and name.startswith("c"):
                got_c += v
    assert got_h == sent_h
    assert got_c == sent_c
    assert w.overload_dropped_total == 0
    # per-context attribution: every committed line is attributed to
    # exactly one context, and the books add up to the lifetime total
    assert sum(w.reader_committed) == w.processed_total
    assert w.reader_committed[0] == 0  # nothing ingested via home
    assert all(c > 0 for c in w.reader_committed[1:])


def test_torn_epoch_threaded_conservation():
    """Reader threads hammer their own contexts while the main thread
    swaps mid-stream: the flush-edge fence must neither lose a committed
    sample to a context reset nor fold one twice."""
    w = _mk_worker(True, stage_depth=256)
    stop = threading.Event()
    sent = [0] * R

    def reader(r: int) -> None:
        ctx = w._reader_ctxs[r]
        i = 0
        while not stop.is_set():
            ctx.ingest_owned(b"torn.t:%d|ms\ntorn.c:1|c" % (i % 50))
            sent[r] += 1
            i += 1

    threads = [threading.Thread(target=reader, args=(r,), daemon=True)
               for r in range(R)]
    for t in threads:
        t.start()
    snaps = []
    try:
        for _ in range(5):
            snaps.append(w.flush(QS))
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5.0)
    snaps.append(w.flush(QS))  # residue after the threads stopped
    got_h = got_c = 0.0
    for snap in snaps:
        by = _keyed(snap)
        got_h += by.get(("torn.t.count", MetricType.COUNTER, ()), 0.0)
        got_c += by.get(("torn.c", MetricType.COUNTER, ()), 0.0)
    total = float(sum(sent))
    shed = float(w.overload_dropped_total)
    # One timer + one counter line per send, and overload_dropped counts
    # sheds from EVERY class: on a fast rig only the histogram cap
    # engages (got_c == total), but on a slow or loaded rig the
    # GIL-free reader threads outrun the five flushes far enough that
    # the counter cap sheds too. The two-class identity is exact in
    # both regimes — a torn epoch (lost or double-folded sample)
    # breaks it either way.
    assert got_h + got_c + shed == 2 * total, (got_h, got_c, shed, total)
    assert got_h <= total and got_c <= total, (got_h, got_c, total)
    assert sum(w.reader_committed) == w.processed_total
    np.testing.assert_array_equal(
        np.asarray(w.reader_committed[1:]) >= 0, True)


# -- funnel fix -------------------------------------------------------------


def test_events_and_errors_stay_on_committing_context():
    w = _mk_worker(True)
    w._reader_ctxs[1].ingest_owned(
        b"_e{5,2}:hello|hi\nbad line\nok:1|c")
    assert w._reader_ctxs[1].drain_other() == [b"_e{5,2}:hello|hi"]
    assert int(w._reader_ctxs[1].errors) == 1
    for r in (0, 2):
        assert w._reader_ctxs[r].drain_other() == []
        assert int(w._reader_ctxs[r].errors) == 0
    assert int(w._native.errors) == 0
    assert w.parse_errors == 0  # not yet drained into the worker tally
    w.drain_native()
    assert w.parse_errors == 1


# -- lock stats -------------------------------------------------------------


@pytest.mark.parametrize("how", ["whole", "splits", "by_line"])
def test_chunk_commit_over_two_contexts_matches_line_by_line(how):
    """digest % 2 routing: a buffer's lines are bucketed by target and
    each context is locked once; what each context holds afterwards
    (counts, series queue, staged plane, SoA batches, events and errors
    on the home context) is what one line a call leaves, over three
    intervals with resets. Each context's lock record has one entry a
    lock hold: one a line it took, fed a line a call."""
    from tests.test_native import assert_same_record, run_intervals
    want, _ = run_intervals(2, "by_line", 41)
    got, counters = run_intervals(2, how, 41)
    for g, w in zip(got, want):
        for g_ctx, w_ctx in zip(g, w):
            assert_same_record(g_ctx, w_ctx)
    assert all(rec["processed"] > 100 for rec in want[0])  # both took lines
    assert want[0][0]["other"] and not want[0][1]["other"]  # home is 0
    assert want[0][0]["errors"] > 0 and want[0][1]["errors"] == 0
    assert all(c["lock_acquisitions"] == c["commit_batches"]
               for c in counters)
    if how == "whole":
        assert [c["commit_batches"] for c in counters] == [3, 3]
    elif how == "by_line":
        assert [c["lock_acquisitions"] for c in counters] \
            == [c["commit_lines"] for c in counters]


def test_owned_context_lock_uncontended():
    """The shared-nothing proof at unit scale: a single owner committing
    into its private context records zero contended acquisitions."""
    w = _mk_worker(True)
    for ctx in w._reader_ctxs:
        ctx.reset_lock_stats()
    for i in range(200):
        for ctx in w._reader_ctxs:
            ctx.ingest_owned(b"lk.h:1.5|ms\nlk.c:1|c")
    for ctx in w._reader_ctxs:
        st = ctx.lock_stats()
        assert st["acquisitions"] == 200
        assert st["contended"] == 0, st
    rs = w.reader_stats()
    assert rs["shards"] == R
    assert len(rs["lock"]) == R + 1
    assert [lk["acquisitions"] for lk in rs["lock"][1:]] == [200] * R


# -- config resolution ------------------------------------------------------


def _cfg(**kw) -> Config:
    base = dict(tpu_native_ingest=True, tpu_native_readers=True,
                num_workers=1, num_readers=4)
    base.update(kw)
    return Config(**base)


def test_resolve_reader_shards_auto_and_explicit(monkeypatch):
    monkeypatch.delenv("VENEUR_READER_SHARDS", raising=False)
    assert resolve_reader_shards(_cfg()) == 4          # auto = num_readers
    assert resolve_reader_shards(_cfg(num_readers=1)) == 0
    assert resolve_reader_shards(_cfg(reader_shards=2)) == 2
    assert resolve_reader_shards(_cfg(reader_shards=0)) == 0


def test_resolve_reader_shards_gates(monkeypatch):
    monkeypatch.delenv("VENEUR_READER_SHARDS", raising=False)
    assert resolve_reader_shards(_cfg(num_workers=4)) == 0
    assert resolve_reader_shards(_cfg(tpu_native_readers=False)) == 0
    assert resolve_reader_shards(_cfg(tpu_native_ingest=False)) == 0
    assert resolve_reader_shards(_cfg(tpu_mesh_devices=2)) == 0


def test_resolve_reader_shards_env_hatch(monkeypatch):
    monkeypatch.setenv("VENEUR_READER_SHARDS", "0")
    assert resolve_reader_shards(_cfg(reader_shards=4)) == 0
    monkeypatch.setenv("VENEUR_READER_SHARDS", "3")
    assert resolve_reader_shards(_cfg()) == 3
    monkeypatch.setenv("VENEUR_READER_SHARDS", "junk")
    assert resolve_reader_shards(_cfg(reader_shards=2)) == 2


def test_reader_shards_config_validation(monkeypatch):
    # the VENEUR_* overlay in load_config would mask the invalid values
    # when the CI reader-shard lane exports VENEUR_READER_SHARDS
    monkeypatch.delenv("VENEUR_READER_SHARDS", raising=False)
    load_config(data={"reader_shards": 4})
    with pytest.raises(ValueError, match="reader_shards"):
        load_config(data={"reader_shards": -2})
    with pytest.raises(ValueError, match="reader_shards"):
        load_config(data={"reader_shards": 1000})
