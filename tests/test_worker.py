"""DeviceWorker + flusher tests.

Mirrors the reference's worker_test.go (ingest/import/scope) and the
deterministic end-to-end value assertions of server_test.go:110-127 /
TestLocalServerMixedMetrics (:299).
"""

import numpy as np
import pytest

import veneur_tpu.core.worker as W
from veneur_tpu.core.directory import ScopeClass
from veneur_tpu.core.flusher import (
    device_quantiles,
    forwardable_rows,
    generate_inter_metrics,
)
from veneur_tpu.core.metrics import (
    HistogramAggregates,
    MetricType,
)
from veneur_tpu.core.worker import DeviceWorker
from veneur_tpu.protocol.dogstatsd import parse_metric

AGGS = HistogramAggregates.from_names(["min", "max", "count"])
PCTS = [0.5, 0.9, 0.99]


def _flush(worker, is_local=True, percentiles=PCTS, aggregates=AGGS):
    qs = device_quantiles(percentiles, aggregates)
    snap = worker.flush(qs, interval_s=10.0)
    metrics = generate_inter_metrics(snap, is_local, percentiles, aggregates,
                                     now=1000)
    return snap, {(m.name, m.type): m for m in metrics}, metrics


def test_counter_with_sample_rate():
    w = DeviceWorker()
    for _ in range(3):
        w.process_metric(parse_metric(b"a.b.c:1|c"))
    w.process_metric(parse_metric(b"a.b.c:1|c|@0.5"))
    _, by_key, _ = _flush(w)
    m = by_key[("a.b.c", MetricType.COUNTER)]
    assert m.value == 5.0  # 3*1 + 1*2


def test_gauge_last_write_wins():
    w = DeviceWorker()
    w.process_metric(parse_metric(b"g:1|g"))
    w.process_metric(parse_metric(b"g:42|g"))
    _, by_key, _ = _flush(w)
    assert by_key[("g", MetricType.GAUGE)].value == 42.0


def test_mixed_histo_local_instance_aggregates_only():
    w = DeviceWorker()
    for v in [1, 2, 3, 4, 5]:
        w.process_metric(parse_metric(f"t:{v}|ms".encode()))
    _, by_key, metrics = _flush(w, is_local=True)
    assert by_key[("t.min", MetricType.GAUGE)].value == 1.0
    assert by_key[("t.max", MetricType.GAUGE)].value == 5.0
    assert by_key[("t.count", MetricType.COUNTER)].value == 5.0
    # no percentiles on a forwarding (local) instance for mixed scope
    assert not any(".percentile" in m.name or "percentile" in m.name
                   for m in metrics)


def test_local_only_histo_gets_percentiles():
    w = DeviceWorker()
    for v in range(1, 101):
        w.process_metric(
            parse_metric(f"t:{v}|ms|#veneurlocalonly".encode())
        )
    _, by_key, _ = _flush(w, is_local=True)
    assert ("t.50percentile", MetricType.GAUGE) in by_key
    p50 = by_key[("t.50percentile", MetricType.GAUGE)].value
    assert abs(p50 - 50.5) < 2.0
    assert by_key[("t.min", MetricType.GAUGE)].value == 1.0
    assert by_key[("t.max", MetricType.GAUGE)].value == 100.0


def test_global_only_histo_forwarded_not_emitted():
    w = DeviceWorker()
    w.process_metric(parse_metric(b"t:5|ms|#veneurglobalonly"))
    snap, by_key, metrics = _flush(w, is_local=True)
    assert not metrics  # nothing emitted locally
    fw = list(forwardable_rows(snap))
    assert len(fw) == 1
    assert fw[0][0] == "timer"
    assert fw[0][3] == ScopeClass.GLOBAL


def test_mixed_set_only_on_global():
    w = DeviceWorker()
    for i in range(100):
        w.process_metric(parse_metric(f"s:item{i}|s".encode()))
    snap, by_key, metrics = _flush(w, is_local=True)
    assert not metrics  # mixed sets have no local part
    fw = [f for f in forwardable_rows(snap) if f[0] == "set"]
    assert len(fw) == 1

    # global instance emits the estimate
    w2 = DeviceWorker(is_local=False)
    for i in range(100):
        w2.process_metric(parse_metric(f"s:item{i}|s".encode()))
    _, by_key2, _ = _flush(w2, is_local=False)
    est = by_key2[("s", MetricType.GAUGE)].value
    assert abs(est - 100) / 100 < 0.03


def test_local_set_always_flushes():
    w = DeviceWorker()
    for i in range(50):
        w.process_metric(
            parse_metric(f"s:item{i}|s|#veneurlocalonly".encode())
        )
    _, by_key, _ = _flush(w, is_local=True)
    est = by_key[("s", MetricType.GAUGE)].value
    assert abs(est - 50) / 50 < 0.05


def test_global_counter_forward_only():
    w = DeviceWorker()
    w.process_metric(parse_metric(b"c:7|c|#veneurglobalonly"))
    snap, by_key, metrics = _flush(w, is_local=True)
    assert not metrics
    fw = list(forwardable_rows(snap))
    assert fw[0][0] == "counter" and fw[0][3] == 7


def test_status_check_flushes():
    from veneur_tpu.protocol.dogstatsd import parse_service_check
    w = DeviceWorker()
    w.process_metric(parse_service_check(b"_sc|svc|1|h:host9|m:warn msg"))
    _, by_key, _ = _flush(w)
    m = by_key[("svc", MetricType.STATUS)]
    assert m.value == 1.0
    assert m.message == "warn msg"
    assert m.hostname == "host9"


def test_import_digest_merge_on_global():
    # 8 local workers each aggregate a shard; the global worker merges
    # their forwarded digests and emits percentiles (reference forward path
    # §3.4 of SURVEY.md)
    rng = np.random.default_rng(23)
    all_vals = []
    g = DeviceWorker(is_local=False)
    for _ in range(8):
        w = DeviceWorker()
        vals = rng.normal(100, 10, 5000)
        all_vals.append(vals)
        for v in vals:
            w.process_metric(parse_metric(f"lat:{v}|h".encode()))
        snap = w.flush(device_quantiles(PCTS, AGGS))
        for item in forwardable_rows(snap):
            kind, key, tags, cls, means, weights, dmin, dmax, drecip = item
            g.import_digest(key, tags, kind, cls, means, weights,
                            dmin, dmax, drecip)
    _, by_key, _ = _flush(g, is_local=False)
    combined = np.concatenate(all_vals)
    p50 = by_key[("lat.50percentile", MetricType.GAUGE)].value
    p99 = by_key[("lat.99percentile", MetricType.GAUGE)].value
    assert abs(p50 - np.quantile(combined, 0.5)) < 0.5
    assert abs(p99 - np.quantile(combined, 0.99)) < 1.0
    # mixed histo on global with no local samples: no min/max/count
    assert ("lat.min", MetricType.GAUGE) not in by_key
    assert ("lat.count", MetricType.COUNTER) not in by_key


def test_import_hll_merge():
    g = DeviceWorker(is_local=False)
    for shard in range(4):
        w = DeviceWorker()
        for i in range(shard * 500, shard * 500 + 1000):
            w.process_metric(parse_metric(f"s:u{i}|s".encode()))
        snap = w.flush(device_quantiles(PCTS, AGGS))
        for item in forwardable_rows(snap):
            if item[0] == "set":
                _, key, tags, regs = item
                g.import_hll(key, tags, ScopeClass.MIXED, regs)
    _, by_key, _ = _flush(g, is_local=False)
    est = by_key[("s", MetricType.GAUGE)].value
    true_n = 2500  # overlapping ranges
    assert abs(est - true_n) / true_n < 0.03


def test_import_counter_gauge():
    g = DeviceWorker(is_local=False)
    from veneur_tpu.core.metrics import MetricKey
    key = MetricKey("reqs", "counter", "")
    g.import_counter(key, [], 10)
    g.import_counter(key, [], 5)
    gkey = MetricKey("temp", "gauge", "")
    g.import_gauge(gkey, [], 3.5)
    _, by_key, _ = _flush(g, is_local=False)
    assert by_key[("reqs", MetricType.COUNTER)].value == 15.0
    assert by_key[("temp", MetricType.GAUGE)].value == 3.5


def test_flush_resets_state():
    w = DeviceWorker()
    w.process_metric(parse_metric(b"c:1|c"))
    _flush(w)
    _, by_key, metrics = _flush(w)
    assert not metrics  # state expires every interval


def test_growth_across_capacity():
    w = DeviceWorker(initial_histo_rows=64, initial_set_rows=64,
                     batch_size=128)
    for i in range(500):
        w.process_metric(parse_metric(f"h{i}:{i}|h".encode()))
        w.process_metric(parse_metric(f"s{i}:v{i}|s".encode()))
    snap, _, _ = _flush(w, is_local=False)
    assert snap.directory.num_histo_rows == 500
    assert snap.directory.num_set_rows == 500
    # spot check one series
    row = snap.directory.histo.index[
        (parse_metric(b"h123:1|h").key, ScopeClass.MIXED)]
    assert snap.lmin[row] == 123.0 and snap.lmax[row] == 123.0


def test_same_key_different_scopes_coexist():
    # reference: the same MetricKey can live in timers and globalTimers
    w = DeviceWorker()
    w.process_metric(parse_metric(b"t:1|ms"))
    w.process_metric(parse_metric(b"t:2|ms|#veneurglobalonly"))
    snap, _, _ = _flush(w)
    assert snap.directory.num_histo_rows == 2


def test_histo_sum_avg_hmean_aggregates():
    aggs = HistogramAggregates.from_names(
        ["min", "max", "count", "sum", "avg", "hmean", "median"])
    w = DeviceWorker()
    for v in [1.0, 2.0, 4.0]:
        w.process_metric(parse_metric(f"t:{v}|h".encode()))
    _, by_key, _ = _flush(w, is_local=True, aggregates=aggs)
    assert by_key[("t.sum", MetricType.GAUGE)].value == 7.0
    assert abs(by_key[("t.avg", MetricType.GAUGE)].value - 7.0 / 3) < 1e-6
    hmean = by_key[("t.hmean", MetricType.GAUGE)].value
    assert abs(hmean - 3.0 / (1 + 0.5 + 0.25)) < 1e-5
    med = by_key[("t.median", MetricType.GAUGE)].value
    assert 1.0 <= med <= 4.0


def test_unique_timeseries_counting():
    w = DeviceWorker(count_unique_timeseries=True, is_local=False)
    for i in range(200):
        w.process_metric(parse_metric(f"m{i}:1|c".encode()))
        w.process_metric(parse_metric(f"m{i}:2|c".encode()))  # same series
    snap = w.flush(device_quantiles(PCTS, AGGS))
    regs = snap.unique_timeseries_registers
    assert regs is not None
    import jax.numpy as jnp
    from veneur_tpu.ops import hll as hll_ops
    est = float(hll_ops.estimate(jnp.asarray(regs[None, :]))[0])
    assert abs(est - 200) / 200 < 0.05


def test_scalar_accumulators_survive_large_counts():
    """Compensated-f32 scalar accumulators (VERDICT r1 #10): after the
    running count passes 2^24, bare f32 adds silently drop small batch
    increments (2^25 + 1 == 2^25 in f32). The reference keeps these in
    float64 (tdigest/merging_digest.go scalars); here the _comp_add
    two-float sum must carry them."""
    w = DeviceWorker()
    w.process_metric(parse_metric(b"big:3|h"))
    row = w._ph_rows[0]
    w._flush_pending_histos()

    big = float(2 ** 25)
    # seed one enormous-weight sample (its own device batch)
    w._ph_rows.append(row)
    w._ph_vals.append(3.0)
    w._ph_wts.append(big - 1.0)
    w._flush_pending_histos()

    # then 512 separate unit batches — each add is below f32 resolution
    # at the accumulator's magnitude
    for _ in range(512):
        w._ph_rows.append(row)
        w._ph_vals.append(3.0)
        w._ph_wts.append(1.0)
        w._flush_pending_histos()

    snap = w.flush(device_quantiles(PCTS, AGGS))
    count = float(snap.lweight[0])
    total = float(snap.lsum[0])
    recip = float(snap.lrecip[0])
    expect_n = big + 512.0
    assert abs(count - expect_n) / expect_n < 1e-6, count
    assert abs(total - 3.0 * expect_n) / (3.0 * expect_n) < 1e-6, total
    assert abs(recip - expect_n / 3.0) / (expect_n / 3.0) < 1e-6, recip


def test_swap_then_extract_two_phase_flush():
    """swap() closes the epoch without device readback; ingest landing
    between swap and extract_snapshot goes to the NEW epoch and the old
    snapshot is unaffected (map-swap intent of worker.go:498-517)."""
    w = DeviceWorker()
    for v in [1, 2, 3]:
        w.process_metric(parse_metric(f"t:{v}|ms".encode()))
    qs = device_quantiles(PCTS, AGGS)
    sw = w.swap(qs)

    # next-interval ingest proceeds while the old epoch awaits extraction
    for v in [10, 20]:
        w.process_metric(parse_metric(f"t:{v}|ms".encode()))
    w.process_metric(parse_metric(b"c:7|c"))

    snap_old = w.extract_snapshot(sw, qs, interval_s=10.0)
    assert float(snap_old.lweight[0]) == 3.0
    assert float(snap_old.lmin[0]) == 1.0
    assert float(snap_old.lmax[0]) == 3.0
    assert len(snap_old.scalars.counter_meta) == 0

    snap_new = w.flush(qs)
    assert float(snap_new.lweight[0]) == 2.0
    assert float(snap_new.lmin[0]) == 10.0
    assert float(snap_new.lmax[0]) == 20.0
    assert len(snap_new.scalars.counter_meta) == 1


class _SpillSteps:
    """While open, every dispatch of the spill fold's ingest step, as
    (op, pool rows, row bucket, samples, summed weight); `check` sees
    each step's batch weights and output fields."""

    def __init__(self, check=None):
        self.steps, self.check = [], check

    def __enter__(self):
        self._orig = orig = W.DeviceWorker._spill_step
        rec = self

        def recording(self, op, fields, pool_rows, active, lids, v, wts):
            out = orig(self, op, fields, pool_rows, active, lids, v, wts)
            if rec.check is not None:
                rec.check(wts, out)
            rec.steps.append((op, pool_rows, len(active), len(v),
                              float(wts.sum())))
            return out

        W.DeviceWorker._spill_step = recording
        return self.steps

    def __exit__(self, *exc):
        W.DeviceWorker._spill_step = self._orig


def test_chunked_drain_fold_conserves_samples():
    """A drain after a stall can hold far more spilled samples than one
    fold batch should carry (each fold's padded arrays are O(batch));
    _fold_batch_direct folds in slices of _FOLD_CHUNK, each padded to
    that one length. Weight conservation across the slice boundary
    proves no sample is lost or doubled."""
    w = DeviceWorker(stage_depth=2)
    if not w.attach_native():
        pytest.skip("native library unavailable")
    total = 3000  # several chunks at the test-observable scale
    per_row = total // 4
    for i in range(per_row):
        w._native.ingest(
            b"\n".join(b"chunk.r%d:%d|ms" % (r, (i + r) % 97)
                       for r in range(4)))
    # shrink the chunk so this test crosses several boundaries
    orig_chunk = W._FOLD_CHUNK
    orig_fold = W.DeviceWorker._fold_slice_direct
    calls = []

    def counting(self, rows, vals, wts):
        calls.append(len(rows))
        return orig_fold(self, rows, vals, wts)

    W._FOLD_CHUNK = 512
    W.DeviceWorker._fold_slice_direct = counting
    try:
        with _SpillSteps() as steps:
            w.drain_native()
    finally:
        W._FOLD_CHUNK = orig_chunk
        W.DeviceWorker._fold_slice_direct = orig_fold
    assert len(calls) > 1  # the drain really folded in chunks
    assert all(c <= 512 for c in calls)
    # and the one-shape pad takes the shrunk chunk: every dispatch, the
    # short last slice too, is 512 samples over the least row bucket
    assert [st[2:4] for st in steps] == [(256, 512)] * len(calls)
    qs = device_quantiles(PCTS, AGGS)
    snap = w.flush(qs)
    # staged (2/row) + spilled samples all land: lweight == total
    assert float(np.sum(snap.lweight[:4])) == float(total)


def test_terminal_worker_skips_digest_pool_readback():
    """Only a forwarding (local) worker materializes the [S,C] centroid
    pools host-side — they exist solely for the forward codec, and at 1M
    series they are ~1GB of device→host traffic per flush (the round-4
    on-chip E2E run measured them at >90% of a 105s extract phase). A
    terminal worker (global or standalone) must leave them on device."""
    qs = device_quantiles(PCTS, AGGS)

    term = DeviceWorker(is_local=False)
    term.process_metric(parse_metric(b"t:5|ms"))
    snap = term.flush(qs)
    assert snap.digest_means is None
    assert snap.digest_weights is None
    # the extraction itself is unaffected: quantiles still come back
    assert snap.quantile_values is not None

    fwd = DeviceWorker(is_local=True)
    fwd.process_metric(parse_metric(b"t:5|ms"))
    snap = fwd.flush(qs)
    assert snap.digest_means is not None
    assert float(snap.digest_weights.sum()) == 1.0


def test_server_flush_does_not_hold_ingest_lock_during_extraction():
    """The server flush loop must release the per-worker ingest lock
    before extraction: with extraction artificially blocked, a reader
    thread can still acquire the lock and ingest (VERDICT r1 weak #5)."""
    import threading

    from veneur_tpu.core.config import Config
    from veneur_tpu.core.factory import build_server

    cfg = Config(statsd_listen_addresses=[], interval="10s",
                 percentiles=[0.5], aggregates=["min", "max", "count"])
    server = build_server(cfg)
    try:
        worker = server.workers[0]
        worker.process_metric(parse_metric(b"t:1|ms"))

        gate = threading.Event()
        entered = threading.Event()
        orig = worker._extract

        def blocked_extract(histo, qs):
            entered.set()
            assert gate.wait(10.0), "test deadlock"
            return orig(histo, qs)

        worker._extract = blocked_extract
        t = threading.Thread(target=server.flush, daemon=True)
        t.start()
        assert entered.wait(10.0), "flush never reached extraction"
        # extraction is mid-flight; ingest must not block on the lock
        got_lock = server._worker_locks[0].acquire(timeout=5.0)
        assert got_lock, "ingest lock held across extraction"
        try:
            worker.process_metric(parse_metric(b"t:2|ms"))
        finally:
            server._worker_locks[0].release()
        gate.set()
        t.join(30.0)
        assert not t.is_alive()
        # the concurrently ingested sample is alive in the new epoch
        snap = worker.flush(device_quantiles([0.5], AGGS))
        assert float(snap.lweight[0]) == 1.0
        assert float(snap.lmin[0]) == 2.0
    finally:
        server.shutdown()


# -- staged-ingest plane (worker._device_histo_step / _histo_fold_staged) ---


def _histo_aggs(w, name="t"):
    _, by_key, _ = _flush(w, is_local=False,
                          aggregates=HistogramAggregates.from_names(
                              ["min", "max", "count", "sum", "avg"]))
    return {
        "min": by_key[(f"{name}.min", MetricType.GAUGE)].value,
        "max": by_key[(f"{name}.max", MetricType.GAUGE)].value,
        "count": by_key[(f"{name}.count", MetricType.COUNTER)].value,
        "sum": by_key[(f"{name}.sum", MetricType.GAUGE)].value,
        "p50": by_key[("t.50percentile", MetricType.GAUGE)].value,
    }


def test_staged_spill_boundary_exact():
    """Aggregates stay exact when one batch exactly fills, then crosses,
    the staging plane (fit boundary at slots == stage_depth)."""
    for n in (4, 5, 9):  # == B, B+1, 2B+1 with B=4
        w = DeviceWorker(stage_depth=4, batch_size=1 << 20)
        vals = list(range(1, n + 1))
        for v in vals:
            w.process_metric(parse_metric(f"t:{v}|ms".encode()))
        a = _histo_aggs(w)
        assert a["count"] == float(n), (n, a)
        assert a["min"] == 1.0 and a["max"] == float(n)
        assert a["sum"] == float(sum(vals))


def test_staged_multi_batch_accumulation():
    """Counts accumulate across many small device batches: each batch's
    slot base must continue where the previous one stopped."""
    w = DeviceWorker(stage_depth=8, batch_size=1 << 20)
    total = 0
    for batch in range(5):
        for v in range(3):  # 3 samples per batch -> crosses B=8 at batch 3
            w.process_metric(parse_metric(f"t:{batch * 3 + v}|ms".encode()))
            total += 1
        w._flush_pending_histos()
    a = _histo_aggs(w)
    assert a["count"] == float(total)
    assert a["min"] == 0.0 and a["max"] == float(total - 1)
    assert a["sum"] == float(sum(range(total)))


def test_staged_growth_preserves_planes():
    """Pool growth mid-interval (past initial_histo_rows) must carry the
    already-staged samples into the resized planes."""
    w = DeviceWorker(stage_depth=16, initial_histo_rows=4,
                     batch_size=1 << 20)
    # stage a sample on an early row, then register enough series to
    # force _ensure_histo growth (4 -> bigger), then flush
    w.process_metric(parse_metric(b"t:7|ms"))
    w._flush_pending_histos()
    for i in range(12):
        w.process_metric(parse_metric(f"grow{i}:1|ms".encode()))
    a = _histo_aggs(w)
    assert a["count"] == 1.0 and a["min"] == 7.0 and a["max"] == 7.0


def test_native_spill_fold_deferred_to_extract():
    """The hot-row spill batch drained at epoch close is NOT folded in
    swap() (which holds the ingest lock — round-5 overload measurement:
    the backlog fold was 42s of a 44s flush); it rides the SwappedEpoch
    and extract_snapshot folds it off the lock. Aggregates stay exact."""
    import pytest

    w = DeviceWorker(stage_depth=2, batch_size=1 << 20)
    if not w.attach_native():
        pytest.skip("native lib unavailable")
    n = 9
    for v in range(1, n + 1):
        w.ingest_datagram(b"t:%d|ms" % v)
    qs = device_quantiles([0.5], AGGS)
    sw = w.swap(qs)
    # 2 staged in the plane, 7 spilled — the spill is deferred, unfolded
    assert sw.spill_histo is not None
    assert len(sw.spill_histo[0]) == n - 2
    snap = w.extract_snapshot(sw, qs, interval_s=10.0)
    assert float(snap.lweight[0]) == float(n)
    assert float(snap.lmin[0]) == 1.0
    assert float(snap.lmax[0]) == float(n)
    assert abs(float(snap.lsum[0]) - sum(range(1, n + 1))) < 1e-6


def test_adaptive_spill_cap_controller():
    """Flushes overrunning the interval halve the spill caps (shed
    earlier, keep cadence); comfortable flushes grow them back toward
    the configured ceiling. Floor and ceiling are respected."""
    from veneur_tpu.core.config import Config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.channel import ChannelMetricSink

    cfg = Config(interval="10s", tpu_spill_cap=1 << 20)
    srv = Server(cfg, metric_sinks=[ChannelMetricSink()])
    try:
        assert srv._spill_cap_now == 1 << 20
        srv._adapt_spill_caps(9.5)          # overrun: halve
        assert srv._spill_cap_now == 1 << 19
        for _ in range(10):
            srv._adapt_spill_caps(20.0)     # keep overrunning
        assert srv._spill_cap_now == 1 << 16   # floor
        assert srv.workers[0].spill_cap == 1 << 16
        srv._adapt_spill_caps(5.0)          # mid-band: hold
        assert srv._spill_cap_now == 1 << 16
        for _ in range(10):
            srv._adapt_spill_caps(0.5)      # fast: grow back
        assert srv._spill_cap_now == 1 << 20   # ceiling
    finally:
        srv.shutdown()


def test_native_staged_weighted_flat_upload_exact():
    """A sampled (@rate) timer makes the staging plane non-unit: the
    flush's compacted upload must carry the weights flat array and the
    device rebuild must place every weight at its value's slot
    (count = sum of weights, reference rate correction)."""
    import pytest

    w = DeviceWorker(stage_depth=8, batch_size=1 << 20)
    if not w.attach_native():
        pytest.skip("native lib unavailable")
    w.ingest_datagram(b"wf.t:10|ms")
    w.ingest_datagram(b"wf.t:20|ms|@0.5")   # weight 2
    w.ingest_datagram(b"wf.t:30|ms|@0.25")  # weight 4
    w.ingest_datagram(b"wf.u:5|ms")         # second row, unit
    qs = device_quantiles([0.5], AGGS)
    snap = w.flush(qs, interval_s=10.0)
    by = {}
    for m in generate_inter_metrics(snap, False, [0.5], AGGS):
        by[(m.name, m.type)] = m.value
    assert by[("wf.t.count", MetricType.COUNTER)] == 7.0  # 1+2+4
    assert by[("wf.t.min", MetricType.GAUGE)] == 10.0
    assert by[("wf.t.max", MetricType.GAUGE)] == 30.0
    assert by[("wf.u.count", MetricType.COUNTER)] == 1.0


def test_staged_matches_direct_fold():
    """The staged path and the per-batch direct device fold agree exactly
    on scalar aggregates and closely on quantiles."""
    rng = np.random.default_rng(7)
    vals = rng.gamma(2.0, 10.0, size=300).astype(np.float32)

    staged = DeviceWorker(stage_depth=512, batch_size=1 << 20)
    direct = DeviceWorker(stage_depth=512, batch_size=1 << 20)
    rows = []
    for v in vals:
        staged.process_metric(parse_metric(b"t:%.4f|ms" % v))
        direct.process_metric(parse_metric(b"t:%.4f|ms" % v))
        rows.append(0)
    # route the direct worker's pending samples through the spill fold
    direct._ensure_histo(direct.directory.num_histo_rows)
    pv = np.asarray(direct._ph_vals, np.float32)
    pw = np.asarray(direct._ph_wts, np.float32)
    pr = np.asarray(direct._ph_rows, np.int32)
    direct._ph_rows, direct._ph_vals, direct._ph_wts = [], [], []
    direct._fold_batch_direct(pr, pv, pw)

    sa = _histo_aggs(staged)
    da = _histo_aggs(direct)
    assert sa["count"] == da["count"]
    assert sa["min"] == da["min"] and sa["max"] == da["max"]
    assert abs(sa["sum"] - da["sum"]) <= 1e-3 * abs(da["sum"])
    # both digests see the same samples; p50 agrees within digest error
    assert abs(sa["p50"] - da["p50"]) <= 0.05 * max(1.0, abs(da["p50"]))


def _spill_worker(n_rows: int, **kw) -> DeviceWorker:
    """A worker with `n_rows` timer rows of one sample (value 1) each and
    a device pool to spill into."""
    w = DeviceWorker(stage_depth=512, batch_size=1 << 20, **kw)
    for r in range(n_rows):
        w.process_metric(parse_metric(b"spill.r%d:1|ms" % r))
    w._ensure_histo(w.directory.num_histo_rows)
    return w


def _pool_bytes(fields) -> list:
    """The pool's rows below the scratch row, byte for byte. (The
    scratch row takes every fold's padding: it stays at weight 0 and
    its centroid means go from a fresh pool's 0 to the empty digest's
    inf with the first fold of anything.)"""
    assert not np.asarray(fields[1])[-1].any()
    return [np.asarray(a)[:-1].tobytes() for a in fields]


@pytest.mark.parametrize("n", [1, 2, 255, 256, 257, 4095, 4097, 8191,
                               16383, 16384])
def test_spill_pad_has_one_sample_length(n):
    """_pad_spill_batch pads every batch to _FOLD_CHUNK samples, so the
    ingest step's programs differ by row bucket alone: powers of four
    from 256, padding at weight 0 on the scratch row's bucket slot."""
    assert W._FOLD_CHUNK == 16384 and W._SHED_FLOOR == 262144
    rng = np.random.default_rng(n)
    n_uniq = min(n, int(rng.integers(1, 5000)))
    rows = rng.choice(100000, size=n_uniq, replace=False)[
        rng.integers(0, n_uniq, size=n)].astype(np.int32)
    vals = rng.integers(1, 1000, size=n).astype(np.float32)
    active, lids, v, w = DeviceWorker._pad_spill_batch(
        rows, vals, np.ones(n, np.float32), scratch=131071)
    assert len(lids) == len(v) == len(w) == W._FOLD_CHUNK
    k = len(active)
    uniq = len(np.unique(rows))
    assert k in (256, 1024, 4096, 16384) and uniq <= k
    assert k == 256 or uniq > k // 4
    np.testing.assert_array_equal(active[lids[:n]], rows)
    np.testing.assert_array_equal(v[:n], vals)
    assert (active[uniq:] == 131071).all()
    assert (lids[n:] == k - 1).all() and not w[n:].any() and w[:n].all()


def test_a_long_spill_batch_folds_in_slices_to_what_the_reference_says():
    """40,000 samples over 300 rows fold in three dispatches of
    _FOLD_CHUNK samples; count, min and max of every row are the
    float64 reference's, the sum is to float32 rounding, and each
    quantile's rank error is inside the benchmark's limit (PERF.md
    section 2; bench/reference.py)."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import reference

    n_rows, extra = 300, 40000
    w = _spill_worker(n_rows)
    rng = np.random.default_rng(11)
    rows = rng.integers(0, n_rows, size=extra).astype(np.int32)
    vals = rng.integers(2, 5000, size=extra).astype(np.float32)
    with _SpillSteps() as steps:
        w._fold_batch_direct(rows, vals, np.ones(extra, np.float32))
    chunk = W._FOLD_CHUNK
    # 300 rows: bucket 1024, after bucket 256 once on nothing
    assert [st[2:] for st in steps] == [
        (256, chunk, 0.0), (1024, chunk, float(chunk)),
        (1024, chunk, float(chunk)), (1024, chunk, float(extra - 2 * chunk))]
    snap = w.flush(device_quantiles(PCTS, AGGS))
    worst = 0.0
    for r in range(n_rows):
        seg = np.sort(np.append(vals[rows == r].astype(np.float64), 1.0))
        n = len(seg)
        assert float(snap.lweight[r]) == n
        # float32 segment sums inside a 16,384-sample step: to rounding
        assert abs(float(snap.lsum[r]) - seg.sum()) <= 1e-4 * seg.sum()
        assert float(snap.lmin[r]) == seg[0] == 1.0
        assert float(snap.lmax[r]) == seg[-1]
        assert n > reference.unmerged_n(w.compression)
        for j, q in enumerate(snap.quantile_qs):
            if not 0.0 < q < 1.0:
                continue
            got = float(snap.quantile_values[r, j])
            err = max(0.0, np.searchsorted(seg, got, "left") / n - q,
                      q - np.searchsorted(seg, got, "right") / n)
            worst = max(worst, err / reference.rank_bound(q, n, w.compression))
    assert 0.0 < worst <= reference.LIMITS["quantile_rank_over_bound"]


@pytest.mark.parametrize("shards", [0, 2], ids=["one-device", "sharded"])
def test_a_row_buckets_first_spill_fold_warms_the_smaller_buckets(shards):
    """Before the first fold of row bucket 4,096 at a pool size, the
    buckets 256 and 1,024 are dispatched once each on nothing, inside
    `spill.warm` spans, and leave every series' row bitwise as it was;
    later folds warm nothing; a second pool size warms anew; and a swapped
    epoch's deferred spill (_fold_spill_chunk) shares the book."""
    import jax

    if shards and len(jax.devices()) < shards:
        pytest.skip("needs %d devices" % shards)
    n_rows = 1100
    w = _spill_worker(n_rows, **({"series_shards": shards} if shards else {}))
    pool_rows = w._histo.num_rows
    before = _pool_bytes(w._histo.fields())

    def nothing_changes_nothing(wts, out):
        if not wts.any():
            assert _pool_bytes(out) == before

    def warm_spans():
        return [(s.attrs["pool_rows"], s.attrs["bucket"])
                for s in w.rec.closed() if s.name == "spill.warm"]

    rows = np.arange(n_rows, dtype=np.int32)
    ones = np.ones(n_rows, np.float32)
    chunk = W._FOLD_CHUNK
    with _SpillSteps(nothing_changes_nothing) as steps:
        w._fold_batch_direct(rows, 2 * ones, ones)
        assert steps == [("fold", pool_rows, 256, chunk, 0.0),
                         ("fold", pool_rows, 1024, chunk, 0.0),
                         ("fold", pool_rows, 4096, chunk, float(n_rows))]
        assert warm_spans() == [(pool_rows, 256), (pool_rows, 1024)]
        # every bucket up to 4,096 is there now: nothing warms again,
        # on the live pool or on a swapped epoch's
        w._fold_batch_direct(rows[:300], 3 * ones[:300], ones[:300])
        w._fold_batch_direct(rows[:7], 3 * ones[:7], ones[:7])
        h = w._histo
        (h.means, h.weights, h.dmin, h.dmax, h.drecip, h.drecip_c, h.lmin,
         h.lmax, h.lsum, h.lsum_c, h.lweight, h.lweight_c, h.lrecip,
         h.lrecip_c) = w._fold_spill_chunk(
             h.fields(), rows[:9], 3 * ones[:9], ones[:9], pool_rows)
        assert [(st[0], st[2], st[4]) for st in steps[3:]] == [
            ("fold", 1024, 300.0), ("fold", 256, 7.0), ("spill", 256, 9.0)]
        assert len(warm_spans()) == 2
        snap = w.flush(device_quantiles(PCTS, AGGS))
        assert float(np.sum(snap.lweight[:n_rows])) == 2 * n_rows + 316
        # a second pool size: its programs are others, so it warms anew,
        # here from a swapped epoch's deferred spill
        del steps[:]
        w.process_metric(parse_metric(b"spill.r0:1|ms"))
        w._ensure_histo(4 * pool_rows)
        grown = w._histo.num_rows
        assert grown > pool_rows
        before = _pool_bytes(w._histo.fields())
        w._fold_spill_chunk(w._histo.fields(), rows[:300], ones[:300],
                            ones[:300], grown)
        assert steps == [("spill", grown, 256, chunk, 0.0),
                         ("spill", grown, 1024, chunk, 300.0)]
        assert warm_spans()[2:] == [(grown, 256)]


def test_a_thin_flush_folds_at_a_row_bucket_it_has_programs_for():
    """A flush that needs fewer rows than an earlier one at this pool
    size folds and extracts at the earlier one's row count (`fold_rows`
    on the extract span, beside `rows_used`) and compiles nothing; one
    that needs more than any before takes its own bucket; all of them
    hold to the samples."""
    w = DeviceWorker(stage_depth=8, initial_histo_rows=8192)
    qs = device_quantiles([0.5], AGGS)
    rng = np.random.default_rng(5)

    def flush_of(n_series):
        per = rng.integers(1, 6, size=n_series)
        vals = [np.sort(rng.integers(1, 4000, size=k)).astype(np.float64)
                for k in per]
        for i, vs in enumerate(vals):
            for v in rng.permutation(vs):
                w.process_metric(parse_metric(b"thin.s%d:%d|ms" % (i, v)))
        with w.rec.span("flush.extract") as sp:
            snap = w.flush(qs)
        assert snap.directory.num_histo_rows == n_series
        ops = {s.attrs.get("op") for s in w.rec.closed()[-40:]
               if s.name == "dispatch"}
        assert {"staged", "extract"} <= ops
        (j,) = [j for j, q in enumerate(snap.quantile_qs) if q == 0.5]
        for i, vs in enumerate(vals):
            k = len(vs)
            assert float(snap.lweight[i]) == k
            assert float(snap.lmin[i]) == vs[0]
            assert float(snap.lmax[i]) == vs[-1]
            # few samples: the median lies between the order statistics
            # on either side of rank k/2 (bench/reference.py)
            lo = vs[max(int(np.floor(0.5 * k)) - 1, 0)]
            hi = vs[min(int(np.ceil(0.5 * k)), k - 1)]
            assert lo <= float(snap.quantile_values[i, j]) <= hi
        return sp.attrs["rows_used"], sp.attrs["fold_rows"]

    def programs():
        return (W._histo_fold_staged._cache_size(),
                W._histo_flush_extract._cache_size())

    assert flush_of(3000) == (3000, 4096)
    had = programs()
    assert flush_of(1000) == (1000, 4096)
    assert programs() == had
    assert flush_of(5000) == (5000, 8192)
    assert flush_of(1000) == (1000, 4096)
    assert flush_of(4000) == (4000, 4096)
    assert flush_of(4097) == (4097, 8192)
    assert w._flush_rows_had == {8192: {4096, 8192}}
    # a bucket is a pool size's own: another pool size starts anew
    assert w._flush_rows(16384, 1000) == 1024
    assert w._flush_rows(16384, 9000) == 16384
    assert w._flush_rows(16384, 600) == 1024
    assert w._flush_rows(16384, 1025) == 16384


def test_scalar_pool_growth_at_capacity_boundary():
    """Regression: adopting the row that crosses the pool's capacity
    (row == initial capacity) crashed in ensure() because `used` was
    bumped before the grow — and np.resize's recycled data leaked into
    the new row's value slot (caught by tools/soak_topology.py at >256
    counter series per worker)."""
    from veneur_tpu.core.worker import ScalarPool

    pool = ScalarPool(initial=8)
    for i in range(20):  # crosses capacity at rows 8 and 16
        row = pool.upsert(f"c{i}", ScopeClass.LOCAL, (), None)
        assert row == i
        # the freshly adopted row must start zeroed even after np.resize
        # recycles old contents into the grown tail
        assert pool.values[row] == 0.0
        assert not pool.present[row]
        pool.values[row] = float(i + 1)
        pool.present[row] = True
    assert pool.used == 20
    assert list(pool.values[:20]) == [float(i + 1) for i in range(20)]
    assert pool.present[:20].all()


# -- adoption by the batch (worker._adopt_pending) --------------------------
#
# The native directory forgets its rows at every flush and the same series
# register again; the worker adopts them a batch at a time by lifetime
# series id. The plain reference below is the per-series loop the program
# had before (one RowMeta lookup, one adopt call per series), kept here so
# that the batch path is held to its answers field by field.

from veneur_tpu.core.directory import RowMeta, SeriesDirectory
from veneur_tpu.core.metrics import MetricKey, route_info, tenant_of
from veneur_tpu.core.tenancy import TenantLedger
from veneur_tpu.core.worker import HostScalars, _series_budget_id


class _PerSeriesReference:
    """Adopts (pool, row, kind, scope, name, joined) records one at a
    time, with the cross-epoch cache keyed by the strings."""

    def __init__(self, budget: int = 0) -> None:
        self.cache: dict = {}
        self.tenancy = (TenantLedger(default_budget=budget, budgets={})
                        if budget else None)
        self.new_epoch()

    def new_epoch(self) -> None:
        self.directory = SeriesDirectory()
        self.scalars = HostScalars()

    def adopt(self, records) -> None:
        from veneur_tpu.native import NativeIngest

        for pool, row, kind, scope, name, joined in records:
            ck = (pool, kind, scope, name, joined)
            meta = self.cache.get(ck)
            if meta is None:
                key = MetricKey(name=name,
                                type=NativeIngest.TYPE_BY_KIND[kind],
                                joined_tags=joined)
                tags = joined.split(",") if joined else []
                tenant, admitted = "", True
                if self.tenancy is not None:
                    tenant = tenant_of(tags, self.tenancy.tag_key)
                    admitted = self.tenancy.admit(
                        tenant, _series_budget_id(ScopeClass(scope), key))
                meta = self.cache[ck] = RowMeta(
                    key=key, tags=tags, scope_class=ScopeClass(scope),
                    sinks=route_info(tags), tenant=tenant,
                    admitted=admitted)
            if pool == 0:
                self.directory.histo.adopt_meta(row, meta)
            elif pool == 1:
                self.directory.sets.adopt_meta(row, meta)
            else:
                spool = (self.scalars.counters if pool == 2
                         else self.scalars.gauges)
                spool._append(
                    row, (meta.key, meta.tags, meta.scope_class, meta.sinks),
                    meta.scope_class, meta.sinks, meta.admitted,
                    meta.wire_frag())
                spool.ensure(row + 1)
                spool.used = row + 1


def _pool_fields(pool) -> dict:
    entries = [
        (e.key, e.tags, e.scope_class, e.sinks, e.tenant, e.admitted)
        if isinstance(e, RowMeta) else e for e in pool.entries]
    blob = pool.frag_blob()
    return {
        "entries": entries,
        "scope_codes": pool.scope_codes.tobytes(),
        "admit_codes": pool.admit_codes.tobytes(),
        "routed_rows": pool.routed_rows,
        "rejected_rows": pool.rejected_rows,
        "frag_blob": None if blob is None else bytes(blob),
        "index": dict(pool.index),
    }


def _assert_directories_equal(w, ref) -> None:
    for name, got, want in (
            ("histo", w.directory.histo, ref.directory.histo),
            ("sets", w.directory.sets, ref.directory.sets),
            ("counters", w.scalars.counters, ref.scalars.counters),
            ("gauges", w.scalars.gauges, ref.scalars.gauges)):
        got_f, want_f = _pool_fields(got), _pool_fields(want)
        for field_name in want_f:
            assert got_f[field_name] == want_f[field_name], (name,
                                                             field_name)
    for got, want in ((w.scalars.counters, ref.scalars.counters),
                      (w.scalars.gauges, ref.scalars.gauges)):
        assert got.used == want.used == len(got.meta)
        assert len(got.values) >= got.used


def _seeded_lines(rng, n: int) -> list[bytes]:
    """n series of every class, some of them the odd ones: a routed
    series (veneursinkonly:), scope twins, tenants over a budget of 5,
    separators in a name and in a tag (the Python formatter's rows)."""
    lines = []
    for i in range(n):
        tenant = b"|#tenant:t%d" % (i % 3)
        lines += [b"ad.t%d:%d|ms%s" % (i, i, tenant),
                  b"ad.c%d:1|c%s" % (i, tenant),
                  b"ad.g%d:%d|g" % (i, i),
                  b"ad.s%d:m%d|s%s" % (i, i, tenant)]
    lines += [b"ad.routed:1|ms|#veneursinkonly:datadog",
              b"ad.routed:1|c|#veneursinkonly:datadog,x:y",
              b"ad.t0:1|ms|#tenant:t0,veneurlocalonly",
              b"ad.c0:1|c|#tenant:t0,veneurglobalonly",
              b"ad.sep\x1ename:1|c", b"ad.sep:1|g|#k:\x1fv",
              b"ad.sep\x1ename:3|ms"]
    order = rng.permutation(len(lines))
    return [lines[i] for i in order]


@pytest.mark.parametrize("budget", [0, 5], ids=["no-tenancy", "budget-5"])
def test_batch_adoption_equals_the_per_series_loop(budget):
    """Three intervals of the same seeded series in three orders, adopted
    in two batches an interval: every field of the four pools equals what
    the per-series loop gives, the lazily built index included."""
    from veneur_tpu.native import NativeIngest

    w = DeviceWorker(stage_depth=8, batch_size=1 << 12)
    if budget:
        w.tenancy = TenantLedger(default_budget=budget, budgets={})
    if not w.attach_native():
        pytest.skip("native ingest library unavailable")
    ref = _PerSeriesReference(budget)
    rng = np.random.default_rng(29)
    qs = device_quantiles(PCTS, AGGS)
    for interval in range(3):
        lines = _seeded_lines(rng, 40)
        # a context of the interval's own sees every series for the
        # first time: its drain is the per-series loop's input
        plain = NativeIngest()
        half = len(lines) // 2
        for part in (lines[:half], lines[half:]):
            w.ingest_datagram(b"\n".join(part))
            w.sync_native_series()
            plain.ingest(b"\n".join(part))
            ref.adopt(plain.drain_new_series().first_records())
        known = w._adopt_cache[0]
        if interval:
            assert len(known) == learnt  # nothing is learnt twice
        learnt = len(known)
        _assert_directories_equal(w, ref)
        if budget:
            assert w.directory.histo.rejected_rows > 0
            assert w.scalars.counters.rejected_rows > 0
        assert w.directory.histo.routed_rows == 1
        assert w.scalars.counters.routed_rows == 1
        assert w.scalars.counters.frag_blob() is not None
        snap = w.flush(qs)
        assert len(snap.directory.histo.rows) == len(
            ref.directory.histo.rows)
        ref.new_epoch()


def test_unique_timeseries_tally_is_fed_from_the_batch():
    """count_unique_timeseries: the batch feeds the HLL what the
    per-series insert fed it, in the first interval (strings arrive) and
    in the ones after (integers only)."""
    from veneur_tpu.utils.hashing import fmix64, metric_digest
    from veneur_tpu.ops import hll as hll_ops

    w = DeviceWorker(count_unique_timeseries=True, is_local=True)
    if not w.attach_native():
        pytest.skip("native ingest library unavailable")
    lines = ([b"u.t%d:1|ms|#veneurlocalonly" % i for i in range(50)]
             + [b"u.fwd%d:1|ms" % i for i in range(50)]  # forwarded: not
             + [b"u.c%d:1|c" % i for i in range(50)])    # counted locally
    want = np.zeros_like(w._umts)
    hashes = np.array(
        [fmix64(metric_digest("u.t%d" % i, "timer", "")) for i in range(50)]
        + [fmix64(metric_digest("u.c%d" % i, "counter", ""))
           for i in range(50)], dtype=np.uint64)
    idx, rank = hll_ops.split_hashes(hashes, w.hll_precision)
    np.maximum.at(want, idx, rank)
    qs = device_quantiles(PCTS, AGGS)
    for interval in range(2):
        w.ingest_datagram(b"\n".join(lines[::-1] if interval else lines))
        w.sync_native_series()
        assert (w._umts == want).all() and want.any()
        w.flush(qs)


def test_the_intern_bound_clears_both_sides():
    """Past the bound the context drops its table at the reset and says
    so by the generation; the worker drops its side with it, so no sid
    names another series' RowMeta: each interval's directory still reads
    as the per-series loop's."""
    from veneur_tpu.native import NativeIngest

    w = DeviceWorker(stage_depth=8)
    if not w.attach_native():
        pytest.skip("native ingest library unavailable")
    w._native.set_intern_cap(100)
    ref = _PerSeriesReference()
    qs = device_quantiles(PCTS, AGGS)
    generations = []
    for interval in range(4):
        # a sliding window of 80 series: 40 known, 40 new each interval
        names = range(interval * 40, interval * 40 + 80)
        lines = ([b"ib.t%d:1|ms" % i for i in names]
                 + [b"ib.c%d:1|c|#k:%d" % (i, i) for i in names])
        plain = NativeIngest()
        w.ingest_datagram(b"\n".join(lines))
        w.sync_native_series()
        plain.ingest(b"\n".join(lines))
        ref.adopt(plain.drain_new_series().first_records())
        _assert_directories_equal(w, ref)
        generations.append((w._adopt_cache[0].generation,
                            len(w._adopt_cache[0])))
        w.flush(qs)
        ref.new_epoch()
    # 160 series after interval 0 is past 100: dropped at that flush,
    # and again every interval (each learns 160 anew)
    assert generations == [(0, 160), (1, 160), (2, 160), (3, 160)]
    assert w.interned_series == 160


# -- a fault inside the native object is the caller's to see ----------------

def _boom(*a, **k):
    raise AttributeError("'NativeIngest' object has no attribute 'misspelt'")


def _native_worker(**kw):
    w = DeviceWorker(compression=100, stage_depth=16, batch_size=8,
                     micro_fold_rows=1, micro_fold_max_age_s=1e9, **kw)
    if not w.attach_native():
        pytest.skip("native ingest library unavailable")
    w.ingest_datagram(b"ae.t:1|ms\nae.t:2|ms\nae.c:1|c")
    return w


@pytest.mark.parametrize("site,attr,micro", [
    ("micro_fold_pending", "stage_pending", True),
    ("micro_fold_once", "stage_pending", True),
    ("micro_fold_once", "drain_stage_delta", True),
    ("swap_residual_drain", "drain_stage_delta", True),
    ("swap_detach_stage", "detach_stage", False),
    ("swap_ssf_fallback", "drain_ssf_fallback", False),
    ("attach_native_stage_depth", "set_stage_depth", False),
    ("attach_native_spill_cap", "set_spill_cap", False),
    ("attach_reader_shards", "set_spill_cap", False),
    ("commit_counters", "commit_counters", False),
    ("reader_ns", "reader_ns", False),
])
def test_an_attribute_error_inside_native_ingest_reaches_the_caller(
        monkeypatch, site, attr, micro):
    """Each of these sites used to catch AttributeError as "the library
    predates this call", and went on without micro-folds, the staging
    plane or the cap, in silence: a misspelt name inside NativeIngest
    read as an old library. The loader now refuses such a library
    whole, so here the error can only be a fault, and it raises."""
    from veneur_tpu.native import NativeIngest

    w = _native_worker(micro_fold=micro)
    patched = (property(_boom) if attr == "stage_pending" else _boom)
    monkeypatch.setattr(NativeIngest, attr, patched)
    qs = device_quantiles(PCTS, AGGS)
    call = {
        "micro_fold_pending": w.micro_fold_pending,
        "micro_fold_once": w.micro_fold_once,
        "swap_residual_drain": lambda: w.swap(qs),
        "swap_detach_stage": lambda: w.swap(qs),
        "swap_ssf_fallback": lambda: w.swap(qs),
        "attach_native_stage_depth":
            DeviceWorker(stage_depth=16).attach_native,
        "attach_native_spill_cap":
            DeviceWorker(stage_depth=0, spill_cap=1 << 16).attach_native,
        "attach_reader_shards": lambda: w.attach_reader_shards(2),
        "commit_counters": w.commit_counters,
        "reader_ns": w.reader_ns,
    }[site]
    with pytest.raises(AttributeError, match="misspelt"):
        call()
