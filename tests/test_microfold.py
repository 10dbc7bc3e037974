"""Always-hot flush: micro-fold parity, transfer accounting, swap fence.

The micro-fold path's contract is BIT-identity: a flush must produce
byte-for-byte the same snapshot whether the staged epoch was folded once
at the deadline or streamed to the device mirror across any number of
sub-interval micro-folds (ops/microfold.py builds the mirror so the
deadline fold consumes literally the same dense array either way).
Pinned here for all three metric classes — t-digest planes, HLL/set
registers, scalar planes — across >= 3 flush intervals with >= 4
micro-folds per interval, on both the python staging plane and the
native (C++) one, plus:

- transfer-ledger equality: N micro-folds of the same stream cost the
  same H2D bytes (+-0) as a single drain, independent of stage depth —
  O(samples), never O(micro_folds x depth);
- the epoch-swap fence: a swap landing between (or racing) micro-folds
  loses no rows and double-folds none;
- the loadgen controller's warmup/steady-state split (classify_warmup),
  which keeps a first-interval XLA compile from being judged as a
  cadence failure of the pipeline.

CI runs this file twice — default (micro-folds on) and with
VENEUR_MICRO_FOLD=0 (tools/ci.sh) — mirroring the emit-parity lane: the
worker-level tests pin the mechanism explicitly, the server-level test
honors the env overlay, so the second pass proves the escape hatch
really disengages the path.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from veneur_tpu.core.config import Config, load_config
from veneur_tpu.core.flusher import device_quantiles
from veneur_tpu.core.metrics import HistogramAggregates, MetricType
from veneur_tpu.core.worker import DeviceWorker
from veneur_tpu.health.ledger import TransferLedger
from veneur_tpu.loadgen.controller import classify_warmup
from veneur_tpu.protocol.dogstatsd import parse_metric

AGGS = HistogramAggregates.from_names(["min", "max", "count"])
PCTS = [0.5, 0.9, 0.99]
QS = device_quantiles(PCTS, AGGS)

INTERVALS = 3
MIN_FOLDS_PER_INTERVAL = 4


def _assert_snapshots_identical(a, b, path):
    """Bitwise snapshot equality: every numpy field of the two
    FlushSnapshots compares as raw bytes (stricter than array_equal —
    distinguishes NaN payloads and signed zeros), and the generated
    InterMetric streams (which cover the host-side scalars, names and
    tags) compare exactly."""
    import dataclasses

    from veneur_tpu.core.flusher import generate_inter_metrics

    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, np.ndarray) or isinstance(vb, np.ndarray):
            assert va is not None and vb is not None, (path, f.name)
            assert va.dtype == vb.dtype and va.shape == vb.shape, (
                path, f.name, va.dtype, vb.dtype, va.shape, vb.shape)
            assert va.tobytes() == vb.tobytes(), (path, f.name, va, vb)
        elif isinstance(va, (int, float)) or va is None:
            assert va == vb, (path, f.name, va, vb)
    ma = generate_inter_metrics(a, True, PCTS, AGGS, now=1000)
    mb = generate_inter_metrics(b, True, PCTS, AGGS, now=1000)
    key = lambda m: (m.name, m.type, tuple(m.tags))  # noqa: E731
    da = {key(m): m.value for m in ma}
    db = {key(m): m.value for m in mb}
    assert da == db, (path, {k: (da.get(k), db.get(k))
                             for k in set(da) ^ set(db) or
                             {k for k in da if da[k] != db.get(k)}})


def _drive_worker(micro: bool, use_native: bool, *, fold_every: int = 2,
                  intervals: int = INTERVALS):
    """Ingest a deterministic mixed workload (t-digest timers, HLL sets,
    scalar counters/gauges) for `intervals` flush intervals, micro-
    folding every `fold_every` batches; return (snapshots, worker,
    folds-per-interval). batch_size is small so the python staging
    plane fills mid-interval; thresholds stay under the stage depth so
    no nondeterministic spill folds run."""
    w = DeviceWorker(compression=100, stage_depth=64, batch_size=6,
                     micro_fold=micro, micro_fold_rows=1,
                     micro_fold_max_age_s=1e9)
    if use_native:
        if not w.attach_native():
            pytest.skip("native ingest library unavailable")
    rng = np.random.default_rng(7)
    snaps, folds = [], []
    for _ in range(intervals):
        for batch in range(8):
            lines = []
            for i in range(6):
                lines.append(f"h{i}:{rng.normal():.6f}|ms|#a:b")
                lines.append(f"c{i}:1.5|c")
                lines.append(f"g{i}:{rng.normal():.6f}|g")
                lines.append(f"s{i}:{rng.integers(100)}|s")
            if use_native:
                w.ingest_datagram("\n".join(lines).encode())
            else:
                for ln in lines:
                    w.process_metric(parse_metric(ln.encode()))
            if micro and batch % fold_every == 0 and w.micro_fold_due():
                w.micro_fold_once()
        folds.append(w.micro_folds_epoch)
        snaps.append(w.flush(QS))
    return snaps, w, folds


@pytest.mark.parametrize("use_native", [False, True],
                         ids=["python-plane", "native-plane"])
def test_micro_fold_bit_identical_to_batch_fold(use_native):
    base, _, _ = _drive_worker(False, use_native)
    micro, w, folds = _drive_worker(True, use_native)
    assert len(folds) >= INTERVALS
    assert all(f >= MIN_FOLDS_PER_INTERVAL for f in folds), folds
    assert w.micro_folds_total == sum(folds)
    for n, (a, b) in enumerate(zip(base, micro)):
        _assert_snapshots_identical(a, b, f"interval{n}")


@pytest.mark.parametrize("use_native", [False, True],
                         ids=["python-plane", "native-plane"])
def test_swap_mid_micro_fold_no_loss_no_double(use_native):
    """The fence, deterministically: folds land at different batch
    offsets (including right before the swap with residual staged rows
    outstanding), so every interval's swap runs with a partially
    mirrored plane. Identity must hold for every partition."""
    base, _, _ = _drive_worker(False, use_native)
    for fold_every in (1, 3, 7):
        micro, _, folds = _drive_worker(True, use_native,
                                        fold_every=fold_every)
        assert all(f >= 1 for f in folds), (fold_every, folds)
        for n, (a, b) in enumerate(zip(base, micro)):
            _assert_snapshots_identical(a, b, f"every{fold_every}.interval{n}")


def test_swap_racing_micro_folds_conserves_samples():
    """Threaded smoke of the swap fence: a scheduler thread micro-folds
    while the main thread flushes mid-stream. Lost rows would show up
    as a short histogram count; double-folded rows as a long one (and
    as an inflated counter total)."""
    w = DeviceWorker(compression=100, stage_depth=256, batch_size=4,
                     micro_fold=True, micro_fold_rows=1,
                     micro_fold_max_age_s=1e9)
    lock = threading.Lock()
    stop = threading.Event()

    def scheduler():
        while not stop.is_set():
            with lock:
                if w.micro_fold_due():
                    w.micro_fold_once()
            time.sleep(0.001)

    t = threading.Thread(target=scheduler, daemon=True)
    t.start()
    total = 0
    counts = []
    try:
        for burst in range(6):
            for i in range(200):
                with lock:
                    w.process_metric(parse_metric(b"race.t:%d|ms" % i))
                    w.process_metric(parse_metric(b"race.c:1|c"))
                total += 1
            with lock:
                swapped = w.swap(QS)
            snap = w.extract_snapshot(swapped, QS)
            counts.append(snap)
    finally:
        stop.set()
        t.join(timeout=5.0)
    from veneur_tpu.core.flusher import generate_inter_metrics

    got_histo = 0.0
    got_counter = 0.0
    for snap in counts:
        by_key = {(m.name, m.type): m.value
                  for m in generate_inter_metrics(snap, True, PCTS, AGGS,
                                                  now=1000)}
        got_histo += by_key.get(("race.t.count", MetricType.COUNTER), 0.0)
        got_counter += by_key.get(("race.c", MetricType.COUNTER), 0.0)
    assert got_histo == float(total)
    assert got_counter == float(total)


# -- transfer-ledger accounting -------------------------------------------


def _micro_ledger_bytes(fold_every: int, depth: int) -> tuple[int, dict]:
    w = DeviceWorker(compression=100, stage_depth=depth, batch_size=6,
                     micro_fold=True, micro_fold_rows=1,
                     micro_fold_max_age_s=1e9)
    if not w.attach_native():
        pytest.skip("native ingest library unavailable")
    rng = np.random.default_rng(3)
    for batch in range(12):
        lines = [f"h{i}:{rng.normal():.6f}|ms" for i in range(6)]
        w.ingest_datagram("\n".join(lines).encode())
        if batch % fold_every == 0 and w.micro_fold_due():
            w.micro_fold_once()
    w.flush(QS)
    h2d = dict(w.ledger.flush_h2d())
    return h2d.get("micro_fold", 0), h2d


def test_ledger_micro_fold_bytes_partition_invariant():
    """N micro-folds of the same staged stream book exactly the bytes
    of a single final drain: uploads go out in fixed padded chunks, the
    remainder carries host-side across drains (+-0, not approximately)."""
    ref, _ = _micro_ledger_bytes(12, 64)  # one drain (all at swap)
    assert ref > 0
    for fold_every in (1, 3):
        got, _ = _micro_ledger_bytes(fold_every, 64)
        assert got == ref, (fold_every, got, ref)


def test_ledger_micro_fold_bytes_independent_of_depth():
    """O(samples), never O(micro_folds x depth): COO entries price the
    samples, not the plane shape they land in."""
    totals = {d: _micro_ledger_bytes(1, d)[0] for d in (16, 64, 128)}
    assert len(set(totals.values())) == 1, totals
    # 72 samples -> one padded MICRO_CHUNK of 16-byte COO entries
    from veneur_tpu.ops.microfold import MICRO_CHUNK

    assert totals[64] == 16 * MICRO_CHUNK


def test_ledger_epoch_window_attribution():
    """Micro-fold bytes accumulate against the EPOCH being staged and
    surface in the flush window that extracts it, not the window that
    happens to be open when the fold runs."""
    led = TransferLedger()
    led.count_epoch_h2d(100, "micro_fold")
    led.roll_epoch()                     # swap closes the epoch
    led.begin_flush()                    # its extraction opens a window
    assert led.flush_h2d() == {"micro_fold": 100}
    led.begin_flush()                    # next window: nothing pending
    assert led.flush_h2d() == {}
    assert led.total_h2d_bytes == 100


# -- config / engagement ---------------------------------------------------


def test_env_escape_hatch_disables_micro_fold():
    assert load_config(data={}, env={}).micro_fold is True
    cfg = load_config(data={}, env={"VENEUR_MICRO_FOLD": "0"})
    assert cfg.micro_fold is False


def test_worker_micro_fold_inert_when_disabled():
    w = DeviceWorker(stage_depth=64, micro_fold=False)
    w.process_metric(parse_metric(b"off.t:1|ms"))
    assert not w.micro_fold_due()
    assert w.micro_fold_once() == 0
    assert w.micro_folds_total == 0


def test_server_flush_parity_with_scheduler(tmp_path):
    """Server-level parity under the real micro-fold scheduler thread:
    identical ingest into a micro-fold server (config via load_config,
    so the CI lane's VENEUR_MICRO_FOLD=0 pass exercises the disabled
    path here) and an explicitly-off server must flush equal metrics,
    whenever the scheduler happened to drain."""
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.channel import ChannelMetricSink

    base = dict(statsd_listen_addresses=["udp://127.0.0.1:0"],
                num_workers=1, num_readers=1, interval="10s",
                percentiles=PCTS, micro_fold_rows=1,
                micro_fold_max_age_s=0.02)

    def boot(cfg):
        sink = ChannelMetricSink()
        srv = Server(cfg, metric_sinks=[sink])
        srv.start()
        # small pending batches so the python staging plane fills (and
        # micro-folds engage) at test-sized sample counts
        for w in srv.workers:
            w.batch_size = 8
        return srv

    on = boot(load_config(data=dict(base)))
    off = boot(Config(micro_fold=False, **base))
    try:
        rng = np.random.default_rng(11)
        lines = []
        for i in range(40):
            lines.append(f"sv.h{i % 5}:{rng.normal():.6f}|ms")
            lines.append(f"sv.c{i % 5}:2|c")
            lines.append(f"sv.s{i % 5}:{rng.integers(50)}|s")
        for srv in (on, off):
            w = srv.workers[0]
            for ln in lines:
                # native-attached workers stage through the C++ plane
                # (the one micro-folds source from); python-only rigs
                # exercise the python plane
                with srv._worker_locks[0]:
                    if w._native is not None:
                        w.ingest_datagram(ln.encode())
                    else:
                        w.process_metric(parse_metric(ln.encode()))
        # reader-shard mode (the CI lane's VENEUR_READER_SHARDS=4 pass)
        # disables micro-folds by design — the per-reader planes fold
        # at the flush edge only — so the scheduler never drains there;
        # the flush-parity assertion below is the contract either way
        if (on.config.micro_fold
                and not getattr(on.workers[0], "_reader_ctxs", None)):
            # let the scheduler drain at least once before the flush
            deadline = time.time() + 5.0
            while (time.time() < deadline
                   and on.workers[0].micro_folds_epoch == 0):
                time.sleep(0.01)
            assert on.workers[0].micro_folds_epoch > 0
        m_on = {(m.name, m.type, tuple(m.tags)): m.value
                for m in on.flush()}
        m_off = {(m.name, m.type, tuple(m.tags)): m.value
                 for m in off.flush()}
        drop = {MetricType.STATUS}
        m_on = {k: v for k, v in m_on.items() if k[1] not in drop}
        m_off = {k: v for k, v in m_off.items() if k[1] not in drop}
        assert m_on == m_off
    finally:
        on.shutdown()
        off.shutdown()


# -- controller warmup classification (satellite: cadence judgment) --------


def _iv(ok: bool, tick: float = 100.0, stall: float = 50.0) -> dict:
    return {"cadence_ok": ok, "tick_block_ms": tick,
            "ingest_stall_ms": stall, "flush_ms": tick * 2,
            "drain_ms": 1.0}


def test_classify_warmup_first_interval_compile():
    """The committed-artifact shape: first confirm interval misses
    cadence under a first-encounter XLA compile, the rest land. The
    compile interval is warmup — excluded from steady means and from
    the judged cadence fraction."""
    ivs = [_iv(False, tick=1105.8)] + [_iv(True) for _ in range(9)]
    out = classify_warmup(ivs)
    assert out["warmup_intervals"] == 1
    assert ivs[0]["warmup"] is True
    assert all(i["warmup"] is False for i in ivs[1:])
    assert out["cadence_frac_steady"] == 1.0
    assert out["tick_block_ms_steady"] == 100.0  # compile spike excluded


def test_classify_warmup_grace_is_one_interval():
    """Two leading misses: only the first is warmup — a second
    straggler is a pipeline problem and must count against cadence."""
    ivs = [_iv(False), _iv(False)] + [_iv(True) for _ in range(8)]
    out = classify_warmup(ivs)
    assert out["warmup_intervals"] == 1
    assert ivs[1]["warmup"] is False
    assert out["cadence_frac_steady"] == round(8 / 9, 4)


def test_classify_warmup_never_reclassifies_good_intervals():
    ivs = [_iv(True)] + [_iv(False)] + [_iv(True) for _ in range(4)]
    out = classify_warmup(ivs)
    assert out["warmup_intervals"] == 0
    assert out["cadence_frac_steady"] == round(5 / 6, 4)


def test_classify_warmup_all_warmup_judges_nothing():
    out = classify_warmup([_iv(False)])
    assert out["warmup_intervals"] == 1
    assert out["cadence_frac_steady"] == 1.0
    assert out["tick_block_ms_steady"] == 0.0


def test_dispatched_chunk_buffers_are_not_refilled():
    """An upload returns before the device has the bytes (XLA:CPU aliases
    an aligned host buffer outright), so the host arrays of a dispatched
    chunk must never be written again. Reusing one carry buffer put the
    next chunk's samples under the previous chunk's rows — found by
    chip_smoke.py as hot-series values under cold series' names."""
    import jax.numpy as jnp

    from veneur_tpu.ops import microfold as mf

    uploaded = []

    class Ledger:
        def epoch_h2d(self, host_arr, kind, replicas=1, put=None):
            uploaded.append(host_arr)
            return jnp.asarray(host_arr)

        h2d = epoch_h2d

    chunk = 8
    m = mf.MicroFoldMirror(depth=4, ledger=Ledger(), initial_rows=16,
                           chunk=chunk)
    rows = np.arange(chunk, dtype=np.int32)
    slots = np.zeros(chunk, np.int32)
    first = np.full(chunk, 1.0, np.float32)
    m.feed(rows, slots, first, first)        # a full chunk: dispatched
    sent = [a.copy() for a in uploaded]
    second = np.full(chunk - 1, 2.0, np.float32)
    m.feed(rows[:-1], slots[:-1] + 1, second, second)   # refills the carry
    for before, now in zip(sent, uploaded):
        np.testing.assert_array_equal(before, now)
    st = m.finish()
    dense = np.asarray(mf.mirror_dense(st.vals, chunk, 4))
    np.testing.assert_array_equal(dense[:, 0], first)
    np.testing.assert_array_equal(dense[:chunk - 1, 1], second)


@pytest.mark.parametrize("use_native", [False, True],
                         ids=["python-plane", "native-plane"])
def test_feed_spans_say_what_the_mirror_holds_and_what_they_dispatched(
        use_native):
    """`micro_fold.feed` carries `mirror_rows` (the rows the mirror has
    allocated: what a scatter writes into) and `chunks` (the scatters
    this feed dispatched) beside `samples` and `rows`, and
    `swap.mirror_handoff` carries what the epoch's feeds came to
    (PERF.md section 3): a scatter's device time can then be laid
    against the entries it wrote and the array it wrote them into."""
    from veneur_tpu.ops import microfold as mf

    w = DeviceWorker(compression=100, stage_depth=64, batch_size=6,
                     micro_fold=True, micro_fold_rows=1,
                     micro_fold_max_age_s=1e9, initial_histo_rows=32)
    if use_native and not w.attach_native():
        pytest.skip("native ingest library unavailable")
    w._micro = mf.MicroFoldMirror(
        w.stage_depth, ledger=w.ledger, initial_rows=w._initial_histo_rows,
        chunk=8, guard=w.guard)
    fed = 0
    for batch in range(5):
        lines = [f"h{i}:{batch + i}.5|ms" for i in range(6)]
        if use_native:
            w.ingest_datagram("\n".join(lines).encode())
        else:
            for ln in lines:
                w.process_metric(parse_metric(ln.encode()))
        fed += w.micro_fold_once()
    assert fed == 30
    w.flush(QS)
    spans = w.rec.closed()
    feeds = [s.attrs for s in spans if s.name == "micro_fold.feed"]
    assert [a["samples"] for a in feeds] == [6] * 5
    assert all(a["rows"] == 6 for a in feeds)
    # 30 entries in chunks of 8: the feeds that reach 8, 16 and 24
    # entries scatter, the padded fourth chunk is the flush's
    assert [a["chunks"] for a in feeds] == [0, 1, 1, 1, 0]
    # nothing is allocated before the first scatter
    assert [a["mirror_rows"] for a in feeds] == [0, 32, 32, 32, 32]
    (handoff,) = [{k: v for k, v in s.attrs.items() if k != "cpu_s"}
                  for s in spans if s.name == "swap.mirror_handoff"]
    assert handoff == {"samples": 30, "rows": 6, "mirror_rows": 32,
                       "chunks": 3}


# -- the flat mirror (PR 41) -----------------------------------------------

def _host_plane(s_eff, depth, rows, slots, data):
    plane = np.zeros((s_eff, depth), np.float32)
    plane[rows, slots] = data
    return plane


@pytest.mark.parametrize("tail", [0, 3], ids=["full-chunks", "padded-chunk"])
@pytest.mark.parametrize("grow", [False, True],
                         ids=["preset", "grown-mid-epoch"])
@pytest.mark.parametrize("s_eff", [24, 32, 64],
                         ids=["mirror-larger", "mirror-equal",
                              "mirror-smaller"])
def test_flat_mirror_through_mirror_dense_is_the_host_plane(
        s_eff, grow, tail):
    """The mirror keeps flat [M x B] arrays, entry row x B + slot;
    `mirror_dense` yields bitwise the dense [s_eff, B] plane the batch
    path would have uploaded: a prefix when the mirror is the larger,
    the whole, or zero-padded when the directory outgrew the mirror;
    whether the mirror was allocated at its size or grew in mid-epoch
    (`_grow_mirror`: a prefix copy), and whether or not the last chunk
    went out padded with DROP_ROW."""
    from veneur_tpu.ops import microfold as mf

    depth, chunk, m_rows = 4, 8, 32
    rng = np.random.default_rng(s_eff + 2 * grow + tail)
    # distinct (row, slot) pairs over rows 0..19: the first feed stays
    # under 8 rows, the second reaches row 19 and forces 8 -> 32
    n = 5 * chunk + tail
    low = rng.permutation(8 * depth)[:2 * chunk]
    high = 8 * depth + rng.permutation(12 * depth)[:n - 2 * chunk]
    high[0] = 19 * depth + 1
    at = np.concatenate([low, high])
    rows = (at // depth).astype(np.int32)
    slots = (at % depth).astype(np.int32)
    vals = rng.normal(size=n).astype(np.float32)
    wts = rng.integers(1, 9, n).astype(np.float32)
    m = mf.MicroFoldMirror(depth=depth, chunk=chunk,
                           initial_rows=8 if grow else m_rows)
    cut = 2 * chunk
    m.feed(rows[:cut], slots[:cut], vals[:cut], wts[:cut])
    assert m.mirror_rows == (8 if grow else m_rows)
    m.feed(rows[cut:], slots[cut:], vals[cut:], wts[cut:])
    st = m.finish()
    assert st.chunks == 5 + (tail > 0) and st.samples == n
    assert st.rows_hi == 20
    assert st.vals.shape == st.wts.shape == (m_rows * depth,)
    for got, data in ((st.vals, vals), (st.wts, wts)):
        dense = np.asarray(mf.mirror_dense(got, s_eff, depth))
        want = _host_plane(s_eff, depth, rows, slots, data)
        assert dense.dtype == want.dtype and dense.shape == want.shape
        assert dense.tobytes() == want.tobytes()


@pytest.mark.parametrize("row,slot", [
    (int(np.iinfo(np.int32).max), 0),   # DROP_ROW: x 64 wraps to -64
    (16, 0),                            # the first row past the mirror
    (1 << 26, 3),                       # x 64 wraps to 0: row 0's slots
    ((1 << 26) + 5, 3),                 # x 64 wraps into row 5
    (-1, 0),                            # would count back from the end
    (3, 64),                            # a slot past the depth: row 4's
    (3, -1),                            # row 2's last slot
], ids=["drop-row", "row-eq-mirror-rows", "wraps-to-zero",
        "wraps-into-live-row", "negative-row", "slot-eq-depth",
        "negative-slot"])
def test_scatter_entries_outside_the_mirror_land_nowhere(row, slot):
    """`rows x depth + slots` is int32: DROP_ROW x 64 wraps, and so does
    any row from 2^25 up. An entry whose row is outside [0, mirror_rows)
    or whose slot is outside [0, depth) changes no element of either
    array; the live entry beside it in the chunk still lands."""
    import jax.numpy as jnp

    from veneur_tpu.ops import microfold as mf

    depth, m_rows = 64, 16
    before = np.arange(1, m_rows * depth + 1, dtype=np.float32)
    rows = np.asarray([row, 7], np.int32)
    slots = np.asarray([slot, 2], np.int32)
    dv, dw = mf._scatter_chunk(
        jnp.asarray(before), jnp.asarray(-before), jnp.asarray(rows),
        jnp.asarray(slots), jnp.asarray([111.0, 222.0], jnp.float32),
        jnp.asarray([333.0, 444.0], jnp.float32), depth=depth)
    want_v, want_w = before.copy(), -before
    want_v[7 * depth + 2], want_w[7 * depth + 2] = 222.0, 444.0
    np.testing.assert_array_equal(np.asarray(dv), want_v)
    np.testing.assert_array_equal(np.asarray(dw), want_w)


@pytest.mark.parametrize("allocated", [False, True],
                         ids=["first-allocation", "growth"])
def test_mirror_refuses_a_flat_index_past_int32(allocated):
    """2^25 rows x 64 slots = 2^31 entries: the flat index would not fit
    int32. `_ensure_rows` refuses before it allocates or grows."""
    from veneur_tpu.ops import microfold as mf

    m = mf.MicroFoldMirror(depth=64,
                           initial_rows=16 if allocated else 1 << 25)
    if allocated:
        m._ensure_rows(1)
        assert m.mirror_rows == 16
    with pytest.raises(ValueError, match="int32"):
        m._ensure_rows(1 << 25)
    assert m.mirror_rows == (16 if allocated else 0)


@pytest.mark.parametrize("use_native", [False, True],
                         ids=["python-plane", "native-plane"])
def test_mirror_dense_span_says_what_the_conversion_met(use_native):
    """`extract.mirror_dense` wraps the dispatch of the flush's one
    change of layout (flat mirror -> the fold's [rows, depth] planes)
    inside `extract.mirror_fold`, with `mirror_rows` (what the mirror had
    allocated) and `rows` (what the fold takes) as attrs; it waits for
    nothing, so `extract_wait_s` keeps its meaning (PERF.md section 3)."""
    from veneur_tpu.ops import microfold as mf

    w = DeviceWorker(compression=100, stage_depth=64, batch_size=6,
                     micro_fold=True, micro_fold_rows=1,
                     micro_fold_max_age_s=1e9, initial_histo_rows=64)
    if use_native and not w.attach_native():
        pytest.skip("native ingest library unavailable")
    w._micro = mf.MicroFoldMirror(
        w.stage_depth, ledger=w.ledger, initial_rows=16, chunk=8,
        guard=w.guard)
    lines = [f"h{i}:{i}.5|ms" for i in range(6)]
    if use_native:
        w.ingest_datagram("\n".join(lines).encode())
    else:
        for ln in lines:
            w.process_metric(parse_metric(ln.encode()))
    assert w.micro_fold_once() == 6
    w.flush(QS)
    spans = {s.id: s for s in w.rec.closed()}
    (dense,) = [s for s in spans.values() if s.name == "extract.mirror_dense"]
    assert {k: v for k, v in dense.attrs.items() if k != "cpu_s"} == {
        "mirror_rows": 16, "rows": 64}
    up = spans[dense.parent]
    assert (up.name, up.attrs["op"]) == ("dispatch", "staged")
    assert spans[up.parent].name == "extract.mirror_fold"
    # the planes the fold reads, booked on its dispatch span as before
    assert up.attrs["bytes"] >= 2 * 64 * 64 * 4
