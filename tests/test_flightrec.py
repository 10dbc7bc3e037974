"""The server's span record (core/flightrec.py): the recorder itself,
the spans a served flush leaves and what last_flush_phases derives from
them, the names on the device programs, the C++ reader's clock, and the
lifetime sample tally the swap keeps under the native lock."""

import json
import socket
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from veneur_tpu.core import flightrec
from veneur_tpu.core.config import Config
from veneur_tpu.core.server import Server
from veneur_tpu.sinks.channel import ChannelMetricSink

PHASE_SPANS = {"swap_s": "flush.begin", "extract_s": "flush.extract",
               "generate_s": "flush.generate", "sink_flush_s": "emit.sinks"}


def _wait_for(predicate, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


def _less_cpu(attrs: dict) -> dict:
    """A span's attrs without the one every span has."""
    assert isinstance(attrs["cpu_s"], float) and attrs["cpu_s"] >= 0.0
    return {k: v for k, v in attrs.items() if k != "cpu_s"}


# -- the recorder ----------------------------------------------------------

def test_spans_nest_by_thread_and_inherit_the_flush_ordinal():
    rec = flightrec.Recorder()
    with rec.span("flush", flush=7) as root:
        with rec.span("flush.extract") as ext:
            with rec.span("extract.readback", wait=True) as rb:
                assert rec.current() is rb
        with rec.span("flush.emit", flush=8) as emit:
            pass
    assert (ext.parent, rb.parent, emit.parent) == (root.id, ext.id, root.id)
    assert (root.flush, ext.flush, rb.flush, emit.flush) == (7, 7, 7, 8)
    assert root.parent is None and rec.current() is None
    # children close before their parents; as_list is JSON types only
    assert [s.name for s in rec.closed()] == [
        "extract.readback", "flush.extract", "flush.emit", "flush"]
    assert _less_cpu(rb.as_list()[6]) == {"wait": True}
    assert root.t0 <= ext.t0 <= rb.t0 <= rb.t1 <= ext.t1 <= root.t1
    json.dumps(rec.of_flush(7))
    assert [s[1] for s in rec.of_flush(8)] == ["flush.emit"]


def test_parents_do_not_cross_threads_unless_handed_over():
    rec = flightrec.Recorder()
    seen = {}

    def other(parent):
        with rec.span("micro_fold") as mf:          # its own root
            with rec.span("micro_fold.feed") as feed:
                pass
        with rec.span("emit.sink", parent=parent, sink="x") as sink:
            pass
        seen.update(mf=mf, feed=feed, sink=sink)

    with rec.span("flush", flush=3) as root:
        t = threading.Thread(target=other, args=(root,))
        t.start()
        t.join()
    assert seen["mf"].parent is None and seen["mf"].flush is None
    assert seen["feed"].parent == seen["mf"].id
    assert seen["sink"].parent == root.id and seen["sink"].flush == 3


def test_the_ring_is_bounded_and_add_sums_into_the_open_span():
    rec = flightrec.Recorder(capacity=16)
    for i in range(100):
        with rec.span("s", flush=i):
            rec.add("bytes", 3)
            rec.add("bytes", 4)
    kept = rec.closed()
    assert len(kept) == 16 and kept[-1].flush == 99 and kept[0].flush == 84
    assert _less_cpu(kept[0].attrs) == {"bytes": 7}
    assert rec.last("s") is kept[-1] and rec.last("nope") is None
    rec.add("bytes", 1)  # no span open: dropped, not raised


def test_a_span_that_raises_closes_and_says_so():
    rec = flightrec.Recorder()
    with pytest.raises(ValueError):
        with rec.span("flush", flush=1):
            with rec.span("flush.extract"):
                raise ValueError("boom")
    by = {s.name: s for s in rec.closed()}
    assert by["flush.extract"].attrs["error"] == "ValueError"
    assert by["flush"].attrs["error"] == "ValueError"
    assert by["flush"].t1 >= by["flush.extract"].t1 > 0
    assert rec.current() is None


# -- what a span's thread did with its time ----------------------------------

def _spin(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("how", ["sleeps", "spins", "spins_then_sleeps"])
def test_cpu_s_tells_a_span_that_worked_from_one_that_stood_still(how):
    rec = flightrec.Recorder()
    with rec.span("s", flush=1) as sp:
        if how != "sleeps":
            _spin(0.1)
        if how != "spins":
            time.sleep(0.1)
    cpu = sp.attrs["cpu_s"]
    if how == "sleeps":
        assert cpu < 0.1 * sp.seconds
    elif how == "spins":
        # a preempted spin is longer on the wall, never on the thread
        assert 0.1 <= cpu <= sp.seconds + 1e-3 and cpu < 0.12
    else:
        assert 0.1 <= cpu < 0.12 and sp.seconds >= 0.2
    # in as_list's attrs, JSON-clean, and the list keeps its seven fields
    (row,) = rec.of_flush(1)
    assert len(row) == 7 and row[6]["cpu_s"] == cpu
    assert json.loads(json.dumps(row))[6]["cpu_s"] == cpu


def test_cpu_s_is_the_span_threads_own_clock():
    """A span open on a thread that sleeps while another spins reads
    the sleeper's clock, not the process's."""
    rec = flightrec.Recorder()
    other = threading.Thread(target=_spin, args=(0.15,))
    with rec.span("sleeper") as sp:
        other.start()
        other.join(10.0)
    assert not other.is_alive() and sp.seconds >= 0.1
    assert sp.attrs["cpu_s"] < 0.5 * sp.seconds


@pytest.mark.parametrize("case", ["full", "young_and_long",
                                  "young_and_short"])
def test_a_collection_inside_an_open_span_is_a_gc_child_of_it(case,
                                                              monkeypatch):
    import gc

    generation = 2 if case == "full" else 0
    # a pass of the young generation is a span from GC_SPAN_S on
    monkeypatch.setattr(flightrec, "GC_SPAN_S",
                        0.0 if case == "young_and_long" else 10.0)
    rec = flightrec.Recorder()
    with rec.span("flush.begin", flush=5) as begin:
        with rec.span("swap.handoff") as handoff:
            gc.collect(generation)
        assert rec.current() is begin
    mine = [s for s in rec.closed() if s.name == "gc"
            and s.attrs["generation"] == generation]
    if case == "young_and_short":
        assert mine == []
        return
    assert mine, [s.as_list() for s in rec.closed()]
    g = mine[-1]
    assert g.parent == handoff.id and g.flush == 5
    assert handoff.t0 <= g.t0 <= g.t1 <= handoff.t1
    assert g.attrs["collected"] >= 0 and 0.0 <= g.attrs["cpu_s"]
    assert g.id not in (begin.id, handoff.id)
    json.dumps(rec.of_flush(5))
    # with no span open on the thread a collection leaves nothing
    n = len(rec.closed())
    gc.collect(generation)
    assert len(rec.closed()) == n


def test_a_record_that_goes_takes_its_gc_callback_with_it():
    import gc

    before = list(gc.callbacks)
    rec = flightrec.Recorder()
    (mine,) = [cb for cb in gc.callbacks if cb not in before]
    del rec
    gc.collect()
    assert mine not in gc.callbacks


# -- a served flush --------------------------------------------------------

def _served(**kw):
    cfg = Config(**{**dict(
        statsd_listen_addresses=["tcp://127.0.0.1:0"], num_workers=1,
        num_readers=1, interval="10s", percentiles=[0.5, 0.99],
        tpu_native_ingest=True, micro_fold_rows=1,
        micro_fold_max_age_s=0.02), **kw})
    sink = ChannelMetricSink()
    srv = Server(cfg, metric_sinks=[sink])
    ports = srv.start()
    return srv, sink, next(iter(ports.values()))


def _lines(n_series=50, per=8):
    out = []
    for i in range(n_series):
        out += [f"fr.t{i}:{v + 1}|ms" for v in range(per)]
        out += [f"fr.c{i}:2|c", f"fr.g{i}:1.5|g", f"fr.s:{i}|s"]
    return ("\n".join(out) + "\n").encode(), len(out)


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s[1], []).append(s)
    return out


def test_a_served_flush_leaves_its_spans_and_the_old_phase_keys():
    srv, sink, port = _served()
    if not srv.native_mode:
        srv.shutdown()
        pytest.skip("native ingest library unavailable")
    try:
        payload, n = _lines()
        with socket.create_connection(("127.0.0.1", port)) as s:
            s.sendall(payload)
            assert _wait_for(
                lambda: srv.ingress_stats()["samples_processed"] >= n)
            # the scheduler thread drains what was staged: a micro-fold
            assert _wait_for(lambda: srv.workers[0].micro_folds_epoch > 0)
            srv.flush()
        phases = srv.last_flush_phases
        spans = phases["spans"]
        json.dumps(spans)
        by = _by_name(spans)
        ordinal = srv.flush_count
        assert {s[5] for s in spans} == {ordinal}

        # every old key is the length of its span
        for key, name in PHASE_SPANS.items():
            (sp,) = by[name]
            assert phases[key] == pytest.approx(sp[3] - sp[2], abs=1e-9)
        assert phases["drain_s"] == pytest.approx(sum(
            s[3] - s[2] for s in spans
            if s[1] in ("swap.drain.residual", "swap.mirror_handoff")))
        assert set(phases) >= set(PHASE_SPANS) | {"drain_s", "spans"}
        # and ingress_stats keeps handing on numbers only
        assert "spans" not in srv.ingress_stats()["last_flush_phases"]
        assert srv.ingress_stats()["last_tick_s"] == pytest.approx(
            by["flush"][0][3] - by["flush"][0][2])

        # the tree: flush -> phases -> their children, on one thread
        (root,) = by["flush"]
        for name in ("flush.begin", "flush.extract", "flush.generate",
                     "flush.emit"):
            assert by[name][0][4] == root[0]
        begin, extract = by["flush.begin"][0][0], by["flush.extract"][0][0]
        assert by["swap.lock_wait"][0][4] == begin
        (swap,) = by["swap"]
        assert swap[4] == begin
        for name in ("swap.drain", "swap.spill_fold", "swap.handoff",
                     "swap.reset"):
            assert all(s[4] == swap[0] for s in by[name]), name
        assert by["swap.drain.residual"][0][4] == by["swap.drain"][0][0]
        for name in ("extract.mirror_fold", "extract.quantiles",
                     "extract.readback", "extract.unpack", "extract.sets",
                     "extract.guard_tick"):
            assert by[name][0][4] == extract, name
        assert _less_cpu(by["extract.readback"][0][6]) == {"wait": True}
        # what extract.sets is made of: the tick's compaction of what the
        # drains left pending, then every row's estimate (one set, sparse)
        (sets,) = by["extract.sets"]
        (compact,), (estimate,) = (by["extract.sets.compact"],
                                   by["extract.sets.estimate"])
        assert compact[4] == estimate[4] == sets[0]
        assert 0 < compact[6]["pending"] <= 50
        assert _less_cpu(estimate[6]) == {"sparse_rows": 1, "dense_rows": 0}
        assert sets[2] <= compact[2] <= compact[3] <= estimate[2] \
            <= estimate[3] <= sets[3]
        (sinks,) = by["emit.sinks"]
        assert sinks[4] == by["flush.emit"][0][0]
        assert [(s[4], s[6]["sink"]) for s in by["emit.sink"]] == [
            (sinks[0], "channel")]

        # the ingest side of the epoch: micro-folds on the scheduler's
        # thread and adoption wherever it ran, under the same ordinal
        assert by["micro_fold"] and by["adopt"]
        mf = next(m for m in by["micro_fold"] if m[6]["samples"] > 0)
        assert mf[4] is None and mf[6]["rows"] > 0
        kids = {s[1] for s in spans if s[4] == mf[0]}
        assert kids == {"micro_fold.lock_wait", "micro_fold.drain",
                        "micro_fold.feed"}
        assert sum(s[6]["series"] for s in by["adopt"]
                   + by.get("swap.adopt", [])) == 50 * 3 + 1

        # device dispatches take their span at the guard's seam; the
        # fold's and the extract's say how many bytes they had to move
        ops = {s[6]["op"]: s for s in by["dispatch"]}
        assert {"micro", "staged", "extract"} <= set(ops)
        s_eff, depth = 1024, srv.config.tpu_stage_depth
        pool = 2 * s_eff * 128 * 4 + 12 * s_eff * 4
        assert ops["staged"][6]["bytes"] == 2 * pool + 2 * s_eff * depth * 4
        assert ops["staged"][4] == by["extract.mirror_fold"][0][0]
        assert ops["extract"][6]["bytes"] > pool

        # a second flush files under the next ordinal
        srv.flush()
        assert {s[5] for s in srv.last_flush_phases["spans"]} == {ordinal + 1}
    finally:
        srv.shutdown()


def test_a_flush_that_raises_closes_its_spans(monkeypatch):
    srv, sink, port = _served(tpu_native_ingest=False)
    try:
        srv.process_metric_packet(b"fr.boom:1|ms")
        before = srv.last_flush_phases

        def boom(job):
            raise RuntimeError("generate failed")

        monkeypatch.setattr(srv, "_flush_generate_batch", boom)
        with pytest.raises(RuntimeError):
            srv.flush()
        assert srv.last_flush_phases is before  # nothing half-finished
        by = {s.name: s for s in srv.rec.closed()
              if s.flush == srv.flush_count}
        assert by["flush.generate"].attrs["error"] == "RuntimeError"
        assert by["flush"].attrs["error"] == "RuntimeError"
        assert by["flush"].t1 >= by["flush.generate"].t1 > 0
        assert "error" not in by["flush.extract"].attrs
        assert srv.rec.current() is None
        monkeypatch.undo()
        srv.process_metric_packet(b"fr.boom:2|ms")
        srv.flush()
        assert "flush.emit" in {s[1] for s in srv.last_flush_phases["spans"]}
    finally:
        srv.shutdown()


# -- names on the device ---------------------------------------------------

def _fold_args(rows=256, depth=8):
    f32 = jnp.float32
    two = [jnp.zeros((rows, 128), f32)] * 2
    return two + [jnp.zeros((rows,), f32)] * 12, depth


def _lowered_text(name):
    from veneur_tpu.core import worker as wk
    from veneur_tpu.ops import hll, microfold as mf

    fields, depth = _fold_args()
    rows = fields[0].shape[0]
    plane = jnp.zeros((rows, depth), jnp.float32)
    i32 = jnp.zeros((64,), jnp.int32)
    f32v = jnp.zeros((64,), jnp.float32)
    regs = jnp.zeros((16, 1 << 14), jnp.int8)
    lowered = {
        "fold_staged": lambda: wk._histo_fold_staged.lower(
            *fields, plane, plane, compression=100.0),
        "ingest_step": lambda: wk._histo_ingest_step.lower(
            *fields, i32, jnp.zeros((256,), jnp.int32),
            jnp.zeros((256,), jnp.float32), jnp.zeros((256,), jnp.float32),
            compression=100.0),
        "flush_extract": lambda: wk._histo_flush_extract.lower(
            *fields, jnp.asarray([0.5, 0.99], jnp.float32)),
        "pack_extract": lambda: wk._pack_extract_columns.lower(
            jnp.zeros((rows, 2), jnp.float32),
            *[jnp.zeros((rows,), jnp.float32)] * 10),
        "scatter_chunk": lambda: mf._scatter_chunk.lower(
            plane.reshape(-1), plane.reshape(-1), i32, i32, f32v, f32v,
            depth=depth),
        "mirror_dense": lambda: mf.mirror_dense.lower(
            plane.reshape(-1), rows // 2, depth),
        "hll_estimate": lambda: hll.estimate.lower(regs, 14),
        "hll_insert": lambda: hll.insert_batch.lower(
            regs, i32, i32, jnp.zeros((64,), jnp.int8)),
    }[name]()
    # the compiled program's own text carries op_name metadata
    return lowered.compile().as_text()


@pytest.mark.parametrize("program,scopes", [
    ("fold_staged", ["fold_staged.row_stats", "fold_staged.merge",
                     "tdigest.compress.sort", "tdigest.compress.scan",
                     "tdigest.k_bucket", "tdigest.compress.merge",
                     "segments.last_marked_carry",
                     "tdigest.compress.resort", "fold_staged.scalars"]),
    ("ingest_step", ["ingest_step.gather", "tdigest.add_batch.sort",
                     "tdigest.prefix_scans", "segments.segmented_cumsum",
                     "tdigest.add_batch.row_stats",
                     "tdigest.add_batch.batch_digest", "tdigest.k_bucket",
                     "tdigest.compress.sort", "tdigest.compress.merge",
                     "ingest_step.scatter"]),
    ("flush_extract", ["tdigest.quantile", "flush_extract.sums"]),
    ("pack_extract", ["pack_extract"]),
    ("scatter_chunk", ["microfold.scatter"]),
    ("mirror_dense", ["microfold.dense"]),
    ("hll_estimate", ["hll.estimate"]),
    ("hll_insert", ["hll.insert.sort", "hll.insert.scatter_max"]),
])
def test_every_scope_is_named_in_its_compiled_program(program, scopes):
    text = _lowered_text(program)
    missing = [s for s in scopes if s + "/" not in text]
    assert not missing, missing


# -- the C++ reader's clock ------------------------------------------------

def test_the_reader_counter_rises_with_traffic_and_fits_the_wall_clock():
    srv, sink, port = _served()
    if not srv.native_mode:
        srv.shutdown()
        pytest.skip("native ingest library unavailable")
    try:
        t_open = time.time()
        payload, n = _lines(200, 16)
        with socket.create_connection(("127.0.0.1", port)) as s:
            time.sleep(0.3)                      # idle: inside recv
            recv0, busy0 = srv._reader_ns()
            s.sendall(payload)
            assert _wait_for(
                lambda: srv.ingress_stats()["samples_processed"] >= n)
            time.sleep(0.05)
            s.sendall(b"fr.last:1|c\n")          # ends the recv in flight
            assert _wait_for(lambda: srv._reader_ns()[1] > busy0)
            recv1, busy1 = srv._reader_ns()
        wall_ns = (time.time() - t_open) * 1e9
        assert recv0 >= 0 and busy0 >= 0
        assert busy1 > busy0 and recv1 >= recv0 + 0.2e9
        # one reader thread: its two shares cannot outrun the wall clock
        assert recv1 + busy1 <= wall_ns
        stats = srv.workers[0].reader_stats()
        assert stats["recv_ns"][0] >= recv1 and stats["busy_ns"][0] >= busy1
        srv.flush()
        (begin,) = [s for s in srv.last_flush_phases["spans"]
                    if s[1] == "flush.begin"]
        assert begin[6]["reader_busy_ns"] >= busy1
        assert begin[6]["reader_recv_ns"] >= recv1
    finally:
        srv.shutdown()


def test_flush_begin_says_what_the_readers_busy_time_was_made_of():
    srv, sink, port = _served()
    if not srv.native_mode:
        srv.shutdown()
        pytest.skip("native ingest library unavailable")
    try:
        payload, n = _lines(200, 16)
        with socket.create_connection(("127.0.0.1", port)) as s:
            s.sendall(payload)
            assert _wait_for(
                lambda: srv.ingress_stats()["samples_processed"] >= n)
            busy = srv._reader_ns()[1]
            s.sendall(b"fr.last:1|c\n")         # ends the recv in flight
            assert _wait_for(lambda: srv._reader_ns()[1] > busy)
            time.sleep(0.05)                     # the reader is in recv
            srv.flush()
        (begin,) = [s for s in srv.last_flush_phases["spans"]
                    if s[1] == "flush.begin"]
        a = begin[6]
        assert a["reader_parse_ns"] + a["reader_lock_wait_ns"] \
            + a["reader_commit_ns"] == a["reader_busy_ns"]
        assert a["reader_commit_ns"] > 0 and a["reader_parse_ns"] > 0
        assert a["reader_lock_wait_ns"] >= 0
        # the lock's record is the commit's: one entry a lock hold
        lock = srv.workers[0]._native.lock_stats(samples=False)
        assert lock["acquisitions"] == a["commit_batches"]
        assert lock["hold_ns_total"] == a["reader_commit_ns"]
    finally:
        srv.shutdown()


# -- the ingest side: who holds which lock -----------------------------------

def test_the_ingest_side_says_who_held_which_lock():
    """A server that is built and never started, each of its threads'
    steps called by hand: the pump's threshold drain, a micro-fold, the
    sync sweep and the flush leave the spans that say which of them
    held the worker's ingest lock and which the native context's."""
    cfg = Config(statsd_listen_addresses=["tcp://127.0.0.1:0"],
                 num_workers=1, num_readers=1, interval="10s",
                 percentiles=[0.5], tpu_native_ingest=True,
                 tpu_batch_size=64, tpu_stage_depth=8)
    srv = Server(cfg, metric_sinks=[ChannelMetricSink()])
    try:
        if not srv.native_mode:
            pytest.skip("native ingest library unavailable")
        w = srv.workers[0]
        hot = "\n".join(f"fr.hot:{v}|ms" for v in range(100)).encode()
        srv._drain_native_thresholds()           # nothing due: no span
        assert srv.rec.last("pump") is None
        srv._native_router.ingest(hot)           # 92 past the depth spill
        srv._native_router.ingest(b"fr.c:1|c\nfr.g:2|g\nfr.s:x|s")
        srv._drain_native_thresholds()
        srv._native_router.ingest(_lines(20, 4)[0])
        srv.sync_native_series_once()
        srv._native_router.ingest(b"fr.late:1|ms")
        srv._micro_fold(0, w)
        srv.flush()
        spans = srv.last_flush_phases["spans"]
        json.dumps(spans)
        by = _by_name(spans)
        ids = {s[0]: s for s in spans}

        def up(s):
            return ids[s[4]][1] if s[4] in ids else None

        # each taker of the worker's lock: its span, then the wait
        for name in ("pump", "sync", "micro_fold"):
            (sp,) = by[name]
            (lw,) = by[name + ".lock_wait"]
            assert sp[4] is None and lw[4] == sp[0]
            assert sp[6]["worker"] == 0 and sp[5] == srv.flush_count
        # the pump's drain: the context's lock, the adoption, the apply
        pump = by["pump"][0]
        kids = [s for s in spans if s[4] == pump[0]]
        assert [s[1] for s in sorted(kids, key=lambda s: s[2])] == [
            "pump.lock_wait", "drain.raw", "adopt", "drain.apply"]
        raw = next(s for s in by["drain.raw"] if s[4] == pump[0])
        assert _less_cpu(raw[6]) == {"ctx_lock": True, "ctx": 0, "histo": 92,
                                     "sets": 1, "counters": 1, "gauges": 1}
        apply = next(s for s in by["drain.apply"] if s[4] == pump[0])
        assert apply[6]["spill_samples"] == 92
        # (a spill fold into the live pool is the dispatch op "fold")
        assert "fold" in {s[6]["op"] for s in spans
                          if s[4] == apply[0] and s[1] == "dispatch"}
        # the sweep adopts and holds no context lock of its own
        assert [s[1] for s in spans if s[4] == by["sync"][0][0]] == [
            "sync.lock_wait", "adopt"]
        # a micro-fold: the drain's three under micro_fold.drain, each
        # stage delta under micro_fold.feed
        (mdrain,) = by["micro_fold.drain"]
        assert {s[1] for s in spans if s[4] == mdrain[0]} >= {
            "drain.raw", "drain.apply"}
        deltas = by["feed.stage_delta"]
        assert all(up(s) == "micro_fold.feed" and s[6]["ctx_lock"] is True
                   for s in deltas)
        assert sum(s[6]["samples"] for s in deltas) \
            == by["micro_fold.feed"][0][6]["samples"] == 8 + 20 * 4 + 1
        # one copy into the carry a delta that held something
        assert [up(s) for s in by["feed.carry"]] == ["micro_fold.feed"] * sum(
            1 for s in deltas if s[6]["samples"])
        # and the swap's own hold says so too
        assert all(s[6]["ctx_lock"] is True for s in by["swap.drain"])
        held = [s for s in spans if s[6].get("ctx_lock")]
        assert {s[1] for s in held} == {"drain.raw", "feed.stage_delta",
                                        "swap.drain"}
    finally:
        srv.shutdown()


# -- the mirror's replay copy ----------------------------------------------

@pytest.mark.parametrize("case", ["native_clean", "native_fault_before_fold",
                                  "native_fault_after_fold", "python_plane",
                                  "nothing_staged"])
def test_the_replay_copy_says_what_it_is_and_where_it_went(case):
    """`swap.handoff` names the micro-fold mirror's replay copy (`plane`:
    the detached C++ plane, held as it is; `host`: the Python path's dense
    pair; `none`) and its rows; a native plane's release is the span
    `extract.replay_release` under `flush.extract`, after the extract's
    dispatch and before the readback that waits for it, and is not a
    wait itself; the compaction only a failover needs is
    `extract.replay_compact`, there and nowhere else (PERF.md section 3)."""
    from veneur_tpu.utils import faults as fl

    native = case.startswith("native")
    cfg = Config(statsd_listen_addresses=["tcp://127.0.0.1:0"],
                 num_workers=1, num_readers=1, interval="10s",
                 percentiles=[0.5], tpu_native_ingest=native,
                 device_fault_streak=100)
    srv = Server(cfg, metric_sinks=[ChannelMetricSink()])
    try:
        if native and not srv.native_mode:
            pytest.skip("native ingest library unavailable")
        lines = [b"fr.c:1|c"] if case == "nothing_staged" else [
            b"fr.t%d:%d.5|ms" % (i % 5, i) for i in range(40)]
        if native:
            srv._native_router.ingest(b"\n".join(lines))
        else:
            for ln in lines:
                srv.handle_metric_packet(ln)
        srv._micro_fold(0, srv.workers[0])
        op = {"native_fault_before_fold": "staged",
              "native_fault_after_fold": "extract"}.get(case)
        plan = fl.DeviceFaultPlan(
            seed=3, op_windows={op: [(0, 10**6, "oom")]} if op else {})
        with fl.DeviceFaultInjector(plan):
            srv.flush()
        spans = srv.last_flush_phases["spans"]
        by = _by_name(spans)
        (handoff,) = by["swap.handoff"]
        assert handoff[4] == by["swap"][0][0]
        want = {"native": ("plane", 4096), "python": ("host", 4096),
                "nothing": ("none", 0)}[case.split("_")[0]]
        assert (handoff[6]["replay"], handoff[6]["plane_rows"]) == want
        extract = by["flush.extract"][0][0]
        release = by.get("extract.replay_release", [])
        compact = by.get("extract.replay_compact", [])
        assert len(release) == (case == "native_clean")
        assert len(compact) == (case == "native_fault_before_fold")
        for sp in release:
            assert sp[4] == extract and _less_cpu(sp[6]) == {"rows": 4096}
            assert (by["extract.quantiles"][0][3] <= sp[2]
                    and sp[3] <= by["extract.readback"][0][2])
        for sp in compact:
            assert sp[4] == extract and _less_cpu(sp[6]) == {"samples": 40}
    finally:
        srv.shutdown()


# -- the lifetime tally ----------------------------------------------------

def test_a_line_committed_just_before_the_swap_takes_the_lock_is_tallied():
    from veneur_tpu.core.worker import DeviceWorker

    w = DeviceWorker(stage_depth=8)
    if not w.attach_native():
        pytest.skip("native ingest library unavailable")
    w.ingest_datagram(b"pt.a:1|c\npt.b:2|ms")
    native = w._native
    lock = native.lock
    committed = []

    def lock_after_a_commit():
        # a reader commits between the top of swap() and its lock
        if not committed:
            committed.append(native.ingest(b"pt.a:5|c\npt.c:1|g"))
        lock()

    native.lock = lock_after_a_commit
    try:
        snap = w.flush(np.asarray([0.5], np.float32))
    finally:
        del native.lock
    assert committed == [2]
    assert w.processed_total == 4 and w.processed == 0
    assert float(snap.scalars.counter_values[0]) == 6.0
    # and the next epoch starts from nothing
    w.ingest_datagram(b"pt.a:1|c")
    w.flush(np.asarray([0.5], np.float32))
    assert w.processed_total == 5
