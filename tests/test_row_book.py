"""A pool's row book in its two representations (core/directory.RowBook).

By id the book is an array of one native context's lifetime series ids
and everything a reader asks for is derived from them; materialised it
is the per-interval containers the program always had. Whatever arrives
first decides, and nothing a reader sees may tell the two apart.
"""

import numpy as np
import pytest

from veneur_tpu.core.directory import (LifetimeSeries, RowMeta, RowView,
                                       ScopeClass, _Pool, build_frag)
from veneur_tpu.core.flusher import device_quantiles, generate_columnar
from veneur_tpu.core.metrics import (Aggregate, HistogramAggregates,
                                     MetricKey, route_info)
from veneur_tpu.core.tenancy import TenantLedger
from veneur_tpu.core.worker import DeviceWorker, ScalarPool

POOLS = ["histo", "sets", "counters", "gauges"]
PCTS = [0.5, 0.99]
AGGS = HistogramAggregates(Aggregate.MIN | Aggregate.MAX | Aggregate.COUNT,
                           3)


def _new_book(pool: str):
    return _Pool() if pool in ("histo", "sets") else ScalarPool(initial=4)


def _table(pool: str, rng, n: int, poisoned: bool) -> LifetimeSeries:
    """n series of one pool's kind as a worker would have learnt them:
    some routed, some rejected, and (poisoned) one whose frag is None."""
    mtype = {"histo": "timer", "sets": "set", "counters": "counter",
             "gauges": "gauge"}[pool]
    known = LifetimeSeries()
    known.reserve(n)
    for sid in range(n):
        name = "rb.%s.%d" % (pool, sid)
        if poisoned and sid == n // 2:
            name += "\x1e"
        tags = ["k:%d" % (sid % 7)]
        if rng.random() < 0.2:
            tags.append("veneursinkonly:datadog")
        scope = ScopeClass(int(rng.integers(0, 3)))
        admitted = bool(rng.random() < 0.8)
        key = MetricKey(name=name, type=mtype, joined_tags=",".join(tags))
        sinks = route_info(tags)
        if pool in ("histo", "sets"):
            entry = RowMeta(key=key, tags=tags, scope_class=scope,
                            sinks=sinks, tenant="t", admitted=admitted)
        else:
            entry = (key, tags, scope, sinks)
        known.put(sid, entry, (name, tags, sinks), build_frag(name, tags),
                  int(scope), admitted, None)
    return known


def _append_all(book, known: LifetimeSeries, sids) -> None:
    """The plain reference: one _append a series, as the Python upsert
    path does it."""
    for sid in sids:
        entry = known.entries[sid]
        scope, admitted = (int(known.codes[LifetimeSeries.SCOPE, sid]),
                           bool(known.codes[LifetimeSeries.ADMITTED, sid]))
        book._append(len(book.entries), entry, scope, known.metas[sid][2],
                     admitted, known.frags[sid])
        if isinstance(book, ScalarPool):
            book._cover(len(book.entries))


def _fields(book) -> dict:
    blob = book.frag_blob()
    meta_at, frag_at = book.accessors()
    n = len(book.entries)
    return {
        "entries": list(book.entries),
        "scope_codes": bytes(np.frombuffer(book.scope_codes, np.int8)),
        "admit_codes": bytes(np.frombuffer(book.admit_codes, np.int8)),
        "routed_rows": book.routed_rows,
        "rejected_rows": book.rejected_rows,
        "frag_clean": book.frag_clean,
        "frag_blob": None if blob is None else bytes(blob),
        "meta_at": [meta_at(i) for i in range(n)],
        "frag_at": [frag_at(i) for i in range(n)],
    }


@pytest.mark.parametrize("poisoned", [False, True],
                         ids=["clean", "a-frag-is-None"])
@pytest.mark.parametrize("pool", POOLS)
def test_a_book_of_ids_reads_as_the_materialised_one(pool, poisoned):
    """Random batches into every kind of pool: by id and row by row the
    book gives the same entries, codes, counts, frag blob bytes and
    per-row accessors; a None frag poisons the blob in both."""
    rng = np.random.default_rng(32 + POOLS.index(pool) + 10 * poisoned)
    known = _table(pool, rng, 300, poisoned)
    by_id, plain = _new_book(pool), _new_book(pool)
    order = rng.permutation(300).astype(np.int32)
    at = 0
    for size in (1, 120, 7, 172):
        sids = order[at:at + size]
        assert by_id.adopt_batch(at, known, sids) == size
        _append_all(plain, known, sids.tolist())
        at += size
        if size == 120:  # asked mid-interval: joined so far, then on
            assert _fields(by_id) == _fields(plain)
    assert by_id._known is known and not by_id.materialised
    assert plain._known is None and not plain.materialised
    got, want = _fields(by_id), _fields(plain)
    assert got == want
    assert (got["frag_blob"] is None) == poisoned
    assert got["routed_rows"] > 0 and got["rejected_rows"] > 0
    # one join when asked first, one for the rows that came after, none
    # for asking again; a poisoned arena is never joined
    assert by_id.frag_blob_builds == (0 if poisoned else 2)
    if isinstance(by_id, ScalarPool):
        assert by_id.used == plain.used == 300
        assert len(by_id.values) >= 300
    # and the view is a sequence: length, index, slice, iteration
    view = by_id.entries
    assert isinstance(view, RowView) and len(view) == 300 and view
    assert view[17] is plain.entries[17] and view[-1] is plain.entries[-1]
    assert view[5:9] == plain.entries[5:9]
    assert list(view) == plain.entries


@pytest.mark.parametrize("how", ["_append", "upsert_batch", "index",
                                 "another-table"])
def test_whatever_needs_containers_materialises_once_in_row_order(how):
    """A row from the Python upsert path, a reader-shard batch, a
    request for the index or ids of a second table: the ids are resolved
    once, rows keep their order, and the book carries on."""
    rng = np.random.default_rng(7)
    known = _table("histo", rng, 60, False)
    book, plain = _Pool(), _Pool()
    first = rng.permutation(60).astype(np.int32)[:40]
    assert book.adopt_batch(0, known, first) == 40
    _append_all(plain, known, first.tolist())
    late = RowMeta(key=MetricKey("rb.late", "timer", ""), tags=[],
                   scope_class=ScopeClass.MIXED, sinks=None)
    if how == "_append":
        book.adopt_meta(40, late)
        plain.adopt_meta(40, late)
    elif how == "upsert_batch":
        # 10 series the book has and 10 it has not: only those get rows
        sids = np.concatenate([first[:10],
                               np.arange(60, dtype=np.int32)[
                                   ~np.isin(np.arange(60), first)][:10]])
        rows = book.upsert_batch(known, sids)
        assert rows[:10] == list(range(10))
        assert rows[10:] == list(range(40, 50))
        _append_all(plain, known, sids[10:].tolist())
    elif how == "index":
        assert book.index == plain.index
        assert list(book.index.values()) == list(range(40))
    else:
        other = _table("histo", rng, 20, False)
        sids = np.arange(20, dtype=np.int32)
        assert book.adopt_batch(40, other, sids) == 0
        _append_all(plain, other, sids.tolist())
    assert book.materialised and book._known is None
    assert isinstance(book.entries, list)
    assert _fields(book) == _fields(plain)
    assert book.index == plain.index
    # once: what comes next is appended to the containers, by id or not
    more = np.setdiff1d(np.arange(60, dtype=np.int32), first)[-5:]
    at = len(book.entries)
    assert book.adopt_batch(at, known, more) == 0
    _append_all(plain, known, more.tolist())
    assert _fields(book) == _fields(plain)
    assert book.upsert(late.key, late.scope_class, [])[1] == (
        how != "_append")


def test_derived_codes_are_taken_once_per_row_count_and_read_only():
    known = _table("counters", np.random.default_rng(3), 50, False)
    book = ScalarPool()
    book.adopt_batch(0, known, np.arange(30, dtype=np.int32))
    codes = book.admit_codes
    assert book.admit_codes.base is codes.base  # asked again: not taken
    with pytest.raises(ValueError):
        codes[0] = 0
    book.adopt_batch(30, known, np.arange(30, 50, dtype=np.int32))
    assert len(book.admit_codes) == len(book.scope_codes) == 50


def test_sink_threads_asking_for_the_blob_at_once_get_one_join():
    """Every native-encoding sink asks for the frag blob from a thread of
    its own (emit.sinks): one of them joins, the others wait and get the
    same buffer."""
    import sys
    import threading

    known = _table("histo", np.random.default_rng(5), 4000, False)
    sids = np.random.default_rng(6).permutation(4000).astype(np.int32)
    plain = _Pool()
    _append_all(plain, known, sids.tolist())
    want = bytes(plain.frag_blob())
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(20):
            book = _Pool()
            book.adopt_batch(0, known, sids)
            gate, got = threading.Barrier(16), []
            def ask():
                gate.wait(timeout=10)
                got.append(book.frag_blob())
            threads = [threading.Thread(target=ask) for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
            assert not any(t.is_alive() for t in threads)
            assert len(got) == 16 and all(g is got[0] for g in got)
            assert bytes(got[0]) == want and book.frag_blob_builds == 1
    finally:
        sys.setswitchinterval(was)


def _native_worker(**kw):
    w = DeviceWorker(stage_depth=8, batch_size=1 << 12, **kw)
    if not w.attach_native():
        pytest.skip("native ingest library unavailable")
    return w


def _books(holder) -> list:
    return [holder.directory.histo, holder.directory.sets,
            holder.scalars.counters, holder.scalars.gauges]


def _lines(prefix: bytes, n: int) -> bytes:
    out = []
    for i in range(n):
        out += [b"%s.t%d:%d|ms|#k:%d" % (prefix, i, i, i % 3),
                b"%s.c%d:2|c" % (prefix, i), b"%s.g%d:%d|g" % (prefix, i, i),
                b"%s.s%d:m%d|s|#veneurlocalonly" % (prefix, i, i)]
    return b"\n".join(out)


@pytest.mark.parametrize("drop", ["generation", "intern_cap"])
def test_a_snapshots_book_outlives_the_table_under_it(drop):
    """The context drops its table at a reset (past intern_cap) and
    numbers its series anew: the worker starts a new table, and the
    snapshot of the interval before, whose books hold ids into the old
    one, reads as it did."""
    w = _native_worker()
    qs = device_quantiles(PCTS, AGGS)
    if drop == "intern_cap":
        w._native.set_intern_cap(8)  # 80 series: dropped at the reset
    w.ingest_datagram(_lines(b"old", 20))
    w.sync_native_series()
    old_table = w._adopt_cache[0]
    snap = w.flush(qs)
    assert all(b._known is old_table for b in _books(snap))
    before = [_fields(b) for b in _books(snap)]
    assert [m.key.name for m in snap.directory.histo.rows] == [
        "old.t%d" % i for i in range(20)]
    if drop == "generation":
        # a drain of another generation, as the worker sees it (this
        # context kept its table, so the new sids go on from 80)
        w._adopt_cache[0].generation -= 1
    w.ingest_datagram(_lines(b"new", 30))
    w.sync_native_series()
    new_table = w._adopt_cache[0]
    assert new_table is not old_table
    assert len(old_table) == 80
    assert len(new_table) == (120 if drop == "intern_cap" else 200)
    assert all(b._known is new_table for b in _books(w))
    assert [m.key.name for m in w.directory.histo.rows] == [
        "new.t%d" % i for i in range(30)]
    # dropped, sid 0 names another series now; the snapshot reads its own
    assert [_fields(b) for b in _books(snap)] == before
    assert snap.directory.histo.accessors()[0](0)[0] == "old.t0"
    assert w.directory.histo.accessors()[0](0)[0] == "new.t0"
    snap2 = w.flush(qs)
    assert len(snap2.directory.histo.rows) == 30
    assert [_fields(b) for b in _books(snap)] == before


def _stream(rng) -> bytes:
    lines = _lines(b"fx", 25).split(b"\n")
    lines += [b"fx.routed:1|ms|#veneursinkonly:datadog",
              b"fx.t0:1|ms|#tenant:t0,veneurlocalonly",
              b"fx.c0:1|c|#veneurglobalonly"]
    lines += [b"fx.b%d:1|c|#tenant:big" % i for i in range(8)]
    return b"\n".join(lines[i] for i in rng.permutation(len(lines)))


@pytest.mark.parametrize("is_local", [True, False], ids=["local", "global"])
def test_the_columnar_flush_is_the_same_from_either_representation(is_local):
    """One fixed stream through two workers, one of which has every book
    materialised before it flushes: the columnar batch agrees group by
    group in meta_at, frag_at, meta_blob and every family's values and
    mask (a tenant over budget included, so the masks are not None)."""
    qs = device_quantiles(PCTS, AGGS)
    batches = []
    for materialise in (False, True):
        w = _native_worker(is_local=is_local)
        w.tenancy = TenantLedger(default_budget=5, budgets={})
        for interval in range(2):  # the second: every series known
            w.ingest_datagram(_stream(np.random.default_rng(41)))
            w.sync_native_series()
            if materialise:
                for book in _books(w):
                    book.index
            assert [b.materialised for b in _books(w)] == [materialise] * 4
            snap = w.flush(qs)
        assert snap.scalars.counters.rejected_rows > 0
        batches.append(generate_columnar(snap, is_local, PCTS, AGGS, now=5))
    by_id, plain = batches
    assert len(by_id.groups) == len(plain.groups) == 4
    assert by_id.count() == plain.count()
    for g, h in zip(by_id.groups, plain.groups):
        assert g.nrows == h.nrows and g.has_routing == h.has_routing
        assert ([g.meta_at(i) for i in range(g.nrows)]
                == [h.meta_at(i) for i in range(h.nrows)])
        assert ([g.frag_at(i) for i in range(g.nrows)]
                == [h.frag_at(i) for i in range(h.nrows)])
        assert g.meta_blob is not None
        assert bytes(g.meta_blob) == bytes(h.meta_blob)
        assert len(g.families) == len(h.families)
        for f, k in zip(g.families, h.families):
            assert (f.suffix, f.type) == (k.suffix, k.type)
            np.testing.assert_array_equal(f.values, k.values)
            assert (f.mask is None) == (k.mask is None)
            if f.mask is not None:
                np.testing.assert_array_equal(f.mask, k.mask)
    assert sorted(map(repr, by_id.materialize())) == sorted(
        map(repr, plain.materialize()))
