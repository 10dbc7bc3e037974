"""The dense HyperLogLog tier of ``ops/staged_sets.StagedSetStore`` under
the traffic of the cell ``local-uniques.steady``, cut down: 64 endpoints
under Zipf 1.1, 220,000 set lines an interval whose members come from
universes of four ids a request (``bench/generators/uniques.py``), so
that a dozen sets promote to dense rows and the largest stands above
2.5 m, in the harmonic-mean regime. Drains of uneven length, three
epochs cut at different places, on the device path and in ``host=True``.

What it holds: the registers are a plain scatter-max of every triple;
the dense rows' estimates are bitwise the host twin's and every estimate
is inside ``bench.reference.hll_tolerance``; the device batches have the
ladder's lengths and the pool a power of two, so the process holds no
more ``insert_batch`` programs than lengths x pool sizes after the first
epoch and none more after the second and third; a promotion pass is one
insert; a device fault inside a padded insert fails over to the host
twin; the sharded insert takes the same padded arrays; and the served
path (a ``Server`` with native ingest, lines over its TCP listener)
promotes eight sets of 5,000 members and emits each inside the
tolerance, its pool allocated once an epoch from the second on.
"""

from __future__ import annotations

import os
import socket
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference, run, stream  # noqa: E402
from bench.generators import uniques  # noqa: E402
from veneur_tpu.core import flightrec  # noqa: E402
from veneur_tpu.ops import device_guard as dg  # noqa: E402
from veneur_tpu.ops import hll  # noqa: E402
from veneur_tpu.ops import host_engine as he  # noqa: E402
from veneur_tpu.ops import staged_sets as st  # noqa: E402
from veneur_tpu.utils import faults as fl  # noqa: E402

ENDPOINTS, LINES, EPOCHS = 64, 220_000, 3
P = 14
M = 1 << P


def _mix(x: np.ndarray) -> np.ndarray:
    """murmur3's finalizer over uint64 arrays (utils.hashing.fmix64)."""
    x = x.astype(np.uint64)
    x ^= x >> np.uint64(33)
    x *= np.uint64(0xFF51AFD7ED558CCD)
    x ^= x >> np.uint64(33)
    x *= np.uint64(0xC4CEB9FE1A85EC53)
    x ^= x >> np.uint64(33)
    return x


def set_lines(seed: int, epochs: int = EPOCHS):
    """(row, member) of ``epochs`` intervals of the cell's set lines at
    64 endpoints: fixed Zipf counts, members uniform over four ids a
    request, one permutation an interval."""
    rng = np.random.default_rng(seed)
    n_r = uniques.rank_counts(LINES, ENDPOINTS, 1.1)
    rows, members = [], []
    for _ in range(epochs):
        ep = np.repeat(rng.permutation(ENDPOINTS), n_r)
        mem = np.floor(rng.random(LINES) * 4 * np.repeat(n_r, n_r))
        order = rng.permutation(LINES)
        rows.append(ep[order])
        members.append(mem[order].astype(np.int64))
    return np.concatenate(rows), np.concatenate(members)


def triples(rows: np.ndarray, members: np.ndarray):
    idx, rank = hll.split_hashes(
        _mix(rows.astype(np.uint64) << np.uint64(32)
             | members.astype(np.uint64)), P)
    return rows.astype(np.int32), idx, rank


def drains(rng, n: int):
    """Uneven cuts of [0, n): most drains a few thousand lines, some a
    few hundred, one in ten a stall's tens of thousands."""
    at, out = 0, []
    while at < n:
        step = int(rng.choice([300, 2_500, 5_000, 9_000, 40_000],
                              p=[.15, .3, .3, .15, .1]))
        out.append((at, min(n, at + step)))
        at += step
    return out


def guard_with_record():
    g = dg.DeviceGuard()
    g.rec = flightrec.Recorder()
    return g


def dispatches(guard, kernel: str) -> list:
    return [s.attrs for s in guard.rec.closed()
            if s.name == "dispatch" and s.attrs.get("op") == "sets"
            and s.attrs.get("kernel") == kernel]


def scatter_max(rows, idx, rank, num_rows: int) -> np.ndarray:
    want = np.zeros((num_rows, M), np.int8)
    np.maximum.at(want, (rows, idx), rank)
    return want


def distinct(rows, members, num_rows: int) -> np.ndarray:
    pairs = np.unique(rows.astype(np.int64) << 32 | members)
    return np.bincount(pairs >> 32, minlength=num_rows)


def run_epochs(host: bool, seed: int = 46):
    """The three epochs through a store each, handed on as the worker
    hands them (``DeviceWorker._reset_epoch``): yields (store, guard,
    rows, members, idx, rank) of each epoch, drained."""
    rows, members = set_lines(seed)
    rng = np.random.default_rng(seed + 1)
    # cuts that are not the intervals': 0.93, 2.05 of an interval
    cuts = [0, int(0.93 * LINES), int(2.05 * LINES), EPOCHS * LINES]
    warm: dict = {}
    last = None
    for a, b in zip(cuts, cuts[1:]):
        guard = guard_with_record()
        # the cell compacts every 65,536 pending entries of a million
        # set lines; a fifth of the lines, a quarter of the threshold
        store = st.StagedSetStore(
            P, guard=guard, host=host, warm=warm, compact_every=1 << 14,
            pool_rows=(st.pool_rows_for(last.dense_rows)
                       if last is not None and last.dense_rows else 0))
        r, i, k = triples(rows[a:b], members[a:b])
        for lo, hi in drains(rng, b - a):
            store.insert(r[lo:hi], i[lo:hi], k[lo:hi])
        yield store, guard, rows[a:b], members[a:b], i, k
        last = store


@pytest.mark.parametrize("host", [False, True], ids=["device", "host"])
def test_three_epochs_of_the_cells_sets_are_what_a_scatter_max_says(host):
    programs = hll.insert_batch._cache_size()
    after_first = None
    for n, (store, guard, rows, members, idx, rank) in enumerate(
            run_epochs(host)):
        assert store.host_mode == host
        want = scatter_max(rows, idx, rank, ENDPOINTS)
        np.testing.assert_array_equal(store.registers(ENDPOINTS), want)
        got = store.estimates(ENDPOINTS)
        exact = distinct(rows, members, ENDPOINTS)
        # some rows dense, some sparse; the largest past 2.5 m
        assert 8 <= store.dense_rows <= 32
        assert store.pool_rows == st.POOL_MIN_ROWS
        assert exact.max() > 2.5 * M
        drows, slots = store._dense_rows_below(ENDPOINTS)
        assert len(drows) == store.dense_rows
        twin = he.np_hll_estimate_exact(want[drows], P)
        assert np.array_equal(got[drows], twin)
        tol = reference.hll_tolerance(exact, P)
        assert (np.abs(got - exact) <= tol).all(), \
            (np.abs(got - exact) / tol).max()
        assert got[exact.argmax()] > 2.5 * M  # the harmonic mean answered
        # where the entries went: every line is in one of the two
        assert store.dense_entries + store.sparse_routed == len(rows)
        assert store.dense_entries > len(rows) // 3
        if host:
            assert dispatches(guard, "insert") == []
            continue
        # every device batch had a ladder length, at one pool size
        ins = dispatches(guard, "insert")
        assert ins and {a["padded"] for a in ins} <= set(st.INSERT_LENGTHS)
        assert all(a["entries"] <= a["padded"] for a in ins)
        assert {a["pool_rows"] for a in ins} == {st.POOL_MIN_ROWS}
        assert sum(a["entries"] for a in ins) >= store.dense_entries
        # a stall's batch of 40,000 lines went in slices of the top
        assert max(a["padded"] for a in ins) == st.INSERT_LENGTHS[-1]
        # warmed once, in the first epoch: shorter lengths on nothing
        warms = [s for s in guard.rec.closed() if s.name == "sets.warm"]
        assert bool(warms) == (n == 0)
        assert len(dispatches(guard, "grow")) == 0
        assert len(dispatches(guard, "alloc")) == 1
        if n == 0:
            after_first = hll.insert_batch._cache_size()
            assert after_first - programs <= len(st.INSERT_LENGTHS)
        else:
            assert hll.insert_batch._cache_size() == after_first


def test_a_promotion_pass_of_forty_rows_is_one_insert():
    guard = guard_with_record()
    store = st.StagedSetStore(P, promote_entries=128, compact_every=1 << 30,
                              guard=guard)
    rng = np.random.default_rng(46)
    rows = np.repeat(np.arange(100), 40)          # sparse, all of them
    rows = np.r_[rows, np.repeat(np.arange(5, 85, 2), 300)]  # 40 past 128
    members = rng.integers(0, 1 << 40, rows.size)
    r, i, k = triples(rows, members)
    store.insert(r, i, k)
    assert store.dense_rows == 0
    store._compact()
    assert store.dense_rows == 40
    (promote,) = [s for s in guard.rec.closed() if s.name == "sets.promote"]
    assert promote.attrs["rows"] == 40
    assert promote.attrs["pool_rows"] == st.POOL_MIN_ROWS
    real = [a for a in dispatches(guard, "insert") if a["entries"]]
    assert len(real) == 1
    assert real[0]["entries"] == promote.attrs["entries"] > 40 * 128
    assert real[0]["padded"] == 1 << 14
    # the sparse tier keeps the other sixty rows, and nothing was lost
    assert set((store._ckeys // M).tolist()) == set(range(100)) - set(
        range(5, 85, 2))
    np.testing.assert_array_equal(store.registers(100),
                                  scatter_max(r, i, k, 100))
    # rows that are dense already are passed over
    store._promote_rows(np.arange(5, 85, 2))
    assert store.dense_rows == 40


def test_the_pool_grows_in_powers_of_two_by_one_program_a_size_pair():
    guard = guard_with_record()
    store = st.StagedSetStore(P, guard=guard)
    regs = np.zeros(M, np.int8)
    regs[::7] = 3
    for row in range(70):
        store.import_dense(row * 3, regs)
    got = store.estimates(70 * 3)
    assert store.dense_rows == 70 and store.pool_rows == 128
    assert [a["to_rows"] for a in dispatches(guard, "alloc")] == [128]
    for row in range(70, 140):
        store.import_dense(row * 3, regs)
    store.estimates(140 * 3)
    assert store.dense_rows == 140 and store.pool_rows == 256
    assert [a["to_rows"] for a in dispatches(guard, "grow")] == [256]
    assert st._grow_pool._cache_size() >= 1
    want = he.np_hll_estimate_exact(regs[None], P)[0]
    assert (got[::3] == want).all() and (got[1::3] == 0).all()
    # the next epoch's store starts where this one ended
    nxt = st.StagedSetStore(P, guard=guard,
                            pool_rows=st.pool_rows_for(store.dense_rows))
    nxt.import_dense(0, regs)
    nxt.estimates(1)
    assert nxt.pool_rows == 256 and nxt.dense_rows == 1
    # and a store with no dense row allocates nothing
    assert st.StagedSetStore(P, pool_rows=256).pool_rows == 0


def test_a_device_fault_in_a_padded_insert_fails_over_to_the_host_twin():
    rows, members = set_lines(47, epochs=1)
    r, i, k = triples(rows, members)
    guard = guard_with_record()
    store = st.StagedSetStore(P, guard=guard)
    half = LINES // 2
    store.insert(r[:half], i[:half], k[:half])
    assert store.dense_rows > 0 and not store.host_mode
    # an insert is retried once in place: fault it and its retry
    plan = fl.DeviceFaultPlan(op_windows={"sets": [(1, 3, "lost")]})
    with fl.DeviceFaultInjector(plan) as inj:
        for lo in range(half, LINES, 5_000):
            store.insert(r[lo:lo + 5_000], i[lo:lo + 5_000], k[lo:lo + 5_000])
    assert inj.op_calls["sets"] == 3  # one clean, the fault, its retry
    assert store.host_mode and isinstance(store._dense, np.ndarray)
    assert guard.counters()["device.fault.lost"] == 2
    np.testing.assert_array_equal(store.registers(ENDPOINTS),
                                  scatter_max(r, i, k, ENDPOINTS))
    drows, _ = store._dense_rows_below(ENDPOINTS)
    got = store.estimates(ENDPOINTS)
    assert np.array_equal(got[drows], he.np_hll_estimate_exact(
        scatter_max(r, i, k, ENDPOINTS)[drows], P))
    # and back on the device the registers are the same
    store.to_device()
    assert not store.host_mode
    np.testing.assert_array_equal(store.registers(ENDPOINTS),
                                  scatter_max(r, i, k, ENDPOINTS))


def test_the_sharded_insert_takes_the_padded_arrays():
    import jax

    from veneur_tpu.ops import series_shard as ss

    if len(jax.devices()) < 4:
        pytest.skip("needs the suite's CPU mesh of 4+ devices")
    rows, members = set_lines(48, epochs=1)
    r, i, k = triples(rows, members)
    guard = guard_with_record()
    sharded = st.StagedSetStore(P, shard=ss.SeriesSharding(4), guard=guard)
    plain = st.StagedSetStore(P)
    rng = np.random.default_rng(48)
    for lo, hi in drains(rng, LINES):
        sharded.insert(r[lo:hi], i[lo:hi], k[lo:hi])
        plain.insert(r[lo:hi], i[lo:hi], k[lo:hi])
    assert sharded.dense_rows == plain.dense_rows > 0
    assert sharded.pool_rows == plain.pool_rows == st.POOL_MIN_ROWS
    ins = dispatches(guard, "insert")
    assert ins and {a["padded"] for a in ins} <= set(st.INSERT_LENGTHS)
    np.testing.assert_array_equal(sharded.registers(ENDPOINTS),
                                  plain.registers(ENDPOINTS))
    assert np.array_equal(sharded.estimates(ENDPOINTS),
                          plain.estimates(ENDPOINTS))


# -- the served path ---------------------------------------------------------

def _wait_for(predicate, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


def test_the_served_path_promotes_eight_sets_of_five_thousand_members():
    from veneur_tpu.core.config import Config
    from veneur_tpu.core.server import Server

    cfg = Config(statsd_listen_addresses=["tcp://127.0.0.1:0"],
                 num_workers=1, num_readers=1, interval="600s",
                 percentiles=[0.5], tpu_native_ingest=True,
                 # (or one flush in a hundred leaves a set line of the
                 # server's own, ssf.names_unique, in the next epoch)
                 ssf_span_uniqueness_rate=0.0)
    collector = run.make_collector("")
    srv = Server(cfg, metric_sinks=[collector])
    collector.server = srv
    try:
        if not srv.native_mode:
            pytest.skip("native ingest library unavailable")
        port = next(iter(srv.start().values()))
        rng = np.random.default_rng(46)
        sent = 0
        pools, programs = [], []
        with socket.create_connection(("127.0.0.1", port)) as sock:
            for epoch in range(3):
                # every user comes three times: the store compacts, and
                # promotes, at 65,536 pending entries, so two thirds of
                # an interval's 120,000 lines in
                sid = np.repeat(np.arange(8), 15_000)
                member = np.tile(np.arange(5_000), 24) + epoch * 7
                order = rng.permutation(sid.size)
                ring = stream.Ring(np.full(sid.size, stream.SET, np.int8),
                                   sid[order].astype(np.int32),
                                   member[order].astype(np.float64),
                                   {"set": 8})
                chunks, _ = stream.chunk_lines(stream.format_lines(ring),
                                               65536)
                for chunk in chunks:
                    sock.sendall(chunk)
                    # the pump's drains are the store's batches
                    time.sleep(0.02)
                sent += sid.size
                assert _wait_for(lambda: srv.ingress_stats()[
                    "samples_processed"] >= sent)
                srv.flush()
                view = run.view_of(collector.flushes[-1]["batch"])
                got_sid, got = view.scalar(stream.SET)
                assert sorted(got_sid.tolist()) == list(range(8))
                tol = reference.hll_tolerance(np.full(8, 5_000), P)
                assert (np.abs(got - 5_000) <= tol).all(), got
                by = {}
                for s in srv.last_flush_phases["spans"]:
                    by.setdefault(s[1], []).append(s[6])
                (est,) = by["extract.sets.estimate"]
                assert est["dense_rows"] == 8 and est["sparse_rows"] == 0
                (begin,) = by["flush.begin"]
                assert begin["sets_dense"] + begin["sets_sparse"] == sent
                assert begin["sets_dense"] > 0
                sets = [a for a in by["dispatch"] if a.get("op") == "sets"]
                assert {a["kernel"] for a in sets} >= {"insert", "estimate"}
                pools.append({a["pool_rows"] for a in sets
                              if a["kernel"] == "insert"})
                programs.append(hll.insert_batch._cache_size())
                assert ("sets.promote" in by)
        # one pool size, and from the second epoch on no new program
        assert pools == [{st.POOL_MIN_ROWS}] * 3
        assert programs[1] == programs[2] == programs[0]
        assert run.device_path_faults(srv) == []
    finally:
        srv.shutdown()
