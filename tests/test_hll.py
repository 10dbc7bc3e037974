"""HyperLogLog + scalar aggregator tests.

Accuracy envelope mirrors the reference's HLL behavior: σ ≈ 1.04/√m ≈ 0.81%
at p=14; we assert 3%≈3.7σ over a sweep of cardinalities, plus exact
merge/union semantics and counter truncation rules.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from veneur_tpu.ops import hll, scalars
from veneur_tpu.utils.hashing import hll_hash


def _insert_values(registers, row, values, precision=14):
    hashes = np.array([hll_hash(v) for v in values], dtype=np.uint64)
    idx, rank = hll.split_hashes(hashes, precision)
    rows = np.full(len(values), row, dtype=np.int32)
    return hll.insert_batch(
        registers, jnp.asarray(rows), jnp.asarray(idx), jnp.asarray(rank)
    )


@pytest.mark.parametrize("n", [10, 100, 1000, 50000, 200000])
def test_cardinality_accuracy(n):
    regs = hll.init_pool(1)
    values = [f"value-{i}".encode() for i in range(n)]
    regs = _insert_values(regs, 0, values)
    est = float(hll.estimate(regs)[0])
    assert abs(est - n) / n < 0.03, f"n={n} est={est}"


def test_duplicates_not_counted():
    regs = hll.init_pool(1)
    values = [f"v{i % 500}".encode() for i in range(20000)]
    regs = _insert_values(regs, 0, values)
    est = float(hll.estimate(regs)[0])
    assert abs(est - 500) / 500 < 0.03


def test_empty_estimate_zero():
    regs = hll.init_pool(3)
    est = np.asarray(hll.estimate(regs))
    assert np.allclose(est, 0.0)


def test_multi_row_independence():
    regs = hll.init_pool(4)
    sizes = [100, 1000, 5000, 25000]
    for row, n in enumerate(sizes):
        values = [f"row{row}-{i}".encode() for i in range(n)]
        regs = _insert_values(regs, row, values)
    est = np.asarray(hll.estimate(regs))
    for row, n in enumerate(sizes):
        assert abs(est[row] - n) / n < 0.03, row


def test_merge_union_semantics():
    a = hll.init_pool(1)
    b = hll.init_pool(1)
    # overlapping sets: |A|=3000, |B|=3000, |A∪B|=4500
    a = _insert_values(a, 0, [f"x{i}".encode() for i in range(3000)])
    b = _insert_values(b, 0, [f"x{i}".encode() for i in range(1500, 4500)])
    merged = hll.merge(a, b)
    est = float(hll.estimate(merged)[0])
    assert abs(est - 4500) / 4500 < 0.03


def test_merge_associative_8_shards():
    # 8-local → 1-global merge: same estimate regardless of merge shape
    shards = []
    for s in range(8):
        r = hll.init_pool(1)
        vals = [f"u{i}".encode() for i in range(s * 500, s * 500 + 1000)]
        shards.append(_insert_values(r, 0, vals))
    left = shards[0]
    for s in shards[1:]:
        left = hll.merge(left, s)
    import functools
    tree = functools.reduce(hll.merge, shards)
    assert np.array_equal(np.asarray(left), np.asarray(tree))
    est = float(hll.estimate(left)[0])
    true_n = len({i for s in range(8) for i in range(s * 500, s * 500 + 1000)})
    assert abs(est - true_n) / true_n < 0.03


def test_registers_roundtrip():
    regs = hll.init_pool(1)
    regs = _insert_values(regs, 0, [b"a", b"b", b"c"])
    row = np.asarray(regs)[0]
    data = hll.registers_to_bytes(row)
    assert len(data) == 16384
    back = hll.registers_from_bytes(data)
    assert np.array_equal(back, row)


def test_split_hashes_rank_bounds():
    h = np.array([0, 1, 2**63, 2**64 - 1], dtype=np.uint64)
    idx, rank = hll.split_hashes(h)
    assert idx.min() >= 0 and idx.max() < 16384
    assert rank.min() >= 1 and rank.max() <= 51  # 64-14+1


# ---------------------------------------------------------------------------
# Counters / gauges


def test_counter_truncation_semantics():
    # reference: value += int64(sample) * int64(1/rate)
    assert scalars.counter_contribution(2.7, 1.0) == 2
    assert scalars.counter_contribution(1.0, 0.3) == 3  # 1/0.3 = 3.33 → 3
    assert scalars.counter_contribution(5.0, 0.1) == 50  # 1/0.1 = 10.000004?
    assert scalars.counter_contribution(-3.9, 1.0) == -3  # trunc toward zero


def test_counter_accumulate_exact():
    state = np.zeros(4, dtype=np.float64)
    rows = np.array([0, 1, 0, 3, 0], dtype=np.int64)
    contrib = np.array([1, 10, 100, 2**40, 1], dtype=np.float64)
    scalars.accumulate_counters(state, rows, contrib)
    assert state[0] == 102
    assert state[1] == 10
    assert state[2] == 0
    assert state[3] == 2**40


def test_gauge_last_write_wins():
    state = np.zeros(3, dtype=np.float64)
    present = np.zeros(3, dtype=bool)
    rows = np.array([0, 1, 0, 0], dtype=np.int64)
    vals = np.array([1.0, 5.0, 2.0, 7.0])
    scalars.apply_gauges(state, present, rows, vals)
    assert state[0] == 7.0  # last write for row 0
    assert state[1] == 5.0
    assert not present[2]


def test_segment_gauge_last_device():
    rows = jnp.array([0, 1, 0, 0], dtype=jnp.int32)
    vals = jnp.array([1.0, 5.0, 2.0, 7.0], dtype=jnp.float32)
    out, present = scalars.segment_gauge_last(rows, vals, 3)
    assert float(out[0]) == 7.0
    assert float(out[1]) == 5.0
    assert bool(present[0]) and bool(present[1]) and not bool(present[2])


def test_insert_batch_variants_agree():
    """The sorted-unique-scatter insert must equal the plain scatter-max."""
    import numpy as np

    rng = np.random.default_rng(17)
    s, p = 7, 8
    m = hll.num_registers(p)
    regs = jnp.asarray(rng.integers(0, 5, (s, m)).astype(np.int8))
    n = 5000
    rows = jnp.asarray(rng.integers(0, s, n).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, m, n).astype(np.int32))
    rank = jnp.asarray(rng.integers(0, 50, n).astype(np.int8))
    a = hll.insert_batch(regs, rows, idx, rank)
    b = hll.insert_batch_scatter(regs, rows, idx, rank)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# staged (sparse host / dense device) store


def test_staged_store_matches_dense_estimates():
    from veneur_tpu.ops.staged_sets import StagedSetStore

    rng = np.random.default_rng(7)
    store = StagedSetStore(promote_entries=128, compact_every=512)
    pool = hll.init_pool(8)
    # rows 0..7 with wildly different cardinalities; row 3 crosses the
    # promotion threshold
    counts = [5, 40, 90, 5000, 200, 1, 17, 300]
    for row, n in enumerate(counts):
        hashes = np.array([hll_hash(f"r{row}-m{i}".encode())
                           for i in range(n)], dtype=np.uint64)
        idx, rank = hll.split_hashes(hashes)
        rows = np.full(n, row, np.int32)
        store.insert(rows, idx, rank)
        pool = hll.insert_batch(pool, jnp.asarray(rows), jnp.asarray(idx),
                                jnp.asarray(rank))
    assert store.dense_rows >= 1  # row 3 promoted
    got = store.estimates(8)
    want = np.asarray(hll.estimate(pool))
    # f64 host estimator vs f32 device kernel: same formula, tiny drift
    np.testing.assert_allclose(got, want, rtol=1e-3)
    # register materialization identical to the dense pool
    np.testing.assert_array_equal(store.registers(8), np.asarray(pool))


def test_staged_store_import_dense_merges():
    from veneur_tpu.ops.staged_sets import StagedSetStore

    store = StagedSetStore()
    hashes = np.array([hll_hash(f"a{i}".encode()) for i in range(500)],
                      dtype=np.uint64)
    idx, rank = hll.split_hashes(hashes)
    store.insert(np.zeros(500, np.int32), idx, rank)
    # imported registers for the same row covering different members
    regs = np.zeros(hll.num_registers(), np.int8)
    h2 = np.array([hll_hash(f"b{i}".encode()) for i in range(500)],
                  dtype=np.uint64)
    i2, r2 = hll.split_hashes(h2)
    np.maximum.at(regs, i2, r2)
    store.import_dense(0, regs)
    est = store.estimates(1)[0]
    assert abs(est - 1000) / 1000 < 0.05


def test_staged_store_memory_stays_sparse_for_small_sets():
    from veneur_tpu.ops.staged_sets import StagedSetStore

    rng = np.random.default_rng(3)
    store = StagedSetStore()
    n_series, per = 5000, 30
    rows = np.repeat(np.arange(n_series, dtype=np.int32), per)
    hashes = rng.integers(0, 2**64, n_series * per, dtype=np.uint64)
    idx, rank = hll.split_hashes(hashes)
    store.insert(rows, idx, rank)
    assert store.dense_rows == 0  # nothing promoted
    assert store.sparse_entries <= n_series * per
    est = store.estimates(n_series)
    # every series ~30 distinct members
    assert np.all(np.abs(est - per) / per < 0.35)


def _estimates_row_at_a_time(store, num_rows):
    """The estimator one row at a time in float64: the loop
    ``StagedSetStore.estimates`` was until PR 45, kept as its reference.
    Reads the store's tiers as a call of ``estimates`` leaves them."""
    from veneur_tpu.ops import host_engine as he

    store._apply_imports()
    store._compact_no_promote()
    m = float(store.m)
    alpha = 0.7213 / (1.0 + 1.079 / m)
    out = np.zeros(num_rows, np.float32)
    rows = store._ckeys // store.m
    inv = np.power(2.0, -store._crank.astype(np.float64))
    urows, starts = np.unique(rows, return_index=True)
    ends = np.r_[starts[1:], rows.size]
    csum = np.r_[0.0, np.cumsum(inv)]
    for r, a, b in zip(urows, starts, ends):
        if r >= num_rows:
            continue
        zeros = m - (b - a)
        inv_sum = zeros + (csum[b] - csum[a])
        raw = alpha * m * m / inv_sum
        if raw <= 2.5 * m and zeros > 0:
            out[r] = m * np.log(m / zeros)
        else:
            out[r] = raw
    if store.dense_rows:
        dense = (he.np_hll_estimate_exact(store._dense, store.precision)
                 if store.host_mode
                 else np.asarray(hll.estimate(store._dense, store.precision)))
        for r, s in zip(*store._dense_rows_below(num_rows)):
            out[r] = dense[s]
    return out


def _random_members(store, rng, rows):
    hashes = rng.integers(0, 2**64, len(rows), dtype=np.uint64)
    idx, rank = hll.split_hashes(hashes, store.precision)
    store.insert(np.asarray(rows, np.int32), idx, rank)


def _fill_empty(store, rng):
    return 16


def _fill_one_row(store, rng):
    _random_members(store, rng, np.full(300, 2))
    return 4


def _fill_rows_past_num_rows(store, rng):
    # rows 0..9 live; the caller asks for 6: row 5 is the last it sees
    _random_members(store, rng, np.repeat(np.arange(10), 40))
    return 6


def _fill_zipf_with_pending(store, rng):
    # 2,000 rows under Zipf keys, drained in batches as the ingest side
    # does; compact_every 4,096 leaves the tail uncompacted at the call
    for _ in range(27):
        _random_members(store, rng, (rng.zipf(1.1, 1000) - 1) % 2000)
    assert store._pend > 0 and store._ckeys.size > 0
    return 2000


def _fill_linear_beside_raw(store, rng):
    _random_members(store, rng, np.full(500, 0))
    _random_members(store, rng, np.full(120_000, 1))
    assert store.dense_rows == 0
    return 2


def _fill_no_zero_register(store, rng):
    # every register of row 0 hit at rank 1: raw = 1.44 m sits in the
    # linear-counting range, where there is no zero register to count
    m = store.m
    store.insert(np.zeros(m, np.int32), np.arange(m), np.ones(m, np.int8))
    _random_members(store, rng, np.full(50, 1))
    return 2


def _fill_sparse_beside_dense(store, rng):
    _random_members(store, rng, np.repeat(np.arange(6), 60))
    _random_members(store, rng, np.full(5000, 3))  # promoted
    regs = np.zeros(store.m, np.int8)
    regs[rng.integers(0, store.m, 900)] = 3
    store.import_dense(7, regs)  # dense by nature
    store.import_dense(9, regs)  # past num_rows
    _random_members(store, rng, np.repeat(np.arange(6), 5))
    return 8


@pytest.mark.parametrize("fill,kw", [
    (_fill_empty, {}),
    (_fill_one_row, {}),
    (_fill_rows_past_num_rows, {}),
    (_fill_zipf_with_pending, {"compact_every": 4096}),
    (_fill_linear_beside_raw, {"promote_entries": 1 << 20}),
    (_fill_no_zero_register, {"promote_entries": 1 << 20}),
    (_fill_sparse_beside_dense, {"promote_entries": 128,
                                 "compact_every": 512}),
    (_fill_sparse_beside_dense, {"promote_entries": 128,
                                 "compact_every": 512, "host": True}),
], ids=["empty", "one_row", "rows_past_num_rows", "zipf_with_pending",
        "linear_beside_raw", "no_zero_register", "sparse_beside_dense",
        "host"])
def test_staged_store_estimates_are_the_row_at_a_time_loops(fill, kw):
    from veneur_tpu.ops.staged_sets import StagedSetStore

    store = StagedSetStore(**kw)
    num_rows = fill(store, np.random.default_rng(45))
    with np.errstate(all="raise"):
        got = store.estimates(num_rows)
    want = _estimates_row_at_a_time(store, num_rows)
    assert got.dtype == np.float32 and got.shape == (num_rows,)
    assert np.isfinite(got).all()
    assert np.array_equal(got, want)
    if fill is _fill_linear_beside_raw:
        assert got[0] < 2.5 * store.m < got[1]
    if fill is _fill_no_zero_register:
        assert got[0] == np.float32(
            0.7213 / (1.0 + 1.079 / store.m) * store.m * 2)
    if fill is _fill_sparse_beside_dense:
        assert store.dense_rows == 3 and store.host_mode == bool(
            kw.get("host"))
        assert got[3] > 4000 and got[7] > 800 and (got[:3] > 50).all()


def test_staged_store_registers_dense_beside_sparse():
    from veneur_tpu.ops.staged_sets import StagedSetStore

    rng = np.random.default_rng(45)
    store = StagedSetStore(promote_entries=128, compact_every=512)
    want = np.zeros((10, store.m), np.int8)
    rows = np.r_[np.repeat(np.arange(6), 60), np.full(5000, 3)]
    hashes = rng.integers(0, 2**64, rows.size, dtype=np.uint64)
    idx, rank = hll.split_hashes(hashes)
    store.insert(rows.astype(np.int32), idx, rank)
    np.maximum.at(want, (rows, idx), rank)
    regs = np.zeros(store.m, np.int8)
    regs[rng.integers(0, store.m, 900)] = 3
    for row in (7, 9):
        store.import_dense(row, regs)
        want[row] = regs
    assert store.dense_rows == 1 and store.sparse_entries > 0
    # a dense beside a sparse row, a row that is neither, and the rows
    # past num_rows (9 is dense, 5 is sparse when 5 are asked for) left out
    np.testing.assert_array_equal(store.registers(8), want[:8])
    assert store.dense_rows == 3
    np.testing.assert_array_equal(store.registers(5), want[:5])
    np.testing.assert_array_equal(store.registers(10), want)
