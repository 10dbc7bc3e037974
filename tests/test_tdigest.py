"""Statistical correctness tests for the batched t-digest.

Modeled on the reference's tdigest/histo_test.go (merge correctness, quantile
error bounds) and tdigest/analysis harness: we assert q-space error bounds
against exact empirical quantiles rather than bit-equality (the reference's
own merge order is randomized).
"""

import numpy as np
import pytest

from veneur_tpu.ops import tdigest as td


def _ingest(values, weights=None, rows=None, k=1, c=128, batch=None,
            compression=100.0):
    """Helper: push values through add_batch in one or more fixed-size
    batches, return the resulting pool arrays for k rows."""
    import jax.numpy as jnp

    values = np.asarray(values, dtype=np.float32)
    n = len(values)
    if weights is None:
        weights = np.ones(n, dtype=np.float32)
    if rows is None:
        rows = np.zeros(n, dtype=np.int32)
    pool = td.init_pool(k, c)
    means, w, dmin, dmax, drecip = (
        pool.means, pool.weights, pool.min, pool.max, pool.recip)
    step = batch or n
    for i in range(0, n, step):
        j = min(i + step, n)
        pad = step - (j - i)
        bv = np.pad(values[i:j], (0, pad))
        bw = np.pad(weights[i:j], (0, pad))
        br = np.pad(rows[i:j], (0, pad))
        means, w, dmin, dmax, drecip, _ = td.add_batch(
            means, w, dmin, dmax, drecip,
            jnp.asarray(br), jnp.asarray(bv), jnp.asarray(bw),
            compression=compression)
    return td.TDigestPool(means, w, dmin, dmax, drecip)


def _q(pool, qs):
    import jax.numpy as jnp
    return np.asarray(td.quantile(
        pool.means, pool.weights, pool.min, pool.max,
        jnp.asarray(qs, dtype=jnp.float32)))


def test_uniform_quantile_error():
    rng = np.random.default_rng(42)
    vals = rng.uniform(0, 1, 50000)
    pool = _ingest(vals, batch=8192)
    qs = [0.001, 0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 0.999]
    est = _q(pool, qs)[0]
    truth = np.quantile(vals, qs)
    # interior quantiles: loose bound; tails: tight (t-digest promise)
    for q, e, t in zip(qs, est, truth):
        tol = 0.005 if 0.1 <= q <= 0.9 else 0.002
        assert abs(e - t) < tol, f"q={q}: est={e} true={t}"


def test_normal_quantile_error():
    # t-digest's guarantee is in quantile space: the empirical CDF evaluated
    # at the estimate must be close to the requested q, with tail error
    # shrinking as q(1-q) (the reference's analysis harness measures the
    # same thing, tdigest/analysis/main.go).
    rng = np.random.default_rng(7)
    vals = np.sort(rng.normal(100.0, 15.0, 100000))
    pool = _ingest(vals, batch=16384)
    qs = [0.001, 0.01, 0.5, 0.9, 0.99, 0.999]
    est = _q(pool, qs)[0]
    for q, e in zip(qs, est):
        q_hat = np.searchsorted(vals, e) / len(vals)
        tol = max(0.001, 0.25 * min(q, 1 - q))
        assert abs(q_hat - q) < tol, f"q={q}: est={e} q_hat={q_hat}"


def test_scalar_stats_exact():
    rng = np.random.default_rng(1)
    vals = rng.uniform(1, 10, 1000).astype(np.float32)
    pool = _ingest(vals, batch=256)
    assert np.isclose(np.asarray(pool.min)[0], vals.min())
    assert np.isclose(np.asarray(pool.max)[0], vals.max())
    count = np.asarray(td.row_count(pool.weights))[0]
    assert count == pytest.approx(1000, rel=1e-6)
    total = np.asarray(td.row_sum(pool.means, pool.weights))[0]
    assert total == pytest.approx(vals.sum(), rel=1e-4)
    assert np.asarray(pool.recip)[0] == pytest.approx((1.0 / vals).sum(), rel=1e-3)


def test_weighted_samples():
    # sample_rate 0.1 → weight 10 each (reference Histo.Sample weight=1/rate)
    vals = np.array([1.0, 2.0, 3.0, 4.0], dtype=np.float32)
    pool = _ingest(vals, weights=np.full(4, 10.0, np.float32))
    count = np.asarray(td.row_count(pool.weights))[0]
    assert count == pytest.approx(40.0)
    est = _q(pool, [0.5])[0][0]
    assert 2.0 <= est <= 3.0


def test_capacity_bound():
    rng = np.random.default_rng(3)
    vals = rng.normal(0, 1, 200000)
    pool = _ingest(vals, batch=32768)
    nonempty = (np.asarray(pool.weights)[0] > 0).sum()
    assert nonempty <= 101  # δ+1 for δ=100


def test_multi_series_independent():
    rng = np.random.default_rng(5)
    k = 16
    per = 5000
    offsets = np.arange(k, dtype=np.float32) * 100.0
    vals = np.concatenate(
        [rng.uniform(0, 1, per).astype(np.float32) + offsets[i]
         for i in range(k)])
    rows = np.repeat(np.arange(k, dtype=np.int32), per)
    # shuffle so batches interleave series
    perm = rng.permutation(len(vals))
    pool = _ingest(vals[perm], rows=rows[perm], k=k, batch=8192)
    est = _q(pool, [0.5])
    for i in range(k):
        # 0.02 budget: δ=100 interior q-error plus f32 resolution at
        # values ~1500 under incremental interleaved merging
        assert abs(est[i][0] - (offsets[i] + 0.5)) < 0.02, i


def test_merge_pools_matches_combined():
    rng = np.random.default_rng(11)
    a_vals = rng.normal(0, 1, 30000)
    b_vals = rng.normal(0.5, 2, 30000)
    pa = _ingest(a_vals, batch=8192)
    pb = _ingest(b_vals, batch=8192)
    merged = td.merge_pools(pa, pb)
    combined = np.concatenate([a_vals, b_vals])
    qs = [0.01, 0.25, 0.5, 0.75, 0.99]
    est = _q(merged, qs)[0]
    truth = np.quantile(combined, qs)
    for q, e, t in zip(qs, est, truth):
        assert abs(e - t) < 0.08, f"q={q}: est={e} true={t}"
    assert np.asarray(merged.min)[0] == pytest.approx(combined.min(), rel=1e-6)
    assert np.asarray(merged.max)[0] == pytest.approx(combined.max(), rel=1e-6)
    cnt = np.asarray(td.row_count(merged.weights))[0]
    assert cnt == pytest.approx(60000, rel=1e-5)


def test_merge_many_8_to_1():
    # the 8-local → 1-global cross-host merge shape
    import jax.numpy as jnp
    rng = np.random.default_rng(13)
    h, s = 8, 4
    pools = []
    all_vals = [[] for _ in range(s)]
    for _ in range(h):
        vals_h = []
        rows_h = []
        for series in range(s):
            v = rng.gamma(2.0, 10.0 * (series + 1), 2000).astype(np.float32)
            all_vals[series].append(v)
            vals_h.append(v)
            rows_h.append(np.full(2000, series, np.int32))
        pools.append(_ingest(np.concatenate(vals_h),
                             rows=np.concatenate(rows_h), k=s, batch=4096))
    stacked = td.TDigestPool(
        means=jnp.stack([p.means for p in pools]),
        weights=jnp.stack([p.weights for p in pools]),
        min=jnp.stack([p.min for p in pools]),
        max=jnp.stack([p.max for p in pools]),
        recip=jnp.stack([p.recip for p in pools]))
    merged = td.merge_many(stacked)
    for series in range(s):
        combined = np.concatenate(all_vals[series])
        est = _q(merged, [0.5, 0.99])[series]
        truth = np.quantile(combined, [0.5, 0.99])
        scale = combined.std()
        assert abs(est[0] - truth[0]) < 0.05 * scale
        assert abs(est[1] - truth[1]) < 0.10 * scale


def test_empty_digest_nan():
    pool = td.init_pool(2)
    est = _q(pool, [0.5])
    assert np.isnan(est).all()


def test_single_value():
    pool = _ingest([42.0])
    est = _q(pool, [0.0, 0.5, 1.0])[0]
    assert np.allclose(est, 42.0)


def test_cdf_roundtrip():
    import jax.numpy as jnp
    rng = np.random.default_rng(17)
    vals = rng.uniform(0, 100, 20000)
    pool = _ingest(vals, batch=4096)
    test_points = np.array([10.0, 50.0, 90.0], dtype=np.float32)
    for v in test_points:
        c = np.asarray(td.cdf(
            pool.means, pool.weights, pool.min, pool.max,
            jnp.asarray([v], dtype=jnp.float32).repeat(1)))[0]
        assert abs(c - v / 100.0) < 0.01, v
    # boundary semantics (reference CDF :272-277)
    below = np.asarray(td.cdf(pool.means, pool.weights, pool.min, pool.max,
                              jnp.asarray([-1.0], dtype=jnp.float32)))[0]
    above = np.asarray(td.cdf(pool.means, pool.weights, pool.min, pool.max,
                              jnp.asarray([101.0], dtype=jnp.float32)))[0]
    assert below == 0.0 and above == 1.0


def test_incremental_vs_bulk():
    rng = np.random.default_rng(19)
    vals = rng.lognormal(3, 1, 60000).astype(np.float32)
    p_bulk = _ingest(vals)
    p_inc = _ingest(vals, batch=1024)
    qs = [0.1, 0.5, 0.9, 0.99]
    eb = _q(p_bulk, qs)[0]
    ei = _q(p_inc, qs)[0]
    truth = np.quantile(vals, qs)
    for q, b, i, t in zip(qs, eb, ei, truth):
        assert abs(b - t) / t < 0.02, f"bulk q={q}"
        assert abs(i - t) / t < 0.02, f"incremental q={q}"


def test_staged_fold_quantile_accuracy():
    """The <1% q-space error budget holds through the round-4 cadence —
    one staged-plane fold per interval (fewer compressions than the
    per-batch path, so accuracy should be at least as good)."""
    import numpy as np
    import jax.numpy as jnp
    from veneur_tpu.core.worker import _histo_fold_staged

    rng = np.random.default_rng(11)
    S, B, intervals = 64, 256, 4
    pool = td.init_pool(S, td.DEFAULT_CAPACITY)

    def _full(v):
        return jnp.full((S,), v, jnp.float32)

    fields = [pool.means, pool.weights, pool.min, pool.max, pool.recip,
              _full(0.0), _full(np.inf), _full(-np.inf), _full(0.0),
              _full(0.0), _full(0.0), _full(0.0), _full(0.0), _full(0.0)]
    all_vals = [[] for _ in range(S)]
    for _ in range(intervals):
        sv = rng.gamma(2.0, 50.0, (S, B)).astype(np.float32)
        sw = np.ones((S, B), np.float32)
        for r in range(S):
            all_vals[r].extend(sv[r])
        fields = list(_histo_fold_staged(
            *fields, jnp.asarray(sv), jnp.asarray(sw))[:14])

    qs = jnp.asarray(np.array([0.25, 0.5, 0.9, 0.99], np.float32))
    quant = np.asarray(td.quantile(fields[0], fields[1], fields[2],
                                   fields[3], qs))
    worst = 0.0
    for r in range(S):
        vals = np.sort(np.asarray(all_vals[r]))
        n = len(vals)
        for j, q in enumerate((0.25, 0.5, 0.9, 0.99)):
            # q-space error: where the reported value actually sits in
            # the empirical distribution vs where it should
            pos = np.searchsorted(vals, quant[r, j]) / n
            worst = max(worst, abs(pos - q))
    assert worst < 0.01, f"q-space error {worst:.4f} exceeds the 1% budget"


def test_quantile_gather_and_mask_forms_agree():
    """The backend-dispatched slot-selection strategies (host gather vs
    TPU select+reduce) must be BIT-identical, including NaN patterns for
    empty rows and zero-weight slot ties."""
    import numpy as np

    from veneur_tpu.ops.tdigest import _quantile_impl

    rng = np.random.default_rng(5)
    S, C = 512, 64
    means = np.sort(rng.gamma(2.0, 50.0, (S, C)).astype(np.float32), axis=1)
    weights = rng.integers(0, 4, (S, C)).astype(np.float32)  # many zeros
    weights[::17] = 0.0  # some fully empty digests
    dmin = means.min(axis=1) - 1.0
    dmax = means.max(axis=1) + 1.0
    qs = np.array([0.0, 0.5, 0.9, 0.99, 1.0], np.float32)

    a = np.asarray(_quantile_impl(means, weights, dmin, dmax, qs,
                                  use_gather=True))
    b = np.asarray(_quantile_impl(means, weights, dmin, dmax, qs,
                                  use_gather=False))
    assert np.array_equal(np.isnan(a), np.isnan(b))
    m = ~np.isnan(a)
    assert np.array_equal(a[m], b[m])
