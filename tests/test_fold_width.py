"""The staged fold compresses a row at the width of what the row holds
(core/worker._histo_fold_staged, ops/tdigest._compress_narrow): whatever
the mix of rows, and however many trips its full-width pass makes, the
result is bit for bit the full-width fold's and the host engine's; and
the program stays the size it is, so that it compiles in the time it
does."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veneur_tpu.core import worker as wk
from veneur_tpu.core.config import Config
from veneur_tpu.core.server import Server
from veneur_tpu.ops import host_engine as he
from veneur_tpu.ops import tdigest as td

S, B, C = 64, 64, td.DEFAULT_CAPACITY
W = td.NARROW_WIDTH
K = wk.fold_wide_slots(S, B, C)
G = wk.FOLD_GATHER_TRIPS


def _fresh_fields(rows=S):
    pool = td.init_pool(rows, C)

    def full(v):
        return np.full((rows,), v, np.float32)

    return [np.asarray(pool.means), np.asarray(pool.weights),
            full(np.inf), full(-np.inf), full(0.0), full(0.0),
            full(np.inf), full(-np.inf)] + [full(0.0)] * 6


def _planes(counts, rng, weight=1.0):
    counts = np.asarray(counts)
    live = np.arange(B)[None, :] < counts[:, None]
    vals = np.where(live, rng.gamma(2.0, 50.0, live.shape), 0.0)
    return vals.astype(np.float32), (live * weight).astype(np.float32)


def _with_digests(fields, rows, rng):
    """`fields` after a fold that leaves a digest in each of `rows`."""
    counts = np.zeros(fields[0].shape[0], np.int64)
    counts[list(rows)] = B
    vals, wts = _planes(counts, rng)
    return [np.asarray(a) for a in he.np_fold_staged(*fields, vals, wts)]


@jax.jit
def _full_width(means, weights, svals, swts):
    """The fold's merge as it was: every row at C + B."""
    return td._compress_rows(jnp.concatenate([means, svals], axis=-1),
                             jnp.concatenate([weights, swts], axis=-1),
                             td.DEFAULT_COMPRESSION, C)


def _case(name):
    """(fields, svals, swts, wide rows expected) of one occupancy mix."""
    rng = np.random.default_rng(sum(map(ord, name)))
    fields = _fresh_fields()
    if name == "empty":
        return fields, *_planes(np.zeros(S, int), rng), 0
    if name.startswith("all_"):
        n = {"all_1": 1, "all_W": W, "all_W+1": W + 1, "all_B": B}[name]
        return fields, *_planes(np.full(S, n), rng), S if n > W else 0
    if name == "cell":
        # local-timers.steady: two samples a cold timer; a hot one comes
        # with a full staging row and the digest its spill fold left
        hot = range(0, S, 32)
        counts = np.full(S, 2)
        counts[list(hot)] = B
        return (_with_digests(fields, hot, rng), *_planes(counts, rng),
                len(hot))
    if name == "digest_and_one_sample":
        rows = (3, 40)
        return (_with_digests(fields, rows, rng),
                *_planes(np.ones(S, int), rng), len(rows))
    if name.startswith("wide_"):
        n = WIDE[name]
        counts = rng.integers(0, W + 1, S)
        counts[rng.choice(S, n, replace=False)] = W + 1
        return fields, *_planes(counts, rng), n
    if name == "whole_weights":
        # 1 / sample rate, a whole number: every partial sum is exact
        vals, wts = _planes(rng.integers(0, W + 1, S), rng)
        wts *= rng.choice([1.0, 2.0, 10.0, 1000.0], wts.shape)
        return fields, vals, wts.astype(np.float32), 0
    if name == "fractional_weights":
        # @0.3: the sums round, so such a row keeps the full width
        counts = rng.integers(0, W + 1, S)
        vals, wts = _planes(counts, rng)
        frac = [1, 9, 17]
        wts[frac] *= np.float32(1 / 0.3)
        return fields, vals, wts, int((counts[frac] > 0).sum())
    if name == "huge_weights":
        vals, wts = _planes(np.full(S, 3), rng)
        wts[5] *= np.float32(2.0 ** 20)
        return fields, vals, wts, 1
    if name == "equal_values":
        # ties keep their staged order, told apart by their weights
        vals, wts = _planes(np.full(S, W), rng)
        vals = np.where(wts > 0, np.round(vals, -2), 0).astype(np.float32)
        wts *= rng.integers(1, 50, wts.shape)
        return fields, vals, wts.astype(np.float32), 0
    if name == "minus_zero":
        vals, wts = _planes(rng.integers(1, W + 1, S), rng)
        vals[::2] = -0.0
        vals[1::4] *= -1.0
        return fields, vals, wts, 0
    if name == "infinite_value":
        vals, wts = _planes(np.full(S, 4), rng)
        vals[7, 1] = np.inf
        vals[9, 0] = -np.inf
        return fields, vals, wts, 2
    raise KeyError(name)


#: wide rows of the `wide_*` cases: one trip, two, three, the last count
#: that is still compacted, and the first that is not
WIDE = {"wide_K-1": K - 1, "wide_K": K, "wide_K+1": K + 1,
        "wide_2K+1": 2 * K + 1, "wide_GK": G * K, "wide_GK+1": G * K + 1}

CASES = ["empty", "all_1", "all_W", "all_W+1", "all_B", "cell",
         "digest_and_one_sample", *WIDE,
         "whole_weights", "fractional_weights", "huge_weights",
         "equal_values", "minus_zero", "infinite_value"]


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("name", CASES)
def test_fold_is_bitwise_the_full_width_fold_and_the_host_engine(name):
    fields, svals, swts, n_wide = _case(name)
    want_m, want_w = _full_width(jnp.asarray(fields[0]),
                                 jnp.asarray(fields[1]),
                                 jnp.asarray(svals), jnp.asarray(swts))
    with np.errstate(all="ignore"):
        host = he.np_fold_staged(*[a.copy() for a in fields], svals, swts)
    got = wk._histo_fold_staged(*[jnp.asarray(a) for a in fields],
                                jnp.asarray(svals), jnp.asarray(swts))
    np.testing.assert_array_equal(_bits(got[0]), _bits(want_m))
    np.testing.assert_array_equal(_bits(got[1]), _bits(want_w))
    assert len(host) == 14 and len(got) == 15 and int(got[14]) == n_wide
    for i, (g, h) in enumerate(zip(got, host)):
        np.testing.assert_array_equal(_bits(g), _bits(h), err_msg=f"field {i}")


def test_trips_are_read_from_the_input_by_one_program():
    """No trip, one, several, and the whole pool in chunks: neither the
    count of wide rows nor the occupancy is a shape, so (S, B) has one
    executable."""
    assert 0 < K < G * K < S
    assert [wk.fold_takes_all(n, K) for n in (0, K, G * K, G * K + 1, S)] \
        == [False, False, False, True, True]
    for name in ("wide_K", "wide_K+1", "wide_GK+1", "cell", "all_B"):
        fields, svals, swts, _ = _case(name)
        wk._histo_fold_staged(*[jnp.asarray(a) for a in fields],
                              jnp.asarray(svals), jnp.asarray(swts))
    sizes = {wk._histo_fold_staged._cache_size()}
    fields, svals, swts, _ = _case("empty")
    wk._histo_fold_staged(*[jnp.asarray(a) for a in fields],
                          jnp.asarray(svals), jnp.asarray(swts))
    sizes.add(wk._histo_fold_staged._cache_size())
    assert len(sizes) == 1
    # a depth the narrow width does not divide, or no wider: never split
    assert wk.fold_wide_slots(S, W, C) == 0
    assert wk.fold_wide_slots(S, 3 * W // 2, C) == 0


def test_rows_the_chunk_does_not_divide_are_folded_once():
    """S = 100, K = 6: the seventeenth chunk of a dense pool is clamped
    back over rows the sixteenth has done, which reads the fold's input
    and not its output, so they come out the same."""
    rows = 100
    rng = np.random.default_rng(100)
    fields = _with_digests(_fresh_fields(rows), range(0, rows, 3), rng)
    svals, swts = _planes(np.full(rows, W + 1), rng)
    host = he.np_fold_staged(*[a.copy() for a in fields], svals, swts)
    got = wk._histo_fold_staged(*[jnp.asarray(a) for a in fields],
                                jnp.asarray(svals), jnp.asarray(swts))
    assert int(got[14]) == rows
    for i, (g, h) in enumerate(zip(got, host)):
        np.testing.assert_array_equal(_bits(g), _bits(h), err_msg=f"field {i}")


def _stablehlo(fn, rows=4096):
    f32 = jnp.float32
    args = ([jax.ShapeDtypeStruct((rows, C), f32)] * 2
            + [jax.ShapeDtypeStruct((rows,), f32)] * 12
            + [jax.ShapeDtypeStruct((rows, B), f32)] * 2)
    # a function of its own each time: jit keeps traces by function
    return jax.jit(lambda *a: fn(*a)).lower(*args).as_text()


def test_the_program_is_held_to_its_size(monkeypatch):
    """What the chip's compiler is handed, counted at (S, B) = (4096, 64):
    the compress twice (two sorts each) and the sort of the row numbers,
    in 1.76 times the lines of the fold that compresses every row at the
    full width (one compress: 2 sorts, 1,297 lines). PR 37's program held
    the compress three times and a *stable* sort of S row numbers, 7
    sorts in 2.34 times the lines, and took 15-19 s to compile on the
    chip's host where the full-width fold takes 6 (PERF.md section 6, PR
    38): a flush that meets a new row count pays that inside its
    interval. Whoever grows the program meets this test first."""
    fold = wk._histo_fold_staged.__wrapped__
    text = _stablehlo(fold)
    monkeypatch.setattr(wk, "fold_wide_slots", lambda *a: 0)
    full = _stablehlo(fold)
    assert full.count("stablehlo.sort") == 2
    assert text.count("stablehlo.sort") == 5
    lines, full_lines = len(text.splitlines()), len(full.splitlines())
    assert lines <= 1.8 * full_lines, (lines, full_lines)
    # the row numbers differ, so their sort need not be stable: 2 s to
    # compile for the chip at S = 262,144 where the stable one takes 15
    assert text.count("is_stable = false") == 1


def _extract_attrs(srv):
    return next(s[6] for s in srv.last_flush_phases["spans"]
                if s[1] == "flush.extract")


def test_extract_span_reads_the_mix_that_was_fed():
    srv = Server(Config(interval="3600s", percentiles=[0.5],
                        aggregates=["count"], hostname="h"))
    try:
        for i in range(40):
            for v in (1, 2):
                srv.process_metric_packet(f"fw.cold.{i}:{v}|ms".encode())
        for v in range(W + 1):
            srv.process_metric_packet(f"fw.hot:{v}|ms".encode())
        srv.process_metric_packet(b"fw.sampled:5|ms|@0.3")
        srv.flush()
        attrs = _extract_attrs(srv)
        rows = attrs["wide_rows"] + attrs["narrow_rows"]
        assert attrs["wide_rows"] == 2 and rows >= 42
        assert attrs["fold_path"] == "split"
        # every timer past the narrow width: no room to compact them
        for i in range(rows):
            for v in range(W + 1):
                srv.process_metric_packet(f"fw.wide.{i}:{v}|ms".encode())
        srv.flush()
        attrs = _extract_attrs(srv)
        assert attrs["wide_rows"] >= rows
        assert attrs["fold_path"] == "full"
    finally:
        srv.shutdown()
