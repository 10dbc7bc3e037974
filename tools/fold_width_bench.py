#!/usr/bin/env python3
"""fold_width_bench.py: the staged fold on the chip, by piece (PRs 37, 38).

Times `core/worker._histo_fold_staged` against the full-width fold it
replaced, on the occupancy of `local-timers.steady` (most rows hold two
samples, 1/64 hold a full staging row and a digest), on sparse and on
dense ones and on either side of `worker.FOLD_GATHER_TRIPS`, and each
piece of the split path alone at several widths and shares: how
`td.NARROW_WIDTH`, `worker.FOLD_WIDE_SHARE` and FOLD_GATHER_TRIPS were
chosen. Holds the two folds to bitwise equality on the device. And
compiles both programs cold (`lower().compile()`, the persistent cache
off) at --compile-rows: what a flush that meets a new row count pays
inside its interval.

    chiprun -- python tools/fold_width_bench.py [--rows 262144]

One JSON object a line; times are host-clock seconds around a call that
ends in block_until_ready, best of --reps.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from veneur_tpu.core import worker as wk  # noqa: E402
from veneur_tpu.ops import exactnum as exn  # noqa: E402
from veneur_tpu.ops import tdigest as td  # noqa: E402

C, B = td.DEFAULT_CAPACITY, 64


def say(**kw):
    print(json.dumps(kw), flush=True)


def timed(fn, make_args, reps):
    best = None
    out = None
    for _ in range(reps + 1):  # the first call compiles
        args = make_args()
        jax.block_until_ready(args)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        if _ > 0:
            best = dt if best is None else min(best, dt)
    return best, out


@jax.jit
def _full_width_fold(means, weights, svals, swts):
    """The parent's merge: every row at C + B."""
    return td._compress_rows(jnp.concatenate([means, svals], axis=-1),
                             jnp.concatenate([weights, swts], axis=-1),
                             100.0, C)


def cold_compile_s(fn, rows):
    """Seconds of `fn.lower(...).compile()` at (rows, B), nothing cached."""
    f32 = jnp.float32
    args = ([jax.ShapeDtypeStruct((rows, C), f32)] * 2
            + [jax.ShapeDtypeStruct((rows,), f32)] * 12
            + [jax.ShapeDtypeStruct((rows, B), f32)] * 2)
    t0 = time.perf_counter()
    # a function of its own each time: jit keeps traces by function
    jax.jit(lambda *a: fn(*a)).lower(*args).compile()
    return time.perf_counter() - t0


def compile_times(rows_list):
    """The fold as it is and the fold that never splits (the parent's
    program: `fold_wide_slots` answering 0), compiled cold."""
    fold = wk._histo_fold_staged.__wrapped__
    was_cache = jax.config.jax_enable_compilation_cache
    was_slots = wk.fold_wide_slots
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for rows in rows_list:
            new_s = cold_compile_s(fold, rows)
            wk.fold_wide_slots = lambda *a: 0
            try:
                full_s = cold_compile_s(fold, rows)
            finally:
                wk.fold_wide_slots = was_slots
            say(event="compile", rows=rows, fold_staged_s=new_s,
                full_width_only_s=full_s, ratio=new_s / full_s)
    finally:
        jax.config.update("jax_enable_compilation_cache", was_cache)


def planes(rows, rng, cold, hot_every):
    """[S, B] staging planes: `cold` samples a row, B in every
    `hot_every`-th (0: none)."""
    counts = np.full(rows, cold, np.int32)
    if hot_every:
        counts[::hot_every] = B
    live = np.arange(B)[None, :] < counts[:, None]
    sv = np.where(live, rng.gamma(2.0, 50.0, (rows, B)), 0).astype(np.float32)
    return sv, live.astype(np.float32), counts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=262144)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=37)
    ap.add_argument("--compile-rows", default="8192,131072,262144",
                    help="row counts to compile both folds cold at; "
                         "'' for none")
    a = ap.parse_args()
    s, reps = a.rows, a.reps
    dev = jax.devices()[0]
    say(event="device", platform=dev.platform, kind=dev.device_kind)
    rng = np.random.default_rng(a.seed)
    compile_times([int(r) for r in a.compile_rows.split(",") if r])

    pool = td.init_pool(s, C)

    def scalars():
        return [jnp.full((s,), v, jnp.float32)
                for v in (np.inf, -np.inf, 0.0, 0.0, np.inf, -np.inf)
                + (0.0,) * 6]

    # the hot rows' digests: what a spill fold leaves (a full staging
    # row folded into an empty digest)
    hot_every = 64
    pre_v, pre_w, _ = planes(s, rng, 0, hot_every)
    dm, dw = _full_width_fold(pool.means, pool.weights,
                              jnp.asarray(pre_v), jnp.asarray(pre_w))
    dm, dw = np.asarray(dm), np.asarray(dw)

    k = wk.fold_wide_slots(s, B, C)
    g = wk.FOLD_GATHER_TRIPS

    def some_wide(n):
        """`n` rows spread over the pool hold W + 1 samples, the rest 2."""
        sv, sw, counts = planes(s, rng, 2, 0)
        rows = np.linspace(0, s - 1, n).astype(np.int64)
        counts[rows] = td.NARROW_WIDTH + 1
        live = np.arange(B)[None, :] < counts[:, None]
        return np.where(live, np.maximum(sv, 1.0), 0).astype(np.float32), \
            live.astype(np.float32), counts

    mixes = {
        "cell": planes(s, rng, 2, hot_every),
        "dense32": planes(s, rng, 32, 0),
        "all2": planes(s, rng, 2, 0),
        "wide_K+1": some_wide(k + 1),
        "wide_GK": some_wide(g * k),
        "wide_GK+1": some_wide(g * k + 1),
    }
    for name, (sv, sw, _counts) in mixes.items():
        svj, swj = jnp.asarray(sv), jnp.asarray(sw)
        means0 = jnp.asarray(dm if name == "cell" else np.asarray(pool.means))
        w0 = jnp.asarray(dw if name == "cell" else np.asarray(pool.weights))

        def fresh():
            return ([jnp.copy(means0), jnp.copy(w0)] + scalars()
                    + [svj, swj])

        t_new, out = timed(wk._histo_fold_staged, fresh, reps)
        t_old, ref = timed(_full_width_fold,
                           lambda: (means0, w0, svj, swj), reps)
        same = all(
            np.array_equal(np.asarray(o).view(np.uint32),
                           np.asarray(r).view(np.uint32))
            for o, r in zip(out[:2], ref))
        n_wide = int(out[14])
        say(event="fold", mix=name, rows=s, wide_rows=n_wide, slots=k,
            takes_all=wk.fold_takes_all(n_wide, k), fold_staged_s=t_new,
            full_width_merge_s=t_old, bitwise_same=same)

    # ---- the pieces, on the cell's mix ---------------------------------
    sv, sw, _ = mixes["cell"]
    svj, swj = jnp.asarray(sv), jnp.asarray(sw)
    means0, w0 = jnp.asarray(dm), jnp.asarray(dw)

    @jax.jit
    def row_stats(svals, swts):
        live = swts > 0
        return (exn.tsum(swts), exn.tsum(jnp.where(live, svals * swts, 0.0)),
                exn.tsum(jnp.where(live, swts / svals, 0.0)),
                jnp.min(jnp.where(live, svals, jnp.inf), axis=-1),
                jnp.max(jnp.where(live, svals, -jnp.inf), axis=-1))

    t, _ = timed(row_stats, lambda: (svj, swj), reps)
    say(event="piece", piece="row_stats", s=t)

    for w in (8, 16, 32):
        f = jax.jit(lambda v, x, w=w: td._compress_narrow(
            v[:, :w], x[:, :w], 100.0, C))
        t, _ = timed(f, lambda: (svj, swj), reps)
        say(event="piece", piece="narrow", width=w, s=t)

    wide = wk._staged_rows_wide(w0, svj, swj)
    row = jnp.arange(s, dtype=jnp.int32)
    for stable in (True, False):
        f = jax.jit(lambda wide, stable=stable: jax.lax.sort(
            jnp.where(wide, row, s + row), is_stable=stable))
        t, _ = timed(f, lambda: (wide,), reps)
        say(event="piece", piece="sort_row_numbers", stable=stable, s=t)

    for share in (64, 32, 16):
        k = s // share

        @jax.jit
        def compact(wide, k=k):
            return jnp.nonzero(wide, size=k, fill_value=s)[0]

        @jax.jit
        def compact_sort(wide, k=k):
            return jax.lax.sort(jnp.where(wide, row, s + row),
                                is_stable=False)[:k]

        t, _ = timed(compact, lambda: (wide,), reps)
        say(event="piece", piece="nonzero", slots=k, s=t)
        t, rows = timed(compact_sort, lambda: (wide,), reps)
        say(event="piece", piece="nonzero_by_sort", slots=k, s=t)

        @jax.jit
        def wide_pass(means, weights, svals, swts, rows):
            take = lambda x: x.at[rows].get(  # noqa: E731
                mode="clip", indices_are_sorted=True)
            return td._compress_rows(
                jnp.concatenate([take(means), take(svals)], axis=-1),
                jnp.concatenate([take(weights), take(swts)], axis=-1),
                100.0, C)

        t, (wm, ww) = timed(wide_pass,
                            lambda: (means0, w0, svj, swj, rows), reps)
        say(event="piece", piece="gather+compress", slots=k, s=t)

        @jax.jit
        def gather_only(means, weights, svals, swts, rows):
            take = lambda x: x.at[rows].get(  # noqa: E731
                mode="clip", indices_are_sorted=True)
            return take(means), take(svals), take(weights), take(swts)

        t, _ = timed(gather_only,
                     lambda: (means0, w0, svj, swj, rows), reps)
        say(event="piece", piece="gather", slots=k, s=t)

        @jax.jit
        def scatter_only(means, weights, rows, wm, ww):
            put = lambda x, u: x.at[rows].set(  # noqa: E731
                u, mode="drop", indices_are_sorted=True,
                unique_indices=True)
            return put(means, wm), put(weights, ww)

        t, _ = timed(scatter_only,
                     lambda: (means0, w0, rows, wm, ww), reps)
        say(event="piece", piece="scatter", slots=k, s=t)

        @jax.jit
        def chunk_pass(means, weights, svals, swts, k=k):
            """A dense pool's trip: slice, compress, write back."""
            cut = lambda x: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                x, 3 * k, k)
            m, w = td._compress_rows(
                jnp.concatenate([cut(means), cut(svals)], axis=-1),
                jnp.concatenate([cut(weights), cut(swts)], axis=-1),
                100.0, C)
            return (jax.lax.dynamic_update_slice_in_dim(means, m, 3 * k, 0),
                    jax.lax.dynamic_update_slice_in_dim(weights, w, 3 * k, 0))

        t, _ = timed(jax.jit(chunk_pass, donate_argnums=(0, 1)),
                     lambda: (jnp.copy(means0), jnp.copy(w0), svj, swj),
                     reps)
        say(event="piece", piece="slice+compress+update", slots=k, s=t)


if __name__ == "__main__":
    main()
