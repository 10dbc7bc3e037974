"""DeviceWorker: the batched aggregation engine.

Replaces the reference's worker goroutines (worker.go:265-517): instead of N
workers each holding Go maps of per-series sampler objects and processing
one metric at a time, one DeviceWorker owns dense device pools —

  t-digest rows   f32[S_h, C]×2 + scalars   (histogram & timer series)
  HLL registers   int8[S_s, 2^p]            (set series)
  local stats     f32[S_h] × 5              (the Histo sampler's host-local
                                             aggregates, samplers.go:467-494)

— and ingests *batches*: samples buffer host-side into SoA pending arrays,
and one jitted program per batch gathers the active rows, runs the digest
compression / HLL scatter, and scatters the rows back. Counters and gauges
are not sketches; their running state stays host-side in exact float64
(np.bincount-style segment adds), since f32 device accumulators would lose
counts past 2^24 — see ops/scalars.py.

Scope handling: the reference splits state across 13 maps by (type, scope)
(worker.go:60-103); here scope is a per-row *label* (directory.ScopeClass)
and the device programs are scope-oblivious — flush/forward select rows by
label (core/flusher.py).

Flush is a buffer swap (the map-swap of worker.go:498-517): the directory
and pools are handed to the flusher wholesale and replaced with fresh ones,
so next-interval ingest proceeds while extraction runs on the old buffers.

The import path (global tier) merges serialized sketches from downstream
instances: digests buffer host-side per row and merge in one concat+compress
program at flush; HLLs fold with np.maximum and one scatter-max
(reference Worker.ImportMetric/ImportMetricGRPC, worker.go:394-495).
"""

from __future__ import annotations

import functools
import logging
import time
from array import array
from dataclasses import dataclass, field
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.core import columnar, flightrec
from veneur_tpu.core.directory import (LifetimeSeries, RowBook, RowMeta,
                                       ScopeClass, SeriesDirectory,
                                       build_frag, classify)
from veneur_tpu.core.metrics import (DEFAULT_TENANT, MetricKey, UDPMetric,
                                     route_info, tenant_of)
from veneur_tpu.core.tenancy import TenantTallies
from veneur_tpu.health.ledger import TransferLedger
from veneur_tpu.ops import device_guard as dg
from veneur_tpu.ops import exactnum as exn
from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.ops import host_engine as he
from veneur_tpu.ops import microfold as mf
from veneur_tpu.ops import reader_stack as rstack
from veneur_tpu.ops import series_shard as ss
from veneur_tpu.ops import tdigest as td
from veneur_tpu.ops.scalars import counter_contribution
from veneur_tpu.utils.hashing import hll_hash, fmix64, metric_digest

log = logging.getLogger("veneur_tpu.core.worker")


# spilled samples per spill-fold dispatch: the one sample shape the spill
# fold has (see _pad_spill_batch). A longer batch goes in slices, which
# also bounds drain memory to O(chunk) x the in-flight window.
_FOLD_CHUNK = 1 << 14

# the least backlog a flush's spill-fold budget keeps (_shed_spill_budget)
_SHED_FLOOR = 1 << 18

# The spill fold's shape ladder (see _pad_spill_batch): active rows in
# powers of four from 256, samples always _FOLD_CHUNK. Every (rows,
# samples) pair is a program of its own — 10-66 s to compile on a v5e,
# 0.15-0.5 s to load, under the ingest lock — and a drain's spill batch
# is whatever arrived since the last micro-fold: with samples in powers
# of two as well, a seventh shape was still first met thirty intervals
# into a run (PERF.md section 6, PR 39). A row bucket's first fold also
# runs the smaller buckets once on nothing (_warm_spill_rows), so what a
# traffic needs is compiled when its first interval spills and not when
# a thin batch happens by. Padding is cheap since the k-bucket is a
# count: the step takes 8 ms on the chip at 4096 x 16,384, against 74
# with the search.
_SPILL_MIN_ROWS = 256

# HBM valve threshold (see _ensure_histo): pool growths whose estimated
# device footprint stays under this skip the allocation pre-flight — a
# kB-scale grow cannot exhaust HBM, and pre-flighting it would put an
# extra device dispatch on every interval's early-growth ladder
_GROW_PREFLIGHT_MIN_BYTES = 4 << 20


def _next_pow2(n: int, floor: int = 1) -> int:
    v = floor
    while v < n:
        v *= 2
    return v


def _series_budget_id(scope_class: ScopeClass, key: MetricKey) -> str:
    """The tenant ledger's series identity: distinct (key, scope_class)
    pairs occupy distinct rows (see SeriesDirectory), so each consumes
    budget separately."""
    return f"{int(scope_class)}\x1f{key.key_string()}"


# ---------------------------------------------------------------------------
# Jitted device steps


def _comp_add(s, c, x):
    """Neumaier compensated add: (sum, compensation) += x, in f32.

    Long-running scalar accumulators (sum/count/reciprocal-sum) see 10^8+
    samples per series; a bare f32 add loses increments once the running
    value passes 2^24. The reference keeps these in float64
    (tdigest/merging_digest.go scalars); TPUs have no fast f64, so a
    two-float compensated sum carries the residual instead — the true
    value is s + c, reconstructed at flush extraction."""
    t = s + x
    # pick the larger-magnitude operand as the base of the residual;
    # on overflow (t = ±inf) the residual is inf-inf = NaN — drop it so
    # the accumulator saturates at inf like a bare f32 add would
    resid = jnp.where(jnp.abs(s) >= jnp.abs(x), (s - t) + x, (x - t) + s)
    resid = jnp.where(jnp.isfinite(t), resid, 0.0)
    return t, c + resid


@functools.partial(jax.jit, static_argnames=("compression",), donate_argnums=tuple(range(14)))
def _histo_ingest_step(
    means, weights, dmin, dmax, drecip, drecip_c,
    lmin, lmax, lsum, lsum_c, lweight, lweight_c, lrecip, lrecip_c,
    active, lids, values, wts,
    compression: float = td.DEFAULT_COMPRESSION,
):
    """Gather active digest rows, fold one sample batch in, scatter back.

    active: i32[K] pool rows (padded with a scratch row); lids index into
    `active`. Also updates the sampler-local scalar arrays for those rows.
    Scalar accumulators use compensated f32 (see _comp_add); `active`'s
    padding duplicates all point at the scratch row with zero-weight
    stats, so the gather→compensate→set round trip writes identical
    values at every duplicate.
    """
    with jax.named_scope("ingest_step.gather"):
        g_means = means[active]
        g_w = weights[active]
        g_min = dmin[active]
        g_max = dmax[active]
        g_recip = drecip[active]

    n_means, n_w, n_min, n_max, _, stats = td.add_batch(
        g_means, g_w, g_min, g_max, g_recip, lids, values, wts,
        compression=compression,
    )

    with jax.named_scope("ingest_step.scatter"):
        means = means.at[active].set(n_means, mode="drop")
        weights = weights.at[active].set(n_w, mode="drop")
        dmin = dmin.at[active].set(n_min, mode="drop")
        dmax = dmax.at[active].set(n_max, mode="drop")
        n_recip, n_recip_c = _comp_add(g_recip, drecip_c[active], stats.recip)
        drecip = drecip.at[active].set(n_recip, mode="drop")
        drecip_c = drecip_c.at[active].set(n_recip_c, mode="drop")

        lmin = lmin.at[active].min(stats.min, mode="drop")
        lmax = lmax.at[active].max(stats.max, mode="drop")
        n_lsum, n_lsum_c = _comp_add(lsum[active], lsum_c[active], stats.sum)
        lsum = lsum.at[active].set(n_lsum, mode="drop")
        lsum_c = lsum_c.at[active].set(n_lsum_c, mode="drop")
        n_lw, n_lw_c = _comp_add(lweight[active], lweight_c[active], stats.weight)
        lweight = lweight.at[active].set(n_lw, mode="drop")
        lweight_c = lweight_c.at[active].set(n_lw_c, mode="drop")
        n_lr, n_lr_c = _comp_add(lrecip[active], lrecip_c[active], stats.recip)
        lrecip = lrecip.at[active].set(n_lr, mode="drop")
        lrecip_c = lrecip_c.at[active].set(n_lr_c, mode="drop")
    return (means, weights, dmin, dmax, drecip, drecip_c,
            lmin, lmax, lsum, lsum_c, lweight, lweight_c, lrecip, lrecip_c)


class StagedPlane(NamedTuple):
    """One raw-sample staging plane handed to the flush: host arrays
    (vals/wts [S, B], counts [S]) plus the native-memory release hook.
    wts is None when every weight is 1.0 (rebuilt on device from counts);
    free is None for Python-owned planes. While free is set the arrays
    alias a detached C++ plane: whoever holds the StagedPlane owes it
    exactly one free() (wipe and back on its context's shelf), after the
    last read. That holds for a plane to fold (staged_histo) and for
    the micro-fold mirror's replay copy (SwappedEpoch.micro_replay)
    alike; _staged_plane_to_host is the compaction both take when a
    host engine has to read them."""

    vals: np.ndarray
    wts: Optional[np.ndarray]
    counts: Optional[np.ndarray]
    free: Optional[object]


def _free_staged_planes(planes) -> None:
    """Release the native memory of any not-yet-freed planes."""
    for p in planes or ():
        if p.free is not None:
            try:
                p.free()
            except Exception:  # pragma: no cover
                log.exception("staged plane free failed")


def _staged_plane_to_host(plane: StagedPlane) -> StagedPlane:
    """Copy a native plane's content out of C++ memory (flat compaction,
    same layout _fold_one_plane uploads) and release it, so a device
    failover can replay the plane through the host engine. Host-owned
    planes pass through untouched."""
    if plane.free is None:
        return plane
    B = plane.vals.shape[1]
    counts_np = np.minimum(plane.counts, B).astype(np.int32)
    mask = (np.arange(B, dtype=np.int32)[None, :] < counts_np[:, None])
    flat_v = plane.vals[mask]
    flat_w = None if plane.wts is None else plane.wts[mask]
    try:
        plane.free()
    except Exception:  # pragma: no cover
        log.exception("staged plane free failed")
    return StagedPlane(flat_v, flat_w, counts_np, None)


@functools.partial(jax.jit, static_argnames=("depth",))
def _unit_wts_plane(counts, depth: int):
    """Rebuild a unit-weights staging plane from per-row staged counts:
    slot j of row r weighs 1.0 iff j < counts[r]. Uploading [S] i32
    instead of [S, B] f32 halves the flush's host→device bytes when no
    sampled (@rate) metric arrived — the common case."""
    return (jnp.arange(depth, dtype=jnp.int32)[None, :]
            < counts[:, None]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("depth", "unit"))
def _expand_flat_planes(flat_v, flat_w, counts, depth: int, unit: bool):
    """Rebuild the dense [S, depth] value+weight staging planes on
    DEVICE from their row-major compacted form (filled slots only) +
    per-row counts, in ONE dispatch sharing the offset/validity index.

    The dense plane is O(S × depth) bytes regardless of fill — at 1M
    series × depth 64 that is a 268 MB host→device transfer for ~17 MB
    of actual samples, and on a transfer-bound link the dense upload
    alone can blow the 10s flush budget.
    Uploading the compacted samples + counts and paying one gather here
    makes the transfer O(samples), like the readback diet did for the
    extract direction. unit=True ignores flat_w (pass flat_v; XLA DCEs
    it) and uses the validity mask as the weights plane."""
    b = jnp.arange(depth, dtype=jnp.int32)[None, :]
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts, dtype=jnp.int32)[:-1]])
    idx = jnp.clip(offsets[:, None] + b, 0, flat_v.shape[0] - 1)
    valid = b < counts[:, None]
    sv = jnp.where(valid, flat_v[idx], jnp.float32(0))
    if unit:
        sw = valid.astype(jnp.float32)
    else:
        sw = jnp.where(valid, flat_w[idx], jnp.float32(0))
    return sv, sw


#: The staged fold's full-width pass takes K = S // FOLD_WIDE_SHARE rows a
#: trip (fold_wide_slots): its wide rows compacted K at a time while they
#: are at most FOLD_GATHER_TRIPS * K, past that the pool itself in
#: contiguous chunks of K (fold_takes_all).
FOLD_WIDE_SHARE = 16
FOLD_GATHER_TRIPS = 8


def fold_wide_slots(rows: int, depth: int, capacity: int) -> int:
    """K, the rows a trip of `_histo_fold_staged`'s full-width pass takes
    when it splits the rows by width, or 0 where it never splits: the
    staging depth is no wider than the narrow width, or that width does
    not divide both (td._compress_narrow asks for it), or there are
    fewer than FOLD_WIDE_SHARE rows."""
    w = td.NARROW_WIDTH
    if depth <= w or depth % w or capacity % w:
        return 0
    return rows // FOLD_WIDE_SHARE


def fold_takes_all(n_wide, k: int):
    """Whether a fold that found `n_wide` rows wide, k = fold_wide_slots
    > 0, takes every row at the full width. Up to FOLD_GATHER_TRIPS * K
    wide rows are gathered, compressed and scattered back K at a time
    (3.2 ms a trip on the chip at K = 16,384); more, and the trips walk
    the whole pool in slices of K, which need no gather (1.9 ms each,
    S / K = 16 of them: what nine gathered trips would cost; PERF.md
    section 6, PR 38). One rule for the program (`n_wide` traced) and
    for the host that reads its count back."""
    return n_wide > FOLD_GATHER_TRIPS * k


def _staged_rows_wide(weights, svals, swts):
    """bool[S]: the rows the staged fold compresses at the full C + B.

    A row is *narrow*, and is compressed over its first td.NARROW_WIDTH
    staging slots alone, when its digest is empty (its first centroid
    weighs 0: a digest row is sorted live-first), it staged nothing past
    those slots, every staged weight is a whole number of at most 2**19
    (so every partial sum of a row is exact, in any order), and no
    staged value is infinite or NaN (an infinite sample would sort among
    the digest's empty slots). Everything else is wide."""
    w = td.NARROW_WIDTH
    live = swts > 0
    exact = (swts >= 0) & (swts == jnp.floor(swts)) & (swts <= 2.0 ** 19)
    narrow = ((weights[:, 0] == 0)
              & ~jnp.any(live[:, w:], axis=-1)
              & jnp.all(exact & (~live | (jnp.abs(svals) < jnp.inf)),
                        axis=-1))
    return ~narrow


@functools.partial(jax.jit, static_argnames=("compression",),
                   donate_argnums=tuple(range(14)))
def _histo_fold_staged(
    means, weights, dmin, dmax, drecip, drecip_c,
    lmin, lmax, lsum, lsum_c, lweight, lweight_c, lrecip, lrecip_c,
    svals, swts,
    compression: float = td.DEFAULT_COMPRESSION,
):
    """Fold the staged raw-sample plane [S, B] into the digest pool.

    The TPU-first half of staged ingest: samples land in a host-side
    [S, B] plane at O(1) numpy-store cost per sample, and this ONE
    program pays the digest compress once per row per interval — the
    batched analog of the reference's deferred tempCentroids merge
    (tdigest/merging_digest.go:115-137 buffers raw samples, :140-224
    merges on overflow). Replaces per-batch gather→add_batch→scatter,
    whose [K, 2C] sort per batch dominated ingest compute.

    The staged plane is already row-dense, so no batch sort, run
    detection, or prefix-sum gathers are needed: per-row scalar stats
    are masked [S, B] reductions and the merge is one compress over
    [S, C+B]. Empty slots carry weight 0 (value ignored).

    **A row is compressed at the width of what it holds.** Rows are
    handed out per interval, so a timer that saw a handful of samples
    meets this fold with an empty digest: of its C + B slots all but a
    few are +inf / weight-0 padding that sorts last and adds zeros. Such
    a *narrow* row (`_staged_rows_wide` says which) goes through
    td._compress_narrow over its first td.NARROW_WIDTH staging slots.
    The *wide* rows go through td._compress_rows at C + B as ever, K =
    fold_wide_slots rows a trip of one loop whose trip count the
    program reads from its input (fold_takes_all): the wide rows
    compacted, K to a trip (none: no trip), or, where there are too
    many to be worth compacting, the pool itself in contiguous chunks
    of K. So the program specialises on (S, B) alone and holds the
    compress twice, at [S, NARROW_WIDTH] and at [K, C + B], whatever
    the rows hold. The result is bit for bit the full-width one, which
    is ops/host_engine.np_fold_staged's (td._compress_narrow says why;
    tests/test_fold_width.py holds both to it, and the program to its
    size).

    Returns the 14 fields and, fifteenth, the i32 count of the rows it
    found wide (`DeviceWorker._note_fold_widths` reads it once the
    flush's readback has blocked on the device anyway).
    """
    c = means.shape[1]
    s, b = svals.shape
    with jax.named_scope("fold_staged.row_stats"):
        live = swts > 0
        # Order-pinned tree sums (ops/exactnum.py): the host fallback engine
        # replays this fold over the same staged plane bitwise.
        s_w = exn.tsum(swts)
        s_sum = exn.tsum(jnp.where(live, svals * swts, 0.0))
        s_recip = exn.tsum(jnp.where(live, swts / svals, 0.0))
        s_min = jnp.min(jnp.where(live, svals, jnp.inf), axis=-1)
        s_max = jnp.max(jnp.where(live, svals, -jnp.inf), axis=-1)

    def full_width(means, weights, svals, swts):
        with jax.named_scope("fold_staged.merge"):
            cat_means = jnp.concatenate([means, svals], axis=-1)
            cat_w = jnp.concatenate([weights, swts], axis=-1)
        return td._compress_rows(cat_means, cat_w, compression, c)

    k = fold_wide_slots(s, b, c)
    if k == 0:
        n_wide = jnp.int32(s)
        means, weights = full_width(means, weights, svals, swts)
    else:
        with jax.named_scope("fold_staged.widths"):
            wide = _staged_rows_wide(weights, svals, swts)
            n_wide = jnp.sum(wide, dtype=jnp.int32)
            full = fold_takes_all(n_wide, k)
            trips = -(-jnp.where(full, s, n_wide) // k)
            # the wide rows' numbers, ascending; the slots past n_wide
            # point past the pool, each at a row of its own: read
            # clipped, written nowhere. The keys differ, so an unstable
            # sort has one answer, and the chip's compiler takes 2 s
            # over it where the stable sort of 262,144 keys takes 15
            # (jnp.nonzero: 9 s, and 3.0 ms on the chip against 0.7).
            row = jnp.arange(s, dtype=jnp.int32)
            order = jax.lax.sort(jnp.where(wide, row, s + row),
                                 is_stable=False)
        w = td.NARROW_WIDTH
        planes = (means, weights, svals, swts)

        def trip(i, out):
            at = i * k  # clamped to S - K by the slices: rows done twice
            with jax.named_scope("fold_staged.wide_rows"):
                rows = jax.lax.dynamic_slice_in_dim(order, at, k)
                t_means, t_w, t_svals, t_swts = jax.lax.cond(
                    full,
                    lambda: tuple(jax.lax.dynamic_slice_in_dim(a, at, k)
                                  for a in planes),
                    lambda: tuple(a.at[rows].get(mode="clip",
                                                 indices_are_sorted=True)
                                  for a in planes))
            done = full_width(t_means, t_w, t_svals, t_swts)
            with jax.named_scope("fold_staged.wide_rows"):
                return jax.lax.cond(
                    full,
                    lambda out: tuple(
                        jax.lax.dynamic_update_slice_in_dim(a, u, at, 0)
                        for a, u in zip(out, done)),
                    lambda out: tuple(
                        a.at[rows].set(u, mode="drop",
                                       indices_are_sorted=True,
                                       unique_indices=True)
                        for a, u in zip(out, done)),
                    out)

        means, weights = jax.lax.fori_loop(
            0, trips, trip,
            td._compress_narrow(svals[:, :w], swts[:, :w], compression, c))

    with jax.named_scope("fold_staged.scalars"):
        dmin = jnp.minimum(dmin, s_min)
        dmax = jnp.maximum(dmax, s_max)
        drecip, drecip_c = _comp_add(drecip, drecip_c, s_recip)
        lmin = jnp.minimum(lmin, s_min)
        lmax = jnp.maximum(lmax, s_max)
        lsum, lsum_c = _comp_add(lsum, lsum_c, s_sum)
        lweight, lweight_c = _comp_add(lweight, lweight_c, s_w)
        lrecip, lrecip_c = _comp_add(lrecip, lrecip_c, s_recip)
    return (means, weights, dmin, dmax, drecip, drecip_c,
            lmin, lmax, lsum, lsum_c, lweight, lweight_c, lrecip, lrecip_c,
            n_wide)


@functools.partial(jax.jit, static_argnames=("compression",), donate_argnums=(0, 1, 2, 3, 4, 5))
def _histo_import_step(
    means, weights, dmin, dmax, drecip, drecip_c,
    rows, imp_means, imp_w, imp_min, imp_max, imp_recip,
    compression: float = td.DEFAULT_COMPRESSION,
):
    """Merge imported digest rows [K, W] into pool rows (global tier)."""
    c = means.shape[1]
    g_means = means[rows]
    g_w = weights[rows]
    cat_means = jnp.concatenate([g_means, imp_means], axis=-1)
    cat_w = jnp.concatenate([g_w, imp_w], axis=-1)
    n_means, n_w = td.compress_rows(cat_means, cat_w, compression, c)
    means = means.at[rows].set(n_means, mode="drop")
    weights = weights.at[rows].set(n_w, mode="drop")
    dmin = dmin.at[rows].min(imp_min, mode="drop")
    dmax = dmax.at[rows].max(imp_max, mode="drop")
    n_recip, n_recip_c = _comp_add(drecip[rows], drecip_c[rows], imp_recip)
    drecip = drecip.at[rows].set(n_recip, mode="drop")
    drecip_c = drecip_c.at[rows].set(n_recip_c, mode="drop")
    return means, weights, dmin, dmax, drecip, drecip_c


@jax.jit
def _histo_flush_extract(means, weights, dmin, dmax, drecip, drecip_c,
                         lmin, lmax, lsum, lsum_c, lweight, lweight_c,
                         lrecip, lrecip_c, qs):
    """One program extracting everything the flusher needs from all rows.

    Compensated accumulators resolve to their true value (s + c) here."""
    quantiles = td.quantile(means, weights, dmin, dmax, qs)
    with jax.named_scope("flush_extract.sums"):
        dsum = td.row_sum(means, weights)
        dcount = td.row_count(weights)
        return (quantiles, dmin, dmax, dsum, dcount, drecip + drecip_c,
                lmin, lmax, lsum + lsum_c, lweight + lweight_c,
                lrecip + lrecip_c)


@jax.jit
def _pack_extract_columns(qv, *cols):
    """[S,P] quantiles + ten [S] aggregates → one [S,P+10] f32 array, so
    extract_snapshot pays a single device→host transfer instead of
    eleven synchronous ones (the round-trips, not the bytes, dominate on
    a remote-device link).

    The f32 cast is a deliberate precision bound: sums/weights
    ACCUMULATE in compensated f64 on device (error does not grow with
    sample count), and the single final cast caps the REPORTED value at
    f32's 2^-24 relative error (~7 significant digits) — ample for
    observability data, and half the readback bytes of f64 at 1M
    series. Counters are unaffected (host-side exact f64 pools);
    integer-valued digest counts are exact below 2^24 per series per
    interval."""
    with jax.named_scope("pack_extract"):
        return jnp.concatenate(
            [qv] + [c[:, None].astype(jnp.float32) for c in cols], axis=1)


@functools.partial(jax.jit, static_argnames=("new_rows",), donate_argnums=(0,))
def _grow_2d(old, new_rows: int):
    s, c = old.shape
    return jnp.zeros((new_rows, c), old.dtype).at[:s].set(old)


@functools.partial(
    jax.jit, static_argnames=("new_rows", "fill"), donate_argnums=(0,)
)
def _grow_1d(old, new_rows: int, fill: float):
    s = old.shape[0]
    return jnp.full((new_rows,), fill, old.dtype).at[:s].set(old)


# ---------------------------------------------------------------------------
# Host-side state containers


class ScalarPool(RowBook):
    """Growable f64 value array + per-row metadata; row ids are
    append-ordered so the Python dict path and the native directory agree
    on assignment."""

    def __init__(self, initial: int = 256) -> None:
        super().__init__()
        self.values = np.zeros(initial, np.float64)
        self.present = np.zeros(initial, bool)
        self.used = 0

    @property
    def meta(self):
        """Row r's (key, tags, scope_class, sinks) at [r]."""
        return self.entries

    @staticmethod
    def _ikey(entry) -> tuple:
        return (entry[0], entry[2])

    @staticmethod
    def _entry_accessors(meta: list) -> tuple:
        def meta_at(i, _meta=meta):
            key, tags, _cls, sinks = _meta[i]
            return key.name, tags, sinks

        def frag_at(i, _meta=meta):
            key, tags, _cls, _sinks = _meta[i]
            return build_frag(key.name, tags)

        return meta_at, frag_at

    def ensure(self, rows: int) -> None:
        if rows > len(self.values):
            cap = len(self.values)
            while cap < rows:
                cap *= 2
            self.values = np.resize(self.values, cap)
            self.values[self.used:] = 0.0
            newp = np.zeros(cap, bool)
            newp[: self.used] = self.present[: self.used]
            self.present = newp

    def upsert(self, key, scope_class, tags, sinks) -> int:
        row = self.index.get((key, scope_class))
        if row is None:
            row = self.used
            self.adopt_row(row, key, tags, scope_class, sinks)
        return row

    def adopt_row(self, row: int, key, tags, scope_class, sinks) -> None:
        """One row of the Python upsert path."""
        frag = None
        if self.frag_clean:
            frag = build_frag(getattr(key, "name", key), list(tags))
        self._append(row, (key, tags, scope_class, sinks), scope_class,
                     sinks, True, frag)
        self._cover(row + 1)

    def _cover(self, rows: int) -> None:
        """Values for rows [0, rows)."""
        # grow BEFORE bumping used: ensure() copies/zeroes relative to
        # self.used, and with used already including the new row it
        # copies one element past the old arrays (crash at a capacity
        # boundary) and leaves np.resize's recycled junk in the new row
        self.ensure(rows)
        self.used = rows

    def adopt_batch(self, first_row: int, known, sids) -> int:
        by_id = super().adopt_batch(first_row, known, sids)
        self._cover(first_row + len(sids))
        return by_id

    def _extend(self, entries: list, codes, frags: list) -> None:
        super()._extend(entries, codes, frags)
        self._cover(len(self._entries))


@dataclass
class HostScalars:
    """Exact host-side counter/gauge/status state for one interval."""

    counters: ScalarPool = field(default_factory=ScalarPool)
    gauges: ScalarPool = field(default_factory=ScalarPool)

    status_index: dict = field(default_factory=dict)
    status_meta: list = field(default_factory=list)
    status_values: list = field(default_factory=list)  # (value, message, host)

    # compatibility iteration helpers used by the flusher/codec
    @property
    def counter_meta(self):
        return self.counters.meta

    @property
    def counter_values(self):
        return self.counters.values[: self.counters.used]

    @property
    def gauge_meta(self):
        return self.gauges.meta

    @property
    def gauge_values(self):
        return self.gauges.values[: self.gauges.used]


@dataclass
class HistoDeviceState:
    means: jax.Array
    weights: jax.Array
    dmin: jax.Array
    dmax: jax.Array
    drecip: jax.Array
    # compensation halves of the compensated-f32 scalar accumulators
    # (see _comp_add); true value = base + _c, resolved at flush extract
    drecip_c: jax.Array
    lmin: jax.Array
    lmax: jax.Array
    lsum: jax.Array
    lsum_c: jax.Array
    lweight: jax.Array
    lweight_c: jax.Array
    lrecip: jax.Array
    lrecip_c: jax.Array

    @classmethod
    def create(cls, rows: int, capacity: int) -> "HistoDeviceState":
        # every field gets its own buffer — the ingest step donates all of
        # them, and donating one buffer twice is an error
        pool = td.init_pool(rows, capacity)

        def _full(v):
            return jnp.full((rows,), v, jnp.float32)

        return cls(
            means=pool.means, weights=pool.weights, dmin=pool.min,
            dmax=pool.max, drecip=pool.recip, drecip_c=_full(0.0),
            lmin=_full(jnp.inf), lmax=_full(-jnp.inf), lsum=_full(0.0),
            lsum_c=_full(0.0), lweight=_full(0.0), lweight_c=_full(0.0),
            lrecip=_full(0.0), lrecip_c=_full(0.0),
        )

    @property
    def num_rows(self) -> int:
        return self.means.shape[0]

    def fields(self) -> tuple:
        """The 14 device arrays in the kernel argument order."""
        return (self.means, self.weights, self.dmin, self.dmax,
                self.drecip, self.drecip_c, self.lmin, self.lmax,
                self.lsum, self.lsum_c, self.lweight, self.lweight_c,
                self.lrecip, self.lrecip_c)

    def placed(self, shard) -> "HistoDeviceState":
        """Commit every pool array to a SeriesSharding's mesh (fresh
        pools are all-constant, so the initial resharding copy is the
        only cross-device move the sharded pool ever makes)."""
        return HistoDeviceState(*(shard.place(a) for a in self.fields()))

    def grow(self, new_rows: int, shard=None) -> "HistoDeviceState":
        # zero-filled new mean rows are safe: every kernel keys empty slots
        # off weight==0, never the stored mean. Sharded pools pad each
        # shard's local block instead of appending at the end, which keeps
        # every existing logical row on its shard at its local index
        # (ops/series_shard.grow_2d) — growth moves no data between devices.
        inf = float("inf")
        g2 = _grow_2d if shard is None else shard.grow_2d
        g1 = _grow_1d if shard is None else shard.grow_1d
        return HistoDeviceState(
            means=g2(self.means, new_rows),
            weights=g2(self.weights, new_rows),
            dmin=g1(self.dmin, new_rows, inf),
            dmax=g1(self.dmax, new_rows, -inf),
            drecip=g1(self.drecip, new_rows, 0.0),
            drecip_c=g1(self.drecip_c, new_rows, 0.0),
            lmin=g1(self.lmin, new_rows, inf),
            lmax=g1(self.lmax, new_rows, -inf),
            lsum=g1(self.lsum, new_rows, 0.0),
            lsum_c=g1(self.lsum_c, new_rows, 0.0),
            lweight=g1(self.lweight, new_rows, 0.0),
            lweight_c=g1(self.lweight_c, new_rows, 0.0),
            lrecip=g1(self.lrecip, new_rows, 0.0),
            lrecip_c=g1(self.lrecip_c, new_rows, 0.0),
        )


@dataclass
class FlushSnapshot:
    """Everything one interval produced, in host memory: the input to
    InterMetric generation (core/flusher.py) and to forwarding
    (distributed/forward.py)."""

    directory: SeriesDirectory
    scalars: HostScalars
    interval_s: float
    # histogram/timer extraction [rows in directory.histo order]:
    quantile_values: Optional[np.ndarray] = None  # [S, P]
    quantile_qs: Optional[np.ndarray] = None  # [P]
    dmin: Optional[np.ndarray] = None
    dmax: Optional[np.ndarray] = None
    dsum: Optional[np.ndarray] = None
    dcount: Optional[np.ndarray] = None
    drecip: Optional[np.ndarray] = None
    lmin: Optional[np.ndarray] = None
    lmax: Optional[np.ndarray] = None
    lsum: Optional[np.ndarray] = None
    lweight: Optional[np.ndarray] = None
    lrecip: Optional[np.ndarray] = None
    # raw digest rows (for forwarding):
    digest_means: Optional[np.ndarray] = None
    digest_weights: Optional[np.ndarray] = None
    # sets:
    set_estimates: Optional[np.ndarray] = None  # [S_sets]
    set_registers: Optional[np.ndarray] = None  # [S_sets, m] (forwarding)
    # unique-timeseries count for this worker (None if disabled):
    unique_timeseries_registers: Optional[np.ndarray] = None
    # True when this interval's extraction finished on the HOST engine
    # after a device fault or while the device path was quarantined
    # (ops/device_guard); surfaced by the live query layer so readers
    # know the numbers came from the fallback path (still bit-identical
    # by the host-engine parity contract, but worth flagging)
    degraded: bool = False


@dataclass
class SwappedEpoch:
    """A closed interval's state, detached from the live worker by
    DeviceWorker.swap(). Holds device arrays (histo/sets) plus host
    directories; extract_snapshot() turns it into a FlushSnapshot without
    touching the worker's new epoch."""

    directory: SeriesDirectory
    scalars: HostScalars
    histo: Optional["HistoDeviceState"]
    sets: Optional[jax.Array]
    staged_sets: object
    umts: Optional[np.ndarray]
    mesh_out: Optional[dict]
    # raw-sample staging planes still unfolded at swap, each a
    # (vals[S, B], wts[S, B], free_or_None) tuple — the Python plane
    # and/or the detached native C++ plane (whose memory `free` releases
    # once uploaded); extract_snapshot folds them into `histo` off the
    # ingest lock
    staged_histo: Optional[list] = None
    # hot-row spill batch (rows, vals, wts numpy SoA) drained from the
    # C++ context at epoch close but NOT yet folded: under overload the
    # backlog fold is tens of seconds of device work, and running it in
    # swap() held the ingest lock for the whole of it (round-5 overload
    # measurement: swap 42s of a 44s flush, all in the spill fold).
    # extract_snapshot folds it off the lock, like the staged planes.
    spill_histo: Optional[tuple] = None
    # micro-fold device mirror of the epoch's staging plane
    # (ops/microfold.MirrorState): already resident on device at swap, so
    # extract folds it without an upload. Replaces the plane it mirrored
    # in staged_histo — exactly one of the two carries a given sample.
    device_stage: Optional[object] = None
    # the epoch's rotated MicroFoldMirror plus the residual COO deltas
    # collected under the swap fence but NOT yet fed: the device feeds
    # are deferred to extract_snapshot (off the tick) — a starved
    # scheduler must not turn the swap into the upload burst micro-folds
    # exist to remove. extract feeds these, finish()es the mirror, and
    # populates device_stage.
    micro_residual: Optional[tuple] = None
    # shared-nothing reader shards (DeviceWorker.attach_reader_shards):
    # each context's detached staging plane paired with a COPY of its
    # local-row → canonical-row map, in context order. extract_snapshot
    # merges them into ONE flat batch (ops/reader_stack.py) feeding the
    # legacy staged fold; the native memory is released right after the
    # merge copies out of it.
    reader_planes: Optional[list] = None
    # conservation insurance for the micro-fold mirror (device fault
    # domain): the staging plane the mirror fully covered, RETAINED
    # instead of freed at swap. If a device fault voids the mirror
    # before or during extract, the flush folds this plane on the host
    # engine — no epoch lost. A StagedPlane either way: the detached
    # C++ plane ITSELF, uncompacted and with its free (native path:
    # only the failover compacts it, _staged_plane_to_host), or the
    # dense Python pair with counts and free None (python path).
    micro_replay: Optional[StagedPlane] = None
    # the same plane once the mirror's fold has landed: nothing will
    # read it again (a later fault must not fold it twice), it only
    # waits for its release, which extract_snapshot puts under the
    # device's fold + extract
    spent_replay: Optional[StagedPlane] = None
    # spill batches the epoch folded into `histo` while it was live
    # (DeviceWorker.spill_steps_epoch at the swap); the flush's extract
    # span adds the deferred ones and carries the sum as `spill_steps`
    spill_steps: int = 0

    def release(self) -> None:
        """Give back every native staging plane this epoch still
        holds. Each plane leaves its field before its free() runs, so
        a second call, or a call after a clean extraction, frees
        nothing: extract_snapshot ends on it, and so does whoever
        drops a swapped epoch without extracting it."""
        planes = list(self.staged_histo or ())
        planes += [StagedPlane(*st[:3], st[4])
                   for st, _m in self.reader_planes or ()]
        planes += [p for p in (self.micro_replay, self.spent_replay)
                   if p is not None]
        self.staged_histo = self.reader_planes = None
        self.micro_replay = self.spent_replay = None
        _free_staged_planes(planes)


class DeviceWorker:
    """Batched aggregation engine for one shard of the metric space.

    The reference routes each metric to one of N workers by Digest%N
    (server.go:1028,1039) to keep every series in exactly one histogram;
    here a single DeviceWorker typically owns the whole space (the TPU *is*
    the parallelism), but sharding across workers/devices composes the same
    way — see distributed/mesh.py.
    """

    def __init__(
        self,
        batch_size: int = 16384,
        compression: float = td.DEFAULT_COMPRESSION,
        capacity: int = td.DEFAULT_CAPACITY,
        hll_precision: int = hll_ops.DEFAULT_PRECISION,
        initial_histo_rows: int = 1024,
        initial_set_rows: int = 256,
        count_unique_timeseries: bool = False,
        is_local: bool = True,
        set_hash: str = "fnv",
        set_store: str = "staged",
        stage_depth: int = 64,
        spill_cap: int = 1 << 22,
        micro_fold: bool = False,
        micro_fold_rows: int = 8192,
        micro_fold_max_age_s: float = 0.25,
        series_shards: int = 0,
        device_guard: bool = True,
        device_fault_streak: int = dg.DEFAULT_STREAK_LIMIT,
        device_probe_interval_s: float = dg.DEFAULT_PROBE_INTERVAL_S,
    ) -> None:
        self.batch_size = batch_size
        # native pending-batch bound; beyond it samples shed, counted in
        # overload_dropped (drop-don't-block under overload)
        self.spill_cap = spill_cap
        # raw-sample staging slots per digest row (B in _histo_fold_staged);
        # rows whose staged count hits B spill through the direct per-batch
        # device fold — cheap there, since hot rows make K small
        self.stage_depth = stage_depth
        self.compression = compression
        self.capacity = capacity
        self.hll_precision = hll_precision
        self.set_hash = set_hash
        if set_hash == "metro":
            from veneur_tpu.utils.hashing import metro_hash64

            self._set_hash64 = metro_hash64
        else:
            self._set_hash64 = hll_hash
        self._initial_histo_rows = initial_histo_rows
        self._initial_set_rows = initial_set_rows
        # device-sharded series axis (ops/series_shard.py): partition the
        # sketch pools over a 1-D device mesh. Resolved through the
        # VENEUR_SERIES_SHARDS escape hatch; an unusable request (not a
        # pow2, more shards than devices) degrades to the legacy
        # single-device path with a warning rather than failing ingest.
        shards = ss.resolve_series_shards(series_shards)
        self._shard: Optional[ss.SeriesSharding] = None
        if shards > 1:
            if ss.shards_usable(shards):
                self._shard = ss.SeriesSharding(shards, compression)
                # pool row counts must stay pow2 multiples of the shard
                # count so every growth/slice divides evenly
                self._initial_histo_rows = _next_pow2(
                    max(initial_histo_rows, shards))
                self._initial_set_rows = _next_pow2(
                    max(initial_set_rows, shards))
            else:
                log.warning(
                    "series_shards=%d unusable (need a power of two <= "
                    "visible device count); using the single-device pool",
                    shards)
        self.series_shards = self._shard.shards if self._shard else 1
        self.count_unique_timeseries = count_unique_timeseries
        self.is_local = is_local
        self.set_store = set_store
        self._processed_py = 0
        self._native_proc_seen = 0
        # lifetime samples accepted across epochs (accumulated at swap;
        # per-epoch `processed` resets there)
        self.processed_total = 0
        self.imported = 0
        # overload-shedding tallies: per-interval (consumed + reset by
        # the server's flush telemetry) and lifetime (soaks/operators)
        self.overload_dropped = 0
        self.overload_dropped_total = 0
        self._inflight_folds = 0
        # pool rows -> the largest spill row bucket folded at that pool
        # size (_warm_spill_rows)
        self._spill_rows_warm: dict[int, int] = {}
        # the staged set store's, across epochs: dense pool rows -> the
        # longest insert length run there (StagedSetStore._warm_inserts),
        # and set entries routed to a dense row / into the sparse tier
        # by the epochs closed so far (set_counters)
        self._set_inserts_warm: dict[int, int] = {}
        self._sets_dense_closed = 0
        self._sets_sparse_closed = 0
        self._staged_sets = None
        # pool rows -> the row counts flushes have folded and extracted
        # at, at that pool size (_flush_rows)
        self._flush_rows_had: dict[int, set[int]] = {}
        # per-flush spill-fold budget: seconds of fold work one flush may
        # inherit (the server sets this to a fraction of its interval)
        # and the measured fold throughput that converts it to samples.
        # Backlog beyond budget sheds AT SWAP, counted — bounding flush
        # wall time is what keeps the cadence under overload.
        self.fold_budget_s: float = 5.0
        self._fold_rate_ewma: float = 1e6  # samples/s, refined by extract
        # flush-path transfer byte accounting (health/ledger.py); reset
        # each swap, read by the server's flush telemetry and pinned by
        # the O(samples)-transfer regression test
        self.ledger = TransferLedger()
        # flush-deadline governor (health/governor.py), installed by the
        # server; None (or disabled) keeps single-shot extraction
        self.governor = None
        self._native = None
        # shared-nothing reader shards (attach_reader_shards): extra
        # native contexts, one per C++ reader thread, each with its own
        # directory/staging plane/spill epoch so the commit hot path
        # takes no shared mutex. Empty list == legacy single-context
        # mode everywhere (the checks below are `if self._reader_ctxs`).
        self._reader_ctxs: list = []
        # per-reader-context rebasing baselines (the home context keeps
        # its historical scalar fields — the server reads those directly)
        self._reader_errs_seen: list[int] = []
        self._reader_proc_seen: list[int] = []
        self._reader_drop_seen: list[int] = []
        # lifetime per-context conservation attribution, [home] + one
        # per reader shard: samples committed (counted at the flush-edge
        # detach fence) and samples shed at that context's spill caps
        self.reader_committed: list[int] = []
        self.reader_dropped: list[int] = []
        self._mesh_pool = None
        # always-hot flush (ops/microfold.py): when enabled, a scheduler
        # calls micro_fold_once() every time the staged-sample backlog
        # crosses micro_fold_rows or ages past micro_fold_max_age_s, so
        # the staging plane streams to a device mirror DURING the
        # interval and swap's fold shrinks to a residual drain
        self.micro_fold = bool(micro_fold)
        self.micro_fold_rows = int(micro_fold_rows)
        self.micro_fold_max_age_s = float(micro_fold_max_age_s)
        self._micro: Optional[mf.MicroFoldMirror] = None
        self._micro_last_drain = time.monotonic()
        # lifetime / per-epoch micro-fold drains, and the seconds swap
        # spent on the final residual drain + mirror fence (the server's
        # per-flush drain_ms telemetry; captured at swap like
        # staged_samples_swapped)
        self.micro_folds_total = 0
        self.micro_folds_epoch = 0
        self.micro_folds_swapped = 0
        # spill batches (one ingest step each, _fold_slice_direct) folded
        # into the live pool this epoch
        self.spill_steps_epoch = 0
        # what is kept per lifetime series id, one table per native
        # context (see _adopt_pending); deliberately NOT in _reset_epoch —
        # surviving the per-flush directory swap is its whole purpose
        self._adopt_cache = [LifetimeSeries()]
        # per-tenant QoS (core/tenancy.py), installed by the server when
        # tenancy is configured; None keeps every tenant path dormant.
        # The ledger is SHARED across workers (admission is a host-global
        # decision — one tenant's series spread across workers by digest)
        self.tenancy = None
        self.tenant_sketch = None
        # live query subsystem (veneur_tpu/query/): when the server wires
        # a publisher, extract_snapshot hands it this epoch's read view —
        # the FlushSnapshot, a device evaluator closed over the retained
        # post-fold field arrays, and a fenced tenant-sketch view — right
        # before returning. The engine stages per-worker views and the
        # server commits them as ONE epoch after every worker extracted,
        # so queries never see a torn cross-worker state. None keeps the
        # whole path dormant (no retained device memory).
        self.query_publisher = None
        self.query_epoch_seq = 0
        # per-epoch / lifetime sample accounting per tenant; the epoch
        # tallies fold into the totals at swap, the processed_total
        # pattern (see swap())
        self.tenant_tallies = TenantTallies()
        self.tenant_tallies_total = TenantTallies()
        # device fault domain (ops/device_guard.py): one breaker per
        # worker over every device entry point. While quarantined
        # (_host_live) the live pools are host numpy state driven by the
        # host engine (ops/host_engine.py, bit-identical per metric
        # class); device_guard_tick() — run by the server after each
        # extraction, under the ingest lock — handles quarantine of the
        # live epoch, probing, and re-admission.
        self.guard = dg.DeviceGuard(
            streak_limit=device_fault_streak,
            probe_interval_s=device_probe_interval_s,
            enabled=bool(device_guard) and dg.guard_enabled_default())
        # span record (core/flightrec.py): the server hands every worker
        # its own (set_recorder); a worker alone keeps a private one.
        # flight_epoch is the ordinal of the flush that will close the
        # live epoch: the server advances it under the ingest lock right
        # after swap(), so ingest-side spans name their flush exactly
        self.rec = self.guard.rec = flightrec.Recorder()
        self.flight_epoch = 1
        # (rows, wide slots, wide rows) of the flush's staged folds so far
        self._fold_widths: list = []
        # live pools are host-side (HostHistoState / np registers)
        self._host_live = False
        # a device fault voided this epoch's micro-fold mirror: the
        # staging plane retains every sample, micro-folding pauses until
        # the next epoch, and the swap folds the plane as if micro-fold
        # were off
        self._micro_fault_epoch = False
        # lifetime count of flushes whose extraction completed on the
        # host engine (mirrors ledger.host_fallbacks; kept on the worker
        # for the soak's conservation accounting)
        self.host_fallback_flushes = 0
        self._reset_epoch()

    def set_recorder(self, rec: "flightrec.Recorder") -> None:
        self.rec = self.guard.rec = rec

    def attach_mesh_pool(self, pool) -> None:
        """Shard histogram state over a device mesh
        (distributed/mesh.MeshHistoPool): raw samples and imported
        centroids route to mesh shards instead of the single-device
        pool; the cross-host merge rides ICI collectives at flush.
        Intended for the global tier (config tpu_mesh_devices); local
        scalar aggregates (.min/.max of mixed-scope rows emitted by
        locals) are not tracked on the mesh path."""
        if self._shard is not None:
            # the mesh pool owns its own device layout; routing rows into
            # BOTH layouts would split series state. Config validation
            # rejects the combination up front; this guard covers direct
            # construction (tools/tests).
            log.warning("series sharding disabled: mesh pool attached "
                        "(tpu_mesh_devices and series_shards are exclusive)")
            self._shard = None
            self.series_shards = 1
        self._mesh_pool = pool
        if self._native is not None:
            # staging would divert samples from the mesh pool: mesh rows
            # route through add_samples_bulk, not the staged fold
            self._native.set_stage_depth(0)

    @property
    def processed(self) -> int:
        """Samples accepted this epoch. In native mode the router commits
        into the C++ context off the Python path, so the native counter's
        delta since the last rebase is folded in live."""
        n = self._processed_py
        if self._native is not None:
            n += int(self._native.processed) - self._native_proc_seen
        for i, ctx in enumerate(self._reader_ctxs):
            n += int(ctx.processed) - self._reader_proc_seen[i]
        return n

    @processed.setter
    def processed(self, v: int) -> None:
        # preserves `self.processed += k` semantics: the native delta read
        # by the getter is subtracted back out so it isn't double-counted
        nd = 0
        if self._native is not None:
            nd = int(self._native.processed) - self._native_proc_seen
        for i, ctx in enumerate(self._reader_ctxs):
            nd += int(ctx.processed) - self._reader_proc_seen[i]
        self._processed_py = v - nd

    # -- native front-end ----------------------------------------------------

    def attach_native(self) -> bool:
        """Attach the C++ ingest pipeline (native/dogstatsd.cpp): parsing,
        tag normalization, row assignment AND raw-sample staging move off
        the Python path; this worker's Python-side paths (SSF-derived
        metrics, imports) share the native directory through upsert."""
        try:
            from veneur_tpu.native import NativeIngest

            self._native = NativeIngest(self.hll_precision,
                                        set_hash=self.set_hash)
        except (RuntimeError, OSError):
            return False
        if self._mesh_pool is None and self.stage_depth > 0:
            self._native.set_stage_depth(self.stage_depth)
        if self.spill_cap:
            self._native.set_spill_cap(self.spill_cap)
        return True

    def attach_reader_shards(self, n: int) -> bool:
        """Shared-nothing multi-reader ingest: give each of n reader
        threads its OWN native context — private directory, staging
        plane, SoA spill epoch — so the commit hot path takes no shared
        mutex (the per-context lock survives only at the flush-edge
        detach fence and the periodic drains, where it is uncontended).

        Series identity becomes (reader, local row), reconciled into
        this worker's canonical Python directory at the series sync
        (_sync_native_series appends each context's local row to a
        local→canonical map); the flush folds all staging planes
        on-device as ONE stacked batch (ops/reader_stack.py), so every
        downstream consumer sees the same output as the legacy
        digest-routed path. Requires an attached home context and no
        mesh pool. Returns False (legacy path keeps working) when either
        precondition fails."""
        if n < 1 or self._reader_ctxs:
            return bool(self._reader_ctxs)
        if self._native is None or self._mesh_pool is not None:
            return False
        from veneur_tpu.native import NativeIngest

        ctxs = []
        try:
            for _ in range(n):
                ctx = NativeIngest(self.hll_precision,
                                   set_hash=self.set_hash)
                if self.stage_depth > 0:
                    ctx.set_stage_depth(self.stage_depth)
                if self.spill_cap:
                    ctx.set_spill_cap(self.spill_cap)
                ctxs.append(ctx)
        except (RuntimeError, OSError):
            for ctx in ctxs:
                ctx.close()
            return False
        self._reader_ctxs = ctxs
        self._reader_errs_seen = [0] * n
        self._reader_proc_seen = [0] * n
        self._reader_drop_seen = [0] * n
        self.reader_committed = [0] * (n + 1)
        self.reader_dropped = [0] * (n + 1)
        # the maps were sized for zero reader contexts at construction
        self._ctx_maps = [tuple(array("i") for _ in range(4))
                          for _ in range(n + 1)]
        self._adopt_cache += [LifetimeSeries() for _ in range(n)]
        return True

    def _all_ctxs(self) -> list:
        """[home context] + reader-shard contexts, in the context order
        every reconciliation structure is indexed by."""
        return [self._native] + self._reader_ctxs

    def ingest_datagram(self, datagram: bytes) -> int:
        """Native-path ingest of one (possibly multi-line) datagram.
        Returns leftover event/service-check lines via drain_other on the
        caller's schedule."""
        n = self._native.ingest(datagram)
        if (self._native.pending_histo >= self.batch_size
                or self._native.pending_set >= self.batch_size):
            self.drain_native()
        return n

    def ingest_ssf_packet(self, packet: bytes, indicator_name: bytes,
                          objective_name: bytes,
                          uniqueness_rate: float = 0.0) -> int:
        """Native-path SSF span ingest (decode + span→metric extraction in
        C++). Returns the vn_ingest_ssf rc: 1 ok, 0 decode error, -1 the
        caller must take the Python path (STATUS samples aboard)."""
        rc = self._native.ingest_ssf(packet, indicator_name, objective_name,
                                     uniqueness_rate)
        if rc == 1:
            self.processed += 1
            if (self._native.pending_histo >= self.batch_size
                    or self._native.pending_set >= self.batch_size):
                self.drain_native()
        return rc

    def _sync_native_series(self, ctx=None, ctx_i: int = 0,
                            span: str = "adopt") -> None:
        if ctx is None:
            ctx = self._native
        if not ctx.pending_new_series:
            return
        with self.rec.span(span, flush=self.flight_epoch) as sp:
            n, first_seen, by_id = self._adopt_pending(ctx, ctx_i)
            sp.attrs["series"] = n
            sp.attrs["known"] = n - first_seen
            sp.attrs["first_seen"] = first_seen
            sp.attrs["by_id"] = by_id

    def _adopt_pending(self, ctx, ctx_i: int) -> tuple[int, int, int]:
        """Give every series the context created since the last drain its
        row in the pools, a batch per pool; returns (series adopted, how
        many of them the context handed over for the first time, how
        many took their row without a per-series step).

        Every flush resets the native directory and the same series
        re-register next interval, so the drain names a series by its
        lifetime id: what a row needs (entry, codes, frag) was built when
        the series' strings first arrived (_learn_series) and stays in
        the context's LifetimeSeries; a pool's book takes the ids
        (RowBook.adopt_batch)."""
        batch = ctx.drain_new_series()
        if not len(batch):
            return 0, 0, 0
        known = self._adopt_cache[ctx_i]
        if batch.generation != known.generation:
            # the context dropped its table and hands out sids anew: a
            # new table here too, never the old one wiped, because the
            # books of snapshots still in flight hold ids into it
            known = self._adopt_cache[ctx_i] = LifetimeSeries(
                batch.generation)
        if len(batch.first_at):
            self._learn_series(known, batch)
        # reader-shard mode: context rows are LOCAL — reconcile each into
        # the worker's canonical directory (dedup by series identity, so
        # the same series arriving via several readers shares one
        # canonical row) and append the translation to this context's
        # local→canonical map. The home context (ctx_i 0) reconciles the
        # same way so every native row space is treated uniformly.
        shard_maps = self._ctx_maps[ctx_i] if self._reader_ctxs else None
        pools = (self.directory.histo, self.directory.sets,
                 self.scalars.counters, self.scalars.gauges)
        by_id = 0
        # the drain comes grouped by pool, a pool's rows consecutive:
        # nothing here sorts, compares or gathers over the batch (every
        # such call gives the interpreter up, under the ingest lock,
        # and waits a switch interval for it while another thread is busy)
        for pool_i, start, stop in batch.pool_slices():
            pool = pools[pool_i]
            sids = batch.sids[start:stop]
            first_row = (len(pool.entries) if shard_maps is None
                         else len(shard_maps[pool_i]))
            assert (batch.rows[start] == first_row
                    and batch.rows[stop - 1] - first_row
                    == stop - start - 1), \
                "native series must drain in row order"
            if shard_maps is None:
                by_id += pool.adopt_batch(first_row, known, sids)
            else:
                shard_maps[pool_i].extend(pool.upsert_batch(known, sids))
            if self._umts is not None:
                # feed the unique-timeseries HLL once per new series; the
                # HLL insert is idempotent so per-sample feeding (the
                # Python path, worker.go:300-341) and per-series feeding
                # agree
                counted = known.codes[LifetimeSeries.COUNTED, sids] != 0
                idx, rank = hll_ops.split_hashes(
                    known.ts_hash[sids[counted]], self.hll_precision)
                np.maximum.at(self._umts, idx, rank)
        return len(batch), len(batch.first_at), by_id

    def _learn_series(self, known: LifetimeSeries, batch) -> None:
        """The once-in-a-lifetime part of adoption: build what the pools
        keep for each series whose strings just arrived."""
        from veneur_tpu.native import NativeIngest

        at = batch.first_at
        sids = batch.sids[at].tolist()
        known.reserve(max(sids) + 1)
        for sid, pool_i, kind, scope, name, joined in zip(
                sids, batch.pools[at].tolist(), batch.first_kinds.tolist(),
                batch.first_scopes.tolist(), batch.first_names,
                batch.first_tags):
            mtype = NativeIngest.TYPE_BY_KIND[kind]
            key = MetricKey(name=name, type=mtype, joined_tags=joined)
            tags = joined.split(",") if joined else []
            scope_class = ScopeClass(scope)
            sinks = route_info(tags)
            tenant = ""
            admitted = True
            if self.tenancy is not None:
                # native-path budget gate: C++ already assigned the
                # row, so a rejected series keeps its row but is
                # marked admitted=False — the flusher skips it on
                # both emit paths. The decision is kept with the
                # series (admission is per series lifetime).
                tenant = tenant_of(tags, self.tenancy.tag_key)
                admitted = self.tenancy.admit(
                    tenant, _series_budget_id(scope_class, key))
            if pool_i < 2:
                entry = RowMeta(key=key, tags=tags, scope_class=scope_class,
                                sinks=sinks, tenant=tenant,
                                admitted=admitted)
                frag = entry.wire_frag()
            else:
                entry = (key, tags, scope_class, sinks)
                frag = build_frag(name, tags)
            ts_hash = None
            if (self._umts is not None
                    and self._should_count_timeseries(mtype, scope_class)):
                ts_hash = fmix64(metric_digest(name, mtype, joined))
            known.put(sid, entry, (name, tags, sinks), frag, scope, admitted,
                      ts_hash)

    @property
    def interned_series(self) -> int:
        """Lifetime series ids held, over every native context."""
        return sum(len(known) for known in self._adopt_cache)

    def sync_native_series(self) -> None:
        """Adopt pending new-series registrations mid-epoch.

        Every interval re-registers every series (metrics expire at
        flush, reference README.md:135-137). Left to epoch close the
        whole interval's adoption lands in swap(), UNDER the server's
        ingest lock; called periodically (Server._series_sync_loop) it
        spreads across the interval and swap only adopts the last cadence
        window's tail. Caller holds the worker lock, which is what guards
        the directory; the native context lock is NOT held across the
        adoption (the drain takes it for its own copy): holding it locks
        the C++ readers out, and with the per-series loop of the time a
        window of 1M fresh series that is accepted in 3.5s took more
        than an interval (chip_smoke.py's first finding, PR 22)."""
        if self._native is None:
            return
        for i, ctx in enumerate(self._all_ctxs()
                                if self._reader_ctxs else [self._native]):
            self._sync_native_series(ctx, i)

    def native_series_pending(self) -> bool:
        """Lock-free pending-new-series probe across every native
        context (the server's sync-sweep early-out)."""
        if self._native is None:
            return False
        if any(ctx.pending_new_series for ctx in self._reader_ctxs):
            return True
        return bool(self._native.pending_new_series)

    def drain_native(self) -> None:
        """Move everything pending in the native pipeline into device/host
        state. Holds the context lock across the raw sample drain so
        routed commits from reader threads can't interleave between
        calls; the new-series adoption runs after the unlock (it only
        has to come after the sample drain, see _drain_native_raw_ctx,
        and it is seconds of Python at 1M fresh series).

        Three spans a context, whoever calls (a micro-fold, the pump, a
        routed upsert): ``drain.raw`` is the hold of the context's lock,
        the one the C++ readers commit under (``ctx_lock``); then
        ``adopt``; then ``drain.apply``, whose spill folds are its
        ``dispatch`` children."""
        if self._native is None:
            return
        # shard mode: per-context drain → local→canonical row
        # translation → apply. Each context's lock is held only for
        # its own drain (shared-nothing extends to the drain path).
        sharded = bool(self._reader_ctxs)
        for i, ctx in enumerate(self._all_ctxs() if sharded
                                else [self._native]):
            with self.rec.span("drain.raw", flush=self.flight_epoch,
                               ctx_lock=True, ctx=i) as sp:
                ctx.lock()
                try:
                    raw = self._drain_native_raw_ctx(ctx, i, sync=False)
                finally:
                    ctx.unlock()
                h, st, c, g = raw[:4]
                sp.attrs.update(
                    histo=0 if h is None else len(h[0]),
                    sets=0 if st is None else len(st[0]),
                    counters=len(c[0]), gauges=len(g[0]))
            self._sync_native_series(ctx, i)
            with self.rec.span("drain.apply", flush=self.flight_epoch,
                               ctx=i, spill_samples=sp.attrs["histo"]):
                self._apply_native_raw(
                    self._map_raw_rows(i, raw) if sharded else raw)

    def _map_raw_rows(self, ctx_i: int, raw):
        """Translate one context's drained SoA batches from its LOCAL
        row space to canonical rows via the reconciliation maps built at
        series sync. Samples drain after their series registration (same
        C++ critical section ordering), so every row has a map entry —
        a miss is a bug and raises IndexError loudly."""
        h, s, c, g, st, others, ssf_fb = raw
        maps = self._ctx_maps[ctx_i]

        def translate(pool_i: int, rows):
            return np.frombuffer(maps[pool_i], dtype=np.int32)[rows]

        if h is not None and len(h[0]):
            h = (translate(0, h[0]), h[1], h[2])
        if s is not None and len(s[0]):
            s = (translate(1, s[0]), s[1], s[2])
        rows, contribs = c
        if len(rows):
            c = (translate(2, rows), contribs)
        rows, vals = g
        if len(rows):
            g = (translate(3, rows), vals)
        return h, s, c, g, st, others, ssf_fb

    def native_rows_canonical(self, rows, kinds, sel):
        """Translate rows handed back by the home context's batched
        upsert (native.upsert_many — the import wire path) to canonical
        rows. Identity on the legacy path; in reader-shard mode every
        native row space is local and maps through the home context's
        reconciliation map (the caller must have synced new series
        first, so the map covers every returned row)."""
        if not self._reader_ctxs:
            return rows
        maps = self._ctx_maps[0]
        out = np.asarray(rows).copy()
        for pool_i, kmask in ((0, (kinds == 2) | (kinds == 3)),
                              (1, kinds == 4),
                              (2, kinds == 0),
                              (3, kinds == 1)):
            m = sel & kmask
            if m.any():
                lookup = np.frombuffer(maps[pool_i], dtype=np.int32)
                out[m] = lookup[out[m]]
        return out

    def reader_ns(self) -> Optional[list]:
        """Per native context, [home] + reader shards: the lifetime
        (ns inside recv, ns outside it: parse + commit + lock wait) of
        the C++ reader threads homed on it. None without native
        ingest."""
        if self._native is None:
            return None
        return [ctx.reader_ns() for ctx in self._all_ctxs()]

    def commit_counters(self) -> Optional[dict]:
        """NativeIngest.commit_counters summed over this worker's native
        contexts; None without native ingest."""
        if self._native is None:
            return None
        per_ctx = [ctx.commit_counters() for ctx in self._all_ctxs()]
        return {k: sum(c[k] for c in per_ctx) for k in per_ctx[0]}

    def set_counters(self) -> Optional[dict]:
        """Set entries the staged store routed to a dense device row
        (``sets_dense``) and into the sparse host tier (``sets_sparse``),
        lifetime: the closed epochs' and the live one's. None with the
        all-dense store."""
        live = self._staged_sets
        if live is None:
            return None
        return {"sets_dense": self._sets_dense_closed + live.dense_entries,
                "sets_sparse": self._sets_sparse_closed + live.sparse_routed}

    def reader_lock_ns(self) -> Optional[tuple]:
        """(ns the committers waited for a native context's lock, ns
        they held it committing), lifetime, summed over this worker's
        contexts: what the C++ readers' busy time is made of besides the
        parse. None without native ingest."""
        if self._native is None:
            return None
        per_ctx = [ctx.lock_stats(samples=False) for ctx in self._all_ctxs()]
        return (sum(st["wait_ns_total"] for st in per_ctx),
                sum(st["hold_ns_total"] for st in per_ctx))

    def reader_stats(self) -> dict:
        """Per-context ingest attribution for Server.ingress_stats /
        flush telemetry: context order is [home] + reader shards.
        ``lock`` is each context's commit-mutex record (always on: one
        entry a lock hold of the chunk commit)."""
        out = {
            "shards": len(self._reader_ctxs),
            "committed": list(self.reader_committed),
            "dropped": list(self.reader_dropped),
        }
        ns = self.reader_ns()
        if ns is not None:
            out["recv_ns"] = [r for r, _ in ns]
            out["busy_ns"] = [b for _, b in ns]
        if self._native is not None:
            locks = []
            for ctx in self._all_ctxs():
                st = ctx.lock_stats()
                acq = st["acquisitions"]
                waits = sorted(st["wait_ns_samples"])
                holds = sorted(st["hold_ns_samples"])

                def pct(sorted_ns, q):
                    if not sorted_ns:
                        return 0
                    return sorted_ns[min(len(sorted_ns) - 1,
                                         int(q * len(sorted_ns)))]

                locks.append({
                    "acquisitions": acq,
                    "contended": st["contended"],
                    "contended_fraction": (st["contended"] / acq
                                           if acq else 0.0),
                    "wait_ns_p50": pct(waits, 0.50),
                    "wait_ns_p99": pct(waits, 0.99),
                    "hold_ns_p50": pct(holds, 0.50),
                    "hold_ns_p99": pct(holds, 0.99),
                })
            out["lock"] = locks
        return out

    def _drain_native_raw(self, detach_stage: bool = False,
                          sync: bool = True):
        return self._drain_native_raw_ctx(self._native, 0, detach_stage,
                                          sync)

    def _drain_native_raw_ctx(self, ctx, ctx_i: int,
                              detach_stage: bool = False,
                              sync: bool = True):
        """Pull raw sample buffers + bookkeeping out of the C++ context.
        Caller holds the context lock. Samples drain BEFORE the new-series
        sync: a sample's series record is committed at-or-before the
        sample itself (same C++ critical section), so syncing afterwards
        can only over-adopt rows with no samples yet — never leave a
        drained sample without directory metadata. sync=False leaves the
        adoption to the caller, after it has released the lock
        (mid-epoch drains; the epoch close keeps it in here, because its
        reset destroys what is not adopted).

        detach_stage (flush only): also detach the C++ staging plane —
        must happen in the same critical section as the epoch close so no
        staged sample is destroyed by the reset."""
        errs = int(ctx.errors)
        dropped = int(ctx.overload_dropped)
        if ctx_i == 0:
            e_seen, d_seen = self._native_errs_seen, self._native_drop_seen
            self._native_errs_seen = errs
            self._native_drop_seen = dropped
        else:
            j = ctx_i - 1
            e_seen = self._reader_errs_seen[j]
            d_seen = self._reader_drop_seen[j]
            self._reader_errs_seen[j] = errs
            self._reader_drop_seen[j] = dropped
        self.parse_errors += errs - e_seen
        delta = dropped - d_seen
        self.overload_dropped += delta
        # lifetime tally (never reset): self-telemetry consumes the
        # per-interval field above; soaks/operators read this one
        self.overload_dropped_total += delta
        if self.reader_dropped:
            # per-context shed attribution (conservation: committed ==
            # folded + shed, per reader)
            self.reader_dropped[ctx_i] += delta
        n = ctx.pending_histo
        h = ctx.drain_histo(n) if n else None
        n = ctx.pending_set
        s = ctx.drain_set(n) if n else None
        # sized by the actual pending counts: a fixed 4M-entry drain both
        # allocated ~50MB of scratch per (100ms-cadence) pump call and
        # silently destroyed anything beyond it at the epoch reset when
        # tpu_spill_cap is raised above the old constant
        n = ctx.pending_counter
        c = ctx.drain_counter(n)
        n = ctx.pending_gauge
        g = ctx.drain_gauge(n)
        st = None
        others: list = []
        ssf_fb: list = []
        if detach_stage:
            st = ctx.detach_stage()
            # epoch close: pull buffered event/service-check lines and
            # Python-fallback SSF payloads in the SAME critical section —
            # the reset right after this drain clears both buffers, and
            # anything landing between a separate drain and the reset
            # would be destroyed
            others = ctx.drain_other()
            ssf_fb = ctx.drain_ssf_fallback()
        if sync:
            # the epoch close adopts under the context lock: "swap.adopt"
            self._sync_native_series(
                ctx, ctx_i, span="swap.adopt" if detach_stage else "adopt")
        return h, s, c, g, st, others, ssf_fb

    def _apply_native_raw(self, raw, defer_histo_spill: bool = False):
        """Apply drained buffers to device/host pools (no context lock —
        device dispatch must not stall reader commits). The detached
        staging plane (raw[4]) and event lines (raw[5], both flush only)
        are the caller's to hand to the swapped epoch.

        defer_histo_spill (swap only): skip the histo spill fold and
        return the (rows, vals, wts) SoA for the caller to attach to the
        SwappedEpoch — extract_snapshot runs the fold off the ingest
        lock. Only the direct-fold path defers (mesh and plane-staging
        paths are host-cheap); returns None when nothing was deferred."""
        h, s, c, g, _st, _others, _ssf_fb = raw
        deferred = None
        if h is not None and len(h[0]):
            if self._mesh_pool is not None:
                self._mesh_pool.add_samples_bulk(*h)
            else:
                self._ensure_histo(self.directory.num_histo_rows)
                if self._native is not None and self.stage_depth > 0:
                    # with native staging on, the SoA batch holds only
                    # hot-row spill: fold it directly (K is small there;
                    # re-staging it in the Python plane would just add a
                    # second fold). The fold goes in slices of
                    # _FOLD_CHUNK: a drain after a stall can hold
                    # millions of spilled samples, and one fold's padded
                    # [N] arrays at that size are ~100MB — eight in
                    # flight was most of the RSS in the overload soak.
                    if defer_histo_spill:
                        deferred = h
                    else:
                        self._fold_batch_direct(*h)
                else:
                    self._device_histo_step(*h)
        if s is not None and len(s[0]):
            self._ensure_sets(self.directory.num_set_rows)
            self._device_set_step(*s)
        rows, contribs = c
        if len(rows):
            pool = self.scalars.counters
            np.add.at(pool.values, rows, contribs)
            pool.present[rows] = True
        rows, vals = g
        if len(rows):
            pool = self.scalars.gauges
            pool.values[rows] = vals  # in-order: last write wins
            pool.present[rows] = True
        return deferred

    # -- micro-folds (always-hot flush) --------------------------------------

    def _micro_active(self) -> bool:
        """Micro-folds engage only where the staged fold exists: staging
        on and no mesh (mesh rows bypass the staging plane entirely).
        Reader-shard mode also opts out: the mirror would need N
        per-context COO streams re-keyed to canonical rows mid-interval;
        the stacked flush-edge merge (ops/reader_stack.py) covers the
        same work, so always-hot flush stays a legacy-path feature.

        The device fault domain pauses micro-folds too: quarantined (or
        live-failed-over) workers have no device to mirror into, and an
        epoch whose mirror already faulted keeps every sample in the
        retained staging plane instead (conservation over warmth)."""
        return (self.micro_fold and self.stage_depth > 0
                and self._mesh_pool is None and not self._reader_ctxs
                and not self._host_live and not self.guard.quarantined
                and not self._micro_fault_epoch)

    def _ensure_micro(self) -> "mf.MicroFoldMirror":
        if self._micro is None:
            self._micro = mf.MicroFoldMirror(
                self.stage_depth, ledger=self.ledger,
                initial_rows=self._initial_histo_rows,
                shard=self._shard, guard=self.guard)
        return self._micro

    def micro_fold_pending(self) -> int:
        """Staged samples not yet streamed to the device mirror (the
        scheduler's due check; caller holds the worker ingest lock)."""
        if not self._micro_active():
            return 0
        if self._native is not None:
            return int(self._native.stage_pending)
        if self._stage_count is None:
            return 0
        total = int(self._stage_count.sum())
        mark = self._ustage_mark
        if mark is not None:
            total -= int(mark[:len(self._stage_count)].sum())
        return total

    def micro_fold_due(self) -> bool:
        pending = self.micro_fold_pending()
        if pending <= 0:
            return False
        if pending >= self.micro_fold_rows:
            return True
        return (time.monotonic() - self._micro_last_drain
                >= self.micro_fold_max_age_s)

    def micro_fold_once(self) -> int:
        """One micro-fold: stream the staged samples accumulated since
        the last drain into the device mirror (ops/microfold.py), and —
        native mode — drain the pending scalar/set/spill SoA batches so
        swap inherits none of them either. Caller holds the worker
        ingest lock. Returns samples streamed."""
        if not self._micro_active():
            return 0
        self._micro_last_drain = time.monotonic()
        try:
            if self._native is not None:
                # mid-interval SoA drain first: counters are np.add.at in
                # drain order and gauges last-write-wins, so draining more
                # often splits the stream into ordered deltas — the folded
                # result is bitwise what one deadline-time drain produces
                with self.rec.span("micro_fold.drain",
                                   flush=self.flight_epoch):
                    self.drain_native()
                fed = self._micro_feed(self._micro_drain_native)
            else:
                fed = self._micro_feed(self._micro_drain_python)
        except dg.DeviceFaultError as exc:
            # the mirror is a CACHE of the staging plane — the plane
            # retains every sample (watermarks advanced, counts did
            # not), so dropping the mirror loses nothing. Micro-folding
            # pauses for the rest of the epoch; the swap folds the
            # retained plane exactly as if micro-fold were off.
            log.warning("micro-fold device fault (%s); mirror dropped, "
                        "epoch falls back to the staged plane", exc)
            self._micro = None
            self._micro_fault_epoch = True
            return 0
        if fed:
            self.micro_folds_total += 1
            self.micro_folds_epoch += 1
            gov = self.governor
            if gov is not None:
                gov.note_micro_fold(fed)
        return fed

    def _micro_feed(self, drain) -> int:
        """One drain into the mirror as a ``micro_fold.feed`` span that
        says what it fed and into what: ``samples``, ``rows`` (1 + the
        highest row mirrored), ``mirror_rows`` (the rows the mirror has
        allocated) and ``chunks`` (scatters this feed dispatched)."""
        before = self._micro.chunks if self._micro is not None else 0
        with self.rec.span("micro_fold.feed",
                           flush=self.flight_epoch) as sp:
            fed = drain()
            micro = self._micro
            if micro is not None:
                sp.attrs.update(samples=fed, rows=micro.rows_hi,
                                mirror_rows=micro.mirror_rows,
                                chunks=micro.chunks - before)
        return fed

    def _micro_drain_native(self) -> int:
        """COO-drain the C++ staging plane's undrained delta into the
        mirror. drain_stage_delta advances the plane's per-row watermark
        WITHOUT touching counts, so the per-epoch depth cap (and the
        spill partitioning) is identical to a run with no micro-folds."""
        if self._native.stage_pending <= 0:
            return 0
        micro = self._ensure_micro()
        fed = 0
        cap = 1 << 18
        while True:
            # one C call that copies under the context's lock: the
            # readers' commits wait for it
            with self.rec.span("feed.stage_delta", flush=self.flight_epoch,
                               ctx_lock=True) as sp:
                rows, slots, vals, wts = self._native.drain_stage_delta(cap)
                n = sp.attrs["samples"] = len(rows)
            if n == 0:
                break
            # the copy into the mirror's carry; a full carry's scatter
            # is its dispatch child (op micro)
            with self.rec.span("feed.carry", flush=self.flight_epoch):
                micro.feed(rows, slots, vals, wts)
            fed += n
            if n < cap:
                break
        return fed

    def _python_stage_delta(self) -> Optional[tuple]:
        """The Python staging plane's [mark, count) delta per row as one
        COO tuple (rows, slots, vals, wts — all copies), advancing the
        watermark; None when nothing is undrained. Touches only what
        _device_histo_step already wrote — it never forces the pending
        SoA batches through, so the spill-fold batch boundaries stay
        exactly the batch path's."""
        counts = self._stage_count
        if counts is None:
            return None
        rows_n = len(counts)
        mark = self._ustage_mark
        if mark is None or len(mark) < rows_n:
            nm = np.zeros(rows_n, np.int32)
            if mark is not None:
                nm[:len(mark)] = mark
            mark = self._ustage_mark = nm
        delta = counts - mark[:rows_n]
        live = np.flatnonzero(delta > 0)
        if not len(live):
            return None
        reps = delta[live]
        total = int(reps.sum())
        rows = np.repeat(live.astype(np.int32), reps)
        run_starts = np.cumsum(reps) - reps
        intra = (np.arange(total, dtype=np.int32)
                 - np.repeat(run_starts, reps).astype(np.int32))
        slots = np.repeat(mark[live], reps).astype(np.int32) + intra
        coo = (rows, slots, self._stage_vals[rows, slots],
               self._stage_wts[rows, slots])
        mark[live] = counts[live]
        return coo

    def _micro_drain_python(self) -> int:
        coo = self._python_stage_delta()
        if coo is None:
            return 0
        self._ensure_micro().feed(*coo)
        return len(coo[0])

    # -- epoch lifecycle ----------------------------------------------------

    def _reset_epoch(self) -> None:
        if getattr(self, "_native_epoch_closed", False):
            # flush already reset the context(s) atomically with its
            # drain; resetting again here would destroy new-epoch commits
            # that routed readers landed in the meantime
            self._native_epoch_closed = False
        else:
            if self._native is not None:
                self._native.reset()
            self._native_errs_seen = 0
            self._native_proc_seen = 0
            self._native_drop_seen = 0
            for i, ctx in enumerate(self._reader_ctxs):
                ctx.reset()
                self._reader_errs_seen[i] = 0
                self._reader_proc_seen[i] = 0
                self._reader_drop_seen[i] = 0
        # per-context local-row → canonical-row reconciliation maps, one
        # int32 array per pool kind (histo/set/counter/gauge), [home] +
        # readers. Rebuilt every epoch: context resets restart local rows
        # at 0 and the canonical directory is fresh too.
        self._ctx_maps = [tuple(array("i") for _ in range(4))
                          for _ in range(1 + len(self._reader_ctxs))]
        self._processed_py = 0
        self.parse_errors = getattr(self, "parse_errors", 0)
        # the epoch's per-tenant tallies were accumulated into the
        # lifetime totals by swap() before this reset (never reset the
        # totals — they are the cross-epoch truth, like processed_total)
        self.tenant_tallies.reset()
        self.directory = SeriesDirectory()
        self.scalars = HostScalars()
        # fresh epoch, fresh mirror fault state (the voided mirror was
        # epoch-scoped; a new epoch may micro-fold again if the guard is
        # otherwise healthy)
        self._micro_fault_epoch = False
        self._histo: Optional[HistoDeviceState] = None
        self._sets: Optional[jax.Array] = None
        # staged (sparse-host / dense-device) set store — the scalable
        # default; tpu_set_store: dense keeps the all-dense pool
        if self.set_store == "staged":
            from veneur_tpu.ops.staged_sets import (StagedSetStore,
                                                    pool_rows_for)

            # the epoch just closed says what this one's dense pool
            # starts at: the power of two that held its dense rows, and
            # nothing if it had none
            last = self._staged_sets
            if last is not None:
                self._sets_dense_closed += last.dense_entries
                self._sets_sparse_closed += last.sparse_routed
            self._staged_sets = StagedSetStore(
                self.hll_precision, shard=self._shard, guard=self.guard,
                host=self._host_live, warm=self._set_inserts_warm,
                pool_rows=(pool_rows_for(last.dense_rows)
                           if last is not None and last.dense_rows else 0))
        else:
            self._staged_sets = None
        # host raw-sample staging planes (see _device_histo_step); created
        # lazily alongside _histo
        self._stage_vals: Optional[np.ndarray] = None
        self._stage_wts: Optional[np.ndarray] = None
        self._stage_count: Optional[np.ndarray] = None
        # micro-fold watermark for the Python plane: slots
        # [mark[r], count[r]) are staged but not yet mirrored
        self._ustage_mark: Optional[np.ndarray] = None
        self.micro_folds_epoch = 0
        self.spill_steps_epoch = 0
        self._micro_last_drain = time.monotonic()
        # pending SoA buffers (host)
        self._ph_rows: list[int] = []
        self._ph_vals: list[float] = []
        self._ph_wts: list[float] = []
        self._ps_rows: list[int] = []
        self._ps_idx: list[int] = []
        self._ps_rank: list[int] = []
        # import buffers (global tier)
        self._imp_digests: dict[int, list] = {}
        self._imp_hll: dict[int, np.ndarray] = {}
        # unique-timeseries HLL registers (host, tiny)
        m = hll_ops.num_registers(self.hll_precision)
        self._umts = (
            np.zeros(m, dtype=np.int8) if self.count_unique_timeseries else None
        )

    def _ensure_histo(self, needed_rows: int) -> None:
        # a tripped breaker fails the live epoch over right here, before
        # any pool is created or grown on the dying device — the server's
        # post-flush device_guard_tick() would do it anyway, but ingest
        # between the trip and the tick must not re-fault
        if self.guard.quarantined and not self._host_live:
            self._quarantine_live()
        # keep one scratch row free at the top for gather/scatter padding
        # (under sharding the scratch row — logical S-1 — maps to physical
        # S-1, shard D-1's last local row, so sizing is shard-oblivious)
        if self._host_live or isinstance(self._histo, he.HostHistoState):
            if self._histo is None:
                rows = _next_pow2(needed_rows + 1, self._initial_histo_rows)
                self._histo = he.HostHistoState.create(rows, self.capacity)
            elif needed_rows + 1 > self._histo.num_rows:
                self._flush_pending_histos()
                self._histo = self._histo.grow(
                    _next_pow2(needed_rows + 1, self._histo.num_rows * 2))
            return
        if self._histo is None:
            rows = _next_pow2(needed_rows + 1, self._initial_histo_rows)
            st = HistoDeviceState.create(rows, self.capacity)
            self._histo = (st if self._shard is None
                           else st.placed(self._shard))
        elif needed_rows + 1 > self._histo.num_rows:
            self._flush_pending_histos()  # pending lids reference old layout
            if isinstance(self._histo, he.HostHistoState):
                # the pending fold itself faulted and quarantined us
                self._ensure_histo(needed_rows)
                return
            new_rows = _next_pow2(needed_rows + 1, self._histo.num_rows * 2)
            # HBM pressure valve: growth doubles the pool's device
            # footprint and the donating grow programs free the OLD
            # buffers only after the new ones materialize. Pre-flight
            # the allocation with a throwaway (non-donated) buffer of
            # the target size: an OOM here is a clean fault — the old
            # pool is untouched — and degrades to the host engine
            # instead of faulting mid-grow. Only worth a dispatch when
            # the target is big enough to plausibly OOM: pools are
            # re-created per epoch, so an unconditional pre-flight would
            # tax every interval's early-growth ladder (~0.5ms/dispatch)
            # to guard kB-scale allocations that cannot exhaust HBM.
            try:
                if (self.guard.enabled and new_rows * self.capacity * 12
                        >= _GROW_PREFLIGHT_MIN_BYTES):
                    def _preflight():
                        probe = jnp.zeros((new_rows, 2 * self.capacity),
                                          jnp.float32)
                        if self._shard is not None:
                            probe = self._shard.place(probe)
                        jax.block_until_ready(probe)

                    self.guard.call("grow", _preflight, retryable=True)
                self._histo = self.guard.call(
                    "grow", self._histo.grow, new_rows, shard=self._shard)
            except dg.DeviceFaultError as exc:
                self.guard.bump("device.valve.grow_oom")
                self.guard.trip(f"pool growth to {new_rows} rows faulted "
                                f"[{exc.kind}] — HBM valve")
                self._quarantine_live()
                # _quarantine_live moved the (old-size) pool to host;
                # grow it there
                self._histo = self._histo.grow(new_rows)

    def _ensure_sets(self, needed_rows: int) -> None:
        if self.guard.quarantined and not self._host_live:
            self._quarantine_live()
        if self._staged_sets is not None:
            return  # the staged store sizes itself
        if self._host_live or isinstance(self._sets, np.ndarray):
            if self._sets is None:
                rows = _next_pow2(needed_rows + 1, self._initial_set_rows)
                m = hll_ops.num_registers(self.hll_precision)
                self._sets = np.zeros((rows, m), np.int8)
            elif needed_rows + 1 > self._sets.shape[0]:
                self._flush_pending_sets()
                new_rows = _next_pow2(needed_rows + 1,
                                      self._sets.shape[0] * 2)
                grown = np.zeros((new_rows, self._sets.shape[1]), np.int8)
                grown[:self._sets.shape[0]] = self._sets
                self._sets = grown
            return
        if self._sets is None:
            rows = _next_pow2(needed_rows + 1, self._initial_set_rows)
            pool = hll_ops.init_pool(rows, self.hll_precision)
            self._sets = (pool if self._shard is None
                          else self._shard.place(pool))
        elif needed_rows + 1 > self._sets.shape[0]:
            self._flush_pending_sets()
            if isinstance(self._sets, np.ndarray):
                self._ensure_sets(needed_rows)
                return
            new_rows = _next_pow2(needed_rows + 1, self._sets.shape[0] * 2)
            try:
                self._sets = self.guard.call(
                    "grow",
                    (_grow_2d if self._shard is None
                     else self._shard.grow_2d), self._sets, new_rows)
            except dg.DeviceFaultError as exc:
                self.guard.trip(f"set pool growth to {new_rows} rows "
                                f"faulted [{exc.kind}]")
                self._quarantine_live()
                self._ensure_sets(needed_rows)

    # -- ingest -------------------------------------------------------------

    def process_metric(self, m: UDPMetric) -> None:
        """Route one parsed sample into the right pool
        (reference Worker.ProcessMetric, worker.go:344-394)."""
        self.processed += 1
        mtype = m.key.type
        scope_class = classify(mtype, m.scope)
        tenant = ""
        if self.tenancy is not None:
            # budgeted admission (core/tenancy.py): a sample for a series
            # the tenant ledger refuses is rejected HERE, before any row
            # exists — already-admitted series always pass (the ledger is
            # idempotent), so innocent dashboards never flap. Status
            # checks are host-health plumbing, never budgeted.
            tenant = tenant_of(m.tags, self.tenancy.tag_key)
            tt = self.tenant_tallies
            tt.accepted[tenant] = tt.accepted.get(tenant, 0) + 1
            # reader-shard mode takes the Python branch too: its Python-
            # path series live in the Python pools (the canonical row
            # space), so admission happens here exactly like non-native
            if ((self._native is None or self._reader_ctxs)
                    and mtype != "status"):
                if not self._admit_sample(tenant, m.key, scope_class,
                                          mtype):
                    tt.rejected[tenant] = tt.rejected.get(tenant, 0) + 1
                    return
                tt.kept[tenant] = tt.kept.get(tenant, 0) + 1
        if self.count_unique_timeseries:
            self._sample_timeseries(m, mtype, scope_class)

        if mtype == "counter":
            self._host_counter(m.key, scope_class, m.tags,
                               counter_contribution(m.value, m.sample_rate))
        elif mtype == "gauge":
            self._host_gauge(m.key, scope_class, m.tags, float(m.value))
        elif mtype in ("histogram", "timer"):
            row = self._upsert_histo(m.key, scope_class, m.tags, tenant)
            if self._mesh_pool is not None:
                self._mesh_pool.add_sample(
                    row, float(m.value), 1.0 / m.sample_rate,
                    host_slot=m.digest)
                return
            self._ensure_histo(
                max(self.directory.num_histo_rows, row + 1))
            self._ph_rows.append(row)
            self._ph_vals.append(float(m.value))
            self._ph_wts.append(1.0 / m.sample_rate)
            if len(self._ph_rows) >= self.batch_size:
                self._flush_pending_histos()
        elif mtype == "set":
            row = self._upsert_set(m.key, scope_class, m.tags, tenant)
            self._ensure_sets(max(self.directory.num_set_rows, row + 1))
            h = self._set_hash64(str(m.value).encode("utf-8"))
            idx, rank = hll_ops.split_hashes(
                np.array([h], dtype=np.uint64), self.hll_precision
            )
            self._ps_rows.append(row)
            self._ps_idx.append(int(idx[0]))
            self._ps_rank.append(int(rank[0]))
            if len(self._ps_rows) >= self.batch_size:
                self._flush_pending_sets()
        elif mtype == "status":
            self._host_status(m)

    def _admit_sample(self, tenant: str, key: MetricKey,
                      scope_class: ScopeClass, mtype: str) -> bool:
        """Python-path budget gate: a series already rowed this epoch was
        admitted (rejected series never get rows here); otherwise ask the
        shared ledger — which is free for already-admitted series and
        only consumes budget for genuinely new ones."""
        if mtype in ("histogram", "timer"):
            index = self.directory.histo.index
        elif mtype == "set":
            index = self.directory.sets.index
        elif mtype == "counter":
            index = self.scalars.counters.index
        else:
            index = self.scalars.gauges.index
        if (key, scope_class) in index:
            return True
        return self.tenancy.admit(tenant, _series_budget_id(scope_class, key))

    def _upsert_histo(self, key: MetricKey, scope_class: ScopeClass,
                      tags: list[str], tenant: str = "") -> int:
        # reader-shard mode routes Python-path samples through the
        # Python pools: the canonical row space IS the Python directory
        # there, and a native upsert would hand back a context-LOCAL row
        if self._native is not None and not self._reader_ctxs:
            row = self._native.upsert(key.name, key.type, key.joined_tags,
                                      int(scope_class))
            # adoption is deferred and batched: metadata drains every
            # 1024 new series and always before extraction (swap's
            # native drain syncs) — a per-upsert drain dominated the
            # global tier's import cost
            if self._native.pending_new_series >= 1024:
                self._sync_native_series()
            return row
        row, _ = self.directory.upsert_histo(key, scope_class, tags,
                                             tenant=tenant)
        return row

    def _upsert_set(self, key: MetricKey, scope_class: ScopeClass,
                    tags: list[str], tenant: str = "") -> int:
        if self._native is not None and not self._reader_ctxs:
            row = self._native.upsert(key.name, "set", key.joined_tags,
                                      int(scope_class))
            if self._native.pending_new_series >= 1024:
                self._sync_native_series()
            return row
        row, _ = self.directory.upsert_set(key, scope_class, tags,
                                           tenant=tenant)
        return row

    def _should_count_timeseries(self, mtype: str, cls: ScopeClass) -> bool:
        """Forwarding-aware unique-timeseries gating (reference
        SampleTimeseries, worker.go:300-341): a local instance skips series
        it forwards upstream (the global instance counts those)."""
        if not self.is_local:
            return True
        if mtype in ("counter", "gauge"):
            return cls != ScopeClass.GLOBAL
        if mtype in ("histogram", "set", "timer"):
            return cls == ScopeClass.LOCAL
        return True

    def _insert_timeseries(self, digest: int) -> None:
        h = fmix64(digest)
        idx, rank = hll_ops.split_hashes(
            np.array([h], dtype=np.uint64), self.hll_precision
        )
        self._umts[idx[0]] = max(self._umts[idx[0]], rank[0])

    def _sample_timeseries(self, m: UDPMetric, mtype: str,
                           cls: ScopeClass) -> None:
        """Python-path unique-timeseries sampling (one call per sample)."""
        if self._umts is not None and self._should_count_timeseries(mtype, cls):
            self._insert_timeseries(m.digest)

    # host scalar paths

    def _host_counter(self, key: MetricKey, scope_class: ScopeClass,
                      tags: list[str], contribution: int) -> None:
        pool = self.scalars.counters
        if self._native is not None and not self._reader_ctxs:
            row = self._native.upsert(key.name, "counter", key.joined_tags,
                                      int(scope_class))
            self._sync_native_series()
        else:
            row = pool.upsert(key, scope_class, tags, route_info(tags))
        pool.values[row] += contribution
        pool.present[row] = True

    def _host_gauge(self, key: MetricKey, scope_class: ScopeClass,
                    tags: list[str], value: float) -> None:
        pool = self.scalars.gauges
        if self._native is not None and not self._reader_ctxs:
            row = self._native.upsert(key.name, "gauge", key.joined_tags,
                                      int(scope_class))
            self._sync_native_series()
        else:
            row = pool.upsert(key, scope_class, tags, route_info(tags))
        pool.values[row] = value
        pool.present[row] = True

    def _host_status(self, m: UDPMetric) -> None:
        sc = self.scalars
        k = (m.key, ScopeClass.LOCAL)
        row = sc.status_index.get(k)
        if row is None:
            row = len(sc.status_values)
            sc.status_index[k] = row
            sc.status_meta.append(
                (m.key, m.tags, ScopeClass.LOCAL, route_info(m.tags))
            )
            sc.status_values.append(None)
        sc.status_values[row] = (float(m.value), m.message, m.hostname)

    # -- pending-batch device steps ----------------------------------------

    def _flush_pending_histos(self) -> None:
        if not self._ph_rows:
            return
        rows = np.asarray(self._ph_rows, dtype=np.int32)
        vals = np.asarray(self._ph_vals, dtype=np.float32)
        wts = np.asarray(self._ph_wts, dtype=np.float32)
        self._ph_rows, self._ph_vals, self._ph_wts = [], [], []
        self._device_histo_step(rows, vals, wts)

    def _ensure_stage(self) -> None:
        """Size the host staging planes to the digest pool's row count."""
        rows = self._histo.num_rows
        if self._stage_count is None:
            self._stage_vals = np.zeros((rows, self.stage_depth), np.float32)
            self._stage_wts = np.zeros((rows, self.stage_depth), np.float32)
            self._stage_count = np.zeros(rows, np.int32)
        elif len(self._stage_count) < rows:
            old = len(self._stage_count)
            nv = np.zeros((rows, self.stage_depth), np.float32)
            nw = np.zeros((rows, self.stage_depth), np.float32)
            nc = np.zeros(rows, np.int32)
            nv[:old] = self._stage_vals
            nw[:old] = self._stage_wts
            nc[:old] = self._stage_count
            self._stage_vals, self._stage_wts, self._stage_count = nv, nw, nc

    def _device_histo_step(self, rows: np.ndarray, vals: np.ndarray,
                           wts: np.ndarray) -> None:
        """Stage a raw-sample batch host-side; the digest compress is paid
        once per interval in _histo_fold_staged (see its docstring).

        Pure vectorized numpy — no device dispatch on the common path, so
        ingest throughput is bounded by parse + store, not by per-batch
        [K, 2C] sorts. Rows whose staging is full spill through the direct
        per-batch device fold; a row with sustained volume stays full, so
        its samples keep taking the spill path, where a hot batch's K
        (unique rows) is small and the gathered fold is cheap."""
        n = len(rows)
        if n == 0:
            return
        B = self.stage_depth
        self._ensure_stage()
        order = np.argsort(rows, kind="stable")
        srows = rows[order]
        svals = vals[order]
        swts = wts[order]
        newrun = np.empty(n, bool)
        newrun[0] = True
        np.not_equal(srows[1:], srows[:-1], out=newrun[1:])
        starts = np.flatnonzero(newrun)
        runid = np.cumsum(newrun) - 1
        # rank of each sample within its row's run → its staging slot
        slots = self._stage_count[srows] + (np.arange(n) - starts[runid])
        run_rows = srows[starts]
        run_len = np.diff(np.append(starts, n))
        fit = slots < B
        if fit.all():
            self._stage_vals[srows, slots] = svals
            self._stage_wts[srows, slots] = swts
            self._stage_count[run_rows] += run_len.astype(np.int32)
            return
        keep = fit
        self._stage_vals[srows[keep], slots[keep]] = svals[keep]
        self._stage_wts[srows[keep], slots[keep]] = swts[keep]
        self._stage_count[run_rows] = np.minimum(
            self._stage_count[run_rows] + run_len, B).astype(np.int32)
        spill = ~keep
        self._fold_batch_direct(srows[spill], svals[spill], swts[spill])

    @staticmethod
    def _pad_spill_batch(rows: np.ndarray, vals: np.ndarray,
                         wts: np.ndarray, scratch: int):
        """Pad one spill batch (at most _FOLD_CHUNK samples) to the
        ingest step's shape ladder (_SPILL_MIN_ROWS): padding sample
        slots point at `scratch` with weight 0, which the step treats as
        absent. Shared by the live-pool and swapped-epoch folds so their
        jit shapes (and semantics) cannot drift."""
        uniq, inverse = np.unique(rows, return_inverse=True)
        k = _SPILL_MIN_ROWS
        while k < len(uniq):
            k *= 4
        n = _FOLD_CHUNK
        assert len(vals) <= n
        active = np.full(k, scratch, dtype=np.int32)
        active[: len(uniq)] = uniq
        lids = np.full(n, k - 1, dtype=np.int32)
        lids[: len(vals)] = inverse
        v = np.zeros(n, dtype=np.float32)
        v[: len(vals)] = vals
        w = np.zeros(n, dtype=np.float32)
        w[: len(vals)] = wts
        return active, lids, v, w

    def _fold_batch_direct(self, rows: np.ndarray, vals: np.ndarray,
                           wts: np.ndarray) -> None:
        """Gather→add_batch→scatter device fold of one sample batch — the
        spill path for rows whose staging plane is full — in slices of
        _FOLD_CHUNK samples."""
        for i in range(0, len(vals), _FOLD_CHUNK):
            self._fold_slice_direct(rows[i:i + _FOLD_CHUNK],
                                    vals[i:i + _FOLD_CHUNK],
                                    wts[i:i + _FOLD_CHUNK])

    def _fold_slice_direct(self, rows: np.ndarray, vals: np.ndarray,
                           wts: np.ndarray) -> None:
        """One slice (at most _FOLD_CHUNK samples) into the live pool."""
        h = self._histo
        assert h is not None
        active, lids, v, w = self._pad_spill_batch(
            rows, vals, wts, h.num_rows - 1)

        def put(out):
            (h.means, h.weights, h.dmin, h.dmax, h.drecip, h.drecip_c,
             h.lmin, h.lmax, h.lsum, h.lsum_c, h.lweight, h.lweight_c,
             h.lrecip, h.lrecip_c) = out

        if isinstance(h, he.HostHistoState):
            # quarantined: the host engine's bit-identical ingest twin
            put(he.np_ingest_step(*h.fields(), active, lids, v, w,
                                  compression=self.compression))
            self.spill_steps_epoch += 1
            return

        def step(*batch):
            put(self._spill_step("fold", h.fields(), h.num_rows, *batch))

        try:
            self._warm_spill_rows(h.num_rows, len(active), step)
            step(active, lids, v, w)
        except dg.DeviceFaultError:
            # the fold donates the pool, so no in-place retry. The host
            # inputs are still ours: if the breaker tripped, quarantine
            # the live epoch (pool → host) and fold this batch there;
            # otherwise re-stage the samples into the pending SoA — the
            # next flush (or next spill drain) replays them naturally,
            # and a still-sick device walks the streak to the breaker.
            if self.guard.quarantined:
                self._quarantine_live()
                self._fold_slice_direct(rows, vals, wts)
            else:
                self._ph_rows.extend(rows.tolist())
                self._ph_vals.extend(vals.tolist())
                self._ph_wts.extend(wts.tolist())
            return
        self.spill_steps_epoch += 1
        # bound the async dispatch queue: an un-executed fold holds its
        # input buffers, and a backend slower than the offered load
        # would otherwise queue folds without limit (observed: 2.7GB RSS
        # growth in a 10-min overload soak). Blocking the DRAINING
        # thread here throttles drain to device speed — readers are C++
        # and unaffected; backlog then accumulates in the C++ spill
        # batches, which cap and shed load (drop-don't-block, the same
        # policy as trace.Client backpressure).
        self._inflight_folds += 1
        if self._inflight_folds >= 8:
            with self.rec.span("fold.fence", wait=True):
                h.means.block_until_ready()
            self._inflight_folds = 0

    def _spill_step(self, op: str, fields: tuple, pool_rows: int,
                    active: np.ndarray, lids: np.ndarray, v: np.ndarray,
                    w: np.ndarray) -> tuple:
        """One padded spill batch through the ingest step: the whole
        pool `fields` (donated) -> the 14 new fields. `op` is the guard's
        name for the dispatch: "fold" into the live pool, "spill" into a
        swapped epoch's, whose uploads the flush's ledger books."""
        led = self.ledger if op == "spill" else None
        sh = self._shard
        if sh is not None:
            # replicated COO, physical `active`: every shard folds the
            # bit-identical batch and keeps only the writes it owns
            # (ops/series_shard.ingest_step — the OOB-foreign remap).
            # Replication is a real per-device transfer: booked once per
            # shard (the transfer-diet pin stays honest)
            ups = []
            for a in (sh.phys_rows(active, pool_rows), lids, v, w):
                if led is not None:
                    led.count_h2d_shards([a.nbytes] * sh.shards, "spill")
                ups.append(sh.replicate(a))
            return self.guard.call(op, sh.ingest_step, *fields, *ups)
        up = (jnp.asarray if led is None
              else functools.partial(led.h2d, kind="spill"))
        return self.guard.call(
            op, _histo_ingest_step, *fields,
            up(active), up(lids), up(v), up(w),
            compression=self.compression)

    def _warm_spill_rows(self, pool_rows: int, k: int, step) -> None:
        """Before the first fold of row bucket `k` at this pool size,
        fold nothing through each smaller bucket not folded there yet
        (`step` takes one padded batch; all padding here, weight 0 on
        the scratch row, which the ingest step treats as absent: every
        series' row comes back bitwise equal). An interval's spill
        climbs through the buckets as its hot rows fill, and a thin
        batch between two micro-folds may skip one: left to chance, that
        bucket's program compiles the first time one does not, any
        number of intervals later, under the ingest lock (PERF.md
        section 6, PR 39)."""
        top = self._spill_rows_warm.get(pool_rows, 0)
        if k <= top:
            return
        b = max(top * 4, _SPILL_MIN_ROWS)
        zeros = np.zeros(_FOLD_CHUNK, dtype=np.float32)
        while b < k:
            with self.rec.span("spill.warm", pool_rows=pool_rows, bucket=b):
                step(np.full(b, pool_rows - 1, dtype=np.int32),
                     np.full(_FOLD_CHUNK, b - 1, dtype=np.int32),
                     zeros, zeros)
            b *= 4
        self._spill_rows_warm[pool_rows] = k

    def _fold_spill_chunk(self, fields: tuple, rows: np.ndarray,
                          vals: np.ndarray, wts: np.ndarray,
                          pool_rows: int) -> tuple:
        """_fold_slice_direct's twin for a SWAPPED epoch: folds one spill
        chunk into the detached full-pool `fields` tuple instead of the
        live self._histo — same shapes, same jit specialization, so the
        compile _fold_batch_direct paid mid-interval is reused here.
        Runs in extract_snapshot, off the ingest lock. Padding entries
        carry weight 0, which the ingest step treats as absent (same
        invariant _fold_slice_direct relies on for its scratch row)."""
        active, lids, v, w = self._pad_spill_batch(
            rows, vals, wts, pool_rows - 1)

        def step(*batch):
            nonlocal fields
            fields = self._spill_step("spill", fields, pool_rows, *batch)

        self._warm_spill_rows(pool_rows, len(active), step)
        step(active, lids, v, w)
        return fields

    def _flush_pending_sets(self) -> None:
        if not self._ps_rows:
            return
        rows = np.asarray(self._ps_rows, dtype=np.int32)
        idx = np.asarray(self._ps_idx, dtype=np.int32)
        rank = np.asarray(self._ps_rank, dtype=np.int8)
        self._ps_rows, self._ps_idx, self._ps_rank = [], [], []
        self._device_set_step(rows, idx, rank)

    def _device_set_step(self, rows: np.ndarray, idx: np.ndarray,
                         rank: np.ndarray) -> None:
        if self._staged_sets is not None:
            self._staged_sets.insert(rows, idx, rank)
            return
        regs = self._sets
        assert regs is not None
        n = _next_pow2(len(rows), 256)
        scratch = regs.shape[0] - 1
        prow = np.full(n, scratch, dtype=np.int32)
        prow[: len(rows)] = rows
        pidx = np.zeros(n, dtype=np.int32)
        pidx[: len(rows)] = idx
        prank = np.zeros(n, dtype=np.int8)
        prank[: len(rows)] = rank
        if isinstance(regs, np.ndarray):
            # quarantined: host numpy registers, same scatter-max
            self._sets = he.np_hll_insert_batch(
                regs, prow.astype(np.int64), pidx.astype(np.int64), prank)
            return
        sh = self._shard
        try:
            if sh is not None:
                # int8 scatter-max is order- and placement-independent, so
                # the sharded insert is bit-identical by construction;
                # padding rows (scratch, rank 0) stay a no-op max on their
                # owner. The sharded program donates the plane — no retry.
                self._sets = self.guard.call(
                    "sets", sh.hll_insert,
                    regs, sh.replicate(sh.phys_rows(prow, regs.shape[0])),
                    sh.replicate(pidx), sh.replicate(prank))
            else:
                self._sets = self.guard.call(
                    "sets", hll_ops.insert_batch,
                    regs, jnp.asarray(prow), jnp.asarray(pidx),
                    jnp.asarray(prank), retryable=True)
        except dg.DeviceFaultError:
            # max-idempotent: re-applying on host after a partial device
            # write only re-asserts ranks. Pull the plane down and redo.
            if self.guard.quarantined:
                self._quarantine_live()
            else:
                self._sets = self._sets_to_host(regs)
            self._device_set_step(rows, idx, rank)

    # -- import path (global tier) ------------------------------------------

    def import_digest(
        self, key: MetricKey, tags: list[str], mtype: str,
        scope_class: ScopeClass, means: np.ndarray, weights: np.ndarray,
        dmin: float, dmax: float, drecip: float,
    ) -> None:
        """Buffer a downstream instance's digest for row-wise merge at flush
        (reference Histo.Merge path, worker.go:438-495)."""
        self.imported += 1
        row = self._upsert_histo(key, scope_class, tags)
        if self._mesh_pool is not None:
            # mesh path: centroids re-ingest as weighted samples — the
            # reference's own Merge semantics (merging_digest.go:374-389:
            # min/max evolve from centroid means, reciprocalSum carried
            # exactly)
            self._mesh_pool.add_centroids(
                row, np.asarray(means, np.float32),
                np.asarray(weights, np.float32), float(drecip))
            return
        self._ensure_histo(max(self.directory.num_histo_rows, row + 1))
        self._imp_digests.setdefault(row, []).append(
            (np.asarray(means, np.float32), np.asarray(weights, np.float32),
             float(dmin), float(dmax), float(drecip))
        )

    def import_digests_soa(self, rows: np.ndarray, lo: np.ndarray,
                           hi: np.ndarray, means_flat: np.ndarray,
                           weights_flat: np.ndarray, dmin: np.ndarray,
                           dmax: np.ndarray, drecip: np.ndarray) -> None:
        """Batched digest import from a decoded wire batch: rows were
        already assigned by the native batched upsert (vn_upsert_many),
        so no per-metric directory work remains — only buffering views
        of the flat centroid arrays for the flush-time merge."""
        k = len(rows)
        if not k:
            return
        self.imported += k
        if self._mesh_pool is not None:
            for i in range(k):
                self._mesh_pool.add_centroids(
                    int(rows[i]), means_flat[lo[i]:hi[i]],
                    weights_flat[lo[i]:hi[i]], float(drecip[i]))
            return
        self._ensure_histo(max(self.directory.num_histo_rows,
                               int(rows.max()) + 1))
        imp = self._imp_digests
        setdefault = imp.setdefault
        rl = rows.tolist()
        lol = lo.tolist()
        hil = hi.tolist()
        mnl = dmin.tolist()
        mxl = dmax.tolist()
        rcl = drecip.tolist()
        for i in range(k):
            setdefault(rl[i], []).append(
                (means_flat[lol[i]:hil[i]], weights_flat[lol[i]:hil[i]],
                 mnl[i], mxl[i], rcl[i]))

    def import_counter_rows(self, rows: np.ndarray,
                            values: np.ndarray) -> None:
        """Batched counter import by pre-assigned rows (forced-global
        semantics were applied at upsert)."""
        k = len(rows)
        if not k:
            return
        self.imported += k
        pool = self.scalars.counters
        pool.ensure(int(rows.max()) + 1)
        np.add.at(pool.values, rows, values.astype(np.int64))
        pool.present[rows] = True

    def import_gauge_rows(self, rows: np.ndarray,
                          values: np.ndarray) -> None:
        """Batched gauge import: duplicates resolve arbitrarily, which
        is the reference's own semantics for global gauges
        (random-write-wins, README.md:262)."""
        k = len(rows)
        if not k:
            return
        self.imported += k
        pool = self.scalars.gauges
        pool.ensure(int(rows.max()) + 1)
        pool.values[rows] = values
        pool.present[rows] = True

    def import_hll_row(self, row: int, registers: np.ndarray) -> None:
        """Register import by pre-assigned row."""
        self.imported += 1
        if len(registers) != (1 << self.hll_precision):
            raise ValueError(
                f"HLL payload has {len(registers)} registers, expected"
                f" {1 << self.hll_precision}")
        if self._staged_sets is not None:
            self._staged_sets.import_dense(row, registers)
            return
        self._ensure_sets(max(self.directory.num_set_rows, row + 1))
        prev = self._imp_hll.get(row)
        regs = np.asarray(registers, np.int8)
        self._imp_hll[row] = regs if prev is None else np.maximum(prev, regs)

    def import_hll(self, key: MetricKey, tags: list[str],
                   scope_class: ScopeClass, registers: np.ndarray) -> None:
        self.imported += 1
        row = self._upsert_set(key, scope_class, tags)
        if self._staged_sets is not None:
            self._staged_sets.import_dense(row, registers)
            return
        self._ensure_sets(max(self.directory.num_set_rows, row + 1))
        prev = self._imp_hll.get(row)
        regs = np.asarray(registers, np.int8)
        self._imp_hll[row] = regs if prev is None else np.maximum(prev, regs)

    def import_counter(self, key: MetricKey, tags: list[str],
                       value: int) -> None:
        """Imported counters are global by definition
        (reference worker.go:404-407, 449-451)."""
        self.imported += 1
        self._host_counter(key, ScopeClass.GLOBAL, tags, int(value))

    def import_gauge(self, key: MetricKey, tags: list[str],
                     value: float) -> None:
        self.imported += 1
        self._host_gauge(key, ScopeClass.GLOBAL, tags, float(value))

    def _merge_imports(self) -> None:
        if self._imp_digests:
            h = self._histo
            assert h is not None
            rows = sorted(self._imp_digests)
            c = self.capacity
            widths = {
                r: sum(len(m) for m, *_ in self._imp_digests[r])
                for r in rows
            }
            w_bucket = _next_pow2(max(widths.values()), c)
            k = _next_pow2(len(rows), 16)
            scratch = h.num_rows - 1
            arows = np.full(k, scratch, dtype=np.int32)
            imp_means = np.full((k, w_bucket), np.inf, dtype=np.float32)
            imp_w = np.zeros((k, w_bucket), dtype=np.float32)
            imp_min = np.full(k, np.inf, dtype=np.float32)
            imp_max = np.full(k, -np.inf, dtype=np.float32)
            imp_recip = np.zeros(k, dtype=np.float32)
            for i, r in enumerate(rows):
                arows[i] = r
                off = 0
                for m, wts, mn, mx, rc in self._imp_digests[r]:
                    nz = wts > 0
                    cnt = int(nz.sum())
                    imp_means[i, off:off + cnt] = m[nz]
                    imp_w[i, off:off + cnt] = wts[nz]
                    off += cnt
                    imp_min[i] = min(imp_min[i], mn)
                    imp_max[i] = max(imp_max[i], mx)
                    imp_recip[i] += rc
            self._imp_digests = {}

            def _host_digest_merge():
                hh = self._histo
                out = he.np_import_step(
                    hh.means, hh.weights, hh.dmin, hh.dmax, hh.drecip,
                    hh.drecip_c, arows, imp_means, imp_w, imp_min,
                    imp_max, imp_recip, compression=self.compression)
                (hh.means, hh.weights, hh.dmin, hh.dmax, hh.drecip,
                 hh.drecip_c) = out

            if isinstance(h, he.HostHistoState):
                _host_digest_merge()
            else:
                sh = self._shard
                try:
                    if sh is not None:
                        out = self.guard.call(
                            "import", sh.import_step,
                            h.means, h.weights, h.dmin, h.dmax, h.drecip,
                            h.drecip_c,
                            sh.replicate(sh.phys_rows(arows, h.num_rows)),
                            sh.replicate(imp_means), sh.replicate(imp_w),
                            sh.replicate(imp_min), sh.replicate(imp_max),
                            sh.replicate(imp_recip),
                        )
                    else:
                        out = self.guard.call(
                            "import", _histo_import_step,
                            h.means, h.weights, h.dmin, h.dmax, h.drecip,
                            h.drecip_c,
                            jnp.asarray(arows), jnp.asarray(imp_means),
                            jnp.asarray(imp_w), jnp.asarray(imp_min),
                            jnp.asarray(imp_max), jnp.asarray(imp_recip),
                            compression=self.compression,
                        )
                    (h.means, h.weights, h.dmin, h.dmax, h.drecip,
                     h.drecip_c) = out
                except dg.DeviceFaultError as exc:
                    # the merge runs at swap — there is no later retry
                    # point for this epoch, so one fault here forces the
                    # failover (the import buffers are already drained
                    # into locals; the host merge conserves them all)
                    self.guard.trip(f"import merge faulted [{exc.kind}]")
                    self._quarantine_live()
                    _host_digest_merge()

        if self._imp_hll:
            regs = self._sets
            assert regs is not None
            rows = sorted(self._imp_hll)
            k = len(rows)
            arows = np.asarray(rows, dtype=np.int32)
            imp = np.stack([self._imp_hll[r] for r in rows])
            self._imp_hll = {}

            def _host_hll_merge():
                np.maximum.at(self._sets, arows.astype(np.int64), imp)

            if isinstance(regs, np.ndarray):
                _host_hll_merge()
            else:
                sh = self._shard
                try:
                    if sh is not None:
                        self._sets = self.guard.call(
                            "import", sh.hll_max_rows,
                            regs,
                            sh.replicate(sh.phys_rows(arows,
                                                      regs.shape[0])),
                            sh.replicate(imp))
                    else:
                        self._sets = self.guard.call(
                            "import",
                            lambda r, a, m: r.at[a].max(m, mode="drop"),
                            regs, jnp.asarray(arows), jnp.asarray(imp),
                            retryable=True)
                except dg.DeviceFaultError as exc:
                    self.guard.trip(f"HLL import merge faulted "
                                    f"[{exc.kind}]")
                    self._quarantine_live()
                    _host_hll_merge()

    # -- device fault domain -------------------------------------------------

    def _sets_to_host(self, regs) -> np.ndarray:
        """d2h one dense register plane to logical row order; on a hard
        device loss the readback itself can fail, in which case the set
        state restarts empty (logged — honest degraded mode)."""
        try:
            d = np.array(np.asarray(regs), copy=True)
        except Exception:
            log.exception("set pool readback failed during quarantine;"
                          " restarting host registers empty")
            return np.zeros(regs.shape, np.int8)
        if self._shard is not None:
            d = d[self._shard.perm_l2p(d.shape[0])]
        return d

    def _quarantine_live(self) -> None:
        """Fail the LIVE epoch's device state over to the host engine
        (ops/host_engine.py, bit-identical per metric class). Caller
        holds the ingest lock. Idempotent. The d2h snapshots are the one
        device interaction left; on a hard-lost device they can fail
        too, and then the affected pool restarts empty — counted and
        logged, with the retained staging plane and pending SoA batches
        still replaying everything they hold."""
        if self._host_live:
            return
        self._host_live = True
        h = self._histo
        if h is not None and not isinstance(h, he.HostHistoState):
            try:
                perm = (self._shard.perm_l2p(h.num_rows)
                        if self._shard is not None else None)
                self._histo = he.HostHistoState.from_fields(
                    h.fields(), perm=perm)
            except Exception:
                log.exception("digest pool readback failed during "
                              "quarantine; restarting host pools empty")
                self._histo = he.HostHistoState.create(
                    h.num_rows, self.capacity)
        s = self._sets
        if s is not None and not isinstance(s, np.ndarray):
            self._sets = self._sets_to_host(s)
        if self._staged_sets is not None:
            self._staged_sets.to_host()
        # the mirror is device memory; the staging plane retained every
        # sample it mirrored (watermark drains never consumed counts),
        # so dropping it loses nothing and the swap folds the plane
        self._micro = None
        self._micro_fault_epoch = True
        self.guard.bump("device.guard.quarantines")
        log.warning("live epoch quarantined to the host engine (%s)",
                    self.guard.trip_reason)

    def _readmit_device(self) -> None:
        """Re-upload the host pools and leave host mode (the probe
        succeeded; caller holds the ingest lock)."""
        if not self._host_live:
            return
        sh = self._shard
        h = self._histo
        if isinstance(h, he.HostHistoState):
            if sh is not None:
                perm = sh.perm_p2l(h.num_rows)
                self._histo = HistoDeviceState(
                    *(sh.place(a[perm]) for a in h.fields()))
            else:
                self._histo = HistoDeviceState(
                    *(jnp.asarray(a) for a in h.fields()))
        s = self._sets
        if isinstance(s, np.ndarray):
            if sh is not None:
                self._sets = sh.place(s[sh.perm_p2l(s.shape[0])])
            else:
                self._sets = jnp.asarray(s)
        if self._staged_sets is not None:
            self._staged_sets.to_device()
        self._host_live = False
        self.guard.readmit()

    def _device_probe(self) -> bool:
        """Tiny compile+fold+extract round trip through the dispatch
        seam (op "probe") — the half-open breaker's health check. Runs
        on throwaway buffers so a failing probe cannot touch state."""
        def _probe():
            st = HistoDeviceState.create(64, self.capacity)
            rows = np.array([1, 2, 3], np.int32)
            vals = np.array([1.0, 2.0, 3.0], np.float32)
            wts = np.ones(3, np.float32)
            active, lids, v, w = self._pad_spill_batch(rows, vals, wts, 63)
            out = _histo_ingest_step(
                *st.fields(), jnp.asarray(active), jnp.asarray(lids),
                jnp.asarray(v), jnp.asarray(w),
                compression=self.compression)
            qs = jnp.asarray(np.array([0.25, 0.5, 0.75, 0.99], np.float32))
            ext = _histo_flush_extract(*out, qs)
            jax.block_until_ready(ext)
            return True

        try:
            return bool(self.guard.call("probe", _probe))
        except dg.DeviceFaultError:
            return False
        except Exception:
            log.exception("device probe raised a non-device error")
            return False

    def device_guard_tick(self) -> None:
        """Per-flush guard maintenance, run by the server after each
        extraction with this worker's ingest lock held (extraction
        itself must NOT mutate live state — it runs off the lock):
        quarantine the live epoch if the breaker tripped during the
        flush, and while quarantined run the re-admission probe when
        due."""
        if not self.guard.enabled:
            return
        if self.guard.quarantined and not self._host_live:
            self._quarantine_live()
        if self._host_live and self.guard.quarantined \
                and self.guard.probe_due():
            ok = self._device_probe()
            self.guard.note_probe(ok)
            if ok:
                self._readmit_device()
                log.warning("device path re-admitted after probe; host "
                            "state re-uploaded")

    def _extract(self, fields: tuple, qs):
        """Flush extraction. `fields` is the 14-tuple of (possibly
        row-sliced, possibly staged-folded) digest arrays in
        HistoDeviceState order. Classified device faults go to the
        guard's failover as on every other path."""
        return self.guard.call(
            "extract", _histo_flush_extract, *fields, qs, retryable=True)

    # -- flush --------------------------------------------------------------

    def _shed_spill_budget(self, spill_histo):
        """Bound the fold work this flush inherits: backlog past what
        the measured fold rate can absorb in the budget sheds here
        (newest samples kept — freshest values win), counted like every
        other overload drop. Without this a starved host hands a 40s+
        backlog to every flush and the cadence collapses (round-5
        overload measurement). Tenant-aware when a ledger is installed
        (health/policy.py): over-budget tenants shed first."""
        if spill_histo is None:
            return None
        budget = max(_SHED_FLOOR,
                     int(self._fold_rate_ewma * self.fold_budget_s))
        total = len(spill_histo[0])
        if total <= budget:
            return spill_histo
        shed = total - budget
        self.overload_dropped += shed
        self.overload_dropped_total += shed
        led = self.tenancy
        if led is None:
            return tuple(a[-budget:] for a in spill_histo)
        # tenant-aware shed (health/policy.py): samples of over-budget
        # tenants go first; with no such tenant the keep set reduces
        # bitwise to the a[-budget:] slice above. Per-tenant drop
        # attribution lands in the epoch tallies and the governor (the
        # isolation soak's zero-innocent-shed assertion reads both).
        from veneur_tpu.health.policy import shed_spill_keep

        sp_rows = spill_histo[0]
        hrows = self.directory.histo.rows
        row_tenants = np.array(
            [m.tenant or DEFAULT_TENANT for m in hrows],
            dtype=object)
        abusive = led.over_budget()
        if abusive:
            is_abusive = np.isin(
                row_tenants[sp_rows],
                np.array(sorted(abusive), dtype=object))
            keep = shed_spill_keep(is_abusive, budget)
        else:
            keep = np.arange(total - budget, total, dtype=np.int64)
        drop_mask = np.ones(total, bool)
        drop_mask[keep] = False
        t_list, t_counts = np.unique(
            row_tenants[sp_rows[drop_mask]],
            return_counts=True)
        tt = self.tenant_tallies
        gov = self.governor
        for t, c in zip(t_list.tolist(), t_counts.tolist()):
            tt.dropped[t] = tt.dropped.get(t, 0) + int(c)
            if gov is not None:
                gov.note_tenant_shed(t, int(c))
        return tuple(a[keep] for a in spill_histo)

    def swap(self, quantiles: np.ndarray) -> "SwappedEpoch":
        """Close the current epoch and return the old-interval state.

        The map-swap analog of worker.go:498-517, split from extraction so
        the caller's ingest lock is held only across this method: native
        drain/reset, pending device *dispatches* (async on TPU), import
        merges, and the epoch reset — no device readback. Next-interval
        ingest proceeds while extract_snapshot() reads the old buffers.

        The mesh path (global tier) is the one exception: MeshHistoPool
        state is not double-buffered, so its extract+reset happens here,
        under the lock. The overlap-critical 1M-series local path never
        takes it.
        """
        # lifetime sample tally, taken BEFORE the native reset below
        # destroys the per-epoch counter (the server's flush telemetry
        # reads `processed` pre-swap; Server.ingress_stats reads this
        # accumulator — same split as overload_dropped vs
        # overload_dropped_total). The caller holds this worker's ingest
        # lock across swap(), which is what keeps the pair (total,
        # per-epoch) consistent for locked readers. Reader-shard mode
        # accumulates the native deltas inside the flush-edge fence
        # instead: owned readers commit WITHOUT the worker lock, so an
        # unlocked pre-fence read here would miss lines landing before
        # each context's locked fence read and break the exact
        # attribution books (sum(reader_committed) == processed_total).
        # The legacy native path does the same inside its one lock hold
        # below (a line committed between an unlocked read here and that
        # lock would be drained, reset away and never tallied).
        if self._native is not None:
            self.processed_total += self._processed_py
        else:
            self.processed_total += self.processed
        rec = self.rec
        native_stage = None
        spill_histo = None
        micro_coo: list = []
        native_mirrored = False
        reader_planes = None
        if self._native is not None and self._reader_ctxs:
            # shared-nothing flush-edge fence: walk [home] + reader
            # contexts; each context's lock is held only for its OWN
            # drain + detach + reset, so a committing reader contends
            # only when the fence reaches its shard — exactly once per
            # flush. Micro-folds are inactive in shard mode (see
            # _micro_active), so no mirror fence is needed.
            raws = []
            for i, ctx in enumerate(self._all_ctxs()):
                seen = (self._native_proc_seen if i == 0
                        else self._reader_proc_seen[i - 1])
                with rec.span("swap.drain", ctx=i, ctx_lock=True):
                    ctx.lock()
                    try:
                        raw = self._drain_native_raw_ctx(
                            ctx, i, detach_stage=True)
                        # per-context committed attribution, read inside
                        # the lock so the reset below can't race a
                        # commit; the same locked delta feeds
                        # processed_total (see the swap-top comment)
                        delta = int(ctx.processed) - seen
                        self.reader_committed[i] += delta
                        self.processed_total += delta
                        ctx.reset()
                        if i == 0:
                            self._native_errs_seen = 0
                            self._native_proc_seen = 0
                            self._native_drop_seen = 0
                        else:
                            self._reader_errs_seen[i - 1] = 0
                            self._reader_proc_seen[i - 1] = 0
                            self._reader_drop_seen[i - 1] = 0
                    finally:
                        ctx.unlock()
                raws.append(raw)
            self._native_epoch_closed = True
            # off-lock: translate each context's SoA rows to canonical,
            # apply them in context order (counters add in order,
            # gauges stay last-write-wins in context order — the
            # serialized-reader-order ground truth the parity tests
            # pin), and collect the detached planes with map COPIES
            # for the stacked fold at extraction
            others: list = []
            ssf_fb: list = []
            spills: list = []
            planes: list = []
            for i, raw in enumerate(raws):
                mapped = self._map_raw_rows(i, raw)
                with rec.span("swap.spill_fold", ctx=i):
                    d = self._apply_native_raw(mapped,
                                               defer_histo_spill=True)
                if d is not None and len(d[0]):
                    spills.append(d)
                others.extend(raw[5])
                ssf_fb.extend(raw[6])
                if raw[4] is not None:
                    planes.append((raw[4], np.frombuffer(
                        self._ctx_maps[i][0], dtype=np.int32).copy()))
            self.pending_other_lines = others
            self.pending_ssf_fallback = ssf_fb
            if spills:
                spill_histo = (spills[0] if len(spills) == 1 else tuple(
                    np.concatenate([sp[k] for sp in spills])
                    for k in range(3)))
            spill_histo = self._shed_spill_budget(spill_histo)
            reader_planes = planes or None
            if reader_planes:
                # the stacked fold lands in the canonical pool: it must
                # exist even when every sample this epoch was staged
                self._ensure_histo(self.directory.num_histo_rows)
        elif self._native is not None:
            # drain, detach the staging plane, and close the native epoch
            # under one lock hold: a routed commit can otherwise land
            # between the last drain and the reset and be destroyed with
            # the old epoch
            with rec.span("swap.drain", ctx_lock=True):
                self._native.lock()
                try:
                    if self._micro_active():
                        # residual micro-drain in the SAME critical
                        # section as the detach: every staged sample is
                        # either already mirrored or copied out here, and
                        # nothing can land in between — the swap fence
                        # that makes in-flight micro-folds lose or
                        # double-fold nothing. Host memcpy only; the
                        # device feeds run after unlock so reader commits
                        # aren't stalled.
                        with rec.span("swap.drain.residual"):
                            cap = 1 << 18
                            while True:
                                coo = self._native.drain_stage_delta(cap)
                                if not len(coo[0]):
                                    break
                                micro_coo.append(coo)
                                if len(coo[0]) < cap:
                                    break
                            native_mirrored = (
                                self._native.stage_pending == 0)
                    raw = self._drain_native_raw(detach_stage=True)
                    native_stage = raw[4]
                    # event/service-check lines + fallback SSF payloads
                    # caught at epoch close; the server parses them into
                    # the NEW epoch after swap
                    self.pending_other_lines = raw[5]
                    self.pending_ssf_fallback = raw[6]
                    # the lifetime tally's native share, inside the lock
                    # hold that resets it (see the top of swap)
                    self.processed_total += (int(self._native.processed)
                                             - self._native_proc_seen)
                    self._native.reset()
                    self._native_errs_seen = 0
                    self._native_proc_seen = 0
                    self._native_drop_seen = 0
                    self._native_epoch_closed = True
                finally:
                    self._native.unlock()
            with rec.span("swap.spill_fold"):
                spill_histo = self._shed_spill_budget(
                    self._apply_native_raw(raw, defer_histo_spill=True))
            if native_stage is not None and self._mesh_pool is not None:
                # samples staged before attach_mesh_pool() disabled
                # staging belong to the mesh shards, not the local fold
                # (extract would overwrite the local output with mesh_out,
                # silently dropping them)
                sv, sw, counts, _unit, free = native_stage
                mask = (np.arange(sv.shape[1])[None, :]
                        < counts[:, None])
                rows = np.repeat(
                    np.arange(len(counts), dtype=np.int32),
                    np.minimum(counts, sv.shape[1]))
                vals, wts = sv[mask], sw[mask]  # copies; plane can go
                free()
                native_stage = None
                self._mesh_pool.add_samples_bulk(rows, vals, wts)
            if native_stage is not None:
                # all samples may be staged: the device pool must still
                # exist for the fold to land in
                self._ensure_histo(self.directory.num_histo_rows)
        # what is still pending host-side folds now, under the ingest
        # lock: a program compiled here holds the readers for as long
        with rec.span("swap.spill_fold"):
            self._flush_pending_histos()
        if self._ph_rows:
            # a device fault during the pending-batch fold re-staged the
            # batch instead of folding it (_fold_batch_direct's failover
            # contract). The epoch reset below would destroy it — divert
            # the batch into the spill backlog, which extract_snapshot
            # folds off-lock with its own fault handling. No sample is
            # lost to the fault; it just rides the slower path.
            ph = (np.asarray(self._ph_rows, np.int32),
                  np.asarray(self._ph_vals, np.float32),
                  np.asarray(self._ph_wts, np.float32))
            self._ph_rows, self._ph_vals, self._ph_wts = [], [], []
            spill_histo = (ph if spill_histo is None else tuple(
                np.concatenate([spill_histo[k], ph[k]]) for k in range(3)))
        with rec.span("swap.spill_fold"):
            self._flush_pending_sets()
            self._merge_imports()

        mesh_out = None
        if self._mesh_pool is not None and self.directory.num_histo_rows:
            mesh_out = self._mesh_pool.extract(
                quantiles, self.directory.num_histo_rows)
            self._mesh_pool.reset()

        # close the epoch's micro-fold mirror: python-path residual
        # drain (the caller's ingest lock serializes this against
        # _device_histo_step) is host-only COO collection; the device
        # feeds + carry dispatch are DEFERRED to extract_snapshot via
        # micro_residual — a starved scheduler leaves a large residual,
        # and feeding it here would put the upload burst back on the
        # very tick path micro-folds exist to clear. The new epoch gets
        # a fresh mirror lazily (_ensure_micro).
        device_stage = None
        micro_residual = None
        if self._micro_active():
            with rec.span("swap.mirror_handoff") as sp:
                if self._native is None:
                    coo = self._python_stage_delta()
                    if coo is not None:
                        micro_coo.append(coo)
                mirror, self._micro = self._micro, None
                if mirror is not None:
                    # the epoch's whole mirror: what its feeds came to
                    sp.attrs.update(samples=mirror.samples,
                                    rows=mirror.rows_hi,
                                    mirror_rows=mirror.mirror_rows,
                                    chunks=mirror.chunks)
                residual_n = sum(len(c[0]) for c in micro_coo)
                if (mirror is not None and mirror.samples > 0) or residual_n:
                    if mirror is None:
                        mirror = mf.MicroFoldMirror(
                            self.stage_depth, ledger=self.ledger,
                            initial_rows=self._initial_histo_rows,
                            shard=self._shard, guard=self.guard)
                    mirror.book_in_flush = True
                    micro_residual = (mirror, micro_coo)
                    micro_samples = mirror.samples + residual_n
        self.micro_folds_swapped = self.micro_folds_epoch
        # micro-fold upload bytes belong to the flush that extracts this
        # epoch: queue the closed epoch's tally for its begin_flush
        self.ledger.roll_epoch()

        with rec.span("swap.handoff") as handoff:
            staged = 0
            staged_histo = []
            # device-fault replay batch (ops/device_guard failover): when a
            # staging plane is handed over as a MIRROR (micro_residual)
            # instead of a host plane, the mirror is the only carrier of
            # those samples — and the mirror is device state. micro_replay
            # retains the host ground truth, the staging plane itself
            # (which the mirror duplicates bit-for-bit), until the mirror's
            # flush fold succeeds; if the mirror faults first, the plane
            # is compacted and folds through the host engine instead.
            # Nothing of it is read, copied or freed here, on the tick
            # path and under the ingest lock: extract_snapshot releases it
            # once the mirror's fold has landed, under the device's work.
            micro_replay = None
            # a mirrored plane is handed over as micro_residual (mirror +
            # deferred COO) INSTEAD of a host plane — exactly one of the two
            # carries a given sample
            python_mirrored = (micro_residual is not None
                               and self._native is None)
            if self._stage_count is not None and self._stage_count.any():
                if python_mirrored:
                    # the dense host pair IS the mirror's ground truth (the
                    # drains copied deltas out; the plane keeps everything)
                    micro_replay = StagedPlane(
                        self._stage_vals, self._stage_wts, None, None)
                else:
                    staged += int(self._stage_count.sum())
                    # hand the host staging planes to the closed epoch; the
                    # fold into the digest runs in extract_snapshot, OFF the
                    # ingest lock
                    self._ensure_stage()  # pool may have grown since staging
                    staged_histo.append(StagedPlane(
                        self._stage_vals, self._stage_wts, None, None))
            if native_stage is not None:
                sv, sw, counts, unit, free = native_stage
                # unit weights (no sampled metrics this epoch): nobody
                # reads the weights plane; the fold rebuilds it from counts
                plane = StagedPlane(sv, None if unit else sw, counts, free)
                if native_mirrored and micro_residual is not None:
                    # plane content fully captured by the mirror + residual
                    # COO (all copies): nothing to upload at flush, and
                    # nothing to read unless the mirror faults
                    micro_replay = plane
                else:
                    staged += int(counts.sum())
                    staged_histo.append(plane)
            if micro_residual is not None:
                staged += micro_samples
            if reader_planes:
                staged += sum(int(st[2].sum()) for st, _m in reader_planes)
            staged_histo = staged_histo or None
            # flush self-telemetry (veneur.worker.samples_staged_total)
            self.staged_samples_swapped = staged
            # what the mirror's replay copy is: a native plane held
            # uncompacted, the Python path's dense pair, or none
            handoff.attrs.update(
                replay=("none" if micro_replay is None else
                        "host" if micro_replay.free is None else "plane"),
                plane_rows=(0 if micro_replay is None
                            else int(micro_replay.vals.shape[0])))
            swapped = SwappedEpoch(
                directory=self.directory, scalars=self.scalars,
                histo=self._histo, sets=self._sets,
                staged_sets=self._staged_sets, umts=self._umts,
                mesh_out=mesh_out, staged_histo=staged_histo,
                spill_histo=spill_histo, device_stage=device_stage,
                micro_residual=micro_residual, reader_planes=reader_planes,
                micro_replay=micro_replay,
                spill_steps=self.spill_steps_epoch,
            )
        # per-tenant lifetime fold, still under the caller's ingest lock
        # and BEFORE the epoch reset zeroes the per-epoch dicts — the
        # processed_total pattern above, per tenant per kind, so a
        # tenant's drops in this epoch survive the epoch's reset
        with rec.span("swap.reset"):
            self.tenant_tallies.accumulate_into(self.tenant_tallies_total)
            self.processed = 0
            self.imported = 0
            self._reset_epoch()
        return swapped

    def tenant_lifetime(self) -> dict:
        """Lifetime + current-epoch per-tenant tallies as plain dicts
        (the ingress_stats pattern: totals + live epoch). Caller holds
        this worker's ingest lock."""
        return self.tenant_tallies_total.merged_with(self.tenant_tallies)

    def _fold_one_plane(self, fields: tuple, pending: list, s_eff: int
                        ) -> tuple:
        """Upload pending[0], release its native memory, fold it into the
        digest fields, and pop it. The caller owns cleanup of whatever is
        left in `pending` on failure."""
        plane: StagedPlane = pending[0]
        if plane.free is not None:
            # native C++ plane: COMPACT before upload. The dense
            # [rows, B] plane is O(S×B) bytes regardless of fill; the
            # filled slots are O(samples). Host-side fancy indexing
            # copies them out of the C++ memory (so `free` is safe
            # immediately after the tiny uploads land), the device
            # rebuilds the dense plane from flat + counts
            # (_expand_flat_planes), and the host→device transfer drops
            # from 268 MB to ~17 MB at 1M series × depth 64 × 4
            # samples/series — the difference between blowing and
            # fitting the 10s budget on a transfer-bound link.
            B = plane.vals.shape[1]
            rows_avail = min(plane.vals.shape[0], s_eff)
            counts_np = np.minimum(plane.counts[:rows_avail],
                                   B).astype(np.int32)
            mask = (np.arange(B, dtype=np.int32)[None, :]
                    < counts_np[:, None])
            flat_v = plane.vals[:rows_avail][mask]  # copies out of C++
            if rows_avail < s_eff:
                # the native plane grows by its own pow2 schedule and
                # can trail the pool's; rows past its end are empty
                counts_np = np.pad(counts_np, (0, s_eff - rows_avail))
            unit = plane.wts is None
            flat_w = None if unit else plane.wts[:rows_avail][mask]
            sh = self._shard
            if sh is not None:
                fvj, fwj, cj = self._shard_flat_upload(
                    flat_v, flat_w, counts_np, s_eff)
                if unit:
                    fwj = fvj  # ignored under unit=True (XLA DCEs it)
                plane.free()
                # re-stage the HOST copies in place of the freed native
                # plane: a device fault in the fold below leaves pending[0]
                # replayable through the host engine (free=None also means
                # the caller's cleanup won't double-free)
                pending[0] = StagedPlane(flat_v, flat_w, counts_np, None)
                svj, swj = sh.expand_flat(fvj, fwj, cj, B, unit)
            else:
                n_pad = _next_pow2(max(len(flat_v), 1), 1024)
                fv = np.zeros(n_pad, np.float32)
                fv[:len(flat_v)] = flat_v
                # fv/fw/counts_np are Python-owned copies (fancy indexing /
                # np.minimum / np.pad) — nothing below aliases the C++
                # plane, so free() needs no upload synchronization. The
                # ledger pins these uploads at O(samples) + O(rows) bytes:
                # the whole point of the compaction, and what the
                # test_health_ledger regression test asserts
                fvj = self.ledger.h2d(fv, "staged_flat")
                cj = self.ledger.h2d(counts_np, "staged_counts")
                if unit:
                    fwj = fvj  # ignored under unit=True (XLA DCEs it)
                else:
                    fw = np.zeros(n_pad, np.float32)
                    fw[:len(flat_w)] = flat_w
                    fwj = self.ledger.h2d(fw, "staged_flat")
                plane.free()
                # freed: re-stage the host copies (fault-replayable, and
                # the caller's cleanup must not free the plane again)
                pending[0] = StagedPlane(flat_v, flat_w, counts_np, None)
                svj, swj = _expand_flat_planes(fvj, fwj, cj, B, unit)
        elif plane.counts is not None:
            # pre-compacted flat plane (ops/reader_stack.merge_reader_
            # planes): vals/wts are ALREADY the 1-D row-major compaction
            # the native branch above builds, in canonical row order —
            # skip the compaction and go straight to the flat upload +
            # on-device expand, the exact legacy program
            flat_v = plane.vals
            counts_np = plane.counts
            if len(counts_np) < s_eff:
                counts_np = np.pad(counts_np, (0, s_eff - len(counts_np)))
            elif len(counts_np) > s_eff:
                counts_np = counts_np[:s_eff]
            unit = plane.wts is None
            B = self.stage_depth
            sh = self._shard
            if sh is not None:
                fvj, fwj, cj = self._shard_flat_upload(
                    flat_v, plane.wts, counts_np, s_eff)
                if unit:
                    fwj = fvj  # ignored under unit=True (XLA DCEs it)
                svj, swj = sh.expand_flat(fvj, fwj, cj, B, unit)
            else:
                n_pad = _next_pow2(max(len(flat_v), 1), 1024)
                fv = np.zeros(n_pad, np.float32)
                fv[:len(flat_v)] = flat_v
                fvj = self.ledger.h2d(fv, "staged_flat")
                cj = self.ledger.h2d(counts_np.astype(np.int32),
                                     "staged_counts")
                if unit:
                    fwj = fvj  # ignored under unit=True (XLA DCEs it)
                else:
                    fw = np.zeros(n_pad, np.float32)
                    fw[:len(plane.wts)] = plane.wts
                    fwj = self.ledger.h2d(fw, "staged_flat")
                svj, swj = _expand_flat_planes(fvj, fwj, cj, B, unit)
        else:
            # Python-owned plane: the dense upload IS O(rows x depth) —
            # acceptable only because this path serves small non-native
            # deployments; the ledger keeps it visible ("staged_dense"
            # stays zero whenever native staging is attached)
            sh = self._shard
            if sh is not None:
                sv = np.asarray(plane.vals[:s_eff], np.float32)
                sw = np.asarray(plane.wts[:s_eff], np.float32)
                if sv.shape[0] < s_eff:
                    pad = s_eff - sv.shape[0]
                    sv = np.pad(sv, ((0, pad), (0, 0)))
                    sw = np.pad(sw, ((0, pad), (0, 0)))
                # host-permute to the physical interleave, then one
                # partitioned placement per plane
                p2l = sh.perm_p2l(s_eff)
                d = sh.shards
                self.ledger.count_h2d_shards(
                    [sv.nbytes // d] * d, "staged_dense")
                self.ledger.count_h2d_shards(
                    [sw.nbytes // d] * d, "staged_dense")
                svj = sh.place(sv[p2l])
                swj = sh.place(sw[p2l])
            else:
                svj = self.ledger.h2d(plane.vals[:s_eff], "staged_dense")
                swj = self.ledger.h2d(plane.wts[:s_eff], "staged_dense")
                if svj.shape[0] < s_eff:
                    pad = s_eff - svj.shape[0]
                    svj = jnp.concatenate(
                        [svj, jnp.zeros((pad, svj.shape[1]), jnp.float32)])
                    swj = jnp.concatenate(
                        [swj, jnp.zeros((pad, swj.shape[1]), jnp.float32)])
        if self._shard is not None:
            fields = self.guard.call(
                "staged", self._shard.fold_staged, *fields, svj, swj)
        else:
            fields = self.guard.call(
                "staged", self._fold_staged, *fields, svj, swj)
        pending.pop(0)
        return fields

    def _fold_staged(self, *args):
        """`_histo_fold_staged` over (the 14 fields, svals, swts): the 14
        fields; its count of wide rows is kept, on the device, for
        `_note_fold_widths`."""
        weights, svals = args[1], args[14]
        out = _histo_fold_staged(*args, compression=self.compression)
        self._fold_widths.append(
            (svals.shape[0],
             fold_wide_slots(*svals.shape, weights.shape[1]), out[14]))
        return out[:14]

    def _note_fold_widths(self, span) -> None:
        """Onto the flush's extract span: how many rows this flush's
        staged folds found wide and narrow, and whether every fold
        compacted its wide rows (`split`) or some fold took all its rows
        at the full width (`full`: too many wide rows to compact, or a
        depth it never splits)."""
        if span is None or not self._fold_widths:
            return
        wide = total = 0
        split = True
        for rows, k, n in self._fold_widths:
            n = int(n)
            wide += n
            total += rows
            split = split and k > 0 and not fold_takes_all(n, k)
        span.attrs.update(wide_rows=wide, narrow_rows=total - wide,
                          fold_path="split" if split else "full")

    def _shard_flat_upload(self, flat_v, flat_w, counts_np, s_eff: int):
        """Split one compacted staged plane (flat samples in LOGICAL row
        order + per-row counts) into per-shard segments for the sharded
        expand (ops/series_shard.expand_flat).

        Each shard's segment concatenates its local rows' samples in
        local order (= the physical counts order), padded to a common
        pow2 length: a [D, Lmax] upload that stays O(samples/shard) per
        device, against the [s_eff] counts in physical order. Returns
        the placed (flat_v, flat_w_or_None, counts) device arrays with
        per-shard ledger bookings."""
        sh = self._shard
        d = sh.shards
        p2l = sh.perm_p2l(s_eff)
        counts64 = counts_np.astype(np.int64)
        # logical sample offsets per row, then gathered per-shard-major
        off = np.zeros(s_eff, np.int64)
        np.cumsum(counts64[:-1], out=off[1:])
        reps = counts64[p2l]
        total = int(reps.sum())
        run_starts = np.cumsum(reps) - reps
        gidx = (np.repeat(off[p2l], reps)
                + np.arange(total, dtype=np.int64)
                - np.repeat(run_starts, reps))
        seg_len = reps.reshape(d, -1).sum(axis=1)
        lmax = _next_pow2(int(seg_len.max()) if total else 1, 1024)
        seg_off = np.cumsum(seg_len) - seg_len
        col = (np.arange(total, dtype=np.int64)
               - np.repeat(seg_off, seg_len))
        srd = np.repeat(np.arange(d), seg_len)
        led = self.ledger
        fv2 = np.zeros((d, lmax), np.float32)
        fv2[srd, col] = flat_v[gidx]
        led.count_h2d_shards([lmax * 4] * d, "staged_flat")
        fvj = sh.place(fv2)
        counts_phys = counts_np[p2l].astype(np.int32)
        led.count_h2d_shards(
            [counts_phys.nbytes // d] * d, "staged_counts")
        cj = sh.place(counts_phys)
        fwj = None
        if flat_w is not None:
            fw2 = np.zeros((d, lmax), np.float32)
            fw2[srd, col] = flat_w[gidx]
            led.count_h2d_shards([lmax * 4] * d, "staged_flat")
            fwj = sh.place(fw2)
        return fvj, fwj, cj

    def _device_extract_histo(self, snap, swapped, full, s_eff, n,
                              spill, pending, quantiles, gov, st):
        """The device half of the histo extraction: spill fold, staged
        plane folds, micro-mirror fold, quantile extract, column unpack,
        tenant-sketch fold, digest readback. On a DeviceFaultError the
        caller completes the flush on the host engine; ``st`` tracks the
        replayable progress (the newest fold state + the spill sample
        offset) so the failover resumes exactly where the device stopped.
        The injected-fault seam (ops/device_guard.dispatch) raises BEFORE
        a dispatch executes, so the tracked state is exact under seeded
        chaos; a real mid-execution device loss instead replays the
        retained host inputs with whatever fold state is still readable
        (honest degraded replay, logged). Returns (view_fields, s_eff)
        for the query-view publish."""
        directory = swapped.directory
        rec = self.rec
        extract_span = rec.current()
        self._fold_widths = []
        if spill is not None:
            # hot-row spill backlog deferred by swap(): chunked fold
            # off the ingest lock (plain numpy from drain_histo — no
            # native memory to free). Folded at the FULL pool shape —
            # the exact jit specialization _fold_batch_direct keeps
            # warm all interval — because a fresh s_eff-shaped
            # compile on a starved host stalls the flush for longer
            # than the fold itself (observed: 40s+ XLA compile under
            # 33x overload). Timed: the measured rate sizes the NEXT
            # swap's fold budget (closed-loop shedding).
            with rec.span("extract.spill_fold", samples=int(len(spill[0]))):
                sp_rows, sp_vals, sp_wts = spill
                pool_rows = full[0].shape[0]
                t_fold = time.perf_counter()
                inflight = 0
                for i in range(0, len(sp_rows), _FOLD_CHUNK):
                    full = self._fold_spill_chunk(
                        full, sp_rows[i:i + _FOLD_CHUNK],
                        sp_vals[i:i + _FOLD_CHUNK],
                        sp_wts[i:i + _FOLD_CHUNK], pool_rows)
                    st["fields"] = full
                    st["spill_off"] = min(i + _FOLD_CHUNK, len(sp_rows))
                    inflight += 1
                    if inflight >= 8:  # bound the dispatch queue's memory
                        with rec.span("extract.spill_fold.wait", wait=True):
                            self.guard.call("spill",
                                            full[0].block_until_ready)
                        inflight = 0
                        if gov is not None:
                            gov.beat()
                with rec.span("extract.spill_fold.wait", wait=True):
                    self.guard.call("spill", full[0].block_until_ready)
                t_fold = time.perf_counter() - t_fold
                if t_fold > 0.01:
                    rate = len(sp_rows) / t_fold
                    self._fold_rate_ewma = (
                        0.5 * self._fold_rate_ewma + 0.5 * rate)
        sh = self._shard
        with rec.span("extract.shrink"):
            if sh is None:
                fields = tuple(
                    a if a.shape[0] == s_eff else a[:s_eff] for a in full)
            else:
                # sharded shrink: each shard keeps its local prefix (the
                # interleave closure property) — no resharding
                fields = tuple(
                    a if a.shape[0] == s_eff else sh.slice_field(a, s_eff)
                    for a in full)
        st["fields"] = fields
        st["spill_off"] = len(spill[0]) if spill is not None else 0
        try:
            while pending:
                with rec.span("extract.plane_fold"):
                    fields = self._fold_one_plane(fields, pending, s_eff)
                st["fields"] = fields
                if gov is not None:
                    gov.beat()
        except dg.DeviceFaultError:
            # hand the not-yet-folded tail to the failover as host
            # copies (pending[0] already is one — _fold_one_plane
            # re-stages before it dispatches): nothing replayable may
            # be freed, and nothing native may survive this frame
            for k in range(len(pending)):
                pending[k] = _staged_plane_to_host(pending[k])
            raise
        except Exception:
            # an upload/fold failure must not leak the C++ planes: a
            # repeated failing flush at 1M rows would otherwise leak
            # hundreds of MB per interval. Data loss here is fine
            # (per-flush data is expendable, README.md:135-137);
            # leaked native memory is not.
            _free_staged_planes(pending)
            pending.clear()
            raise
        if swapped.micro_residual is not None:
            # deferred residual feeds: whatever the scheduler had not
            # streamed by swap time lands on the device HERE, in the
            # extract stage, exactly like the batch path's upload —
            # the tick paid only the host-side COO memcpy
            with rec.span("extract.micro_residual"):
                mirror, coos = swapped.micro_residual
                swapped.micro_residual = None
                for coo in coos:
                    mirror.feed(*coo)
                swapped.device_stage = mirror.finish()
            if gov is not None:
                gov.beat()
        dstage = swapped.device_stage
        swapped.device_stage = None
        if dstage is not None:
            # micro-fold mirror: the epoch's staging plane is already
            # resident on device, so this is the SAME single fold the
            # batch path runs minus the upload — mirror_dense yields
            # bitwise the array _expand_flat_planes / the dense
            # Python upload would have built (values and weights at
            # the same absolute slots, zeros elsewhere), which is
            # what pins micro-folded == batch-folded
            dense = (functools.partial(mf.mirror_dense,
                                       depth=self.stage_depth)
                     if sh is None else sh.mirror_dense)
            folder = sh.fold_staged if sh is not None else self._fold_staged

            def _mirror_fold(fl):
                # the mirror's one change of layout, flat -> [s_eff,
                # depth], once per array per flush: dispatched, not
                # waited for
                with rec.span("extract.mirror_dense", rows=s_eff,
                              mirror_rows=(dstage.vals.size
                                           // self.stage_depth)):
                    dv = dense(dstage.vals, s_eff)
                    dw = dense(dstage.wts, s_eff)
                # the dispatch span's bytes: the planes the fold reads,
                # which the guard cannot see among its arguments
                rec.add("bytes", int(dv.nbytes) + int(dw.nbytes))
                return folder(*fl, dv, dw)

            with rec.span("extract.mirror_fold"):
                fields = self.guard.call("staged", _mirror_fold, fields)
            st["fields"] = fields
            if gov is not None:
                gov.beat()
        # the mirror's content is folded (or there was none): the replay
        # copy swap() retained will not be read again, and a fault from
        # here on must not fold it a second time. Its release waits
        # until the extract programs are dispatched (_release_replay)
        swapped.spent_replay, swapped.micro_replay = (
            swapped.micro_replay, None)
        qnp = np.asarray(quantiles, dtype=np.float32)
        if sh is None:
            qs = self.ledger.h2d(qnp, "quantiles")
        else:
            qs = self.ledger.h2d(qnp, "quantiles",
                                 replicas=sh.shards, put=sh.replicate)
        run = (gov.begin_extract(s_eff, sh.shards if sh else 1)
               if gov is not None and gov.enabled else None)
        # extract.quantiles dispatches (extract + pack, both async);
        # extract.readback is the packed D2H: the first call of the
        # flush that blocks on the device, so it IS the device wait
        if run is None:
            if sh is not None:
                # sharded extract bypasses the Pallas single-device
                # kernel: the GSPMD XLA program runs shard-local and
                # the one packed readback assembles all shards
                with rec.span("extract.quantiles"):
                    out = self.guard.call("extract", sh.flush_extract,
                                          *fields, qs, retryable=True)
                    pk = _pack_extract_columns(*out)
                self._release_replay(swapped)
                with rec.span("extract.readback", wait=True):
                    packed = np.asarray(pk)
                self.ledger.count_d2h_shards(
                    [packed.nbytes // sh.shards] * sh.shards,
                    "extract_packed")
                packed = packed[sh.perm_l2p(s_eff)]
            else:
                with rec.span("extract.quantiles"):
                    out = self._extract(fields, qs)
                    pk = _pack_extract_columns(*out)
                self._release_replay(swapped)
                # ONE device→host transfer for the whole extraction:
                # eleven per-array np.asarray calls are eleven
                # synchronous D2H round-trips, and on a link with
                # per-transfer latency the round-trips dominate the
                # bytes at 1M rows
                with rec.span("extract.readback", wait=True):
                    packed = self.ledger.d2h(pk, "extract_packed")
            p = out[0].shape[1]
        else:
            # governed degraded mode: extract in row chunks sized to
            # flush_chunk_target_ms (health/governor.py) so an
            # extraction-bound host produces a longer-but-BOUNDED
            # flush with a progress beat per chunk (the watchdog
            # deferral signal). dynamic_slice keeps one executable
            # per (pool, chunk) shape pair — a static a[i:j] slice
            # would compile per start offset.
            parts = []
            p = 0
            while (c := run.next_rows()):
                t0 = time.perf_counter()
                if sh is not None:
                    # lockstep per-shard slice: a c-row chunk at a
                    # D-aligned start is rows [start/D, start/D+c/D)
                    # on every shard; the per-chunk inverse perm
                    # restores logical order, so the concat below is
                    # already logical end to end
                    with rec.span("extract.quantiles"):
                        sub = tuple(sh.slice_chunk(a, run.start, c)
                                    for a in fields)
                        out = self.guard.call(
                            "extract", sh.flush_extract, *sub, qs,
                            retryable=True)
                        pk = _pack_extract_columns(*out)
                    self._release_replay(swapped)
                    with rec.span("extract.readback", wait=True):
                        pk = np.asarray(pk)
                    self.ledger.count_d2h_shards(
                        [pk.nbytes // sh.shards] * sh.shards,
                        "extract_packed")
                    parts.append(pk[sh.chunk_perm(c)])
                else:
                    with rec.span("extract.quantiles"):
                        sub = tuple(
                            jax.lax.dynamic_slice_in_dim(
                                a, run.start, c, 0)
                            for a in fields)
                        out = self._extract(sub, qs)
                        pk = _pack_extract_columns(*out)
                    self._release_replay(swapped)
                    with rec.span("extract.readback", wait=True):
                        parts.append(
                            self.ledger.d2h(pk, "extract_packed"))
                p = out[0].shape[1]
                run.note(c, time.perf_counter() - t0)
            packed = (parts[0] if len(parts) == 1
                      else np.concatenate(parts, axis=0))
        self._note_fold_widths(extract_span)
        with rec.span("extract.unpack"):
            qv, (dmin, dmax, dsum, dcount, drecip, lmin, lmax, lsum,
                 lweight, lrecip) = columnar.unpack_extract_columns(
                     packed, p)
            snap.quantile_values = qv[:n]
            snap.quantile_qs = np.asarray(quantiles, dtype=np.float64)
            snap.dmin, snap.dmax = dmin[:n], dmax[:n]
            snap.dsum, snap.dcount = dsum[:n], dcount[:n]
            snap.drecip = drecip[:n]
            snap.lmin, snap.lmax = lmin[:n], lmax[:n]
            snap.lsum, snap.lweight = lsum[:n], lweight[:n]
            snap.lrecip = lrecip[:n]
        sk = self.tenant_sketch
        if sk is not None and n:
            # heavy-hitter fold (core/tenancy.TenantSketch): one
            # (tenant row, series key, folded sample count) triple
            # per live histo series per interval, scatter-added into
            # the per-tenant count-min pool on device. Runs here —
            # off the ingest lock, extractions never overlap — so
            # detection costs the ingest path nothing.
            with rec.span("extract.tenant_sketch"):
                hrows = directory.histo.rows
                tenants = [m.tenant or DEFAULT_TENANT for m in hrows]
                skeys = [m.key.key_string() for m in hrows]
                kcounts = np.maximum(
                    np.nan_to_num(snap.dcount[:n]), 0).astype(np.int64)
                sk.fold(tenants, skeys, kcounts,
                        _next_pow2(min(len(skeys), 1 << 15), 256))
        # the [S,C] centroid pools are read back ONLY where forwarding
        # can consume them (a local tier serializes digests upstream;
        # reference flusher.go:338-433). A terminal server — global or
        # standalone, forward_address unset — never touches them, and
        # at 1M series the two arrays are ~1GB of device→host traffic
        # that round-4's on-chip E2E run measured at >90s of the 105s
        # extract phase. Consumers (codec.py, flusher.forward
        # iterator) already handle digest_means is None.
        if self.is_local:
            if sh is not None:
                l2p = sh.perm_l2p(s_eff)[:n]
                with rec.span("extract.digest_readback", wait=True):
                    dm = np.asarray(fields[0])
                    dw = np.asarray(fields[1])
                self.ledger.count_d2h_shards(
                    [(dm.nbytes + dw.nbytes) // sh.shards] * sh.shards,
                    "forward_digests")
                snap.digest_means = dm[l2p]
                snap.digest_weights = dw[l2p]
            else:
                with rec.span("extract.digest_readback", wait=True):
                    snap.digest_means = self.ledger.d2h(
                        fields[0], "forward_digests")[:n]
                    snap.digest_weights = self.ledger.d2h(
                        fields[1], "forward_digests")[:n]
        return fields, s_eff

    def _release_replay(self, swapped: "SwappedEpoch") -> None:
        """Give the spent replay plane back to its context: free() wipes
        it and shelves it for the reader's interval after next (with the
        interpreter given up, so ingest goes on). Called once the
        flush's extract programs are dispatched and before their
        readback blocks, so that the wipe runs under the device's fold
        + extract and not ahead of them; a no-op once done, and for the
        Python path's pair, which the collector takes."""
        plane, swapped.spent_replay = swapped.spent_replay, None
        if plane is not None and plane.free is not None:
            with self.rec.span("extract.replay_release",
                               rows=int(plane.vals.shape[0])):
                _free_staged_planes((plane,))

    def _fields_to_host(self, fields) -> tuple:
        """d2h the 14 fold-state arrays in LOGICAL row order for the
        host engine. On a d2h failure (hard device loss took the fold
        state with it) the failover restarts from an empty host pool —
        logged, honest, degraded data loss rather than a dead flush."""
        sh = self._shard
        try:
            rows = int(fields[0].shape[0])
            perm = sh.perm_l2p(rows) if sh is not None else None
            out = []
            for a in fields:
                h = np.asarray(a)
                out.append(np.array(h[perm] if perm is not None else h,
                                    copy=True))
            return tuple(out)
        except Exception:
            log.exception(
                "device fold state unreadable during failover — "
                "restarting from an empty host pool (data loss)")
            return he.HostHistoState.create(
                int(fields[0].shape[0]), self.capacity).fields()

    def _host_fold_plane(self, fields: tuple, plane: StagedPlane,
                         s_eff: int) -> tuple:
        """Host twin of _fold_one_plane's upload + fold for one
        host-owned plane (flat + counts, or dense)."""
        if plane.counts is not None:
            counts = np.asarray(plane.counts, np.int32)
            if len(counts) < s_eff:
                counts = np.pad(counts, (0, s_eff - len(counts)))
            elif len(counts) > s_eff:
                counts = counts[:s_eff]
            unit = plane.wts is None
            sv, sw = he.np_expand_flat_planes(
                np.asarray(plane.vals, np.float32),
                None if unit else np.asarray(plane.wts, np.float32),
                counts, self.stage_depth, unit)
        else:
            sv = np.asarray(plane.vals[:s_eff], np.float32)
            sw = np.asarray(plane.wts[:s_eff], np.float32)
            if sv.shape[0] < s_eff:
                pad = s_eff - sv.shape[0]
                sv = np.pad(sv, ((0, pad), (0, 0)))
                sw = np.pad(sw, ((0, pad), (0, 0)))
        return he.np_fold_staged(*fields, sv, sw,
                                 compression=self.compression)

    def _host_complete_extract(self, snap, swapped, fields, s_eff, n,
                               spill, spill_off, pending, quantiles, gov):
        """Finish a histo extraction on the host engine: the remaining
        spill chunks, the staged planes, the micro replay batch, then
        the quantile extract — the bitwise twin programs in
        ops/host_engine, applied in the device path's exact order with
        the device path's exact chunk boundaries, which is what makes a
        host-completed flush == an all-device flush bit for bit. Called
        either for an epoch quarantined before swap (fields are the
        HostHistoState's arrays) or mid-extraction after a device fault
        (fields are the d2h'd fold state at the fault point). Returns
        (view_fields, s_eff) for the query-view publish."""
        directory = swapped.directory
        fields = tuple(np.asarray(a) for a in fields)
        if spill is not None and spill_off < len(spill[0]):
            sp_rows, sp_vals, sp_wts = spill
            pool_rows = fields[0].shape[0]
            for i in range(spill_off, len(sp_rows), _FOLD_CHUNK):
                active, lids, v, w = self._pad_spill_batch(
                    sp_rows[i:i + _FOLD_CHUNK],
                    sp_vals[i:i + _FOLD_CHUNK],
                    sp_wts[i:i + _FOLD_CHUNK], pool_rows - 1)
                fields = he.np_ingest_step(
                    *fields, active, lids, v, w,
                    compression=self.compression)
                if gov is not None:
                    gov.beat()
        fields = tuple(a if a.shape[0] == s_eff else a[:s_eff]
                       for a in fields)
        while pending:
            plane = _staged_plane_to_host(pending[0])
            fields = self._host_fold_plane(fields, plane, s_eff)
            pending.pop(0)
            if gov is not None:
                gov.beat()
        # the micro mirror (device state) is unreachable or already
        # dropped; its samples fold from the host replay batch swap()
        # retained — the no-epoch-lost contract for streamed samples
        replay = swapped.micro_replay
        if replay is not None and replay.free is not None:
            # a native plane, held as it was detached: compacted here, in
            # a flush that is degraded already, and released by the same
            # call (the epoch keeps it until then, for its release())
            with self.rec.span("extract.replay_compact") as sp:
                replay = _staged_plane_to_host(replay)
                sp.attrs["samples"] = int(len(replay.vals))
        swapped.micro_replay = None
        swapped.device_stage = None
        swapped.micro_residual = None
        if replay is not None:
            fields = self._host_fold_plane(fields, replay, s_eff)
            if gov is not None:
                gov.beat()
        qnp = np.asarray(quantiles, dtype=np.float32)
        out = he.np_flush_extract(*fields, qnp)
        packed = he.np_pack_extract_columns(*out)
        p = out[0].shape[1]
        qv, (dmin, dmax, dsum, dcount, drecip, lmin, lmax, lsum,
             lweight, lrecip) = columnar.unpack_extract_columns(packed, p)
        snap.quantile_values = qv[:n]
        snap.quantile_qs = np.asarray(quantiles, dtype=np.float64)
        snap.dmin, snap.dmax = dmin[:n], dmax[:n]
        snap.dsum, snap.dcount, snap.drecip = (dsum[:n], dcount[:n],
                                               drecip[:n])
        snap.lmin, snap.lmax = lmin[:n], lmax[:n]
        snap.lsum, snap.lweight, snap.lrecip = (lsum[:n], lweight[:n],
                                                lrecip[:n])
        sk = self.tenant_sketch
        if sk is not None and n:
            # the sketch pool is device state: best-effort under a
            # fault — one interval of heavy-hitter attribution is
            # expendable, the flush is not
            try:
                hrows = directory.histo.rows
                tenants = [m.tenant or DEFAULT_TENANT for m in hrows]
                skeys = [m.key.key_string() for m in hrows]
                kcounts = np.maximum(
                    np.nan_to_num(snap.dcount[:n]), 0).astype(np.int64)
                sk.fold(tenants, skeys, kcounts,
                        _next_pow2(min(len(skeys), 1 << 15), 256))
            except Exception:
                log.exception("tenant sketch fold skipped during host"
                              " failover")
        if self.is_local:
            snap.digest_means = np.array(fields[0][:n])
            snap.digest_weights = np.array(fields[1][:n])
        return fields, s_eff

    def _flush_rows(self, pool_rows: int, n: int) -> int:
        """The row count a flush of `n` used rows folds and extracts at.
        Over the USED rows only: the pool is up to 2x oversized from
        power-of-two growth, and both programs' cost is linear in rows;
        pow2 bucketing bounds compile variants. And at a bucket this
        worker already has programs for, where one holds the rows: a
        flush that needs fewer rows than an earlier one (traffic thinned:
        a deploy, a partition, a sender's stop) would otherwise compile
        fold, extract, pack and slices at its own bucket inside the
        flush, 7-25 s on a v5e against the 0.1-0.4 s the larger bucket
        costs (PERF.md section 6, PR 40). The rows between `n` and the
        bucket are unused pool rows either way."""
        want = min(pool_rows, _next_pow2(n, 1024))
        had = self._flush_rows_had.setdefault(pool_rows, set())
        s_eff = min((b for b in had if b >= want), default=want)
        had.add(s_eff)
        return s_eff

    def extract_snapshot(self, swapped: "SwappedEpoch",
                         quantiles: np.ndarray,
                         interval_s: float = 10.0) -> FlushSnapshot:
        """Device readback for a swapped epoch. Safe to run outside the
        ingest lock — it touches only the swapped objects (plus immutable
        worker config), never the live epoch. However it ends (a clean
        flush, a failover, an exception the server logs and walks past),
        no native staging plane outlives it: per-flush data is
        expendable, a plane of a million rows an interval is not."""
        try:
            return self._extract_swapped(swapped, quantiles, interval_s)
        finally:
            swapped.release()

    def _extract_swapped(self, swapped: "SwappedEpoch",
                         quantiles: np.ndarray,
                         interval_s: float) -> FlushSnapshot:
        # one extraction == one transfer window. The reset lives HERE,
        # not in swap(): every ledger-counted transfer (staged-plane
        # uploads, quantile upload, packed readback) happens inside this
        # method, and extractions never overlap each other, so resetting
        # here keeps the windows tiling exactly.
        self.ledger.begin_flush()
        directory = swapped.directory
        scalars = swapped.scalars
        histo = swapped.histo
        sets = swapped.sets
        staged_sets = swapped.staged_sets

        snap = FlushSnapshot(
            directory=directory, scalars=scalars, interval_s=interval_s,
            unique_timeseries_registers=swapped.umts,
        )

        def _mark_degraded():
            # first host-fallback event of this flush: flag the snapshot
            # (query responses surface it as degraded: true) and book the
            # fallback once in the health ledger
            if not snap.degraded:
                snap.degraded = True
                self.ledger.note_fallback()
                self.host_fallback_flushes += 1
        # pop the deferred spill backlog UNCONDITIONALLY: when the histo
        # block below is skipped (pool absent / zero rows) the batch is
        # unfoldable and must be counted as shed, not silently discarded
        # still attached to the swapped epoch
        spill = swapped.spill_histo
        swapped.spill_histo = None
        # the fold loops below are the flush's other long-running stages:
        # each bounded step publishes a progress beat so the watchdog's
        # deferral rule (health/policy.py) sees a fold-bound flush as
        # live, not stalled — chunked extraction alone would leave a
        # multi-second fold silent for longer than the stall window
        gov = self.governor
        # epoch read view for the live query path: the fully-folded field
        # arrays (and their effective row count) captured after the last
        # fold below — the same arrays the extraction reads, retained
        # because no extract program donates them
        view_fields = None
        view_s_eff = 0
        if histo is not None and directory.num_histo_rows:
            n = directory.num_histo_rows
            s_eff = self._flush_rows(histo.num_rows, n)
            self.rec.add("fold_rows", s_eff)
            self.rec.add("rows_used", n)
            full = (histo.means, histo.weights, histo.dmin,
                    histo.dmax, histo.drecip, histo.drecip_c,
                    histo.lmin, histo.lmax, histo.lsum, histo.lsum_c,
                    histo.lweight, histo.lweight_c, histo.lrecip,
                    histo.lrecip_c)
            merged_plane = None
            rplanes = swapped.reader_planes
            swapped.reader_planes = None
            if rplanes:
                # stacked reader-shard fold: host-merge the per-context
                # planes into ONE canonical flat batch (stable context
                # order per row — the serialized-reader-order ground
                # truth), release the C++ memory, and feed the batch to
                # the same flat-upload fold the legacy plane takes.
                # Rows whose stacked total exceeds the staging depth
                # route the excess through the spill fold below —
                # conservation stays exact.
                flat_v, flat_w, rcounts, rspill, per_ctx = (
                    rstack.merge_reader_planes(rplanes, s_eff))
                for st, _m in rplanes:
                    if st[4] is not None:
                        try:
                            st[4]()
                        except Exception:  # pragma: no cover
                            log.exception("reader plane free failed")
                if any(per_ctx):
                    # per-reader upload attribution (health/ledger.py):
                    # the actual h2d bytes are booked by the fold below;
                    # this records who contributed them
                    self.ledger.count_h2d_readers(
                        [int(k) * 4 for k in per_ctx], "staged_flat")
                if flat_v is not None:
                    merged_plane = StagedPlane(flat_v, flat_w, rcounts,
                                               None)
                if rspill is not None:
                    spill = (rspill if spill is None else tuple(
                        np.concatenate([spill[k], rspill[k]])
                        for k in range(3)))
            pending = list(swapped.staged_histo or ())
            if merged_plane is not None:
                pending.append(merged_plane)
            swapped.staged_histo = None
            # ingest steps of the epoch: the live folds and the deferred
            # spill's, one a _FOLD_CHUNK of it
            self.rec.add("spill_steps", swapped.spill_steps + (
                0 if spill is None else -(-len(spill[0]) // _FOLD_CHUNK)))
            if isinstance(histo, he.HostHistoState):
                # the epoch quarantined before swap: the fold state is
                # already host-resident, so the whole flush runs on the
                # host engine (the bitwise twin programs)
                _mark_degraded()
                view_fields, view_s_eff = self._host_complete_extract(
                    snap, swapped, full, s_eff, n, spill, 0, pending,
                    quantiles, gov)
            else:
                # replayable progress for the device→host failover:
                # "fields" is the newest device fold state (full-pool
                # until the shrink, s_eff after), "spill_off" counts the
                # spill samples already folded into it
                st = {"fields": None, "spill_off": 0}
                try:
                    view_fields, view_s_eff = self._device_extract_histo(
                        snap, swapped, full, s_eff, n, spill, pending,
                        quantiles, gov, st)
                except dg.DeviceFaultError as exc:
                    log.error(
                        "device fault during extraction (%s) — completing"
                        " the flush on the host engine", exc)
                    _mark_degraded()
                    host_fields = self._fields_to_host(
                        st["fields"] if st["fields"] is not None else full)
                    view_fields, view_s_eff = self._host_complete_extract(
                        snap, swapped, host_fields, s_eff, n, spill,
                        st["spill_off"], pending, quantiles, gov)
            # the deepest row of the flush: the largest digest count
            # the extract read back
            self.rec.add("hot_row_samples", int(
                np.fmax.reduce(snap.dcount[:n], initial=0.0)))
        elif spill is not None and len(spill[0]):
            # deferred spill with nowhere to fold (ADVICE item 2): the
            # samples are lost either way, but lost-and-counted — the
            # overload_dropped tallies are how operators see shedding
            n_lost = int(len(spill[0]))
            self.overload_dropped += n_lost
            self.overload_dropped_total += n_lost
            log.warning(
                "extract: dropped %d deferred spill samples — swapped "
                "epoch has no histogram pool to fold them into", n_lost)
        # (histo block skipped, no rows: a mirror with nowhere to fold is
        # just device garbage — drop it, along with any never-fed
        # residual. The planes still on the epoch can hold nothing
        # meaningful either: their C++ memory goes with the release()
        # extract_snapshot ends on)
        swapped.device_stage = None
        swapped.micro_residual = None
        if swapped.mesh_out is not None:
            mout = swapped.mesh_out
            n = directory.num_histo_rows
            snap.quantile_values = mout["quant"]
            snap.quantile_qs = np.asarray(quantiles, dtype=np.float64)
            snap.dmin, snap.dmax = mout["dmin"], mout["dmax"]
            snap.dsum = mout["dsum"]
            snap.dcount = mout["dcount"]
            snap.drecip = mout["drecip"]
            # mesh rows carry no host-local scalar aggregates (global
            # tier emits digest-derived values; see attach_mesh_pool)
            snap.lmin = np.full(n, np.inf, np.float32)
            snap.lmax = np.full(n, -np.inf, np.float32)
            snap.lsum = np.zeros(n, np.float64)
            snap.lweight = np.zeros(n, np.float64)
            snap.lrecip = np.zeros(n, np.float64)
        with self.rec.span("extract.sets") as sets_span:
            if staged_sets is not None and directory.num_set_rows:
                n = directory.num_set_rows
                snap.set_estimates = staged_sets.estimates(n)
                sets_span.attrs.update(
                    sets=n, sparse_entries=staged_sets.sparse_entries)
                # register materialization is [n, 2^p] host bytes — only pay
                # it where forwarding can read it (locals forward mixed sets;
                # a global is a terminal aggregator for them)
                if self.is_local:
                    snap.set_registers = staged_sets.registers(n)
                if staged_sets.host_mode:
                    # the store fell to (or started on) its host registers —
                    # the estimates above came from the np twin
                    _mark_degraded()
            elif sets is not None and directory.num_set_rows:
                n = directory.num_set_rows
                if isinstance(sets, np.ndarray):
                    # quarantined epoch: host registers, np estimate twin
                    # (already in logical row order — _sets_to_host gathers)
                    _mark_degraded()
                    snap.set_estimates = he.np_hll_estimate_exact(
                        sets, self.hll_precision)[:n]
                    snap.set_registers = sets[:n]
                else:
                    try:
                        sh = self._shard
                        est = self.guard.call(
                            "extract",
                            hll_ops.estimate if sh is None
                            else sh.hll_estimate,
                            sets, self.hll_precision, retryable=True)
                        # logical row order: a sharded pool is permuted
                        sel = (slice(n) if sh is None
                               else sh.perm_l2p(sets.shape[0])[:n])
                        with self.rec.span("extract.sets.readback",
                                           wait=True):
                            est, regs = np.asarray(est), np.asarray(sets)
                        snap.set_estimates = est[sel]
                        snap.set_registers = regs[sel]
                    except dg.DeviceFaultError:
                        _mark_degraded()
                        regs = self._sets_to_host(sets)
                        snap.set_estimates = he.np_hll_estimate_exact(
                            regs, self.hll_precision)[:n]
                        snap.set_registers = regs[:n]
        pub = self.query_publisher
        if pub is not None:
            # publish this epoch's read view. A publish failure must not
            # fail the flush — the query surface going stale for one
            # interval is strictly better than losing the interval.
            self.query_epoch_seq += 1
            sk = self.tenant_sketch
            try:
                with self.rec.span("extract.query_publish"):
                    pub(self.query_epoch_seq, snap,
                        self._make_query_eval(view_fields, view_s_eff),
                        sk.snapshot() if sk is not None else None)
            except Exception:
                log.exception("query view publish failed")
        return snap

    def _make_query_eval(self, fields, s_eff: int):
        """Build the epoch's device query evaluator: a closure over the
        retained post-fold field arrays that re-runs the SAME compiled
        extraction programs the flush used (`_extract` unsharded,
        `SeriesSharding.flush_extract` sharded) at an arbitrary quantile
        vector. Identical executable + identical input arrays is what
        makes a query at the flush qs bitwise equal to the flush readback
        (the parity CI lane in tools/ci.sh). Retaining `fields` is safe:
        no extract program donates them (the donating fold programs ran
        earlier, producing these arrays). Transfers here deliberately
        bypass the flush TransferLedger — a query must not perturb the
        O(samples) transfer-window accounting the flush telemetry pins.

        Returns None when the epoch had no histogram rows.

        Host-fallback epochs (quarantined at swap, or failed over
        mid-extraction) retain HOST field arrays; their evaluator runs
        the np twin programs — same bits, no device dependency, so the
        query surface stays live through a quarantine."""
        if fields is None:
            return None
        sh = self._shard

        if isinstance(fields[0], np.ndarray):
            def evaluate_host(qs_np: np.ndarray) -> tuple[np.ndarray, int]:
                qnp = np.asarray(qs_np, dtype=np.float32)
                out = he.np_flush_extract(*fields, qnp)
                return he.np_pack_extract_columns(*out), out[0].shape[1]

            return evaluate_host

        def evaluate(qs_np: np.ndarray) -> tuple[np.ndarray, int]:
            """f32[P] quantiles → (packed [s_eff, P+10] host array in
            LOGICAL row order, P). Column layout: see
            columnar.unpack_extract_columns. A device fault falls back
            to the np twins over a one-shot d2h of the fields — a query
            must survive the breaker tripping between publish and
            read."""
            qnp = np.asarray(qs_np, dtype=np.float32)
            try:
                if sh is not None:
                    qs = sh.replicate(qnp)
                    out = self.guard.call("query", sh.flush_extract,
                                          *fields, qs, retryable=True)
                    packed = np.asarray(_pack_extract_columns(*out))
                    packed = packed[sh.perm_l2p(s_eff)]
                else:
                    qs = jnp.asarray(qnp)
                    # _extract is already guard-wrapped (op "extract")
                    out = self._extract(fields, qs)
                    packed = np.asarray(_pack_extract_columns(*out))
                return packed, out[0].shape[1]
            except dg.DeviceFaultError:
                host = self._fields_to_host(fields)
                out = he.np_flush_extract(*host, qnp)
                return he.np_pack_extract_columns(*out), out[0].shape[1]

        return evaluate

    def flush(self, quantiles: np.ndarray, interval_s: float = 10.0
              ) -> FlushSnapshot:
        """Swap state and extract the finished interval in one call.

        Callers that want ingest to continue during extraction (the server
        flush loop) use swap() under the ingest lock and extract_snapshot()
        outside it; this composition is for tests/tools and the import
        paths where overlap doesn't matter.
        """
        return self.extract_snapshot(self.swap(quantiles), quantiles,
                                     interval_s)
