"""Batched, array-native t-digest for TPU.

Semantics spec: the reference's merging t-digest
(tdigest/merging_digest.go:115-389 — Add/mergeAllTemps/mergeOne/Quantile/CDF/
Merge), re-derived for SIMD execution instead of translated:

* The reference maintains one Go slice of centroids per series and merges a
  temp buffer with an inherently sequential in-place walk (mergeAllTemps,
  :140-224), deciding greedily whether each element opens a new centroid
  (mergeOne :229-254, arcsine index estimate :259-262).

* Here a *pool* of digests is a pair of dense arrays `means/weights: f32[S,C]`
  (rows sorted by mean, empty slots mean=+inf/weight=0) plus per-row scalars
  min/max/reciprocal-sum. Compression is one data-parallel program over all
  rows at once:

      sort by mean  →  per-row cumulative weight  →  arcsine k-function
      bucket quantization  →  flat segment-sum into [S*C] slots  →  re-sort

  Elements whose left cumulative quantile falls in the same integer bucket of
  k(q) = δ·(asin(2q−1)/π + ½) merge into one centroid (exact weighted mean —
  the order-independent closed form of the reference's Welford update,
  :245-246). Since k ranges over [0, δ], a row holds ≤ δ+1 centroids; with the
  default δ=100 that fits C=128, one TPU lane tile. The reference's own merge
  order is randomized (Merge :374-389 shuffles), so bit-equality is not a
  goal; the tests hold the same quantile-error budget the reference's
  statistical tests use.

Raw-sample ingest (`add_batch`) consumes an unordered batch of (row, value,
weight) triples: the batch is first collapsed into per-row "batch digests"
with the same bucketing math (a segmented sort + one segment-sum), then
concatenated with the existing rows and re-compressed — the batched analog of
the reference's temp-buffer merge. Cross-digest merge for the global tier
(`merge`) concatenates centroid rows and re-compresses, replacing the
reference's shuffled re-Add loop with one deterministic program.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.ops import exactnum as exn
from veneur_tpu.ops import segments


def _prefix_scans_xla(srows, svals, sw, n):
    """The XLA scan stack: three prefix sums + forward/backward
    segmented sums (see add_batch for what each feeds).

    All float scans run order-pinned (ops/exactnum.py Hillis-Steele,
    product rounded via exn.block before the adds) so the host fallback
    engine's NumPy twin reproduces them bitwise.

    Takes the batch sorted by (row, value): rows, values, weights.
    Returns the running sums of weight, value*weight and weight/value
    over the whole batch (each [N+1], leading zero), and each sample's
    running weight within its own row, counted from the row's first
    sample (seg_cum) and from its last (suffix). The staged fold
    (core/worker._histo_fold_staged) does not run these scans: add_batch
    serves the hot-row spill and the import merge."""
    with jax.named_scope("tdigest.prefix_scans"):
        zero1 = jnp.zeros((1,), sw.dtype)
        pre_w = jnp.concatenate([zero1, exn.cumsum(sw)])  # [N+1]
        pre_vw = jnp.concatenate([zero1, exn.cumsum(exn.block(svals * sw))])
        pre_recip = jnp.concatenate(
            [zero1, exn.cumsum(jnp.where(sw > 0, sw / svals, 0.0))])
        row_starts = jnp.concatenate(
            [jnp.ones((1,), bool), srows[1:] != srows[:-1]])
        seg_cum = segments.segmented_cumsum(sw, row_starts)
        row_ends = jnp.concatenate([row_starts[1:], jnp.ones((1,), bool)])
        suffix = segments.segmented_cumsum(sw[::-1], row_ends[::-1])[::-1]
    return pre_w, pre_vw, pre_recip, seg_cum, suffix


DEFAULT_COMPRESSION = 100.0
# Capacity per row: δ+1 buckets can be produced by the k-function; round up
# to the TPU lane width. δ up to 127 fits C=128.
DEFAULT_CAPACITY = 128

_INF = jnp.inf


class TDigestPool(NamedTuple):
    """A pool of S t-digests as dense device arrays.

    means:   f32[S, C], rows sorted ascending, empty slots +inf
    weights: f32[S, C], empty slots 0
    min:     f32[S], +inf when empty   (reference MergingDigest.min)
    max:     f32[S], -inf when empty   (reference MergingDigest.max)
    recip:   f32[S], reciprocal sum    (reference MergingDigest.reciprocalSum)
    """

    means: jax.Array
    weights: jax.Array
    min: jax.Array
    max: jax.Array
    recip: jax.Array

    @property
    def num_rows(self) -> int:
        return self.means.shape[0]

    @property
    def capacity(self) -> int:
        return self.means.shape[1]


def capacity_for(compression: float) -> int:
    """Smallest multiple of 128 that can hold δ+1 bucket centroids."""
    need = int(math.floor(compression)) + 2
    return max(128, ((need + 127) // 128) * 128)


def init_pool(num_rows: int, capacity: int = DEFAULT_CAPACITY) -> TDigestPool:
    return TDigestPool(
        means=jnp.full((num_rows, capacity), _INF, dtype=jnp.float32),
        weights=jnp.zeros((num_rows, capacity), dtype=jnp.float32),
        min=jnp.full((num_rows,), _INF, dtype=jnp.float32),
        max=jnp.full((num_rows,), -_INF, dtype=jnp.float32),
        recip=jnp.zeros((num_rows,), dtype=jnp.float32),
    )


def _k_bucket(q: jax.Array, compression: float, capacity: int) -> jax.Array:
    """floor of the t-digest k1 scale function δ·(asin(2q−1)/π + ½)
    (reference tdigest/merging_digest.go:259-262), clipped to the row
    capacity. Table form (exactnum.kscale_bucket): the arcsin is
    inverted once on the host into the δ bucket-boundary quantiles and
    the device counts the boundaries ≤ q, ⌊δ⌋ elementwise compares —
    bitwise what the host engine's NumPy twin finds by searchsorted,
    with no transcendental and no gather on any element."""
    with jax.named_scope("tdigest.k_bucket"):
        return jnp.clip(exn.kscale_bucket(q, compression), 0, capacity - 1)


def _compress_rows(
    means: jax.Array, weights: jax.Array, compression: float, capacity: int
) -> tuple[jax.Array, jax.Array]:
    """Compress candidate centroid rows [S, M] → [S, capacity].

    Empty candidate slots must have weight 0 (mean value is then ignored).
    Output rows are sorted by mean with +inf padding.

    Every row pays for all M slots, live or not: two sorts and three
    log2(M)-step scans over [S, M]. A row that holds a few raw samples
    and nothing else has the same result at `NARROW_WIDTH`
    (`_compress_narrow`, which the staged fold takes for such rows); the
    import and merge paths stay here, their rows are dense.
    """
    s, m = means.shape
    # 1. Sort each row by mean, carrying weights. Zero-weight slots are
    #    keyed to +inf so they sort to the end.
    with jax.named_scope("tdigest.compress.sort"):
        sort_keys = jnp.where(weights > 0, means, _INF)
        sorted_means, sorted_w = jax.lax.sort(
            (sort_keys, weights), dimension=-1, num_keys=1
    )
    # Stage barriers: each stage's outputs feed several consumers below;
    # without them XLA's fusion duplicates whole producer chains into
    # every consumer (measured 1.8x end-to-end at S=262k on CPU, and the
    # same recompute heuristic exists on TPU).
    sorted_means, sorted_w = jax.lax.optimization_barrier(
        (sorted_means, sorted_w))
    # 2. Per-row cumulative weight and left-edge quantile. (Order-pinned
    #    Hillis scan — the host engine twin mirrors it bitwise.)
    with jax.named_scope("tdigest.compress.scan"):
        w_cum = exn.cumsum(sorted_w)
        total = w_cum[:, -1:]
        q_left = (w_cum - sorted_w) / jnp.maximum(total, 1e-30)
        # 3. Quantize to k-function buckets. (Zero-weight padding slots land in
        #    whatever bucket q=1 maps to; they only ever extend a run with zero
        #    weight, so the sums below are unaffected.)
        bucket = _k_bucket(q_left, compression, capacity)
    w_cum, bucket = jax.lax.optimization_barrier((w_cum, bucket))
    # 4. Bucket accumulation, scatter- AND broadcast-free: buckets are
    #    non-decreasing along a sorted row, so each bucket is one
    #    contiguous run; its sum is a difference of row-prefix sums at the
    #    run ends. Run placement is irrelevant — step 5 re-sorts by mean —
    #    so results stay where the run ends and a sort compacts them.
    #    (The previous [S, M, C] compare+select+reduce formulation was
    #    fused but compute-bound: ~34G lane-ops at S=1M; this is O(S·M).)
    with jax.named_scope("tdigest.compress.merge"):
        mw_cum = exn.cumsum(
            jnp.where(sorted_w > 0, sorted_means * sorted_w, 0.0))
        nxt = jnp.concatenate(
            [bucket[:, 1:], jnp.full((s, 1), -1, jnp.int32)], axis=-1)
        is_end = bucket != nxt  # last slot of each bucket run (row end included)
        w_before, mw_before = segments.last_marked_carry(is_end, w_cum, mw_cum)
        seg_w = w_cum - w_before
        seg_mw = mw_cum - mw_before
        live = is_end & (seg_w > 0)
        new_means = jnp.where(live, seg_mw / jnp.maximum(seg_w, 1e-30), _INF)
        new_w = jnp.where(live, seg_w, 0.0)
    new_means, new_w = jax.lax.optimization_barrier((new_means, new_w))
    # 5. Sort by mean (empties keyed +inf sort last) and keep the first
    #    `capacity` slots — the k-function emits ≤ δ+1 ≤ capacity buckets,
    #    so the slice only ever drops padding.
    with jax.named_scope("tdigest.compress.resort"):
        new_means, new_w = jax.lax.sort((new_means, new_w), dimension=-1,
                                        num_keys=1)
    return new_means[:, :capacity], new_w[:, :capacity]


@functools.partial(jax.jit, static_argnames=("compression", "capacity"))
def compress_rows(
    means: jax.Array,
    weights: jax.Array,
    compression: float = DEFAULT_COMPRESSION,
    capacity: int = DEFAULT_CAPACITY,
) -> tuple[jax.Array, jax.Array]:
    return _compress_rows(means, weights, compression, capacity)


#: The width a row of few raw samples is compressed at (`_compress_narrow`):
#: a power of two, so that a prefix sum over the first NARROW_WIDTH slots
#: of a wider row is the same tree of adds.
NARROW_WIDTH = 16


def _compress_narrow(
    vals: jax.Array, wts: jax.Array, compression: float, capacity: int
) -> tuple[jax.Array, jax.Array]:
    """`_compress_rows` for rows whose only live slots are raw samples in
    [S, W], W = NARROW_WIDTH: [S, W] → [S, capacity], bit for bit what
    `_compress_rows` gives for the same rows at any width M that W
    divides, empty slots (weight 0) filling the rest.

    Why the same: a stable sort puts the live slots first in both, in the
    same order. exn.cumsum is a doubling scan that shifts zeros in, so
    its prefix at slot i < W is the same tree of adds whatever the row's
    width, and the row's total, slot M-1 of the wide scan, is slot W-1
    here where W divides M (its window splits into aligned blocks of
    zeros and this one). So q_left, the bucket, the run ends and the run
    sums of the live slots are the same, and the slots past them hold
    weight 0 in both. Two things differ. The wide scan goes on adding
    the zeros shifted in, which turns a prefix of -0.0 into +0.0: a
    sample of -0.0 enters here as +0.0, which it equals in the sort and
    in every sum it meets a non-zero in. And a wide row's padding can
    round: its prefix sums are other trees over the same addends, and
    where they fall a bit short of the total the padding splits into
    runs of its own. The caller therefore sends here only rows whose
    weights are whole numbers small enough that every partial sum is
    exact (core/worker._staged_rows_wide).

    On the chip the compiler lays an [S, 16] array out with S on the
    lanes: 3.1 ms for 262,144 rows, pad included, where [S, 192] takes
    62 (tools/fold_width_bench.py; PERF §6, PR 38)."""
    w = vals.shape[1]
    means, weights = _compress_rows(
        jnp.where(vals == 0, jnp.zeros_like(vals), vals), wts,
        compression, capacity)
    with jax.named_scope("tdigest.narrow.pad"):
        pad = ((0, 0), (0, capacity - w))
        return (jnp.pad(means, pad, constant_values=_INF),
                jnp.pad(weights, pad))


class BatchStats(NamedTuple):
    """Per-row statistics of one raw-sample batch; feeds both the digest
    scalars and the sampler's host-local aggregates (the reference keeps
    LocalWeight/Min/Max/Sum/ReciprocalSum outside the digest,
    samplers/samplers.go:467-494)."""

    weight: jax.Array  # [K] Σ sample weights
    min: jax.Array  # [K]
    max: jax.Array  # [K]
    sum: jax.Array  # [K] Σ value·weight
    recip: jax.Array  # [K] Σ weight/value


@functools.partial(jax.jit, static_argnames=("compression",))
def add_batch(
    means: jax.Array,
    weights: jax.Array,
    dmin: jax.Array,
    dmax: jax.Array,
    drecip: jax.Array,
    rows: jax.Array,
    values: jax.Array,
    sample_weights: jax.Array,
    compression: float = DEFAULT_COMPRESSION,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, BatchStats]:
    """Ingest a batch of raw samples into digest rows.

    means/weights: f32[K, C] digest rows (typically a gathered active set)
    dmin/dmax/drecip: f32[K] digest scalars for those rows
    rows: i32[N] row index per sample in [0, K); padding samples must carry
          sample_weights == 0 (their row/value are ignored).
    values, sample_weights: f32[N]

    Returns updated (means, weights, dmin, dmax, drecip, BatchStats).

    The batched analog of reference Add (tdigest/merging_digest.go:115-137) +
    mergeAllTemps (:140-224): the batch is collapsed to per-row bucket
    centroids, then merged with the existing rows in one compression pass.
    """
    k, c = means.shape
    n = rows.shape[0]
    live = sample_weights > 0
    # Padding lanes get row index k so they sort into a tail run past every
    # real row: within a real row, every sorted sample is then live, which
    # makes per-row min/max plain boundary gathers (no segment reductions —
    # those are the most expensive primitive in this whole function on TPU).
    rows = jnp.where(live, rows, k)
    safe_vals = jnp.where(live, values, 1.0)

    # --- 1. Sort the batch by (row, value). Padding is the tail run.
    with jax.named_scope("tdigest.add_batch.sort"):
        srows, svals, sw = jax.lax.sort(
            (rows, safe_vals, sample_weights), dimension=0, num_keys=2
        )

    # --- 2. Per-row stats, scatter-free (TPU-first): rows are contiguous
    #        runs in the sorted order, so every per-row reduction is either
    #        a prefix-sum difference at run boundaries or — because values
    #        sort ascending within a row — a boundary gather (min = first
    #        live element, max = last).
    pre_w, pre_vw, pre_recip, seg_cum, suffix = _prefix_scans_xla(
        srows, svals, sw, n)

    with jax.named_scope("tdigest.add_batch.row_stats"):
        kbins = jnp.arange(k, dtype=jnp.int32)
        row_upper = jnp.searchsorted(srows, kbins, side="right").astype(jnp.int32)
        row_lower = jnp.concatenate([jnp.zeros((1,), jnp.int32), row_upper[:-1]])

        seg_w = (jnp.take(pre_w, row_upper) - jnp.take(pre_w, row_lower))
        seg_sum = (jnp.take(pre_vw, row_upper) - jnp.take(pre_vw, row_lower))
        seg_recip = (jnp.take(pre_recip, row_upper)
                     - jnp.take(pre_recip, row_lower))
        # min/max: every sample inside a real row's run is live (padding was
        # keyed past row k-1) and values sort ascending within the row, so the
        # row min/max are the run's first/last elements — two boundary gathers.
        has = seg_w > 0
        seg_min = jnp.where(has, jnp.take(svals, row_lower), _INF)
        seg_max = jnp.where(
            has, jnp.take(svals, jnp.maximum(row_upper - 1, 0)), -_INF)
        stats = BatchStats(seg_w, seg_min, seg_max, seg_sum, seg_recip)

    # --- 3. Batch digest: segmented cumulative weight → k-bucket per
    #        sample → per-(row, bucket) run sums. Scatter-free and
    #        gather-light: each (row, bucket) is one contiguous run of the
    #        sorted batch, so its sum is a difference of the global prefix
    #        sums at the run's end positions; run-start positions compact
    #        into a dense per-run table with one single-key sort. (The
    #        previous run-sum scheme resolved runs with a searchsorted over
    #        chunk offsets — a [K·C]-sized gather-chain binary search that
    #        alone cost ~80% of add_batch on v5e.)
    with jax.named_scope("tdigest.add_batch.batch_digest"):
        row_total = seg_cum + suffix - sw  # per-sample total weight of its row
        q_left = (seg_cum - sw) / jnp.maximum(row_total, 1e-30)
        bucket = _k_bucket(q_left, compression, c)
        # Non-decreasing run id; padding (row k) forms its own tail runs that
        # no real row's run window reaches.
        seg_id = srows * c + bucket
        starts = jnp.concatenate(
            [jnp.ones((1,), bool), seg_id[1:] != seg_id[:-1]])
        grank = jnp.cumsum(starts.astype(jnp.int32)) - 1  # global run index [N]
        # Dense run-start position table: ascending sort compacts the R true
        # start positions to the front, sentinel n after — so pos_ext[r] is
        # run r's first element and pos_ext[r+1] its end (the next run's
        # start, or n for the last run).
        pos = jnp.where(starts, jnp.arange(n, dtype=jnp.int32), n)
        pos_ext = jnp.concatenate(
            [jax.lax.sort(pos), jnp.full((1,), n, jnp.int32)])
        run_lo = jnp.take(grank, jnp.clip(row_lower, 0, n - 1))  # [K]
        run_hi = jnp.take(grank, jnp.maximum(row_upper - 1, 0)) + 1
        n_runs_row = jnp.where(has, run_hi - run_lo, 0)  # [K]
        j = jnp.arange(c, dtype=jnp.int32)
        runs = jnp.clip(run_lo[:, None] + j[None, :], 0, n - 1)  # [K, C]
        valid = j[None, :] < n_runs_row[:, None]
        # every [K, C]-shaped gather below is ~2M probes at fixed per-element
        # cost — the dominant fixed cost of this function on TPU — so: fetch
        # run starts once; run ends are the NEXT run's start (shift within
        # the row window), and the last run of a row ends where the row does
        # (row_upper — already known, no gather)
        r_start = jnp.take(pos_ext, runs)
        last = j[None, :] == (n_runs_row - 1)[:, None]
        # prefix sums fetched as 2-lane pairs: one gather of [K, C, 2]
        # instead of two of [K, C] per endpoint
        pre = jnp.stack([pre_w, pre_vw], axis=-1)  # [N+1, 2]
        at_start = jnp.take(pre, r_start, axis=0)  # [K, C, 2]
        # run ends need no second [K, C, 2] gather: a run ends where the NEXT
        # run starts, so at_end is at_start shifted one lane left — except a
        # row's last run, which ends at the row end (pre[row_upper], a plain
        # [K, 2] gather). Halves the dominant gather volume of this step.
        at_row_end = jnp.take(pre, row_upper, axis=0)  # [K, 2]
        at_next = jnp.concatenate(
            [at_start[:, 1:, :], jnp.zeros((k, 1, 2), at_start.dtype)], axis=1)
        at_end = jnp.where(last[:, :, None], at_row_end[:, None, :], at_next)
        diff = at_end - at_start
        bd_w = jnp.where(valid, diff[..., 0], 0.0)
        bd_mw = jnp.where(valid, diff[..., 1], 0.0)
        bd_means = jnp.where(bd_w > 0, bd_mw / jnp.maximum(bd_w, 1e-30), _INF)

    # --- 4. Merge with the existing rows and recompress.
    cat_means = jnp.concatenate([means, bd_means], axis=-1)
    cat_w = jnp.concatenate([weights, bd_w], axis=-1)
    new_means, new_w = _compress_rows(cat_means, cat_w, compression, c)

    # --- 5. Digest scalars (reference Add :124-126 updates min/max/recip).
    new_min = jnp.minimum(dmin, seg_min)
    new_max = jnp.maximum(dmax, seg_max)
    new_recip = drecip + seg_recip
    return new_means, new_w, new_min, new_max, new_recip, stats


@functools.partial(jax.jit, static_argnames=("compression",))
def merge_pools(a: TDigestPool, b: TDigestPool, compression: float
                = DEFAULT_COMPRESSION) -> TDigestPool:
    """Row-wise merge of two digest pools (the global-aggregation reduce).

    Replaces the reference's per-series shuffled re-Add loop
    (tdigest/merging_digest.go:374-389) with one concat + compress pass.
    """
    c = a.means.shape[1]
    means = jnp.concatenate([a.means, b.means], axis=-1)
    weights = jnp.concatenate([a.weights, b.weights], axis=-1)
    means, weights = _compress_rows(means, weights, compression, c)
    return TDigestPool(
        means=means,
        weights=weights,
        min=jnp.minimum(a.min, b.min),
        max=jnp.maximum(a.max, b.max),
        recip=a.recip + b.recip,
    )


@functools.partial(jax.jit, static_argnames=("compression",))
def merge_many(stacked: TDigestPool, compression: float = DEFAULT_COMPRESSION
               ) -> TDigestPool:
    """Merge H digests per series: fields shaped [H, S, ...] → [S, ...].

    The 8-local→1-global cross-host merge runs through here: all hosts'
    centroid rows concatenate along the capacity axis and compress once.
    """
    h, s, c = stacked.means.shape
    means = jnp.transpose(stacked.means, (1, 0, 2)).reshape(s, h * c)
    weights = jnp.transpose(stacked.weights, (1, 0, 2)).reshape(s, h * c)
    means, weights = _compress_rows(means, weights, compression, c)
    return TDigestPool(
        means=means,
        weights=weights,
        min=jnp.min(stacked.min, axis=0),
        max=jnp.max(stacked.max, axis=0),
        recip=exn.tsum0(stacked.recip),
    )


def _row_bounds(means: jax.Array, weights: jax.Array, dmax: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
    """Per-slot lower/upper value bounds under the uniform-centroid
    assumption (reference centroidUpperBound :364-370)."""
    s, c = means.shape
    nonempty = weights > 0
    count = jnp.sum(nonempty, axis=-1)  # [S] number of centroids
    idx = jnp.arange(c)
    next_means = jnp.concatenate(
        [means[:, 1:], jnp.full((s, 1), _INF, means.dtype)], axis=-1
    )
    mid = (means + next_means) / 2.0
    is_last = idx[None, :] == (count - 1)[:, None]
    ub = jnp.where(is_last, dmax[:, None], mid)
    return ub, count


@functools.partial(jax.jit, static_argnames=("use_gather",))
def _quantile_impl(
    means: jax.Array,
    weights: jax.Array,
    dmin: jax.Array,
    dmax: jax.Array,
    qs: jax.Array,
    use_gather: bool,
) -> jax.Array:
    with jax.named_scope("tdigest.quantile"):
        s, c = means.shape
        ub, count = _row_bounds(means, weights, dmax)  # [S, C], [S]
        w_cum = exn.cumsum(weights)  # [S, C]
        total = w_cum[:, -1]  # [S]
        lb = jnp.concatenate([dmin[:, None], ub[:, :-1]], axis=-1)  # [S, C]

        target = exn.block(qs[None, :] * total[:, None])  # [S, P]
        # first slot whose cumulative weight reaches the target
        # (reference: q <= weightSoFar + c.Weight), then interpolate inside
        # it. Two equivalent formulations (bit-identical — pinned by
        # test_quantile_gather_and_mask_forms_agree):
        if use_gather:
            # hosts (CPU fallback): per-row binary search + gather is 4.4x
            # the masked-reduce form at 64k series — no [S, C, P]
            # materialization, O(P log C) per row instead of O(C·P)
            first_idx = jax.vmap(
                lambda cw, t: jnp.searchsorted(cw, t, side="left"))(
                    w_cum, target)  # [S, P]
            first_idx = jnp.minimum(first_idx, c - 1)

            def _at(x):  # [S, C] → [S, P] value at the found slot
                return jnp.take_along_axis(x, first_idx, axis=1)
        else:
            # one-hot + masked reduces over [S, C, P]: at S=1M the
            # [S, P]-shaped take_along_axis gathers are the slow path on
            # TPU, while select+reduce streams through the VPU
            reached = target[:, None, :] <= w_cum[:, :, None]  # [S, C, P]
            first = reached & ~jnp.pad(
                reached[:, :-1, :], ((0, 0), (1, 0), (0, 0)))  # one-hot

            def _at(x):  # [S, C] → [S, P] value at the one-hot slot
                return jnp.sum(jnp.where(first, x[:, :, None], 0.0), axis=1)

        w_at = _at(weights)
        w_before = _at(w_cum) - w_at
        lb_at = _at(lb)
        ub_at = _at(ub)
        proportion = (target - w_before) / jnp.maximum(w_at, 1e-30)
        out = lb_at + exn.block(proportion * (ub_at - lb_at))
        return jnp.where((total[:, None] > 0) & (count[:, None] > 0), out, jnp.nan)


def quantile(
    means: jax.Array,
    weights: jax.Array,
    dmin: jax.Array,
    dmax: jax.Array,
    qs: jax.Array,
) -> jax.Array:
    """Batched quantile extraction: [S, C] digests × [P] quantiles → [S, P].

    Linear interpolation over centroid bounds, matching reference Quantile
    (tdigest/merging_digest.go:302-332). Empty digests yield NaN. The
    slot-selection strategy is backend-dependent (gather on hosts,
    select+reduce on TPU) with bit-identical results.
    """
    from veneur_tpu.utils.backend import is_tpu_backend

    return _quantile_impl(means, weights, dmin, dmax, qs,
                          use_gather=not is_tpu_backend())


@jax.jit
def cdf(
    means: jax.Array,
    weights: jax.Array,
    dmin: jax.Array,
    dmax: jax.Array,
    values: jax.Array,
) -> jax.Array:
    """Batched CDF: [S, C] digests × [S] values → [S] fractions below.

    Reference CDF (tdigest/merging_digest.go:266-298).
    """
    s, c = means.shape
    ub, count = _row_bounds(means, weights, dmax)
    w_cum = jnp.cumsum(weights, axis=-1)
    total = w_cum[:, -1]
    lb = jnp.concatenate([dmin[:, None], ub[:, :-1]], axis=-1)

    v = values[:, None]  # [S, 1]
    # weight fully below the value per slot, plus partial weight of the slot
    # the value falls in (uniform within centroid bounds)
    inside = (v >= lb) & (v < ub)
    frac = jnp.where(
        inside,
        weights * (v - lb) / jnp.maximum(ub - lb, 1e-30),
        jnp.where(v >= ub, weights, 0.0),
    )
    result = jnp.sum(frac, axis=-1) / jnp.maximum(total, 1e-30)
    result = jnp.where(values <= dmin, 0.0, result)
    result = jnp.where(values >= dmax, 1.0, result)
    return jnp.where((total > 0) & (count > 0), result, jnp.nan)


@jax.jit
def row_sum(means: jax.Array, weights: jax.Array) -> jax.Array:
    """Σ mean·weight per row (reference Sum :346-353)."""
    return exn.tsum(jnp.where(weights > 0, means * weights, 0.0))


@jax.jit
def row_count(weights: jax.Array) -> jax.Array:
    """Total weight per row (reference Count :340-342)."""
    return exn.tsum(weights)


# ---------------------------------------------------------------------------
# Host-side convenience (numpy) for codecs and tests


def pool_to_numpy(pool: TDigestPool) -> dict[str, np.ndarray]:
    return {
        "means": np.asarray(pool.means),
        "weights": np.asarray(pool.weights),
        "min": np.asarray(pool.min),
        "max": np.asarray(pool.max),
        "recip": np.asarray(pool.recip),
    }
