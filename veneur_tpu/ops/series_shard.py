"""Series-axis device sharding: shard_map-partitioned sketch pools.

Partitions the series axis S of the local aggregation state across a
1-D device mesh (`series_shards` in config), so the t-digest pools, HLL
register planes, and scalar segment ops all run shard-local — upload,
micro-fold, and fold touch no cross-device links until the one packed
readback at extract. ROADMAP direction 2: one chip holds ~1470x compute
headroom at 1M series (PERF_MODEL.md); an 8-way shard of the same
kernels is the 10M+-series-per-host unlock.

Layout: logical row r lives on shard ``d = r % D`` at local index
``l = r // D`` — round-robin, so append-ordered row adoption spreads
live rows evenly across shards (block-sharding would pile every live
row on shard 0 until the pool fills). The device arrays are plain
block-sharded over PHYSICAL rows ``p = (r % D) * cap + r // D`` with
``cap = pool_rows // D``; the interleave lives purely in host-side
index translation (`phys_rows`, `perm_l2p`, `perm_p2l`) — on device a
NamedSharding over the leading axis is all XLA ever sees. This is the
same row-interleave the global tier's MeshHistoPool established
(distributed/mesh.py), kept bit-compatible here.

Closure property (what makes growth, slicing, and chunking shard-local):
``a.reshape(D, cap, ...)[:, :ecap]`` keeps exactly logical rows
[0, s_eff) in s_eff-interleaved layout, because r % D and r // D are
both preserved when cap shrinks to ecap >= ceil(s_eff/D). Hence
slice/grow/chunk are all per-shard prefix ops with no resharding.

Bit-identity (sharded == unsharded, pinned per metric class by
tests/test_series_shard.py) holds because every kernel is either
per-row independent (fold_staged, flush_extract, import, HLL scatter-
max, segment ops) or — for the one batch-global kernel, the spill
ingest — the batch is kept BIT-IDENTICAL on every shard:
`_histo_ingest_step`'s per-row stats are differences of global f32
prefix sums over the whole sorted batch (ops/tdigest.add_batch), so a
shard may not drop or reweight foreign samples. Instead each shard
remaps only the `active` row-id vector: foreign entries map to the
out-of-range local index `cap` — gathers clamp (the fetched row is
ignored), scatters drop — so every shard folds the identical batch and
discards the writes it does not own. shard_map runs with the
replication checker off (check_vma=False): the scan inside add_batch
trips it, harmlessly.

Scope: this module owns the mesh, the shardings, the host-side
permutation caches, and the jitted/shard_mapped device programs. The
worker keeps all policy (when to grow, chunk, spill); microfold takes a
SeriesSharding handle for its scatter/grow/dense programs. Composes
under the global tier: the (hosts, series) mesh of distributed/mesh.py
is the cross-host reduce; this is the within-host series split.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from veneur_tpu.distributed.mesh import make_series_mesh, shard_map
from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.ops import tdigest as td

log = logging.getLogger("veneur_tpu.ops.series_shard")

# escape hatch mirroring VENEUR_MICRO_FOLD / VENEUR_EMIT_NATIVE: 0
# forces the legacy single-device path regardless of config
_ENV_KEY = "VENEUR_SERIES_SHARDS"


def resolve_series_shards(cfg_value: int) -> int:
    """Config value with the env escape hatch applied (the CI lane runs
    the suite once per side: sharded default and VENEUR_SERIES_SHARDS=0)."""
    env = os.environ.get(_ENV_KEY)
    if env is not None:
        try:
            return int(env)
        except ValueError:
            log.warning("ignoring non-integer %s=%r", _ENV_KEY, env)
    return int(cfg_value)


def shards_usable(shards: int) -> bool:
    """Whether a series_shards request can actually be honored here:
    needs >1 shards, a power of two (pow2 pool sizes must divide), and
    that many addressable devices."""
    if shards <= 1:
        return False
    if shards & (shards - 1):
        return False
    try:
        return len(jax.devices()) >= shards
    except RuntimeError:  # pragma: no cover - no backend at all
        return False


class SeriesSharding:
    """The device programs + index math for one worker's series shards.

    One instance per DeviceWorker (jit caches are per-shape and the
    mesh is tiny; sharing across workers would only share compile
    cache, which XLA already does at the executable level).
    """

    def __init__(self, shards: int,
                 compression: float = td.DEFAULT_COMPRESSION) -> None:
        if shards & (shards - 1) or shards < 2:
            raise ValueError(f"series_shards must be a pow2 >= 2: {shards}")
        self.shards = int(shards)
        self.compression = float(compression)
        self.mesh = make_series_mesh(self.shards)
        self.sh1 = NamedSharding(self.mesh, P("series"))
        self.sh2 = NamedSharding(self.mesh, P("series", None))
        self.rep = NamedSharding(self.mesh, P())
        # host-side permutation caches, keyed by row count
        self._l2p: dict[int, np.ndarray] = {}
        self._p2l: dict[int, np.ndarray] = {}
        # per-static-closure program caches (jit handles shape retraces;
        # these key the *closure* constants baked into each shard_map)
        self._expand_cache: dict = {}
        self._slice_cache: dict = {}
        self._chunk_cache: dict = {}
        self._grow2_cache: dict = {}
        self._grow1_cache: dict = {}
        self._mirror_cache: dict = {}
        self._est_cache: dict = {}

    # -- host-side index math ---------------------------------------------

    def perm_l2p(self, rows: int) -> np.ndarray:
        """perm_l2p(n)[r] = physical slot of logical row r. Gathering a
        PHYS-order readback with it yields logical order."""
        p = self._l2p.get(rows)
        if p is None:
            d = self.shards
            cap = rows // d
            r = np.arange(rows, dtype=np.int64)
            p = ((r % d) * cap + r // d).astype(np.int64)
            self._l2p[rows] = p
        return p

    def perm_p2l(self, rows: int) -> np.ndarray:
        """perm_p2l(n)[p] = logical row stored at physical slot p.
        Gathering a LOGICAL-order host array with it yields the physical
        layout for upload."""
        p = self._p2l.get(rows)
        if p is None:
            d = self.shards
            cap = rows // d
            r = np.arange(rows, dtype=np.int64)
            p = ((r % cap) * d + r // cap).astype(np.int64)
            self._p2l[rows] = p
        return p

    def phys_rows(self, rows: np.ndarray, pool_rows: int) -> np.ndarray:
        """Vectorized logical row ids -> physical slots. Sentinel ids >=
        pool_rows (microfold's DROP_ROW) pass through unchanged — they
        stay out of range on every shard and scatter-drop there too."""
        d = self.shards
        cap = pool_rows // d
        r = np.asarray(rows, dtype=np.int64)
        p = (r % d) * cap + r // d
        return np.where(r < pool_rows, p, r).astype(np.int32)

    def chunk_perm(self, chunk_rows: int) -> np.ndarray:
        """Inverse permutation for ONE extraction chunk's readback.

        A chunk of c global rows starting at a D-aligned logical offset
        covers local rows [start//D, start//D + c//D) on every shard;
        the assembled host array is shard-major [D * (c//D)] and logical
        row j of the chunk sits at (j % D) * (c//D) + j // D — the same
        formula as a whole pool of c rows, so the cache is shared."""
        return self.perm_l2p(chunk_rows)

    # -- placement ---------------------------------------------------------

    def place(self, arr):
        """Commit one pool array to the mesh (leading axis = phys rows)."""
        sh = self.sh2 if getattr(arr, "ndim", 1) >= 2 else self.sh1
        return jax.device_put(arr, sh)

    def replicate(self, arr):
        """Commit one batch array replicated on every shard. The CALLER
        books ledger bytes x self.shards — replication is a real per-
        device transfer, and the ledger's O(samples) pin must stay
        honest about it."""
        return jax.device_put(arr, self.rep)

    # -- t-digest programs --------------------------------------------------

    @functools.cached_property
    def fold_staged(self):
        """Sharded `_histo_fold_staged`: per-row independent, each shard
        folding its own rows (and telling its own wide rows from its
        narrow ones: under plain GSPMD the fold's compaction of the wide
        rows would run across the shards)."""
        from veneur_tpu.core.worker import _histo_fold_staged

        comp = self.compression

        def _local(*args):
            return _histo_fold_staged.__wrapped__(
                *args, compression=comp)[:14]

        fields = tuple([P("series", None)] * 2 + [P("series")] * 12)
        sm = shard_map(_local, mesh=self.mesh,
                       in_specs=fields + (P("series", None),) * 2,
                       out_specs=fields, check_vma=False)
        return jax.jit(sm, donate_argnums=tuple(range(14)))

    @functools.cached_property
    def flush_extract(self):
        from veneur_tpu.core.worker import _histo_flush_extract

        in_sh = tuple([self.sh2] * 2 + [self.sh1] * 12 + [self.rep])
        out_sh = tuple([self.sh2] + [self.sh1] * 10)
        return jax.jit(_histo_flush_extract.__wrapped__,
                       in_shardings=in_sh, out_shardings=out_sh)

    @functools.cached_property
    def ingest_step(self):
        """Sharded spill ingest. `active` carries PHYSICAL slots; each
        shard rebases to local and maps foreign entries out of range so
        the (replicated, bit-identical) batch folds everywhere but only
        the owner's writes land. See module docstring for why the batch
        must not be filtered per shard."""
        from veneur_tpu.core.worker import _histo_ingest_step

        comp = self.compression

        def _local(*args):
            fields = args[:14]
            act, lids, vals, wts = args[14:]
            cap = fields[0].shape[0]
            d = jax.lax.axis_index("series")
            la = act - d * cap
            la = jnp.where((la >= 0) & (la < cap), la, cap).astype(jnp.int32)
            return _histo_ingest_step.__wrapped__(
                *fields, la, lids, vals, wts, compression=comp)

        sm = shard_map(
            _local, mesh=self.mesh,
            in_specs=tuple([P("series", None)] * 2 + [P("series")] * 12
                           + [P(None)] * 4),
            out_specs=tuple([P("series", None)] * 2 + [P("series")] * 12),
            check_vma=False)
        return jax.jit(sm, donate_argnums=tuple(range(14)))

    @functools.cached_property
    def import_step(self):
        """Sharded `_histo_import_step` (global tier merge): per-row
        independent, but the row ids are data — same local-rebase +
        out-of-range-foreign remap as ingest."""
        from veneur_tpu.core.worker import _histo_import_step

        comp = self.compression

        def _local(*args):
            fields = args[:6]
            rows, im, iw, imn, imx, irc = args[6:]
            cap = fields[0].shape[0]
            d = jax.lax.axis_index("series")
            lr = rows - d * cap
            lr = jnp.where((lr >= 0) & (lr < cap), lr, cap).astype(jnp.int32)
            return _histo_import_step.__wrapped__(
                *fields, lr, im, iw, imn, imx, irc, compression=comp)

        sm = shard_map(
            _local, mesh=self.mesh,
            in_specs=tuple([P("series", None)] * 2 + [P("series")] * 4
                           + [P(None)] * 6),
            out_specs=tuple([P("series", None)] * 2 + [P("series")] * 4),
            check_vma=False)
        return jax.jit(sm, donate_argnums=tuple(range(6)))

    # -- staged-plane upload ------------------------------------------------

    def expand_flat(self, flat_v, flat_w, counts_phys, depth: int,
                    unit: bool):
        """Sharded `_expand_flat_planes`: the host pre-splits the flat
        compacted samples into per-shard segments padded to a common
        length ([D, Lmax], see worker._fold_one_plane), counts arrive in
        phys order, and each shard rebuilds its own [cap, depth] dense
        planes locally. Keeps the upload O(samples) per shard."""
        fn = self._expand_cache.get((depth, unit))
        if fn is None:
            from veneur_tpu.core.worker import _expand_flat_planes

            def _local(fv, fw, cnt):
                return _expand_flat_planes.__wrapped__(
                    fv[0], fw[0], cnt, depth, unit)

            fn = jax.jit(shard_map(
                _local, mesh=self.mesh,
                in_specs=(P("series", None), P("series", None), P("series")),
                out_specs=(P("series", None), P("series", None)),
                check_vma=False))
            self._expand_cache[(depth, unit)] = fn
        return fn(flat_v, flat_w, counts_phys)

    # -- slicing / growth ---------------------------------------------------

    def slice_field(self, a, s_eff: int):
        """Shrink one pool array [S, ...] -> [s_eff, ...]: each shard
        keeps its local prefix (the interleave closure property)."""
        ecap = s_eff // self.shards
        fn = self._slice_cache.get(ecap)
        if fn is None:
            def _local(x):
                return x[:ecap]

            fn = jax.jit(shard_map(_local, mesh=self.mesh,
                                   in_specs=P("series"),
                                   out_specs=P("series"), check_vma=False))
            self._slice_cache[ecap] = fn
        return fn(a)

    def slice_chunk(self, a, start: int, rows: int):
        """One extraction chunk: global rows [start, start+rows), both
        D-aligned (pow2 chunks >= 1024 over pow2 D <= 1024), are local
        rows [start//D, ...+rows//D) on EVERY shard — a lockstep
        dynamic slice, no resharding."""
        lc = rows // self.shards
        fn = self._chunk_cache.get(lc)
        if fn is None:
            def _local(x, s):
                return jax.lax.dynamic_slice_in_dim(x, s, lc, 0)

            fn = jax.jit(shard_map(_local, mesh=self.mesh,
                                   in_specs=(P("series"), P()),
                                   out_specs=P("series"), check_vma=False))
            self._chunk_cache[lc] = fn
        return fn(a, jnp.int32(start // self.shards))

    def grow_2d(self, old, new_rows: int):
        """Sharded pool growth: each shard zero-pads its local block.
        Because r % D is unchanged by growth (D fixed), every existing
        logical row keeps its shard AND its local index — growth moves
        no data between devices."""
        ncap = new_rows // self.shards
        fn = self._grow2_cache.get(ncap)
        if fn is None:
            def _local(x):
                cap, c = x.shape
                return jnp.zeros((ncap, c), x.dtype).at[:cap].set(x)

            fn = jax.jit(shard_map(_local, mesh=self.mesh,
                                   in_specs=P("series", None),
                                   out_specs=P("series", None),
                                   check_vma=False),
                         donate_argnums=(0,))
            self._grow2_cache[ncap] = fn
        return fn(old)

    def grow_1d(self, old, new_rows: int, fill: float):
        ncap = new_rows // self.shards
        key = (ncap, float(fill))
        fn = self._grow1_cache.get(key)
        if fn is None:
            def _local(x):
                cap = x.shape[0]
                return jnp.full((ncap,), fill, x.dtype).at[:cap].set(x)

            fn = jax.jit(shard_map(_local, mesh=self.mesh,
                                   in_specs=P("series"),
                                   out_specs=P("series"), check_vma=False),
                         donate_argnums=(0,))
            self._grow1_cache[key] = fn
        return fn(old)

    # -- micro-fold mirror --------------------------------------------------
    # Its planes stay [M/D, depth] blocks per shard, not the unsharded
    # mirror's flat arrays (ops/microfold.py): no cell runs them yet, so
    # a flat interleaved layout could not be measured.

    @functools.cached_property
    def scatter_chunk(self):
        """Sharded microfold scatter: rows carry PHYSICAL slots (the
        mirror's carry buffers stay logical; translation happens at
        dispatch). DROP_ROW padding is >= pool rows, hence out of range
        on every shard — dropped, same as the unsharded mode="drop"."""

        def _local(dv, dw, rows, slots, vals, wts):
            cap = dv.shape[0]
            d = jax.lax.axis_index("series")
            lr = rows - d * cap
            lr = jnp.where((lr >= 0) & (lr < cap), lr, cap).astype(jnp.int32)
            dv = dv.at[lr, slots].set(vals, mode="drop")
            dw = dw.at[lr, slots].set(wts, mode="drop")
            return dv, dw

        sm = shard_map(
            _local, mesh=self.mesh,
            in_specs=(P("series", None), P("series", None),
                      P(None), P(None), P(None), P(None)),
            out_specs=(P("series", None), P("series", None)),
            check_vma=False)
        return jax.jit(sm, donate_argnums=(0, 1))

    def mirror_dense(self, arr, s_eff: int):
        """Align a mirror plane [mirror_rows, depth] to the fold's
        [s_eff, depth] phys layout: per shard, slice or zero-pad the
        local block to s_eff // D rows."""
        ecap = s_eff // self.shards
        fn = self._mirror_cache.get(ecap)
        if fn is None:
            def _local(x):
                mcap, depth = x.shape
                if mcap >= ecap:
                    return x[:ecap]
                return jnp.zeros((ecap, depth), x.dtype).at[:mcap].set(x)

            fn = jax.jit(shard_map(_local, mesh=self.mesh,
                                   in_specs=P("series", None),
                                   out_specs=P("series", None),
                                   check_vma=False))
            self._mirror_cache[ecap] = fn
        return fn(arr)

    # -- HLL programs -------------------------------------------------------

    @functools.cached_property
    def hll_insert(self):
        """Sharded HLL register scatter-max. int8 max is order- and
        placement-independent, so only the row rebase matters: foreign
        rows map past the local register plane and drop."""

        def _local(regs, rows, reg_idx, rank):
            cap = regs.shape[0]
            d = jax.lax.axis_index("series")
            lr = rows - d * cap
            lr = jnp.where((lr >= 0) & (lr < cap), lr, cap).astype(jnp.int32)
            return hll_ops.insert_batch(regs, lr, reg_idx, rank)

        sm = shard_map(
            _local, mesh=self.mesh,
            in_specs=(P("series", None), P(None), P(None), P(None)),
            out_specs=P("series", None), check_vma=False)
        return jax.jit(sm, donate_argnums=(0,))

    @functools.cached_property
    def hll_max_rows(self):
        """Sharded register max-merge at explicit rows (import path)."""

        def _local(regs, rows, imp):
            cap = regs.shape[0]
            d = jax.lax.axis_index("series")
            lr = rows - d * cap
            lr = jnp.where((lr >= 0) & (lr < cap), lr, cap).astype(jnp.int32)
            return regs.at[lr].max(imp, mode="drop")

        sm = shard_map(
            _local, mesh=self.mesh,
            in_specs=(P("series", None), P(None), P(None, None)),
            out_specs=P("series", None), check_vma=False)
        return jax.jit(sm, donate_argnums=(0,))

    def hll_estimate(self, registers, precision: int):
        """Per-row HLL estimation over the sharded register plane
        (per-row independent: plain GSPMD jit, precision baked in)."""
        fn = self._est_cache.get(precision)
        if fn is None:
            def _e(regs):
                return hll_ops.estimate(regs, precision)

            fn = jax.jit(_e, in_shardings=(self.sh2,),
                         out_shardings=self.sh1)
            self._est_cache[precision] = fn
        return fn(registers)

    # -- scalar segment ops -------------------------------------------------

    def segment_counter_sum(self, rows, contributions, num_rows: int):
        """Sharded device counter reduction (ops/scalars device path):
        each shard segment-sums the replicated COO into its local rows.
        Host f64 pools remain the exactness-critical default; this is
        the device-resident variant for sharded deployments."""
        fn = getattr(self, "_seg_sum_fn", None)
        if fn is None:
            def _local(r, c, out):
                cap = out.shape[0]
                d = jax.lax.axis_index("series")
                lr = r - d * cap
                lr = jnp.where((lr >= 0) & (lr < cap), lr,
                               cap).astype(jnp.int32)
                return out.at[lr].add(c, mode="drop")

            sm = shard_map(_local, mesh=self.mesh,
                           in_specs=(P(None), P(None), P("series")),
                           out_specs=P("series"), check_vma=False)
            fn = jax.jit(sm, donate_argnums=(2,))
            self._seg_sum_fn = fn
        out = jax.device_put(jnp.zeros(num_rows, jnp.float32), self.sh1)
        return fn(jnp.asarray(rows, jnp.int32),
                  jnp.asarray(contributions, jnp.float32), out)

    def segment_gauge_last(self, rows, values, num_rows: int):
        """Sharded last-write-wins gauge plane. Mirrors
        ops/scalars.segment_gauge_last's (values, present) contract: the
        winner per row is the highest arrival position; each shard
        resolves its own rows from the replicated batch."""
        fn = getattr(self, "_seg_last_fn", None)
        if fn is None:
            def _local(r, v, seq, out_v, out_s):
                cap = out_v.shape[0]
                d = jax.lax.axis_index("series")
                lr = r - d * cap
                lr = jnp.where((lr >= 0) & (lr < cap), lr,
                               cap).astype(jnp.int32)
                # newest sequence number wins per row (seq starts at 1;
                # a row left at 0 had no sample -> present False)
                ns = out_s.at[lr].max(seq, mode="drop")
                win = ns[lr] == seq
                lr_w = jnp.where(win, lr, cap).astype(jnp.int32)
                nv = out_v.at[lr_w].set(v, mode="drop")
                return nv, ns

            sm = shard_map(_local, mesh=self.mesh,
                           in_specs=(P(None), P(None), P(None),
                                     P("series"), P("series")),
                           out_specs=(P("series"), P("series")),
                           check_vma=False)
            fn = jax.jit(sm, donate_argnums=(3, 4))
            self._seg_last_fn = fn
        n = len(np.asarray(rows))
        seq = jnp.arange(1, n + 1, dtype=jnp.int32)
        out_v = jax.device_put(jnp.zeros(num_rows, jnp.float32), self.sh1)
        out_s = jax.device_put(jnp.zeros(num_rows, jnp.int32), self.sh1)
        nv, ns = fn(jnp.asarray(rows, jnp.int32),
                    jnp.asarray(values, jnp.float32), seq, out_v, out_s)
        return nv, ns > 0
