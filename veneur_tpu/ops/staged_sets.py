"""Two-tier (sparse host / dense device) HyperLogLog set store.

The dense pool in ops/hll.py costs 2^p bytes per series (16KB at p=14):
at 1M set series that is 16GB of HBM — past a v5e chip. The reference
avoids the same cliff with the vendored sketch's sparse mode
(vendor/github.com/axiomhq/hyperloglog/hyperloglog.go:31-39: small sets
live as encoded-hash lists, converting to registers past a size bound).

Here the staging is columnar and batched instead of per-sketch:

* Sparse tier (host): inserts accumulate as (row, register-index, rank)
  triples; compaction lexsorts by (row, idx) and keeps the max rank per
  pair — exactly the register content, stored at ~9 bytes per *distinct*
  register instead of 2^p bytes per series.
* Dense tier (device): a row crossing ``promote_entries`` distinct
  registers replays its triples into a dense device row via the same
  scatter-max insert as always; later inserts route straight to the
  device. Imported full-register rows (the global tier's merge) are
  dense by nature and promote immediately.

Crossover: a sparse register costs ~9B host-side, a dense row 2^p bytes
of HBM; the default threshold 2^p/8 (2048 at p=14) promotes when the
sparse form reaches ~18KB — past the dense cost — so memory is within
~2x of optimal on both sides of the boundary.

Estimates use the same harmonic-mean + linear-counting estimator as the
device kernel (ops/hll.py estimate), so a series reports identically on
either side of promotion.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.ops import host_engine as he
from veneur_tpu.ops.device_guard import (DeviceFaultError, guard_span,
                                         wait_span)


class StagedSetStore:
    """Per-epoch set-sketch state for one worker (staged representation).

    All rows are identified by the worker directory's set-row index.

    Device fault domain (ops/device_guard): every dense-tier device op
    routes through the worker's guard under op "sets". Register updates
    are max-merges — idempotent and order-independent — so the failover
    story is the simplest in the system: on a classified device fault
    the dense tier converts to host numpy registers (``to_host``) and
    the faulted update re-applies there; a partially-applied device
    update before the fault can only have asserted ranks the host redo
    asserts again. ``to_device`` re-uploads at probe re-admission.
    """

    def __init__(self, precision: int = hll_ops.DEFAULT_PRECISION,
                 promote_entries: Optional[int] = None,
                 compact_every: int = 1 << 16, shard=None,
                 guard=None, host: bool = False) -> None:
        self.precision = precision
        # series-sharded dense tier (ops/series_shard.SeriesSharding):
        # the [slots, m] register plane partitions over the shard mesh
        # with the same row interleave as the sketch pools — slots are
        # promotion-order, so the interleave spreads hot promoted rows
        # round-robin. The sparse host tier is unaffected.
        self._shard = shard
        self.m = hll_ops.num_registers(precision)
        self.promote_entries = promote_entries or max(self.m // 8, 64)
        self.compact_every = compact_every
        # sparse tier: compacted sorted-unique keys row*m+idx with max rank
        self._ckeys = np.empty(0, np.int64)
        self._crank = np.empty(0, np.int8)
        # pending (uncompacted) triples
        self._p_keys: list[np.ndarray] = []
        self._p_rank: list[np.ndarray] = []
        self._pend = 0
        # dense tier
        self._slot_of_row: dict[int, int] = {}
        # vectorized row→slot lookup (-1 = sparse); grows with max row
        self._slot_lut = np.full(64, -1, np.int32)
        self._guard = guard
        # host mode: _dense is np int8 [slots, m] in LOGICAL slot order
        # (quarantined worker, or failover after a dense-tier fault)
        self._host = bool(host)
        self._dense = None  # jax int8 [slots, m] (np int8 in host mode)
        # imported full-register rows max-merge host-side and batch onto
        # the device once per flush (a per-import device update would
        # copy the whole dense pool each call)
        self._imp_dense: dict[int, np.ndarray] = {}

    # -- device fault domain ------------------------------------------------

    @property
    def host_mode(self) -> bool:
        return self._host

    def _dev_call(self, fn, *args, retryable: bool = False):
        """One dense-tier device op through the worker's guard. The
        sharded register programs donate the plane (retryable=False);
        the unsharded inserts and all estimates do not."""
        if self._guard is None:
            return fn(*args)
        return self._guard.call("sets", fn, *args, retryable=retryable)

    def to_host(self) -> None:
        """Fail the dense tier over to host numpy registers (logical
        slot order). Safe after a partially-applied faulted update:
        max-merges re-applied host-side only re-assert existing ranks."""
        if self._host:
            return
        self._host = True
        if self._dense is None:
            return
        d = np.asarray(self._dense)
        if self._shard is not None:
            d = d[self._shard.perm_l2p(d.shape[0])]
        self._dense = d

    def to_device(self) -> None:
        """Re-admit the dense tier to the device (probe succeeded)."""
        if not self._host:
            return
        self._host = False
        if self._dense is None:
            return
        d = self._dense
        if self._shard is not None:
            self._dense = self._shard.place(
                jnp.asarray(d[self._shard.perm_p2l(d.shape[0])]))
        else:
            self._dense = jnp.asarray(d)

    # -- ingest -------------------------------------------------------------

    def insert(self, rows: np.ndarray, idx: np.ndarray,
               rank: np.ndarray) -> None:
        """Batch of (row, register, rank) updates (host arrays)."""
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return
        idx = np.asarray(idx, np.int64)
        rank = np.asarray(rank, np.int8)
        if self._slot_of_row:
            dense_slot = self._slot_lut[
                np.minimum(rows, self._slot_lut.size - 1)]
            dense_slot = np.where(rows < self._slot_lut.size, dense_slot, -1)
            dmask = dense_slot >= 0
            if dmask.any():
                self._dense_insert(dense_slot[dmask], idx[dmask],
                                   rank[dmask])
            smask = ~dmask
            rows, idx, rank = rows[smask], idx[smask], rank[smask]
            if rows.size == 0:
                return
        self._p_keys.append(rows * self.m + idx)
        self._p_rank.append(rank)
        self._pend += rows.size
        if self._pend >= self.compact_every:
            self._compact()

    def import_dense(self, row: int, registers: np.ndarray) -> None:
        """Merge a full register row (wire import) — dense by nature.
        Max-merged host-side; promoted to the device in one batched
        update at flush (_apply_imports)."""
        row = int(row)
        regs = np.asarray(registers, np.int8)
        prev = self._imp_dense.get(row)
        self._imp_dense[row] = (regs.copy() if prev is None
                                else np.maximum(prev, regs))

    def _apply_imports(self) -> None:
        if not self._imp_dense:
            return
        rows = sorted(self._imp_dense)
        slots = np.asarray([self._promote(r) for r in rows], np.int32)
        stacked = np.stack([self._imp_dense[r] for r in rows])
        self._imp_dense = {}
        assert self._dense is not None
        if self._host:
            np.maximum.at(self._dense, slots.astype(np.int64), stacked)
            return
        sh = self._shard
        try:
            if sh is not None:
                self._dense = self._dev_call(
                    sh.hll_max_rows, self._dense,
                    sh.replicate(sh.phys_rows(slots, self._dense.shape[0])),
                    sh.replicate(stacked))
            else:
                self._dense = self._dev_call(
                    lambda d, s, v: d.at[s].max(v), self._dense,
                    jnp.asarray(slots), jnp.asarray(stacked),
                    retryable=True)
        except DeviceFaultError:
            self.to_host()
            np.maximum.at(self._dense, slots.astype(np.int64), stacked)

    # -- internals ----------------------------------------------------------

    def _dense_insert(self, slots: np.ndarray, idx: np.ndarray,
                      rank: np.ndarray) -> None:
        assert self._dense is not None
        if self._host:
            self._dense = he.np_hll_insert_batch(
                self._dense, slots.astype(np.int64), idx.astype(np.int64),
                rank.astype(np.int8))
            return
        sh = self._shard
        try:
            if sh is not None:
                self._dense = self._dev_call(
                    sh.hll_insert, self._dense,
                    sh.replicate(sh.phys_rows(slots.astype(np.int32),
                                              self._dense.shape[0])),
                    sh.replicate(idx.astype(np.int32)),
                    sh.replicate(rank.astype(np.int8)))
            else:
                self._dense = self._dev_call(
                    hll_ops.insert_batch,
                    self._dense, jnp.asarray(slots.astype(np.int32)),
                    jnp.asarray(idx.astype(np.int32)),
                    jnp.asarray(rank.astype(np.int8)), retryable=True)
        except DeviceFaultError:
            self.to_host()
            self._dense = he.np_hll_insert_batch(
                self._dense, slots.astype(np.int64), idx.astype(np.int64),
                rank.astype(np.int8))

    def _compact(self) -> None:
        self._compact_no_promote()
        self._maybe_promote()

    def _maybe_promote(self) -> None:
        rows = self._ckeys // self.m
        # distinct-register count per row (keys are sorted ⇒ rows grouped)
        urows, counts = np.unique(rows, return_counts=True)
        for r in urows[counts >= self.promote_entries]:
            self._promote(int(r))

    def _promote(self, row: int) -> int:
        """Move one row's sparse entries into a dense device row."""
        if row in self._slot_of_row:
            return self._slot_of_row[row]
        self._compact_pending_row(row)
        slot = len(self._slot_of_row)
        self._slot_of_row[row] = slot
        if row >= self._slot_lut.size:
            grown = np.full(max(self._slot_lut.size * 2, row + 1), -1,
                            np.int32)
            grown[:self._slot_lut.size] = self._slot_lut
            self._slot_lut = grown
        self._slot_lut[row] = slot
        if self._dense is None or slot >= self._dense.shape[0]:
            grown = max(16, (slot + 1) * 2)
            sh = self._shard
            if self._host:
                fresh = np.zeros((grown, self.m), np.int8)
                if self._dense is not None:
                    fresh[:self._dense.shape[0]] = self._dense
                self._dense = fresh
            elif sh is not None:
                # pow2 multiple of the shard count so the slot-axis
                # interleave stays divisible; per-shard local pad keeps
                # every promoted slot on its shard across growth
                g = sh.shards
                while g < grown:
                    g *= 2
                grown = g
                try:
                    if self._dense is None:
                        self._dense = self._dev_call(
                            sh.place, jnp.zeros((grown, self.m), jnp.int8))
                    else:
                        self._dense = self._dev_call(
                            sh.grow_2d, self._dense, grown)
                except DeviceFaultError:
                    self.to_host()
                    fresh = np.zeros((grown, self.m), np.int8)
                    if self._dense is not None:
                        fresh[:self._dense.shape[0]] = self._dense
                    self._dense = fresh
            else:
                try:
                    def _grow(old, n):
                        fresh = jnp.zeros((n, self.m), jnp.int8)
                        return (fresh if old is None
                                else fresh.at[:old.shape[0]].set(old))

                    self._dense = self._dev_call(
                        _grow, self._dense, grown, retryable=True)
                except DeviceFaultError:
                    self.to_host()
                    fresh = np.zeros((grown, self.m), np.int8)
                    if self._dense is not None:
                        fresh[:self._dense.shape[0]] = self._dense
                    self._dense = fresh
        mask = (self._ckeys // self.m) == row
        if mask.any():
            idx = (self._ckeys[mask] % self.m).astype(np.int32)
            rank = self._crank[mask]
            self._dense_insert(np.full(idx.shape, slot, np.int32), idx, rank)
            keep = ~mask
            self._ckeys, self._crank = self._ckeys[keep], self._crank[keep]
        return slot

    def _compact_pending_row(self, row: int) -> None:
        # promotion needs the row's full sparse content; cheapest correct
        # move is a full compaction (amortized by compact_every)
        if self._p_keys:
            self._compact_no_promote()

    def _compact_no_promote(self) -> None:
        if not self._p_keys:
            return
        keys = np.concatenate([self._ckeys] + self._p_keys)
        rank = np.concatenate([self._crank] + self._p_rank)
        self._p_keys, self._p_rank, self._pend = [], [], 0
        if keys.size == 0:
            self._ckeys, self._crank = keys, rank
            return
        order = np.lexsort((rank, keys))
        keys, rank = keys[order], rank[order]
        # last element of each equal-key run holds the max rank
        is_end = np.r_[keys[1:] != keys[:-1], True]
        self._ckeys, self._crank = keys[is_end], rank[is_end]

    # -- flush --------------------------------------------------------------

    def estimates(self, num_rows: int) -> np.ndarray:
        """Cardinality estimate per directory set row [num_rows] (f32).

        Sparse rows evaluate the same estimator as the device kernel
        (harmonic mean + linear counting) over their distinct registers,
        every row at once: the sorted keys group a row's registers into
        one run, and a run's sum is a difference of one cumsum. Dense
        rows read the device result.
        """
        self._apply_imports()
        with guard_span(self._guard, "extract.sets.compact",
                        pending=self._pend):
            self._compact_no_promote()
        with guard_span(self._guard, "extract.sets.estimate",
                        dense_rows=len(self._slot_of_row)) as span:
            out = np.zeros(num_rows, np.float32)
            urows, est = self._sparse_estimates()
            if span is not None:
                span.attrs["sparse_rows"] = int(urows.size)
            keep = urows < num_rows
            out[urows[keep]] = est[keep]
            if self._slot_of_row and self._dense is not None:
                drows, slots = self._dense_rows_below(num_rows)
                out[drows] = self._dense_estimates()[slots]
        return out

    def _dense_estimates(self) -> np.ndarray:
        """The dense tier's estimates by logical slot: the device's, or
        on a fault (and from then on) the host twin's."""
        if not self._host:
            try:
                sh = self._shard
                est = self._dev_call(
                    hll_ops.estimate if sh is None else sh.hll_estimate,
                    self._dense, self.precision, retryable=True)
                with wait_span(self._guard, "sets.readback"):
                    est = np.asarray(est)
                return (est if sh is None
                        else est[sh.perm_l2p(self._dense.shape[0])])
            except DeviceFaultError:
                self.to_host()
        # host mode: the bitwise f32 twin of the device estimator
        # (ops/host_engine parity contract)
        return he.np_hll_estimate_exact(self._dense, self.precision)

    def _sparse_estimates(self):
        """(rows, float64 estimates) of the compacted sparse tier."""
        rows = self._ckeys // self.m
        if not rows.size:
            return rows, np.empty(0)
        # a run a row: where the sorted rows change
        edges = np.flatnonzero(rows[1:] != rows[:-1]) + 1
        starts, ends = np.r_[0, edges], np.r_[edges, rows.size]
        m = float(self.m)
        alpha = 0.7213 / (1.0 + 1.079 / m)
        # 2^-rank, exact
        csum = np.r_[0.0, np.cumsum(
            np.ldexp(1.0, -self._crank.astype(np.int32)))]
        zeros = m - (ends - starts)  # registers the row never hit
        inv_sum = zeros + (csum[ends] - csum[starts])
        est = alpha * m * m / inv_sum
        linear = (est <= 2.5 * m) & (zeros > 0)
        est[linear] = m * np.log(m / zeros[linear])
        return rows[starts], est

    def _dense_rows_below(self, num_rows: int):
        """(rows, slots) of the promoted rows under ``num_rows``."""
        rows = np.flatnonzero(self._slot_lut[:num_rows] >= 0)
        return rows, self._slot_lut[rows]

    def registers(self, num_rows: int) -> np.ndarray:
        """Materialize dense int8 register rows [num_rows, m] (the
        forwarding codec's wire form). Transient — only built at flush
        for rows that actually forward."""
        self._apply_imports()
        self._compact_no_promote()
        out = np.zeros((num_rows, self.m), np.int8)
        rows = (self._ckeys // self.m).astype(np.int64)
        idx = (self._ckeys % self.m).astype(np.int64)
        mask = rows < num_rows
        out[rows[mask], idx[mask]] = self._crank[mask]
        if self._slot_of_row and self._dense is not None:
            if self._host:
                dense_np = self._dense
            else:
                with wait_span(self._guard, "sets.readback"):
                    dense_np = np.asarray(self._dense)
                if self._shard is not None:
                    dense_np = dense_np[
                        self._shard.perm_l2p(self._dense.shape[0])]
            drows, slots = self._dense_rows_below(num_rows)
            out[drows] = dense_np[slots]
        return out

    @property
    def sparse_entries(self) -> int:
        return int(self._ckeys.size) + self._pend

    @property
    def dense_rows(self) -> int:
        return len(self._slot_of_row)
