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

The dense tier's programs come in few shapes (PR 46). A device batch,
a drain's inserts and a promotion's replay alike, is padded to a length
of ``INSERT_LENGTHS`` and a longer one goes in slices of the top: every
(length, pool rows) pair is a program of ``insert_batch``, seconds to
compile on a v5e, and a drain's batch is whatever arrived since the last
one. The pool's rows are a power of two from ``POOL_MIN_ROWS``, and an
epoch's store is told the size the last epoch's ended with.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.ops import exactnum as exn
from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.ops import host_engine as he
from veneur_tpu.ops.device_guard import (DeviceFaultError, guard_span,
                                         wait_span)

# The dense insert's shape ladder: powers of four, as the spill fold's
# row buckets, up to the spill fold's one sample length
# (core/worker._FOLD_CHUNK). The program sorts its batch, and the TPU
# compiler's time for a sort grows with its length: a first call took
# 1.7 s at 1,024, 1.8 s at 4,096, 6.2 s at 16,384 and 22.5 s at 65,536 on
# a v5e, under the ingest lock the first time a length is met, and every
# length then ran in 1.0-1.1 ms a call (PERF.md section 6, PR 46); so the
# top is 16,384 and a longer batch, a stall's or a promotion's, goes in
# slices.
# Padding is (slot 0, register 0, rank 0): registers are >= 0, so max
# with it changes nothing on the device scatter, on the sharded one and
# on the NumPy twin alike, and it needs neither a scratch row nor
# mode="drop".
INSERT_LENGTHS = (1 << 10, 1 << 12, 1 << 14)

# the least dense pool: 64 rows x 2^p bytes = 1 MB at p = 14, allocated
# when the first row promotes and never before
POOL_MIN_ROWS = 64


def pool_rows_for(dense_rows: int, floor: int = POOL_MIN_ROWS) -> int:
    """The pool size, a power of two, that holds ``dense_rows`` rows."""
    return exn.next_pow2(dense_rows, floor)


@functools.partial(jax.jit, static_argnames=("rows",))
def _grow_pool(old: jax.Array, *, rows: int) -> jax.Array:
    """The pool at ``rows`` rows: one program per size pair."""
    return jnp.zeros((rows, old.shape[1]), old.dtype).at[
        :old.shape[0]].set(old)


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

    Shapes: every device batch has a length of ``INSERT_LENGTHS``; the
    pool has ``pool_rows_for(dense rows)`` rows, or ``pool_rows`` (what
    the last epoch's store ended with) if that is more.
    """

    def __init__(self, precision: int = hll_ops.DEFAULT_PRECISION,
                 promote_entries: Optional[int] = None,
                 compact_every: int = 1 << 16, shard=None,
                 guard=None, host: bool = False, pool_rows: int = 0,
                 warm: Optional[dict] = None) -> None:
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
        # dense tier: row -> slot (-1 = sparse), slots in promotion
        # order; the table grows with the largest promoted row
        self._slot_lut = np.full(64, -1, np.int32)
        self._n_dense = 0
        self._guard = guard
        # host mode: _dense is np int8 [slots, m] in LOGICAL slot order
        # (quarantined worker, or failover after a dense-tier fault)
        self._host = bool(host)
        self._dense = None  # jax int8 [slots, m] (np int8 in host mode)
        # the pool is allocated at this size when the first row
        # promotes (0: at the least size that holds it)
        self._pool_hint = int(pool_rows)
        # {pool rows: the longest insert length run there}: its owner's
        # (the worker's, across epochs), or this store's alone
        self._warm = {} if warm is None else warm
        # set entries routed to a dense row / into the sparse tier
        self.dense_entries = 0
        self.sparse_routed = 0
        # imported full-register rows max-merge host-side and batch onto
        # the device once per flush (a per-import device update would
        # copy the whole dense pool each call)
        self._imp_dense: dict[int, np.ndarray] = {}

    # -- device fault domain ------------------------------------------------

    @property
    def host_mode(self) -> bool:
        return self._host

    def _dev_call(self, kernel: str, fn, *args, retryable: bool = False,
                  **attrs):
        """One dense-tier device op through the worker's guard; its
        ``dispatch`` span says which (``kernel``), at what pool size
        and with ``attrs``. The sharded register programs donate the
        plane (retryable=False); the unsharded inserts and all
        estimates do not."""
        if self._guard is None:
            return fn(*args)
        return self._guard.call(
            "sets", fn, *args, retryable=retryable,
            attrs=dict(attrs, kernel=kernel, pool_rows=self.pool_rows))

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

    def _slots_of(self, rows: np.ndarray) -> np.ndarray:
        """Dense slot per row, -1 where the row is sparse."""
        lut = self._slot_lut
        return np.where(rows < lut.size,
                        lut[np.minimum(rows, lut.size - 1)], -1)

    def insert(self, rows: np.ndarray, idx: np.ndarray,
               rank: np.ndarray) -> None:
        """Batch of (row, register, rank) updates (host arrays)."""
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return
        idx = np.asarray(idx, np.int64)
        rank = np.asarray(rank, np.int8)
        if self._n_dense:
            dense_slot = self._slots_of(rows)
            dmask = dense_slot >= 0
            if dmask.any():
                self.dense_entries += int(dmask.sum())
                self._dense_insert(dense_slot[dmask], idx[dmask],
                                   rank[dmask])
            smask = ~dmask
            rows, idx, rank = rows[smask], idx[smask], rank[smask]
            if rows.size == 0:
                return
        self.sparse_routed += rows.size
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
        rows = np.asarray(sorted(self._imp_dense), np.int64)
        self._promote_rows(rows)
        slots = self._slots_of(rows).astype(np.int32)
        stacked = np.stack([self._imp_dense[r] for r in rows.tolist()])
        self._imp_dense = {}
        assert self._dense is not None
        if self._host:
            np.maximum.at(self._dense, slots.astype(np.int64), stacked)
            return
        sh = self._shard
        try:
            if sh is not None:
                self._dense = self._dev_call(
                    "import", sh.hll_max_rows, self._dense,
                    sh.replicate(sh.phys_rows(slots, self._dense.shape[0])),
                    sh.replicate(stacked), rows=len(rows))
            else:
                self._dense = self._dev_call(
                    "import", lambda d, s, v: d.at[s].max(v), self._dense,
                    jnp.asarray(slots), jnp.asarray(stacked),
                    retryable=True, rows=len(rows))
        except DeviceFaultError:
            self.to_host()
            np.maximum.at(self._dense, slots.astype(np.int64), stacked)

    # -- internals ----------------------------------------------------------

    def _dense_insert(self, slots: np.ndarray, idx: np.ndarray,
                      rank: np.ndarray) -> None:
        """Scatter-max (slot, register, rank) triples into the dense
        tier: on the device in batches of a ladder length, the longest
        sliced at the ladder's top."""
        assert self._dense is not None
        top = INSERT_LENGTHS[-1]
        for a in range(0, slots.size, top):
            if not self._host:
                n = min(top, slots.size - a)
                length = next(b for b in INSERT_LENGTHS if b >= n)
                try:
                    self._warm_inserts(length)
                    self._insert_padded(length, slots[a:a + n],
                                        idx[a:a + n], rank[a:a + n])
                    continue
                except DeviceFaultError:
                    self.to_host()
            # host mode, from the start or from the slice that faulted:
            # the NumPy twin takes the rest whole, unpadded
            self._dense = he.np_hll_insert_batch(
                self._dense, slots[a:].astype(np.int64),
                idx[a:].astype(np.int64), rank[a:].astype(np.int8))
            return

    def _insert_padded(self, length: int, slots: np.ndarray,
                       idx: np.ndarray, rank: np.ndarray) -> None:
        """One device batch, padded to the ladder length ``length``."""
        n = slots.size
        ps = np.zeros(length, np.int32)
        ps[:n] = slots
        pi = np.zeros(length, np.int32)
        pi[:n] = idx
        pr = np.zeros(length, np.int8)
        pr[:n] = rank
        sh = self._shard
        if sh is not None:
            self._dense = self._dev_call(
                "insert", sh.hll_insert, self._dense,
                sh.replicate(sh.phys_rows(ps, self._dense.shape[0])),
                sh.replicate(pi), sh.replicate(pr),
                entries=n, padded=length)
        else:
            self._dense = self._dev_call(
                "insert", hll_ops.insert_batch, self._dense,
                jnp.asarray(ps), jnp.asarray(pi), jnp.asarray(pr),
                retryable=True, entries=n, padded=length)

    def _warm_inserts(self, length: int) -> None:
        """Before the first insert of ``length`` at this pool size, run
        each shorter ladder length not run there yet once on nothing
        (all padding: the registers come back as they were). A drain's
        batch is what arrived since the last one, so left to chance a
        length is first met, and compiled, any number of intervals
        later under the ingest lock (core/worker._warm_spill_rows)."""
        pool = self.pool_rows
        done = self._warm.get(pool, 0)
        if length <= done:
            return
        none = np.empty(0, np.int32)
        for b in INSERT_LENGTHS:
            if done < b < length:
                with guard_span(self._guard, "sets.warm", length=b,
                                pool_rows=pool):
                    self._insert_padded(b, none, none, none.astype(np.int8))
        self._warm[pool] = length

    def _ensure_pool(self, needed: int) -> None:
        """The dense pool at a power-of-two size that holds ``needed``
        rows: allocated at the last epoch's size if that is more, grown
        by one jitted program per size pair."""
        have = self.pool_rows
        if needed <= have:
            return
        sh = self._shard
        rows = pool_rows_for(max(needed, self._pool_hint),
                             max(POOL_MIN_ROWS, sh.shards if sh else 1))

        def on_host():
            fresh = np.zeros((rows, self.m), np.int8)
            if self._dense is not None:
                fresh[:have] = self._dense
            return fresh

        if self._host:
            self._dense = on_host()
            return
        try:
            if self._dense is None:
                # an upload, not a program: nothing to compile
                zeros = np.zeros((rows, self.m), np.int8)
                self._dense = self._dev_call(
                    "alloc", jnp.asarray if sh is None else sh.place, zeros,
                    retryable=True, to_rows=rows)
            elif sh is not None:
                # per-shard local pad keeps every promoted slot on its
                # shard across growth
                self._dense = self._dev_call(
                    "grow", sh.grow_2d, self._dense, rows, to_rows=rows)
            else:
                self._dense = self._dev_call(
                    "grow", functools.partial(_grow_pool, rows=rows),
                    self._dense, retryable=True, to_rows=rows)
        except DeviceFaultError:
            self.to_host()
            self._dense = on_host()

    def _compact(self) -> None:
        self._compact_no_promote()
        self._maybe_promote()

    def _maybe_promote(self) -> None:
        rows = self._ckeys // self.m
        if not rows.size:
            return
        # distinct-register count per row (keys are sorted ⇒ rows grouped)
        starts = np.r_[0, np.flatnonzero(rows[1:] != rows[:-1]) + 1]
        counts = np.diff(np.r_[starts, rows.size])
        self._promote_rows(rows[starts[counts >= self.promote_entries]])

    def _promote_rows(self, rows: np.ndarray) -> None:
        """Move the sparse entries of ``rows`` (ascending) into dense
        rows, all in one pass: one table lookup over the sorted keys
        and one insert (in slices of the ladder's top). Rows that are
        dense already stay where they are."""
        rows = rows[self._slots_of(rows) < 0]
        if not rows.size:
            return
        # promotion needs the rows' full sparse content; cheapest correct
        # move is a full compaction (amortized by compact_every)
        self._compact_no_promote()
        with guard_span(self._guard, "sets.promote",
                        rows=int(rows.size)) as span:
            top = int(rows[-1])
            if top >= self._slot_lut.size:
                grown = np.full(max(self._slot_lut.size * 2, top + 1), -1,
                                np.int32)
                grown[:self._slot_lut.size] = self._slot_lut
                self._slot_lut = grown
            self._slot_lut[rows] = np.arange(
                self._n_dense, self._n_dense + rows.size, dtype=np.int32)
            self._n_dense += int(rows.size)
            self._ensure_pool(self._n_dense)
            # the sparse tier holds no dense row but the ones just
            # promoted, so the table itself tells their entries apart
            slots = self._slots_of(self._ckeys // self.m)
            moved = slots >= 0
            if span is not None:
                span.attrs.update(entries=int(moved.sum()),
                                  pool_rows=self.pool_rows)
            if moved.any():
                self._dense_insert(
                    slots[moved], self._ckeys[moved] % self.m,
                    self._crank[moved])
                self._ckeys, self._crank = (self._ckeys[~moved],
                                            self._crank[~moved])

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
                        dense_rows=self._n_dense) as span:
            out = np.zeros(num_rows, np.float32)
            urows, est = self._sparse_estimates()
            if span is not None:
                span.attrs["sparse_rows"] = int(urows.size)
            keep = urows < num_rows
            out[urows[keep]] = est[keep]
            if self._n_dense and self._dense is not None:
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
                    "estimate",
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
        if self._n_dense and self._dense is not None:
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
        return self._n_dense

    @property
    def pool_rows(self) -> int:
        """Rows the dense pool has allocated (0: none promoted yet)."""
        return 0 if self._dense is None else int(self._dense.shape[0])
