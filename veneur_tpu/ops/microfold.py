"""Streaming micro-fold mirror: always-hot device staging.

The once-per-interval flush pays a synchronous upload+fold burst at the
deadline (SUSTAINED_PIPELINE.json: tick_block_ms ~1100 with chip compute
in the milliseconds — the device is cold between flushes). This module
keeps a device-side mirror of the staging plane warm DURING the
interval: every micro-fold drains the staged samples accumulated since
the last drain as COO deltas (row, absolute slot, value, weight) and
scatters them into a persistent mirror with donated dispatches, so by
flush time the staged state is already resident on device and the
tick's fold collapses to a drain.

The mirror is two FLAT float32[M x B] arrays, row-major (entry
``row x B + slot``), not [M, B] planes: XLA will not scatter into the
TPU's tiled 2-D layout, it copies the plane whole into a linear array,
scatters there and converts it back, so a 65,536-entry chunk cost 51.9
ms at M = 2,097,152 against 13.1 ms into the flat array (PERF.md
section 6, PR 41). ``mirror_dense`` turns the flat array into the
[s_eff, B] plane the fold takes, once per array per flush.

Bit-identity by construction: slots are ABSOLUTE positions in the host
staging plane, so after the final drain the mirror holds exactly the
dense [S, B] array the batch path would have uploaded (values/weights at
filled slots, zeros elsewhere — including unit weights, which both paths
materialize as exact 1.0f). The flush then runs the SAME single
``_histo_fold_staged`` program over ``mirror_dense(mirror, s_eff)`` that
the batch path runs over its uploaded plane, so micro-folded ==
batch-folded is bitwise, not approximate (tests/test_microfold.py pins
all three metric classes).

Transfer accounting stays O(samples) and partition-invariant: uploads go
out in fixed MICRO_CHUNK-entry COO chunks (16 bytes/entry), the carry
remainder is buffered host-side across drains, and the final partial
chunk is padded with drop-sentinel rows (scatter ``mode="drop"``).
Total bytes = ceil(samples / MICRO_CHUNK) x MICRO_CHUNK x 16 no matter
how many micro-folds the scheduler ran — the ledger-equality contract
(tests assert +-0 against a single-drain run) and a single jit
specialization (no per-size compile ladder).

Overlap discipline (double buffering): each chunk's four COO arrays are
device_put first (async), then the scatter is dispatched; with at most
two unsynced scatters in the queue the upload of chunk N+1 overlaps the
scatter of chunk N, and the fence (block on the latest mirror) bounds
the dispatch queue so a fast producer cannot run the host arbitrarily
far ahead of the device.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.ops.device_guard import wait_span

# COO entries per upload chunk. 65536 x 16B = 1 MB per dispatch: large
# enough to amortize dispatch overhead (and, on backends that cannot
# honor the scatter's donation — XLA-CPU copies the whole flat [M x B]
# mirror per dispatch — to keep the per-interval dispatch count in the single
# digits), small enough that the carry buffer and the padded final
# chunk stay trivial and uploads still interleave with compute.
MICRO_CHUNK = 65536

# Sentinel row for padding the final partial chunk: out of bounds for
# any mirror, so the donated scatter's mode="drop" discards it. It is
# int32 max, so ``row x B`` wraps: _scatter_chunk sends it out of range
# by the row, never by the product.
DROP_ROW = np.int32(np.iinfo(np.int32).max)


@functools.partial(jax.jit, static_argnames=("depth",),
                   donate_argnums=(0, 1))
def _scatter_chunk(dvals, dwts, rows, slots, vals, wts, *, depth: int):
    """Scatter one COO chunk into the flat mirror at ``row x depth +
    slot``. A row outside [0, M) (the padding's DROP_ROW, whose product
    wraps) or a slot outside [0, depth) goes to the out-of-range index
    ``M x depth`` and is dropped, never into another row."""
    size = dvals.shape[0]
    with jax.named_scope("microfold.scatter"):
        live = ((rows >= 0) & (rows < size // depth)
                & (slots >= 0) & (slots < depth))
        idx = jnp.where(live, rows * depth + slots, size)
        dvals = dvals.at[idx].set(vals, mode="drop")
        dwts = dwts.at[idx].set(wts, mode="drop")
    return dvals, dwts


@functools.partial(jax.jit, static_argnames=("new_size",),
                   donate_argnums=(0,))
def _grow_mirror(old, new_size: int):
    """Row-major, so growth by rows is a prefix copy."""
    with jax.named_scope("microfold.grow"):
        return jnp.zeros((new_size,), old.dtype).at[:old.shape[0]].set(old)


@functools.partial(jax.jit, static_argnames=("s_eff", "depth"))
def mirror_dense(arr, s_eff: int, depth: int):
    """The flat mirror as the dense [s_eff, depth] plane the fold takes:
    its prefix when the mirror is larger, zero-padded when the directory
    outgrew it, reshaped. Either way the result is bitwise the array the
    batch path would have built. One program per (mirror_rows, s_eff),
    run once per array per flush: the only change of layout the mirror
    pays."""
    n = s_eff * depth
    with jax.named_scope("microfold.dense"):
        if arr.shape[0] >= n:
            flat = arr[:n]
        else:
            flat = jnp.zeros((n,), arr.dtype).at[:arr.shape[0]].set(arr)
        return flat.reshape(s_eff, depth)


class MirrorState(NamedTuple):
    """A finished epoch's mirror, handed to the swapped-epoch extract.
    ``vals`` / ``wts`` are the flat float32[mirror_rows x depth] arrays
    (``mirror_dense`` makes the fold's plane of them); the series-sharded
    mirror's are [mirror_rows, depth], per-shard blocks."""

    vals: jax.Array
    wts: jax.Array
    rows_hi: int
    samples: int
    chunks: int


class MicroFoldMirror:
    """Device-side mirror (M rows of B slots) of one epoch's staging
    plane. Unsharded, allocation, scatter, growth and the dense view are
    this module's functions over flat [M x B] arrays; a ``shard`` supplies
    all four of its own over [M/D, B] blocks.

    Single-threaded by contract: the worker's ingest lock serializes
    feed() (micro-fold scheduler) against finish() (swap). The ledger
    (optional) books uploads into its epoch accumulator, so the flush
    that extracts this epoch reports them.
    """

    def __init__(self, depth: int, ledger=None,
                 initial_rows: int = 1024,
                 chunk: int = MICRO_CHUNK, shard=None,
                 guard=None) -> None:
        self.depth = int(depth)
        self.chunk = int(chunk)
        self._ledger = ledger
        # device guard (ops/device_guard.DeviceGuard): the scatter is the
        # mirror's one donating device dispatch, so it routes through the
        # guard's fault seam. A fault here surfaces as DeviceFaultError
        # to the caller (worker.micro_fold_once), which drops the mirror
        # and falls back to the retained staging plane — the mirror is a
        # CACHE of staged state, never the only copy.
        self._guard = guard
        # series-sharded mirror (ops/series_shard.SeriesSharding): the
        # carry buffers keep LOGICAL rows — translation to physical slots
        # happens at dispatch, against the mirror size current THEN, so
        # growth between drains never strands a buffered row. Growth and
        # the dense view go through the shard's per-local-block programs
        # (append-at-end growth would break the interleave).
        self._shard = shard
        # False while the epoch is live (uploads book into the ledger's
        # epoch accumulator, surfaced by the flush that extracts it);
        # the swap rotation flips it True so the deferred residual feeds
        # — which run inside extract_snapshot, after begin_flush() popped
        # this epoch's tally as the open window — book into that same
        # window directly.
        self.book_in_flush = False
        self._rows0 = max(1, int(initial_rows))
        if shard is not None:
            # mirror rows must stay pow2 multiples of the shard count so
            # local blocks are equal-sized
            r = 1
            while r < max(self._rows0, shard.shards):
                r *= 2
            self._rows0 = r
        self._dvals: Optional[jax.Array] = None
        self._dwts: Optional[jax.Array] = None
        self._m = 0
        self.rows_hi = 0   # 1 + highest real row scattered this epoch
        self.samples = 0   # real COO entries fed (padding excluded)
        self.chunks = 0    # fixed-size scatter dispatches
        self._unsynced = 0
        # carry buffer: the partial-chunk remainder persists across
        # drains so upload totals are partition-invariant
        self._new_carry()

    @property
    def mirror_rows(self) -> int:
        """Rows the device mirror has allocated (0 before the first
        dispatch): what a scatter writes into, whatever it writes."""
        return self._m

    def _new_carry(self) -> None:
        """Fresh host buffers for the next chunk. An upload returns
        before the device has the bytes (and the CPU backend aliases an
        aligned host buffer outright), so a dispatched chunk's buffers
        belong to its scatter: refilling them in place put the next
        chunk's samples under the previous chunk's rows."""
        self._c_rows = np.empty(self.chunk, np.int32)
        self._c_slots = np.empty(self.chunk, np.int32)
        self._c_vals = np.empty(self.chunk, np.float32)
        self._c_wts = np.empty(self.chunk, np.float32)
        self._c_n = 0

    def feed(self, rows, slots, vals, wts) -> None:
        """Buffer one drained COO delta; dispatch every full chunk."""
        n = len(rows)
        if n == 0:
            return
        self.samples += n
        hi = int(rows.max()) + 1
        if hi > self.rows_hi:
            self.rows_hi = hi
        i = 0
        while i < n:
            take = min(self.chunk - self._c_n, n - i)
            s = slice(self._c_n, self._c_n + take)
            self._c_rows[s] = rows[i:i + take]
            self._c_slots[s] = slots[i:i + take]
            self._c_vals[s] = vals[i:i + take]
            self._c_wts[s] = wts[i:i + take]
            self._c_n += take
            i += take
            if self._c_n == self.chunk:
                self._dispatch()
                self._new_carry()

    def finish(self) -> Optional[MirrorState]:
        """Flush the carry (padded to a full chunk with drop-sentinel
        rows), detach the mirror for the swapped epoch, and reset.
        None when nothing was staged this epoch."""
        if self.samples == 0:
            self._c_n = 0
            return None
        if self._c_n > 0:
            self._c_rows[self._c_n:] = DROP_ROW
            self._c_slots[self._c_n:] = 0
            self._c_vals[self._c_n:] = 0.0
            self._c_wts[self._c_n:] = 0.0
            self._dispatch()
            self._new_carry()
        state = MirrorState(self._dvals, self._dwts, self.rows_hi,
                            self.samples, self.chunks)
        self._dvals = None
        self._dwts = None
        self._m = 0
        self.rows_hi = 0
        self.samples = 0
        self.chunks = 0
        self._unsynced = 0
        return state

    # -- internals --------------------------------------------------------

    def _dispatch(self) -> None:
        sh = self._shard
        # sharded: the physical-slot translation needs the mirror's
        # CURRENT row count, so sizing runs before the upload; unsharded
        # keeps the upload-first order (it overlaps the in-flight scatter)
        if sh is not None:
            self._ensure_rows(self.rows_hi)
            rows_np = sh.phys_rows(self._c_rows, self._m)
        else:
            rows_np = self._c_rows
        reps = sh.shards if sh is not None else 1
        put = sh.replicate if sh is not None else None
        if self._ledger is not None:
            up = (self._ledger.h2d if self.book_in_flush
                  else self._ledger.epoch_h2d)
            drows = up(rows_np, "micro_fold", replicas=reps, put=put)
            dslots = up(self._c_slots, "micro_fold", replicas=reps, put=put)
            dvals = up(self._c_vals, "micro_fold", replicas=reps, put=put)
            dwts = up(self._c_wts, "micro_fold", replicas=reps, put=put)
        elif sh is not None:
            drows = sh.replicate(rows_np)
            dslots = sh.replicate(self._c_slots)
            dvals = sh.replicate(self._c_vals)
            dwts = sh.replicate(self._c_wts)
        else:
            drows = jnp.asarray(rows_np)
            dslots = jnp.asarray(self._c_slots)
            dvals = jnp.asarray(self._c_vals)
            dwts = jnp.asarray(self._c_wts)
        self._ensure_rows(self.rows_hi)
        # double-buffer fence: at most two unsynced scatters queued
        self._unsynced += 1
        if self._unsynced > 2:
            with wait_span(self._guard, "micro.fence"):
                jax.block_until_ready(self._dvals)
            self._unsynced = 1
        scatter = (functools.partial(_scatter_chunk, depth=self.depth)
                   if sh is None else sh.scatter_chunk)
        if self._guard is not None:
            # donated operands — never retryable
            self._dvals, self._dwts = self._guard.call(
                "micro", scatter,
                self._dvals, self._dwts, drows, dslots, dvals, dwts)
        else:
            self._dvals, self._dwts = scatter(
                self._dvals, self._dwts, drows, dslots, dvals, dwts)
        self.chunks += 1

    def _ensure_rows(self, needed: int) -> None:
        m = self._m or self._rows0
        while m < needed:
            m *= 2
        if m == self._m:
            return
        sh = self._shard
        if sh is None and m * self.depth >= 2 ** 31:
            raise ValueError(
                f"micro-fold mirror of {m} rows x {self.depth} slots: "
                "the flat index row x depth + slot must fit int32")
        if self._dvals is None:
            if sh is None:
                self._dvals = jnp.zeros((m * self.depth,), jnp.float32)
                self._dwts = jnp.zeros((m * self.depth,), jnp.float32)
            else:
                shape = (m, self.depth)
                self._dvals = sh.place(jnp.zeros(shape, jnp.float32))
                self._dwts = sh.place(jnp.zeros(shape, jnp.float32))
        elif sh is None:
            self._dvals = _grow_mirror(self._dvals, m * self.depth)
            self._dwts = _grow_mirror(self._dwts, m * self.depth)
        else:
            self._dvals = sh.grow_2d(self._dvals, m)
            self._dwts = sh.grow_2d(self._dwts, m)
        self._m = m
