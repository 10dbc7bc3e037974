"""Per-flush host<->device transfer byte accounting.

The round-5 transfer diet made both flush boundaries O(samples): the
staged upload compacts the native [S, depth] plane to flat samples +
counts before device_put (worker._fold_one_plane ->
_expand_flat_planes), and the extraction readback packs eleven columns
into one [S, P+10] f32 array (_pack_extract_columns). Both invariants
are easy to regress silently — one refactor that uploads the dense
plane again is a 268 MB/flush mistake at 1M series x depth 64 that no
unit test on VALUES can see, because the dense and compacted paths are
numerically identical.

The ledger makes bytes first-class: every flush-path transfer goes
through `h2d`/`d2h`, which count the array's nbytes per kind before
handing it to jnp.asarray / np.asarray. The per-flush totals surface
as self-telemetry (veneur.flush.transfer_{h2d,d2h}_bytes) and are
pinned by tests/test_health_ledger.py, which asserts the staged upload
is ~ samples x 4 + counts x 4 bytes INDEPENDENT OF DEPTH.

Counting sits host-side around the existing transfer calls rather than
in a jax transfer-guard hook: guards can veto transfers but do not
expose byte counts, and the flush path's transfers are few and known.

Thread-safety: one ledger per worker. `begin_flush` runs at the start
of extract_snapshot — the same stage that performs every counted
transfer — so window reset, counting, and the server's end-of-extract
reads are all serialized on the flush thread.
Telemetry reads from other threads may still race a count, so mutation
goes through a lock. Overhead is a dict update per transfer —
nanoseconds against a millisecond-scale device round-trip.
"""

from __future__ import annotations

import threading

import numpy as np


class TransferLedger:
    """Byte accounting for one worker's flush-path device transfers."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # per-kind byte tallies for the CURRENT flush (reset by
        # begin_flush) and for the process lifetime
        self._flush_h2d: dict[str, int] = {}
        self._flush_d2h: dict[str, int] = {}
        self.total_h2d_bytes = 0
        self.total_d2h_bytes = 0
        self.flushes = 0
        # micro-fold uploads happen DURING the epoch, before the flush
        # window that will report them opens. They accumulate here;
        # roll_epoch() (called at swap) queues the closed epoch's tally,
        # and begin_flush() folds the oldest queued epoch into the new
        # window (swaps and extractions are 1:1).
        self._epoch_h2d: dict[str, int] = {}
        self._pending_epochs: list[dict[str, int]] = []
        # per-SHARD byte breakdown for the current flush (series-sharded
        # pools, ops/series_shard.py): index i = bytes that landed on /
        # came from shard i. Empty on the single-device path. The chunk
        # governor's per-shard sizing and the sharded transfer-diet test
        # read these; kind tallies above stay the cross-shard totals.
        self._flush_h2d_shards: list[int] = []
        self._flush_d2h_shards: list[int] = []
        # per-READER byte attribution for the current flush (reader-
        # sharded ingest, core/worker.attach_reader_shards): index i =
        # staged bytes that originated in context i (0 = home). Unlike
        # the shard lists these do NOT add to the kind tallies — the
        # merged batch is uploaded once and booked by h2d(); this is a
        # provenance breakdown of that single transfer.
        self._flush_h2d_readers: list[int] = []
        # flushes (lifetime) whose extraction completed on the HOST
        # engine after a device fault (ops/device_guard quarantine or a
        # mid-extract fault): the device mirror was bypassed, so the
        # transfer-diet numbers for those flushes legitimately shrink.
        # Surfaced as veneur.flush.host_fallbacks by the server.
        self.host_fallbacks = 0
        self._flush_fallback = False

    def note_fallback(self) -> None:
        """Mark the current flush as host-fallback (device path faulted
        or quarantined; extraction finished on ops/host_engine)."""
        with self._lock:
            if not self._flush_fallback:
                self._flush_fallback = True
                self.host_fallbacks += 1

    @property
    def flush_was_fallback(self) -> bool:
        with self._lock:
            return self._flush_fallback

    def begin_flush(self) -> None:
        with self._lock:
            self._flush_h2d = (
                self._pending_epochs.pop(0) if self._pending_epochs else {})
            self._flush_d2h = {}
            self._flush_h2d_shards = []
            self._flush_d2h_shards = []
            self._flush_h2d_readers = []
            self._flush_fallback = False
            self.flushes += 1

    # -- transfer wrappers ------------------------------------------------

    def h2d(self, host_arr, kind: str, replicas: int = 1, put=None):
        """Count and perform one host->device upload. `replicas` > 1
        books the bytes once per device for a replicated placement
        (series-sharded COO batches, ops/series_shard.py): replication
        is a real per-device transfer, and the O(samples) transfer-diet
        pin must stay honest about the multiplier. `put` overrides the
        placement (e.g. SeriesSharding.replicate / .place); default is
        the process-default device."""
        import jax.numpy as jnp

        self.count_h2d(host_arr.nbytes * replicas, kind)
        return jnp.asarray(host_arr) if put is None else put(host_arr)

    def d2h(self, dev_arr, kind: str) -> np.ndarray:
        """Count and perform one device->host readback."""
        out = np.asarray(dev_arr)
        self.count_d2h(out.nbytes, kind)
        return out

    def epoch_h2d(self, host_arr, kind: str, replicas: int = 1, put=None):
        """Count and perform one mid-epoch (micro-fold) upload. Bytes
        land in the epoch accumulator, not the open flush window — they
        belong to the flush that will extract this epoch's state.
        `replicas`/`put` as in h2d (sharded micro-fold COO batches)."""
        import jax.numpy as jnp

        self.count_epoch_h2d(host_arr.nbytes * replicas, kind)
        return jnp.asarray(host_arr) if put is None else put(host_arr)

    def count_epoch_h2d(self, nbytes: int, kind: str) -> None:
        with self._lock:
            self._epoch_h2d[kind] = self._epoch_h2d.get(kind, 0) + int(nbytes)
            self.total_h2d_bytes += int(nbytes)

    def roll_epoch(self) -> None:
        """Close the current epoch's micro-fold tally (called at swap):
        queue it for the flush window that extracts the swapped state."""
        with self._lock:
            if self._epoch_h2d:
                self._pending_epochs.append(self._epoch_h2d)
                self._epoch_h2d = {}

    def count_h2d(self, nbytes: int, kind: str) -> None:
        with self._lock:
            self._flush_h2d[kind] = self._flush_h2d.get(kind, 0) + int(nbytes)
            self.total_h2d_bytes += int(nbytes)

    def count_d2h(self, nbytes: int, kind: str) -> None:
        with self._lock:
            self._flush_d2h[kind] = self._flush_d2h.get(kind, 0) + int(nbytes)
            self.total_d2h_bytes += int(nbytes)

    # -- per-shard accounting (series-sharded pools) ----------------------

    def count_h2d_shards(self, per_shard, kind: str) -> None:
        """Book one sharded upload: per_shard[i] bytes land on shard i
        (a replicated batch books its nbytes once PER shard; a
        partitioned plane books each shard's segment). The kind tally
        gets the total; the breakdown feeds flush_h2d_per_shard()."""
        per_shard = [int(b) for b in per_shard]
        total = sum(per_shard)
        with self._lock:
            self._flush_h2d[kind] = self._flush_h2d.get(kind, 0) + total
            self.total_h2d_bytes += total
            self._acc_shards(self._flush_h2d_shards, per_shard)

    def count_d2h_shards(self, per_shard, kind: str) -> None:
        per_shard = [int(b) for b in per_shard]
        total = sum(per_shard)
        with self._lock:
            self._flush_d2h[kind] = self._flush_d2h.get(kind, 0) + total
            self.total_d2h_bytes += total
            self._acc_shards(self._flush_d2h_shards, per_shard)

    @staticmethod
    def _acc_shards(acc: list, per_shard: list) -> None:
        if len(acc) < len(per_shard):
            acc.extend([0] * (len(per_shard) - len(acc)))
        for i, b in enumerate(per_shard):
            acc[i] += b

    def flush_h2d_per_shard(self) -> list:
        with self._lock:
            return list(self._flush_h2d_shards)

    # -- per-reader accounting (reader-sharded ingest) --------------------

    def count_h2d_readers(self, per_reader, kind: str) -> None:
        """Attribute already-booked upload bytes to reader contexts:
        per_reader[i] bytes of the merged staged batch originated in
        context i (0 = home, 1.. = reader shards). ATTRIBUTION ONLY —
        the merged flat plane goes through ONE h2d() call in
        _fold_one_plane which books the kind tally and totals; counting
        the bytes again here would double the transfer-diet pin, so
        this only feeds the flush_h2d_per_reader() breakdown."""
        per_reader = [int(b) for b in per_reader]
        with self._lock:
            self._acc_shards(self._flush_h2d_readers, per_reader)

    def flush_h2d_per_reader(self) -> list:
        with self._lock:
            return list(self._flush_h2d_readers)

    def flush_d2h_per_shard(self) -> list:
        with self._lock:
            return list(self._flush_d2h_shards)

    # -- reads ------------------------------------------------------------

    def flush_h2d(self) -> dict[str, int]:
        with self._lock:
            return dict(self._flush_h2d)

    def flush_d2h(self) -> dict[str, int]:
        with self._lock:
            return dict(self._flush_d2h)

    def flush_h2d_bytes(self) -> int:
        with self._lock:
            return sum(self._flush_h2d.values())

    def flush_d2h_bytes(self) -> int:
        with self._lock:
            return sum(self._flush_d2h.values())
