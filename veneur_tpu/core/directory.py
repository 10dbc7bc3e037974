"""Series directory: MetricKey → dense device-pool row assignment.

The reference keys per-flush sampler state with 13 Go maps split by type and
scope (worker.go:60-103). On TPU, sketch state must live in dense, fixed-
shape device arrays, so the maps become this directory: each (key, class)
gets a row index into one of two device pools (t-digest rows for
histogram/timer series, HLL rows for set series), and the scope split
becomes a per-row class label consulted only at flush/forward time — the
device programs are scope-oblivious and operate on whole pools.

Like the reference, all aggregation state lives exactly one flush interval:
the directory (and its pools) is swapped wholesale at flush (the map-swap of
worker.go:498-517 becomes a directory+buffer swap).
"""

from __future__ import annotations

import enum
from array import array
from dataclasses import dataclass
from typing import Optional

import numpy as np

from veneur_tpu.core.metrics import MetricKey, MetricScope, route_info


class ScopeClass(enum.IntEnum):
    """Which of the reference's map groups a series belongs to
    (worker.go:60-103: plain / global* / local* maps)."""

    MIXED = 0
    LOCAL = 1
    GLOBAL = 2


def classify(mtype: str, scope: MetricScope) -> ScopeClass:
    """Reference WorkerMetrics.Upsert routing (worker.go:108-177)."""
    if mtype in ("counter", "gauge"):
        return (
            ScopeClass.GLOBAL
            if scope == MetricScope.GLOBAL_ONLY
            else ScopeClass.MIXED
        )
    if mtype in ("histogram", "timer"):
        if scope == MetricScope.LOCAL_ONLY:
            return ScopeClass.LOCAL
        if scope == MetricScope.GLOBAL_ONLY:
            return ScopeClass.GLOBAL
        return ScopeClass.MIXED
    if mtype == "set":
        return (
            ScopeClass.LOCAL
            if scope == MetricScope.LOCAL_ONLY
            else ScopeClass.MIXED
        )
    if mtype == "status":
        return ScopeClass.LOCAL
    return ScopeClass.MIXED


def build_frag(name: str, tags: list[str]):
    """One blob record for the native batch encoders:
    "name \\x1f tag \\x1f tag ..." utf-8, or None when the data itself
    contains the record/field separators (those rows need the Python
    formatter)."""
    rec = name + "\x1f" + "\x1f".join(tags) if tags else name
    # one \x1f per tag was put in: any more came with the data
    if "\x1e" in rec or rec.count("\x1f") != len(tags):
        return None
    return rec.encode("utf-8")


@dataclass
class RowMeta:
    """Host-side metadata for one pool row (what the dense arrays can't
    hold: names, tags, routing)."""

    key: MetricKey
    tags: list[str]
    scope_class: ScopeClass
    sinks: Optional[frozenset[str]]  # from veneursinkonly: tags
    # per-tenant QoS (core/tenancy.py): which tenant owns the series, and
    # whether the tenant ledger admitted it. The Python upsert path never
    # creates a row for a rejected series; the native path assigns rows in
    # C++ before Python sees them, so a rejected series lands here with
    # admitted=False and the flush skips it (both emit paths).
    tenant: str = ""
    admitted: bool = True
    # lazily-built wire fragment for the native encoders; False = not
    # yet built, None = contains the separators, use the Python path
    _frag: object = False

    def wire_frag(self):
        """Cached blob record for the native batch encoders. RowMeta
        objects outlive epochs (the worker keeps one per lifetime series
        id), so this builds once per series lifetime."""
        frag = self._frag
        if frag is False:
            frag = build_frag(self.key.name, self.tags)
            self._frag = frag
        return frag


class RowBook:
    """What a pool keeps per row beside its values: the row's entry
    (``RowMeta`` in the device pools, a ``(key, tags, scope_class,
    sinks)`` tuple in the worker's scalar pools), packed scope and
    admission codes, the routed/rejected counts, the native emitters'
    frag arena, and the (key, scope class) -> row index. Rows are
    append-only within an interval, one at a time (the Python upsert
    path) or a batch at a time (native adoption, worker._adopt_pending).
    """

    def __init__(self) -> None:
        self.entries: list = []
        # the index is filled when somebody reads it: a batch appends
        # rows without touching it, and on the native single-context
        # path (no tenancy) nobody ever asks
        self._index: dict = {}
        self._indexed = 0
        # per-row scope codes as a packed byte array (zero-copy numpy
        # view for the columnar flush — no O(rows) attribute walk at
        # flush time), plus a count of rows carrying veneursinkonly
        # routing so the common no-routing case skips per-row checks
        self.scope_codes = array("b")
        self.routed_rows = 0
        # per-row admission codes (1 admitted / 0 rejected), same
        # packed-byte idiom; rejected_rows counts them so the common
        # all-admitted case skips per-row checks entirely
        self.admit_codes = array("b")
        self.rejected_rows = 0
        # \x1e-joined wire_frag arena over rows [0, len(entries)),
        # maintained at adopt so the flush hands the native emit tier
        # one contiguous buffer with zero per-row work; poisoned
        # (frag_clean False, arena abandoned) the moment any row's frag
        # is None
        self.frag_arena = bytearray()
        self.frag_clean = True

    @staticmethod
    def _ikey(entry) -> tuple:
        """An entry's index key, (MetricKey, ScopeClass)."""
        raise NotImplementedError

    @property
    def index(self) -> dict:
        """(MetricKey, ScopeClass) -> row over every row adopted so far.
        Its readers: the Python upsert path, the reader-shard reconcile
        (upsert_batch) and the worker's tenancy gate."""
        n = len(self.entries)
        if self._indexed < n:
            at = self._indexed
            self._index.update(
                zip(map(self._ikey, self.entries[at:]), range(at, n)))
            self._indexed = n
        return self._index

    def frag_blob(self) -> Optional[bytearray]:
        """The native emitters' metadata buffer for this pool, or None
        when some row needs the Python path."""
        return self.frag_arena if self.frag_clean else None

    def _append(self, row: int, entry, scope_class, sinks, admitted: bool,
                frag) -> None:
        """One row, assigned by the caller in append order."""
        assert row == len(self.entries), "rows must be adopted in order"
        if self._indexed == row:  # keep a current index current
            self._index[self._ikey(entry)] = row
            self._indexed = row + 1
        self.entries.append(entry)
        self.scope_codes.append(int(scope_class))
        self.admit_codes.append(1 if admitted else 0)
        if not admitted:
            self.rejected_rows += 1
        if sinks is not None:
            self.routed_rows += 1
        if self.frag_clean:
            if frag is None:
                self.frag_clean = False
            else:
                if row:
                    self.frag_arena += b"\x1e"
                self.frag_arena += frag

    def adopt_batch(self, first_row: int, entries: list, codes,
                    frags: list) -> None:
        """Rows [first_row, first_row + len(entries)) at once. The first
        four rows of ``codes`` (int8, n columns: LifetimeSeries.codes) are
        scope class, admitted, routed (has sinks) and whether the row's
        frag is None; ``frags`` the rows' wire frags."""
        assert first_row == len(self.entries), \
            "rows must be adopted in order"
        n = len(entries)
        self.entries.extend(entries)
        self.scope_codes.frombytes(codes[0].tobytes())
        self.admit_codes.frombytes(codes[1].tobytes())
        _, admitted, routed, no_frag = codes[:4].sum(axis=1, dtype=np.int64)
        self.rejected_rows += n - int(admitted)
        self.routed_rows += int(routed)
        if self.frag_clean:
            if no_frag:
                self.frag_clean = False
            else:
                if first_row:
                    self.frag_arena += b"\x1e"
                self.frag_arena += b"\x1e".join(frags)

    def upsert_batch(self, entries: list, codes, frags: list) -> list:
        """The reader-shard reconcile: N per-reader row spaces fold into
        this canonical pool, so a series that arrived through another
        reader (or the Python path) keeps the row it has and only the
        others are adopted. Returns every entry's canonical row."""
        index = self.index
        keys = list(map(self._ikey, entries))
        found = list(map(index.get, keys))
        if None in found:
            fresh = [i for i, row in enumerate(found) if row is None]
            # two wire series whose separators were substituted can read
            # the same: the first one of a key takes the row
            first = dict(zip(map(keys.__getitem__, reversed(fresh)),
                             reversed(fresh)))
            if len(first) != len(fresh):
                fresh = sorted(first.values())
            base = len(self.entries)
            if len(fresh) == len(entries):
                self.adopt_batch(base, entries, codes, frags)
            else:
                self.adopt_batch(
                    base, [entries[i] for i in fresh], codes[:, fresh],
                    [frags[i] for i in fresh])
            index.update(zip(map(keys.__getitem__, fresh),
                             range(base, base + len(fresh))))
            self._indexed = len(self.entries)
            found = list(map(index.__getitem__, keys))
        return found


class _Pool(RowBook):
    """A device pool's rows: ``rows[r]`` is row r's RowMeta."""

    def __init__(self) -> None:
        super().__init__()
        self.rows: list[RowMeta] = self.entries

    @staticmethod
    def _ikey(meta: RowMeta) -> tuple:
        return (meta.key, meta.scope_class)

    def upsert(self, key: MetricKey, scope_class: ScopeClass, tags: list[str],
               tenant: str = "") -> tuple[int, bool]:
        row = self.index.get((key, scope_class))
        if row is not None:
            return row, False
        row = len(self.rows)
        self.adopt(row, key, scope_class, tags, tenant=tenant)
        return row, True

    def adopt(self, row: int, key: MetricKey, scope_class: ScopeClass,
              tags: list[str], tenant: str = "") -> None:
        """Register metadata for a row assigned externally (the native
        directory assigns rows in the same append order)."""
        self.adopt_meta(row, RowMeta(
            key=key, tags=tags, scope_class=scope_class,
            sinks=route_info(tags), tenant=tenant))

    def adopt_meta(self, row: int, meta: RowMeta) -> None:
        """Adopt with prebuilt metadata."""
        self._append(row, meta, meta.scope_class, meta.sinks, meta.admitted,
                     meta.wire_frag() if self.frag_clean else None)


class LifetimeSeries:
    """What the worker keeps per lifetime series id (``sid``) of one
    native context, so that a series' strings and objects are built once
    and an interval's re-registration is a take by integer: the pool
    entry (``RowMeta`` for the device pools, the scalar pools' tuple),
    its wire frag, and packed per-sid codes. A context hands a sid's
    strings over once (NativeIngest.drain_new_series); when it drops its
    table it says so by a new generation and everything here goes too."""

    # rows of ``codes``: what RowBook.adopt_batch reads, then whether the
    # series counts toward the unique-timeseries tally
    SCOPE, ADMITTED, ROUTED, NO_FRAG, COUNTED = range(5)

    def __init__(self) -> None:
        self.generation = 0
        self.entries: list = []  # None: a sid not learnt (yet)
        self.frags: list = []
        self.codes = np.zeros((5, 1024), np.int8)
        self.ts_hash = np.zeros(1024, np.uint64)

    def __len__(self) -> int:
        return len(self.entries)

    def clear(self, generation: int) -> None:
        self.__init__()
        self.generation = generation

    def reserve(self, n: int) -> None:
        """Room for sids [0, n)."""
        short = n - len(self.entries)
        if short > 0:
            self.entries.extend([None] * short)
            self.frags.extend([None] * short)
        cap = self.codes.shape[1]
        if n > cap:
            while cap < n:
                cap *= 2
            grown = np.zeros((5, cap), np.int8)
            grown[:, :self.codes.shape[1]] = self.codes
            self.codes = grown
            self.ts_hash = np.concatenate(
                [self.ts_hash, np.zeros(cap - len(self.ts_hash), np.uint64)])

    def put(self, sid: int, entry, frag, scope_class: int, admitted: bool,
            routed: bool, ts_hash: Optional[int]) -> None:
        self.entries[sid] = entry
        self.frags[sid] = frag
        self.codes[:, sid] = (scope_class, admitted, routed, frag is None,
                              ts_hash is not None)
        self.ts_hash[sid] = ts_hash or 0

    def take(self, sids: np.ndarray) -> tuple:
        """(entries, codes [5, n], frags) of a batch of sids."""
        at = sids.tolist()
        return (list(map(self.entries.__getitem__, at)),
                self.codes[:, sids],
                list(map(self.frags.__getitem__, at)))


class SeriesDirectory:
    """One flush interval's series → row mapping for both device pools.

    Distinct (key, scope_class) pairs get distinct rows, mirroring the
    reference where the same MetricKey can live in e.g. both `timers` and
    `globalTimers` maps simultaneously.
    """

    def __init__(self) -> None:
        self.histo = _Pool()  # histogram + timer series → t-digest rows
        self.sets = _Pool()  # set series → HLL rows

    def upsert_histo(self, key: MetricKey, scope_class: ScopeClass,
                     tags: list[str], tenant: str = "") -> tuple[int, bool]:
        return self.histo.upsert(key, scope_class, tags, tenant=tenant)

    def upsert_set(self, key: MetricKey, scope_class: ScopeClass,
                   tags: list[str], tenant: str = "") -> tuple[int, bool]:
        return self.sets.upsert(key, scope_class, tags, tenant=tenant)

    @property
    def num_histo_rows(self) -> int:
        return len(self.histo.rows)

    @property
    def num_set_rows(self) -> int:
        return len(self.sets.rows)

    def shard_counts(self, shards: int) -> tuple[list[int], list[int]]:
        """Live rows per device shard under the series-sharded row
        interleave (ops/series_shard.py: logical row r lives on shard
        r % shards): (histo_rows_per_shard, set_rows_per_shard).

        The interleave balances by construction — max−min ≤ 1 per pool —
        so this is a telemetry/bench readout (shard occupancy for
        capacity math), never a balancing input."""
        nh, ns = len(self.histo.rows), len(self.sets.rows)
        return ([(nh + shards - 1 - d) // shards for d in range(shards)],
                [(ns + shards - 1 - d) // shards for d in range(shards)])
