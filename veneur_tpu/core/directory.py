"""Series directory: MetricKey → dense device-pool row assignment.

The reference keys per-flush sampler state with 13 Go maps split by type and
scope (worker.go:60-103). On TPU, sketch state must live in dense, fixed-
shape device arrays, so the maps become this directory: each (key, class)
gets a row index into one of two device pools (t-digest rows for
histogram/timer series, HLL rows for set series), and the scope split
becomes a per-row class label consulted only at flush/forward time — the
device programs are scope-oblivious and operate on whole pools.

Like the reference, aggregation state lives one flush interval: the
directory (and its pools) is swapped wholesale at flush (the map-swap of
worker.go:498-517 becomes a directory+buffer swap), and a series not written
in an interval has no row in it. What outlives the interval is what a series
*is*, not what it holds: on the native path a context names a series by a
lifetime id and the worker keeps its strings and objects per id
(``LifetimeSeries``), so an interval's book of rows (``RowBook``) is an array
of those ids until something needs it to be more.
"""

from __future__ import annotations

import enum
import threading
from array import array
from collections.abc import Sequence
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


class RowView(Sequence):
    """Rows [0, len(ids)) of a book that holds lifetime ids: row r is
    ``table[ids[r]]``. What ``entries`` / ``rows`` / ``meta`` read as
    while nothing was copied; it follows the book as rows are added. A
    loop over many rows iterates it (C speed); indexing it is a Python
    call a row, which is why the flush's per-row accessors do not come
    through here (``RowBook.accessors``)."""

    __slots__ = ("_table", "_ids")

    def __init__(self, table: list, ids: array) -> None:
        self._table = table
        self._ids = ids

    def __len__(self) -> int:
        return len(self._ids)

    def __getitem__(self, at):
        if isinstance(at, slice):
            return list(map(self._table.__getitem__, self._ids[at]))
        return self._table[self._ids[at]]

    def __iter__(self):
        return map(self._table.__getitem__, self._ids)


class RowBook:
    """What a pool keeps per row beside its values: the row's entry
    (``RowMeta`` in the device pools, a ``(key, tags, scope_class,
    sinks)`` tuple in the worker's scalar pools), scope and admission
    codes, the routed/rejected counts, the native emitters' frag blob,
    and the (key, scope class) -> row index. Rows are append-only within
    an interval.

    One of two representations at a time, chosen by what arrives:

    * **by id** — every row so far came from one native context's drain
      (worker._adopt_pending, single context): the book is a packed
      array of that context's lifetime series ids and a reference to the
      ``LifetimeSeries`` they index. Adopting a batch is one append of
      its ids (and three sums over the batch once the table holds a
      rejected, routed or frag-less series); entries, codes and the
      frag blob are derived from the ids when a reader asks.
    * **materialised** — a row came one at a time (the Python upsert
      path), a batch came through ``upsert_batch`` (reader shards), a
      second table showed up, or somebody asked for ``index``: the ids
      are resolved once into per-interval containers (an entry list,
      packed code arrays, the frag arena) and every later row is
      appended to those.
    """

    def __init__(self) -> None:
        # by id: the table (None = materialised) and the rows' ids in it
        self._known: Optional["LifetimeSeries"] = None
        self._sids = array("i")
        # [2, n] scope and admission codes taken for the ids, kept while
        # the row count stands (a flush reads them several times)
        self._taken: Optional[np.ndarray] = None
        # materialised: one slot per row
        self._entries: list = []
        # the index is filled when somebody reads it: a batch appends
        # rows without touching it, and on the native single-context
        # path (no tenancy) nobody ever asks
        self._index: dict = {}
        self._indexed = 0
        # per-row scope codes as a packed byte array (zero-copy numpy
        # view for the columnar flush — no O(rows) attribute walk at
        # flush time), plus a count of rows carrying veneursinkonly
        # routing so the common no-routing case skips per-row checks
        self._scope_codes = array("b")
        self.routed_rows = 0
        # per-row admission codes (1 admitted / 0 rejected), same
        # packed-byte idiom; rejected_rows counts them so the common
        # all-admitted case skips per-row checks entirely
        self._admit_codes = array("b")
        self.rejected_rows = 0
        # \x1e-joined wire_frag arena: kept current row by row once
        # materialised; by id joined when frag_blob() is asked (over the
        # _arena_rows rows there were then); poisoned (frag_clean False,
        # arena abandoned) the moment any row's frag is None
        self._arena = bytearray()
        self._arena_rows = 0
        self._blob_lock = threading.Lock()
        self.frag_clean = True
        # what the flush reports (flush.begin attrs): whether ids were
        # resolved into containers, and the joins frag_blob() ran
        self.materialised = False
        self.frag_blob_builds = 0

    @staticmethod
    def _ikey(entry) -> tuple:
        """An entry's index key, (MetricKey, ScopeClass)."""
        raise NotImplementedError

    @property
    def entries(self):
        """Row r's entry at [r]: the list, or a view over the ids."""
        if self._known is not None:
            return RowView(self._known.entries, self._sids)
        return self._entries

    def accessors(self) -> tuple:
        """(meta_at, frag_at): row i's ``(name, tags, sinks)`` and its
        wire frag (None = separators in the data), for the columnar
        flush, whose sinks call them once a row (394k-1.18M times a
        flush). By id each is two built-in subscripts into what the
        table keeps per series, ``metas[ids[i]]``: no attribute walk, no
        tuple built, and never a sequence written in Python. The tuple
        and the tags list are the series' own: callers do not mutate
        them."""
        known = self._known
        if known is None:
            return self._entry_accessors(self._entries)

        def meta_at(i, _metas=known.metas, _ids=self._sids):
            return _metas[_ids[i]]

        def frag_at(i, _frags=known.frags, _ids=self._sids):
            return _frags[_ids[i]]

        return meta_at, frag_at

    @staticmethod
    def _entry_accessors(entries: list) -> tuple:
        """``accessors`` over a materialised book's entry list."""
        raise NotImplementedError

    def _codes(self) -> np.ndarray:
        n = len(self._sids)
        if self._taken is None or self._taken.shape[1] != n:
            sids = np.frombuffer(self._sids, np.int32)
            self._taken = self._known.codes[:2].take(sids, axis=1)
            # derived: a write would be lost with the next batch
            self._taken.flags.writeable = False
        return self._taken

    @property
    def scope_codes(self):
        """int8 per row, as a buffer (np.frombuffer reads either)."""
        if self._known is not None:
            return self._codes()[LifetimeSeries.SCOPE]
        return self._scope_codes

    @property
    def admit_codes(self):
        if self._known is not None:
            return self._codes()[LifetimeSeries.ADMITTED]
        return self._admit_codes

    @property
    def index(self) -> dict:
        """(MetricKey, ScopeClass) -> row over every row adopted so far.
        Its readers: the Python upsert path, the reader-shard reconcile
        (upsert_batch) and the worker's tenancy gate."""
        self._materialise()
        n = len(self._entries)
        if self._indexed < n:
            at = self._indexed
            self._index.update(
                zip(map(self._ikey, self._entries[at:]), range(at, n)))
            self._indexed = n
        return self._index

    def frag_blob(self) -> Optional[bytearray]:
        """The native emitters' metadata buffer for this pool, or None
        when some row needs the Python path. By id it is joined here, on
        the first request, and anew only if rows came since."""
        if not self.frag_clean:
            return None
        if self._known is not None:
            # the sinks' threads may all ask at once: one joins
            with self._blob_lock:
                n = len(self._sids)
                if self._arena_rows != n:
                    self._arena = bytearray(b"\x1e".join(map(
                        self._known.frags.__getitem__, self._sids)))
                    self._arena_rows = n
                    self.frag_blob_builds += 1
        return self._arena

    def _materialise(self) -> None:
        """Resolve the ids into per-interval containers, once; from here
        on the book is what it was before it could hold ids."""
        known = self._known
        if known is None:
            return
        self.frag_blob()  # the arena, current, while the ids still say it
        self._known = None
        self._entries, codes, _ = known.take(
            np.frombuffer(self._sids, np.int32))
        self._keep_codes(codes)
        self._sids = array("i")
        self._taken = None
        self.materialised = True

    def _keep_codes(self, codes) -> None:
        """A batch's scope and admission codes onto the packed arrays."""
        self._scope_codes.frombytes(codes[LifetimeSeries.SCOPE].tobytes())
        self._admit_codes.frombytes(
            codes[LifetimeSeries.ADMITTED].tobytes())

    def _tally(self, admitted, routed, no_frag) -> None:
        """Count a batch's rejected and routed rows and poison the arena
        if a frag is None, from its rows of LifetimeSeries.codes."""
        self.rejected_rows += len(admitted) - np.count_nonzero(admitted)
        self.routed_rows += np.count_nonzero(routed)
        if no_frag.any():
            self.frag_clean = False

    def _append(self, row: int, entry, scope_class, sinks, admitted: bool,
                frag) -> None:
        """One row, assigned by the caller in append order."""
        self._materialise()
        assert row == len(self._entries), "rows must be adopted in order"
        if self._indexed == row:  # keep a current index current
            self._index[self._ikey(entry)] = row
            self._indexed = row + 1
        self._entries.append(entry)
        self._scope_codes.append(int(scope_class))
        self._admit_codes.append(1 if admitted else 0)
        if not admitted:
            self.rejected_rows += 1
        if sinks is not None:
            self.routed_rows += 1
        if self.frag_clean:
            if frag is None:
                self.frag_clean = False
            else:
                if row:
                    self._arena += b"\x1e"
                self._arena += frag

    def _extend(self, entries: list, codes, frags: list) -> None:
        """A batch onto a materialised book (``LifetimeSeries.take``'s
        triple)."""
        first_row = len(self._entries)
        self._entries.extend(entries)
        self._keep_codes(codes)
        self._tally(*codes[LifetimeSeries.ADMITTED:
                           LifetimeSeries.NO_FRAG + 1])
        if self.frag_clean:
            if first_row:
                self._arena += b"\x1e"
            self._arena += b"\x1e".join(frags)

    def adopt_batch(self, first_row: int, known: "LifetimeSeries",
                    sids: np.ndarray) -> int:
        """Rows [first_row, first_row + len(sids)) at once: the series
        ``sids`` (int32) of ``known``, in row order. A book that holds
        nothing yet, or ids of the same table, appends the ids and does
        nothing per series; returns how many rows it took that way."""
        assert first_row == len(self.entries), \
            "rows must be adopted in order"
        if self._known is None and not self._entries:
            self._known = known
        if self._known is not known:
            self._materialise()
            self._extend(*known.take(sids))
            return 0
        self._sids.frombytes(sids.astype(np.int32, copy=False).tobytes())
        if not known.plain:
            # (a take a row: ten times faster than codes[1:4, sids])
            self._tally(*(known.codes[row].take(sids) for row in (
                LifetimeSeries.ADMITTED, LifetimeSeries.ROUTED,
                LifetimeSeries.NO_FRAG)))
        return len(sids)

    def upsert_batch(self, known: "LifetimeSeries",
                     sids: np.ndarray) -> list:
        """The reader-shard reconcile: N per-reader row spaces fold into
        this canonical pool, so a series that arrived through another
        reader (or the Python path) keeps the row it has and only the
        others are adopted. Returns every series' canonical row."""
        entries, codes, frags = known.take(sids)
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
            base = len(self._entries)
            if len(fresh) == len(entries):
                self._extend(entries, codes, frags)
            else:
                self._extend(
                    [entries[i] for i in fresh], codes[:, fresh],
                    [frags[i] for i in fresh])
            index.update(zip(map(keys.__getitem__, fresh),
                             range(base, base + len(fresh))))
            self._indexed = len(self._entries)
            found = list(map(index.__getitem__, keys))
        return found


class _Pool(RowBook):
    """A device pool's rows: ``rows[r]`` is row r's RowMeta."""

    @property
    def rows(self):
        return self.entries

    @staticmethod
    def _ikey(meta: RowMeta) -> tuple:
        return (meta.key, meta.scope_class)

    @staticmethod
    def _entry_accessors(rows: list) -> tuple:
        def meta_at(i, _rows=rows):
            m = _rows[i]
            return m.key.name, m.tags, m.sinks

        def frag_at(i, _rows=rows):
            return _rows[i].wire_frag()

        return meta_at, frag_at

    def upsert(self, key: MetricKey, scope_class: ScopeClass, tags: list[str],
               tenant: str = "") -> tuple[int, bool]:
        row = self.index.get((key, scope_class))
        if row is not None:
            return row, False
        row = len(self._entries)
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
    and an interval's re-registration is its integer: the pool entry
    (``RowMeta`` for the device pools, the scalar pools' tuple), its
    wire frag, and packed per-sid codes. A context hands a sid's strings
    over once (NativeIngest.drain_new_series). A table only grows: the
    books of an interval hold ids into it (RowBook, by id), snapshots
    keep those books past the interval, so when the context drops its
    table (a new ``generation``) the worker starts a new one and leaves
    this one to whoever still reads it."""

    # rows of ``codes``: scope class, then what RowBook._tally sums
    # (admitted, routed = has sinks, the frag is None), then whether the
    # series counts toward the unique-timeseries tally
    SCOPE, ADMITTED, ROUTED, NO_FRAG, COUNTED = range(5)

    def __init__(self, generation: int = 0) -> None:
        self.generation = generation
        self.entries: list = []  # None: a sid not learnt (yet)
        self.frags: list = []
        # (name, tags, sinks): what RowBook.accessors' meta_at returns
        self.metas: list = []
        self.codes = np.zeros((5, 1024), np.int8)
        self.ts_hash = np.zeros(1024, np.uint64)
        # no series so far is rejected, routed or without a frag: a
        # book that takes ids of such a table has nothing to count
        self.plain = True

    def __len__(self) -> int:
        return len(self.entries)

    def reserve(self, n: int) -> None:
        """Room for sids [0, n)."""
        short = n - len(self.entries)
        if short > 0:
            self.entries.extend([None] * short)
            self.frags.extend([None] * short)
            self.metas.extend([None] * short)
        cap = self.codes.shape[1]
        if n > cap:
            while cap < n:
                cap *= 2
            grown = np.zeros((5, cap), np.int8)
            grown[:, :self.codes.shape[1]] = self.codes
            self.codes = grown
            self.ts_hash = np.concatenate(
                [self.ts_hash, np.zeros(cap - len(self.ts_hash), np.uint64)])

    def put(self, sid: int, entry, meta: tuple, frag, scope_class: int,
            admitted: bool, ts_hash: Optional[int]) -> None:
        self.entries[sid] = entry
        self.metas[sid] = meta
        self.frags[sid] = frag
        routed, no_frag = meta[2] is not None, frag is None
        self.codes[:, sid] = (scope_class, admitted, routed, no_frag,
                              ts_hash is not None)
        if routed or no_frag or not admitted:
            self.plain = False
        self.ts_hash[sid] = ts_hash or 0

    def take(self, sids: np.ndarray) -> tuple:
        """(entries, codes [5, n], frags) of a batch of sids, copied by
        reference into new containers: what a materialised book keeps."""
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
