"""Columnar InterMetric batches: the SoA flush path.

The reference materializes one Go struct per flushed metric
(generateInterMetrics, flusher.go:225-298) — cheap in Go, ~1µs each in
CPython. At 1M histogram series × ~6 output series that is several
seconds of host time per flush, which alone blows the 10s interval.
The TPU-native design therefore keeps the flush columnar end to end:
device extraction already produces dense per-row arrays, and this module
wraps them — masks and values computed with numpy vector ops, per-row
metadata referenced from the existing directory lists (never copied) —
so a flush at 1M series costs milliseconds to "generate".

Sinks that can consume columns directly (blackhole, prometheus — any
sink whose wire format is built per-row anyway) implement
``flush_columnar`` and never pay for Python objects; everything else
receives ``materialize()``, which produces exactly the objects
``generate_inter_metrics`` would have (same multiset; family-major
order). The Server picks the path per flush (core/server.py).

Semantics mirror flusher.generate_inter_metrics exactly, including the
mixed-scope double-count rules (flusher.go:61-74): equivalence is
pinned by tests/test_columnar.py against the object path.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from veneur_tpu.core.metrics import InterMetric, MetricType

# aggregate columns appended after the [S, P] quantile block by the
# worker's packed extract (worker._pack_extract_columns): dmin, dmax,
# dsum, dcount, drecip, lmin, lmax, lsum, lweight, lrecip
EXTRACT_AGG_COLUMNS = 10


def unpack_extract_columns(packed: np.ndarray, p: int,
                           perm: Optional[np.ndarray] = None):
    """Split a packed extract array [S, P+10] back into the [S, P]
    quantile block and the ten [S] aggregate columns (the inverse of
    worker._pack_extract_columns, minus the f32 cast — that is one-way
    by design).

    ``perm``: optional row gather applied first — the series-sharded
    extract reads back in physical (shard-interleaved) row order and
    hands the logical-order permutation here."""
    if perm is not None:
        packed = packed[perm]
    qv = packed[:, :p]
    aggs = tuple(packed[:, p + i] for i in range(EXTRACT_AGG_COLUMNS))
    return qv, aggs


@dataclass
class MetricFamily:
    """One output series family over a row group: base-name suffix, type,
    per-row values, and an emission mask (None = every row emits)."""

    suffix: str
    type: MetricType
    values: np.ndarray  # f64[R]
    mask: Optional[np.ndarray]  # bool[R] or None

    def count(self, nrows: int) -> int:
        return int(self.mask.sum()) if self.mask is not None else nrows


@dataclass
class ColumnGroup:
    """Rows sharing a metadata table (histogram rows, set rows, counter
    rows, ...) and the families emitted over them.

    ``meta_at(i)`` returns (name, tags, sinks) for row i — an accessor
    into the directory's existing lists, so building a group never walks
    the rows."""

    nrows: int
    meta_at: Callable[[int], tuple]
    families: list[MetricFamily]
    # rows carrying veneursinkonly routing exist in this group (when
    # False, consumers skip all per-row routing checks)
    has_routing: bool = False
    # optional per-row wire fragment ("name \x1f tag \x1f ..." bytes)
    # accessor for native emitters; None entry = row needs the Python
    # path (separators in the data)
    frag_at: Optional[Callable[[int], Optional[bytes]]] = None
    # where to ask for the pool's \x1e-joined frag blob covering rows
    # [0, nrows) (directory.RowBook.frag_blob): a pool whose book holds
    # ids joins it on the first request, so a flush whose sinks all
    # take the columns pays for none
    blob_of: Optional[Callable[[], Optional[bytearray]]] = None

    @functools.cached_property
    def meta_blob(self) -> Optional[bytearray]:
        """The blob, handed to the native emit tier zero-copy (ctypes
        views the bytearray's buffer directly); None = some row needs
        the Python formatter."""
        return self.blob_of() if self.blob_of is not None else None

    def count(self) -> int:
        return sum(f.count(self.nrows) for f in self.families)

    def rows_for(self, family: MetricFamily) -> np.ndarray:
        if family.mask is None:
            return np.arange(self.nrows)
        return np.nonzero(family.mask)[0]


@dataclass
class EmitGroupPlan:
    """One group's buffers packed for the native emit tier: the frag
    arena plus family columns stacked C-contiguous. Built once per flush
    and shared by every native-capable sink (each used to rebuild the
    blob and restack the columns per flush)."""

    nrows: int
    meta_blob: bytearray  # \x1e-joined "name \x1f tag..." records
    suffixes: list[str]
    family_types: np.ndarray  # i8[F]: 0 = counter, 1 = gauge
    values: np.ndarray  # f64[F, R] C-contiguous
    masks: np.ndarray  # u8[F, R] C-contiguous


@dataclass
class ColumnarMetrics:
    """One flush interval's metric output, columnar."""

    timestamp: int
    groups: list[ColumnGroup] = field(default_factory=list)
    # rare, already-materialized metrics (status checks)
    extras: list[InterMetric] = field(default_factory=list)

    def count(self) -> int:
        return sum(g.count() for g in self.groups) + len(self.extras)

    def __len__(self) -> int:
        return self.count()

    def __iter__(self):
        # drop-in for the object-path list (tests and embedders iterate
        # Server.flush()'s return); memoized, so iterating twice is cheap
        return iter(self.materialize())

    def count_for(self, sink_name: str) -> int:
        """Metrics actually routed to one sink (veneursinkonly rules) —
        the per-sink flushed-total the object path reports. Groups with
        no routed rows (the common case) contribute their full count
        without any per-row walk."""
        total = 0
        for g in self.groups:
            if not g.has_routing:
                total += g.count()
                continue
            meta_at = g.meta_at
            for fam in g.families:
                for i in g.rows_for(fam).tolist():
                    sinks = meta_at(i)[2]
                    if sinks is None or sink_name in sinks:
                        total += 1
        for m in self.extras:
            if m.sinks is None or sink_name in m.sinks:
                total += 1
        return total

    def emit_plan(self) -> list:
        """Per-group native emit plans (EmitGroupPlan), aligned with
        ``groups``; None entries mark groups the native serializers
        can't take (no frag arena, veneursinkonly routing, or a family
        type outside counter/gauge — those go through each sink's
        Python formatter). Memoized: in a multi-sink set every
        native-capable sink shares ONE stacking pass."""
        cached = getattr(self, "_emit_plan", None)
        if cached is not None:
            return cached
        from veneur_tpu.core.metrics import MetricType

        plans: list = []
        for g in self.groups:
            if (g.meta_blob is None or g.has_routing or not g.families
                    or any(f.type not in (MetricType.COUNTER,
                                          MetricType.GAUGE)
                           for f in g.families)):
                plans.append(None)
                continue
            plans.append(EmitGroupPlan(
                nrows=g.nrows,
                meta_blob=g.meta_blob,
                suffixes=[f.suffix for f in g.families],
                family_types=np.asarray(
                    [0 if f.type == MetricType.COUNTER else 1
                     for f in g.families], np.int8),
                values=np.stack([f.values for f in g.families]),
                masks=np.stack([
                    f.mask.astype(np.uint8) if f.mask is not None
                    else np.ones(g.nrows, np.uint8)
                    for f in g.families]),
            ))
        self._emit_plan = plans
        return plans

    def materialize(self) -> list[InterMetric]:
        """The compatibility path: the same InterMetric multiset the
        object generator emits, family-major. Memoized — in a mixed sink
        set every non-columnar sink shares ONE materialization (the base
        MetricSink.flush_columnar routes/filters per sink on top of it)."""
        cached = getattr(self, "_materialized", None)
        if cached is not None:
            return cached
        out: list[InterMetric] = []
        append = out.append
        ts = self.timestamp
        for g in self.groups:
            meta_at = g.meta_at
            for fam in g.families:
                suffix = fam.suffix
                mtype = fam.type
                vals = fam.values.tolist()  # one C pass boxes the floats
                for i in g.rows_for(fam).tolist():
                    name, tags, sinks = meta_at(i)
                    append(InterMetric(
                        name + suffix if suffix else name, ts,
                        vals[i], tags, mtype, sinks=sinks))
        out.extend(self.extras)
        self._materialized = out
        return out

    def iter_rows(self, sink_name: Optional[str] = None,
                  excluded_tags: Optional[set] = None,
                  include_extras: bool = True):
        """Yield (name, value, tags, type, ts) per emitted metric —
        the per-row feed for columnar sinks that format per metric.
        Applies veneursinkonly routing for ``sink_name`` and per-sink
        tag exclusion. Sinks that need the extras' message/hostname
        fields (status checks) pass include_extras=False and consume
        ``self.extras`` (full InterMetric objects) themselves."""
        ts = self.timestamp
        for g in self.groups:
            meta_at = g.meta_at
            check_routing = g.has_routing and sink_name is not None
            for fam in g.families:
                suffix = fam.suffix
                mtype = fam.type
                vals = fam.values.tolist()
                for i in g.rows_for(fam).tolist():
                    name, tags, sinks = meta_at(i)
                    if check_routing and sinks is not None \
                            and sink_name not in sinks:
                        continue
                    if excluded_tags:
                        tags = [t for t in tags
                                if t.split(":", 1)[0] not in excluded_tags]
                    yield (name + suffix if suffix else name,
                           vals[i], tags, mtype, ts)
        if not include_extras:
            return
        for m in self.extras:
            if sink_name is not None and m.sinks is not None \
                    and sink_name not in m.sinks:
                continue
            tags = m.tags
            if excluded_tags:
                tags = [t for t in tags
                        if t.split(":", 1)[0] not in excluded_tags]
            yield (m.name, m.value, tags, m.type, m.timestamp)
