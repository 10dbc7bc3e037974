"""InterMetric generation from a flushed interval.

Behavioral spec: reference generateInterMetrics (flusher.go:225-298) plus the
per-sampler Flush methods (samplers/samplers.go:147-158 Counter, :230-242
Gauge, :319-324 StatusCheck, :392-403 Set, :511-675 Histo) — including the
mixed-scope double-count avoidance: a local (forwarding) instance emits only
host-local aggregates for mixed histograms, never percentiles; the global
instance emits percentiles but no local aggregates (flusher.go:61-74).

The flusher consumes a FlushSnapshot (dense arrays + row metadata) and emits
InterMetric objects row by row; all numeric work already happened on device.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from veneur_tpu.core.columnar import (
    ColumnarMetrics,
    ColumnGroup,
    MetricFamily,
)
from veneur_tpu.core.directory import ScopeClass
from veneur_tpu.core.metrics import (
    Aggregate,
    HistogramAggregates,
    InterMetric,
    MetricType,
)
from veneur_tpu.core.worker import FlushSnapshot


def device_quantiles(
    percentiles: list[float], aggregates: HistogramAggregates
) -> np.ndarray:
    """The quantile vector the device must evaluate: configured percentiles
    plus the median when the median aggregate is enabled (reference
    samplers.go:622-636 pulls the median from the digest)."""
    qs = list(percentiles)
    if aggregates.value & Aggregate.MEDIAN and 0.5 not in qs:
        qs.append(0.5)
    # float64 so host-side lookups by the exact configured value round-trip;
    # the worker casts to f32 only at the device boundary
    return np.asarray(qs, dtype=np.float64)


def _percentile_name(name: str, p: float) -> str:
    # reference formats with int(p*100) (samplers.go:657-672)
    return f"{name}.{int(p * 100)}percentile"


def generate_inter_metrics(
    snap: FlushSnapshot,
    is_local: bool,
    percentiles: list[float],
    aggregates: HistogramAggregates,
    now: Optional[int] = None,
    governor=None,
) -> list[InterMetric]:
    """Emit every InterMetric this interval owes its sinks."""
    if governor is not None:
        # liveness beat for the flush watchdog's deferral rule: at high
        # cardinality the generate phase is seconds of host work, and a
        # deferred-panic decision should see it as progress, not silence
        governor.beat()
    ts = int(time.time()) if now is None else now
    out: list[InterMetric] = []

    # mixed histograms/timers forward their digests, so a local instance
    # flushes only aggregates for them (flusher.go:61-74)
    mixed_percentiles: list[float] = [] if is_local else list(percentiles)

    # -- histogram/timer rows ---------------------------------------------
    # This loop runs once per series per flush (1M+ rows in the
    # prometheus_1m scenario); per-element numpy indexing costs ~µs each,
    # so every column is materialized to a plain Python list up front
    # (tolist is one C pass) and rows touch only list indexing.
    hrows = snap.directory.histo.rows
    if hrows:
        q_index = {
            float(q): i for i, q in enumerate(np.asarray(snap.quantile_qs))
        }
        quant = {float(q): snap.quantile_values[:, i].tolist()
                 for q, i in q_index.items()}
        # digest-side columns are read only on the global instance
        # (use_global rows are skipped on locals): don't box 5M floats
        # a local flush never touches
        empty: list = []
        cols = _HistoCols(
            lmin=snap.lmin.tolist(), lmax=snap.lmax.tolist(),
            lsum=snap.lsum.tolist(), lweight=snap.lweight.tolist(),
            lrecip=snap.lrecip.tolist(),
            dmin=empty if is_local else snap.dmin.tolist(),
            dmax=empty if is_local else snap.dmax.tolist(),
            dsum=empty if is_local else snap.dsum.tolist(),
            dcount=empty if is_local else snap.dcount.tolist(),
            drecip=empty if is_local else snap.drecip.tolist(),
            quant=quant,
            pcols=[(_percentile_name("", p), quant[float(p)])
                   for p in percentiles],
            want_max=bool(aggregates.value & Aggregate.MAX),
            want_min=bool(aggregates.value & Aggregate.MIN),
            want_sum=bool(aggregates.value & Aggregate.SUM),
            want_avg=bool(aggregates.value & Aggregate.AVERAGE),
            want_count=bool(aggregates.value & Aggregate.COUNT),
            want_median=bool(aggregates.value & Aggregate.MEDIAN),
            want_hmean=bool(aggregates.value & Aggregate.HARMONIC_MEAN),
        )
        hrej = snap.directory.histo.rejected_rows > 0
        for row, meta in enumerate(hrows):
            if governor is not None and row and row % 200_000 == 0:
                # the entry beat above covers small flushes; at 1M rows
                # this loop is seconds of host work — the watchdog
                # must keep seeing progress, not entry-silence
                governor.beat()
            if hrej and not meta.admitted:
                # tenant-budget-rejected series (native path marks the
                # row instead of refusing it; see directory.RowMeta) —
                # never emitted, by either path
                continue
            cls = meta.scope_class
            if cls == ScopeClass.MIXED:
                # locals forward mixed digests and emit no percentiles
                ps, use_global = bool(mixed_percentiles), False
            elif cls == ScopeClass.LOCAL:
                ps, use_global = bool(percentiles), False
            else:  # GLOBAL: flushed only by the global instance, from digest
                if is_local:
                    continue
                ps, use_global = bool(percentiles), True
            _flush_histo_row(cols, row, meta, ts, ps, use_global, out)

    # -- set rows ----------------------------------------------------------
    srows = snap.directory.sets.rows
    if srows:
        srej = snap.directory.sets.rejected_rows > 0
        for row, meta in enumerate(srows):
            if srej and not meta.admitted:
                continue
            # mixed sets have no local part: only the global instance emits
            # them (flusher.go:269-274); local-only sets always flush
            if meta.scope_class == ScopeClass.MIXED and is_local:
                continue
            out.append(
                InterMetric(
                    name=meta.key.name,
                    timestamp=ts,
                    value=float(snap.set_estimates[row]),
                    tags=list(meta.tags),
                    type=MetricType.GAUGE,
                    sinks=meta.sinks,
                )
            )

    # -- counters ----------------------------------------------------------
    cpool = snap.scalars.counters
    crej = cpool.rejected_rows > 0
    for row, ((key, tags, cls, sinks), value) in enumerate(zip(
        snap.scalars.counter_meta, snap.scalars.counter_values
    )):
        if crej and not cpool.admit_codes[row]:
            continue
        if cls == ScopeClass.GLOBAL and is_local:
            continue  # forwarded, not emitted (flusher.go:276-283)
        out.append(
            InterMetric(
                name=key.name, timestamp=ts, value=float(value),
                tags=list(tags), type=MetricType.COUNTER, sinks=sinks,
            )
        )

    # -- gauges ------------------------------------------------------------
    gpool = snap.scalars.gauges
    grej = gpool.rejected_rows > 0
    for row, ((key, tags, cls, sinks), value) in enumerate(zip(
        snap.scalars.gauge_meta, snap.scalars.gauge_values
    )):
        if grej and not gpool.admit_codes[row]:
            continue
        if cls == ScopeClass.GLOBAL and is_local:
            continue
        out.append(
            InterMetric(
                name=key.name, timestamp=ts, value=float(value),
                tags=list(tags), type=MetricType.GAUGE, sinks=sinks,
            )
        )

    # -- status checks -----------------------------------------------------
    for (key, tags, _cls, sinks), sv in zip(
        snap.scalars.status_meta, snap.scalars.status_values
    ):
        value, message, hostname = sv
        out.append(
            InterMetric(
                name=key.name, timestamp=ts, value=float(value),
                tags=list(tags), type=MetricType.STATUS, message=message,
                hostname=hostname, sinks=sinks,
            )
        )

    return out


@dataclass
class _HistoCols:
    """Snapshot columns pre-materialized as Python lists for the per-row
    emission loop."""

    lmin: list
    lmax: list
    lsum: list
    lweight: list
    lrecip: list
    dmin: list
    dmax: list
    dsum: list
    dcount: list
    drecip: list
    quant: dict  # percentile -> per-row list
    # (suffix, per-row values) per configured percentile, precomputed so
    # the row loop does one concat instead of number formatting
    pcols: list = None
    # aggregate-flag membership tested once (Flag-enum `&` costs ~1µs a
    # call; at 7 tests × 1M rows that alone was most of the loop)
    want_max: bool = False
    want_min: bool = False
    want_sum: bool = False
    want_avg: bool = False
    want_count: bool = False
    want_median: bool = False
    want_hmean: bool = False


def _flush_histo_row(
    cols: _HistoCols,
    row: int,
    meta,
    ts: int,
    emit_percentiles: bool,
    use_global: bool,
    out: list,
) -> None:
    """One histogram/timer row → aggregate + percentile series
    (reference Histo.Flush, samplers.go:511-675). Appends to `out`.

    The tags list is shared across this row's metrics — InterMetric
    consumers never mutate tags (exclusion builds new lists)."""
    name = meta.key.name
    tags = meta.tags
    sinks = meta.sinks
    append = out.append
    GAUGE = MetricType.GAUGE

    lmin = cols.lmin[row]
    lmax = cols.lmax[row]
    lsum = cols.lsum[row]
    lweight = cols.lweight[row]
    lrecip = cols.lrecip[row]

    if cols.want_max and (not math.isinf(lmax) or use_global):
        append(InterMetric(name + ".max", ts,
                           cols.dmax[row] if use_global else lmax,
                           tags, GAUGE, sinks=sinks))
    if cols.want_min and (not math.isinf(lmin) or use_global):
        append(InterMetric(name + ".min", ts,
                           cols.dmin[row] if use_global else lmin,
                           tags, GAUGE, sinks=sinks))
    if cols.want_sum and (lsum != 0 or use_global):
        append(InterMetric(name + ".sum", ts,
                           cols.dsum[row] if use_global else lsum,
                           tags, GAUGE, sinks=sinks))
    if cols.want_avg and (use_global or (lsum != 0 and lweight != 0)):
        if use_global:
            val = cols.dsum[row] / cols.dcount[row]
        else:
            val = lsum / lweight
        append(InterMetric(name + ".avg", ts, val, tags, GAUGE, sinks=sinks))
    if cols.want_count and (lweight != 0 or use_global):
        append(InterMetric(name + ".count", ts,
                           cols.dcount[row] if use_global else lweight,
                           tags, MetricType.COUNTER, sinks=sinks))
    if cols.want_median:
        # always emitted when configured; the value comes from the digest
        append(InterMetric(name + ".median", ts, cols.quant[0.5][row],
                           tags, GAUGE, sinks=sinks))
    if cols.want_hmean and (
        use_global or (lrecip != 0 and lweight != 0)
    ):
        if use_global:
            val = cols.dcount[row] / cols.drecip[row]
        else:
            val = lweight / lrecip
        append(InterMetric(name + ".hmean", ts, val, tags, GAUGE,
                           sinks=sinks))

    if emit_percentiles:
        for suffix, col in cols.pcols:
            append(InterMetric(name + suffix, ts, col[row], tags, GAUGE,
                               sinks=sinks))


# ---------------------------------------------------------------------------
# Columnar generation (the SoA fast path; see core/columnar.py)


def generate_columnar(
    snap: FlushSnapshot,
    is_local: bool,
    percentiles: list[float],
    aggregates: HistogramAggregates,
    now: Optional[int] = None,
    governor=None,
):
    """Columnar twin of generate_inter_metrics: numpy masks instead of a
    per-row Python loop. Emits the identical metric multiset (pinned by
    tests/test_columnar.py); costs O(R) numpy, not O(R·families) Python.
    """
    if governor is not None:
        # liveness beat for the flush watchdog's deferral rule (see
        # generate_inter_metrics)
        governor.beat()
    ts = int(time.time()) if now is None else now
    batch = ColumnarMetrics(timestamp=ts)
    GAUGE = MetricType.GAUGE

    # -- histogram/timer rows ---------------------------------------------
    hrows = snap.directory.histo.rows
    if hrows:
        sc = np.frombuffer(snap.directory.histo.scope_codes,
                           dtype=np.int8)[: len(hrows)]
        is_global_row = sc == int(ScopeClass.GLOBAL)
        # a local instance forwards global rows instead of emitting them
        base = ~is_global_row if is_local else None
        # tenant-budget-rejected rows (native path) are cut from EVERY
        # family; hadm folds into base AND into pmask below — percentile
        # families bypass base, and a rejected row must not leak through
        # them. Zero-tenant runs never build the mask (rejected_rows 0).
        hadm = None
        if snap.directory.histo.rejected_rows > 0:
            hadm = np.frombuffer(snap.directory.histo.admit_codes,
                                 dtype=np.int8)[: len(hrows)] != 0
            base = hadm if base is None else (base & hadm)
        use_global = (np.zeros(len(hrows), bool) if is_local
                      else is_global_row)
        # widen to f64 up front: the object path boxes every f32 column
        # through .tolist() before arithmetic, so divisions (avg, hmean)
        # happen in f64 — match that exactly
        def as64(a):
            return None if a is None else np.asarray(a, np.float64)

        lmin, lmax = as64(snap.lmin), as64(snap.lmax)
        lsum, lweight, lrecip = (as64(snap.lsum), as64(snap.lweight),
                                 as64(snap.lrecip))
        dmin, dmax = as64(snap.dmin), as64(snap.dmax)
        dsum, dcount, drecip = (as64(snap.dsum), as64(snap.dcount),
                                as64(snap.drecip))

        def _and(a, b):
            return b if a is None else (a & b)

        def pick(global_col, local_col):
            if not use_global.any():
                return local_col
            return np.where(use_global, global_col, local_col)

        fams: list[MetricFamily] = []
        with np.errstate(divide="ignore", invalid="ignore"):
            if aggregates.value & Aggregate.MAX:
                fams.append(MetricFamily(
                    ".max", GAUGE, pick(dmax, lmax),
                    _and(base, ~np.isinf(lmax) | use_global)))
            if aggregates.value & Aggregate.MIN:
                fams.append(MetricFamily(
                    ".min", GAUGE, pick(dmin, lmin),
                    _and(base, ~np.isinf(lmin) | use_global)))
            if aggregates.value & Aggregate.SUM:
                fams.append(MetricFamily(
                    ".sum", GAUGE, pick(dsum, lsum),
                    _and(base, (lsum != 0) | use_global)))
            if aggregates.value & Aggregate.AVERAGE:
                fams.append(MetricFamily(
                    ".avg", GAUGE,
                    pick(dsum / dcount if not is_local else 0.0,
                         lsum / np.maximum(lweight, 1e-300)),
                    _and(base,
                         ((lsum != 0) & (lweight != 0)) | use_global)))
            if aggregates.value & Aggregate.COUNT:
                fams.append(MetricFamily(
                    ".count", MetricType.COUNTER,
                    pick(dcount, lweight),
                    _and(base, (lweight != 0) | use_global)))
            if aggregates.value & Aggregate.MEDIAN:
                q_index = {float(q): i for i, q in
                           enumerate(np.asarray(snap.quantile_qs))}
                fams.append(MetricFamily(
                    ".median", GAUGE,
                    np.asarray(snap.quantile_values[:, q_index[0.5]],
                               np.float64),
                    base))
            if aggregates.value & Aggregate.HARMONIC_MEAN:
                fams.append(MetricFamily(
                    ".hmean", GAUGE,
                    pick(dcount / drecip if not is_local else 0.0,
                         lweight / np.where(lrecip != 0, lrecip, 1.0)),
                    _and(base,
                         ((lrecip != 0) & (lweight != 0)) | use_global)))
            if percentiles:
                # mixed rows emit percentiles only on the global instance
                # (flusher.go:61-74); local-only rows always do
                pmask = (sc == int(ScopeClass.LOCAL)) if is_local else None
                if hadm is not None:
                    pmask = hadm if pmask is None else (pmask & hadm)
                q_index = {float(q): i for i, q in
                           enumerate(np.asarray(snap.quantile_qs))}
                for p in percentiles:
                    fams.append(MetricFamily(
                        _percentile_name("", p), GAUGE,
                        np.asarray(
                            snap.quantile_values[:, q_index[float(p)]],
                            np.float64),
                        pmask))
        batch.groups.append(_group(
            snap.directory.histo, len(hrows), fams))

    # -- set rows ----------------------------------------------------------
    srows = snap.directory.sets.rows
    if srows:
        ssc = np.frombuffer(snap.directory.sets.scope_codes,
                            dtype=np.int8)[: len(srows)]
        smask = (~(ssc == int(ScopeClass.MIXED))) if is_local else None
        if snap.directory.sets.rejected_rows > 0:
            sadm = np.frombuffer(snap.directory.sets.admit_codes,
                                 dtype=np.int8)[: len(srows)] != 0
            smask = sadm if smask is None else (smask & sadm)

        batch.groups.append(_group(
            snap.directory.sets, len(srows),
            [MetricFamily(
                "", GAUGE, np.asarray(snap.set_estimates, np.float64),
                smask)]))

    # -- counters / gauges -------------------------------------------------
    for pool, mtype in ((snap.scalars.counters, MetricType.COUNTER),
                        (snap.scalars.gauges, GAUGE)):
        n = pool.used
        if not n:
            continue
        csc = np.frombuffer(pool.scope_codes, dtype=np.int8)[:n]
        cmask = (~(csc == int(ScopeClass.GLOBAL))) if is_local else None
        if pool.rejected_rows > 0:
            cadm = np.frombuffer(pool.admit_codes, dtype=np.int8)[:n] != 0
            cmask = cadm if cmask is None else (cmask & cadm)

        batch.groups.append(_group(
            pool, n,
            [MetricFamily(
                "", mtype, np.asarray(pool.values[:n], np.float64),
                cmask)]))

    # -- status checks (rare; objects) -------------------------------------
    for (key, tags, _cls, sinks), sv in zip(
        snap.scalars.status_meta, snap.scalars.status_values
    ):
        value, message, hostname = sv
        batch.extras.append(
            InterMetric(
                name=key.name, timestamp=ts, value=float(value),
                tags=list(tags), type=MetricType.STATUS, message=message,
                hostname=hostname, sinks=sinks,
            )
        )
    return batch


def _group(pool, nrows: int, families: list) -> ColumnGroup:
    """A pool's ColumnGroup: the row accessors and the frag blob are the
    pool's book's to give (directory.RowBook), in whatever way it holds
    its rows."""
    meta_at, frag_at = pool.accessors()
    return ColumnGroup(
        nrows=nrows, meta_at=meta_at, families=families,
        has_routing=pool.routed_rows > 0, frag_at=frag_at,
        blob_of=pool.frag_blob)


# ---------------------------------------------------------------------------
# Forwarding selection


def forwardable_rows(snap: FlushSnapshot):
    """Yield the forwardable content of a snapshot, typed, mirroring
    reference ForwardableMetrics (worker.go:181-209): global counters and
    gauges, mixed+global histograms/timers, mixed sets. Local-only series
    never leave the instance.

    Yields tuples:
      ("counter", key, tags, value)
      ("gauge", key, tags, value)
      ("histogram"|"timer", key, tags, scope_class, means, weights,
       dmin, dmax, drecip)
      ("set", key, tags, registers)
    """
    # tenant-budget-rejected rows never forward either: letting them ride
    # upstream would re-spend the tenant's budget on the global tier
    cpool = snap.scalars.counters
    crej = cpool.rejected_rows > 0
    for row, ((key, tags, cls, _sinks), value) in enumerate(zip(
        snap.scalars.counter_meta, snap.scalars.counter_values
    )):
        if crej and not cpool.admit_codes[row]:
            continue
        if cls == ScopeClass.GLOBAL:
            yield ("counter", key, tags, value)
    gpool = snap.scalars.gauges
    grej = gpool.rejected_rows > 0
    for row, ((key, tags, cls, _sinks), value) in enumerate(zip(
        snap.scalars.gauge_meta, snap.scalars.gauge_values
    )):
        if grej and not gpool.admit_codes[row]:
            continue
        if cls == ScopeClass.GLOBAL:
            yield ("gauge", key, tags, value)
    hrej = snap.directory.histo.rejected_rows > 0
    for row, meta in enumerate(snap.directory.histo.rows):
        if hrej and not meta.admitted:
            continue
        if meta.scope_class == ScopeClass.LOCAL:
            continue
        if snap.digest_means is None:
            # mesh-mode snapshots don't materialize per-row centroid
            # arrays host-side; a mesh global is a terminal aggregator
            # (chained-global forwarding needs the single-device path)
            break
        yield (
            meta.key.type, meta.key, meta.tags, meta.scope_class,
            snap.digest_means[row], snap.digest_weights[row],
            float(snap.dmin[row]), float(snap.dmax[row]),
            float(snap.drecip[row]),
        )
    if snap.set_registers is not None:
        # terminal (global) snapshots skip register materialization
        srej = snap.directory.sets.rejected_rows > 0
        for row, meta in enumerate(snap.directory.sets.rows):
            if srej and not meta.admitted:
                continue
            if meta.scope_class == ScopeClass.MIXED:
                yield ("set", meta.key, meta.tags, snap.set_registers[row])
