"""Sketch wire codec: FlushSnapshot rows ↔ protobuf Metric messages.

The forwarding serialization plays the role of the reference's
metricpb/tdigest protos (samplers/metricpb/metric.proto,
tdigest/tdigest.proto:8-22) and gob Export/Combine path
(samplers/samplers.go:161-208, :678-703): counters/gauges travel as exact
scalars, histograms as t-digest centroid rows + min/max/reciprocal-sum,
sets as dense HLL registers. This is also the only serialization state in
the system — like the reference, aggregation state never outlives a flush
interval, so the forwarding codec doubles as the checkpoint format for
host↔host and host↔device movement (SURVEY.md §5.4).
"""

from __future__ import annotations

import numpy as np

from veneur_tpu.core.directory import ScopeClass
from veneur_tpu.core.metrics import MetricKey
from veneur_tpu.core.worker import FlushSnapshot
from veneur_tpu.gen import veneur_tpu_pb2 as pb

_SCOPE_TO_PB = {
    ScopeClass.MIXED: pb.SCOPE_MIXED,
    ScopeClass.LOCAL: pb.SCOPE_LOCAL,
    ScopeClass.GLOBAL: pb.SCOPE_GLOBAL,
}
_SCOPE_FROM_PB = {v: k for k, v in _SCOPE_TO_PB.items()}

_KIND_TO_TYPE = {
    pb.KIND_COUNTER: "counter",
    pb.KIND_GAUGE: "gauge",
    pb.KIND_HISTOGRAM: "histogram",
    pb.KIND_TIMER: "timer",
    pb.KIND_SET: "set",
}
_TYPE_TO_KIND = {v: k for k, v in _KIND_TO_TYPE.items()}


def snapshot_to_batch(snap: FlushSnapshot,
                      compression: float = 100.0,
                      hll_precision: int = 14) -> pb.MetricBatch:
    """Serialize the forwardable part of a snapshot
    (reference ForwardableMetrics, worker.go:181-209).

    The histogram rows are the cardinality driver (1M+ in the big
    configs), so their numeric prep is vectorized over the whole pool —
    one nonzero mask + one boxed flat list, per-row Python work reduced
    to list slicing — instead of per-row fancy indexing (~3x on the
    forward-build path)."""
    batch = pb.MetricBatch()
    # scalars and sets: same selection as forwardable_rows (global
    # counters/gauges, mixed sets), iterated directly so the histo rows
    # below never materialize per-row tuples
    for (key, tags, cls, _sinks), value in zip(
        snap.scalars.counter_meta, snap.scalars.counter_values
    ):
        if cls == ScopeClass.GLOBAL:
            m = batch.metrics.add()
            m.name = key.name
            m.tags.extend(tags)
            m.kind = pb.KIND_COUNTER
            m.scope = pb.SCOPE_GLOBAL
            m.counter.value = int(value)
    for (key, tags, cls, _sinks), value in zip(
        snap.scalars.gauge_meta, snap.scalars.gauge_values
    ):
        if cls == ScopeClass.GLOBAL:
            m = batch.metrics.add()
            m.name = key.name
            m.tags.extend(tags)
            m.kind = pb.KIND_GAUGE
            m.scope = pb.SCOPE_GLOBAL
            m.gauge.value = float(value)
    if snap.set_registers is not None:
        for row, meta in enumerate(snap.directory.sets.rows):
            if meta.scope_class == ScopeClass.MIXED:
                m = batch.metrics.add()
                m.name = meta.key.name
                m.tags.extend(meta.tags)
                m.kind = pb.KIND_SET
                m.scope = pb.SCOPE_MIXED
                m.hll.registers = np.asarray(
                    snap.set_registers[row], np.int8).tobytes()
                m.hll.precision = hll_precision

    hrows = snap.directory.histo.rows
    if hrows and snap.digest_means is not None:
        weights2 = np.asarray(snap.digest_weights, np.float32)
        means2 = np.asarray(snap.digest_means, np.float32)
        nz = weights2 > 0
        offs = np.concatenate(
            [np.zeros(1, np.int64), np.cumsum(nz.sum(axis=1))]).tolist()
        flat_means = means2[nz].tolist()
        flat_weights = weights2[nz].tolist()
        dmin = np.asarray(snap.dmin, np.float64).tolist()
        dmax = np.asarray(snap.dmax, np.float64).tolist()
        drecip = np.asarray(snap.drecip, np.float64).tolist()
        local = ScopeClass.LOCAL
        for row, meta in enumerate(hrows):
            cls = meta.scope_class
            if cls == local:
                continue
            m = batch.metrics.add()
            m.name = meta.key.name
            m.tags.extend(meta.tags)
            m.kind = _TYPE_TO_KIND[meta.key.type]
            m.scope = _SCOPE_TO_PB[cls]
            lo, hi = offs[row], offs[row + 1]
            m.digest.centroids.means.extend(flat_means[lo:hi])
            m.digest.centroids.weights.extend(flat_weights[lo:hi])
            m.digest.min = dmin[row]
            m.digest.max = dmax[row]
            m.digest.reciprocal_sum = drecip[row]
            m.digest.compression = compression
    return batch


_PB_KIND_CODE = {"histogram": int(pb.KIND_HISTOGRAM),
                 "timer": int(pb.KIND_TIMER)}


def _histo_wire_native(snap: FlushSnapshot, compression: float
                       ) -> "tuple[bytes, int] | None":
    """Histogram rows as MetricBatch wire bytes via the C++ encoder
    (native/dogstatsd.cpp vn_encode_histo_batch): no per-row Python
    protobuf messages. Returns (bytes, emitted_count), or None when the
    native library is unavailable or a name/tag contains the blob
    separators (falls back to the Python encoder)."""
    from veneur_tpu import native as native_mod

    if not native_mod.available():
        return None  # before the O(rows) meta build, not after
    hrows = snap.directory.histo.rows
    nrows = len(hrows)
    kinds = np.zeros(nrows, np.int8)
    scopes = np.frombuffer(snap.directory.histo.scope_codes,
                           np.int8)[:nrows].copy()
    emit = (scopes != int(ScopeClass.LOCAL)).astype(np.uint8)
    parts = []
    append = parts.append
    count = 0
    for row, meta in enumerate(hrows):
        if not emit[row]:
            continue
        frag = meta.wire_frag()  # cached across epochs
        if frag is None:
            return None  # separators inside the data: python path
        append(frag)
        kinds[row] = _PB_KIND_CODE[meta.key.type]
        count += 1
    blob = native_mod.encode_histo_batch(
        b"\x1e".join(parts), kinds, scopes, emit,
        np.asarray(snap.digest_means, np.float32),
        np.asarray(snap.digest_weights, np.float32),
        np.asarray(snap.dmin, np.float64),
        np.asarray(snap.dmax, np.float64),
        np.asarray(snap.drecip, np.float64), compression)
    if blob is None:
        return None
    return blob, count


def snapshot_to_wire(snap: FlushSnapshot,
                     compression: float = 100.0,
                     hll_precision: int = 14) -> tuple[bytes, int]:
    """Serialized MetricBatch bytes + metric count for one snapshot.

    The histogram rows — the cardinality driver — encode through the
    native C++ wire encoder when available; scalars/sets go through the
    Python protobuf objects (rare at scale). Serialized protobuf
    concatenates: appending two MetricBatch blobs merges their repeated
    `metrics` fields, so the two parts join with bytes concatenation.
    """
    native_part = b""
    native_count = 0
    skip_histos = False
    if (snap.directory.histo.rows and snap.digest_means is not None):
        res = _histo_wire_native(snap, compression)
        if res is not None:
            native_part, native_count = res
            skip_histos = True
    if skip_histos:
        # python-encode only scalars/sets: a snapshot view with the
        # histo rows masked off would complicate the codec, so reuse
        # snapshot_to_batch on a shallow copy without digest arrays
        import copy

        rest = copy.copy(snap)
        rest.digest_means = None
        batch = snapshot_to_batch(rest, compression, hll_precision)
    else:
        batch = snapshot_to_batch(snap, compression, hll_precision)
    return (batch.SerializeToString() + native_part,
            len(batch.metrics) + native_count)


# --------------------------------------------------------------- dedup
#
# Wire-level idempotency envelope.  grpc_tools isn't available to grow
# the proto schema, so the dedup key rides as a versioned byte header
# prepended to the serialized MetricBatch.  The magic's leading byte is
# 'V' (0x56): as a protobuf tag it decodes to field 10 / wire type 6,
# which is invalid, so a headered blob can never parse as a legacy
# MetricBatch and the two shapes sniff apart unambiguously.  Headerless
# blobs pass through untouched — a dedup-unaware sender interops at
# at-least-once semantics, exactly as before.
#
# The VDE1/VSF1 encode/decode hot paths dispatch to the native codec
# (native/forward_codec.cpp, GIL released) when libveneur_native.so
# carries it; the *_py functions below are the pinned byte-identical
# reference — the wire contract — and the only implementation when the
# library is absent or VENEUR_CODEC_NATIVE=0 masks it out. Native
# entry points decline (return None) on any input whose Python
# semantics they don't replicate exactly, so the dispatchers fall back
# per-call, never per-process.

DEDUP_MAGIC = b"VDE1"  # 'V'-leading, versioned; u16 LE header length follows

_native_codec_mod = None
_native_codec_checked = False


def _native_codec():
    """The native module when the forward codec is usable, else None.
    Cached after the first probe (build-on-load makes the probe
    expensive); VENEUR_CODEC_NATIVE is read at probe time, so the
    escape hatch is a process-start switch like VENEUR_EMIT_NATIVE."""
    global _native_codec_mod, _native_codec_checked
    if not _native_codec_checked:
        _native_codec_checked = True
        try:
            from veneur_tpu import native as _native

            _native_codec_mod = (_native if _native.codec_available()
                                 else None)
        except Exception:
            _native_codec_mod = None
    return _native_codec_mod


def encode_dedup_envelope_py(sender: str, dedup_id: int, count: int,
                             body: bytes) -> bytes:
    """Pinned Python reference for the VDE1 envelope wire bytes."""
    import json as _json

    hdr = _json.dumps(
        {"s": sender, "i": int(dedup_id), "n": int(count)},
        separators=(",", ":"),
    ).encode("utf-8")
    if len(hdr) > 0xFFFF:
        raise ValueError("dedup header too large")
    return DEDUP_MAGIC + len(hdr).to_bytes(2, "little") + hdr + body


def encode_dedup_envelope(sender: str, dedup_id: int, count: int,
                          body: bytes) -> bytes:
    """Prepend the versioned idempotency header to MetricBatch bytes.

    ``count`` (the batch's metric count) is REQUIRED in the header: a
    receiver that dedups a replay must still report the batch's size as
    accepted (the HTTP import path treats 0 as a malformed body)."""
    n = _native_codec()
    if (n is not None and isinstance(sender, str)
            and isinstance(body, bytes)):
        try:
            sender_b = sender.encode("utf-8")
        except UnicodeEncodeError:
            sender_b = None  # lone surrogates: Python json handles them
        if sender_b is not None:
            prefix = n.dedup_header_encode(sender_b, int(dedup_id),
                                           int(count))
            if prefix is not None:
                return prefix + body
    return encode_dedup_envelope_py(sender, dedup_id, count, body)


def decode_dedup_envelope_py(
    blob: bytes,
) -> "tuple[tuple[str, int, int] | None, bytes]":
    """Pinned Python reference for the VDE1 envelope split."""
    import json as _json

    if not blob.startswith(DEDUP_MAGIC):
        return None, blob
    if len(blob) < len(DEDUP_MAGIC) + 2:
        raise ValueError("truncated dedup envelope")
    off = len(DEDUP_MAGIC)
    hlen = int.from_bytes(blob[off:off + 2], "little")
    off += 2
    if len(blob) < off + hlen:
        raise ValueError("truncated dedup envelope header")
    try:
        meta = _json.loads(blob[off:off + hlen].decode("utf-8"))
        key = (str(meta["s"]), int(meta["i"]), int(meta["n"]))
    except (ValueError, KeyError, TypeError, UnicodeDecodeError) as e:
        raise ValueError(f"bad dedup envelope header: {e}") from e
    return key, blob[off + hlen:]


def decode_dedup_envelope(
    blob: bytes,
) -> "tuple[tuple[str, int, int] | None, bytes]":
    """Split a wire blob into ``((sender, id, count) | None, body)``.

    Headerless blobs (old senders) return ``(None, blob)`` unchanged.
    A blob that *starts* like an envelope but is malformed raises
    ValueError — it cannot be a legacy MetricBatch either."""
    n = _native_codec()
    if (n is None or not isinstance(blob, bytes)
            or not blob.startswith(DEDUP_MAGIC)):
        return decode_dedup_envelope_py(blob)
    if len(blob) < len(DEDUP_MAGIC) + 2:
        raise ValueError("truncated dedup envelope")
    off = len(DEDUP_MAGIC)
    hlen = int.from_bytes(blob[off:off + 2], "little")
    off += 2
    if len(blob) < off + hlen:
        raise ValueError("truncated dedup envelope header")
    key = n.dedup_header_parse(blob[off:off + hlen])
    if key is None:  # non-canonical header: exact Python semantics
        return decode_dedup_envelope_py(blob)
    return key, blob[off + hlen:]


# ------------------------------------------------------------- stream
#
# Framing for the long-lived StreamMetrics channel (reference
# forwardrpc SendMetricsV2 client-streaming + importsrv server-side
# batching).  gRPC already length-delimits messages, so a frame is one
# gRPC message: a versioned magic, a u64 LE sequence number minted by
# the sender, then the exact bytes a unary SendMetrics would have
# carried (a VDE1 dedup envelope or a bare MetricBatch).  Acks flow
# the other way as (u64 LE seq, u8 status) — a frame is "delivered"
# only when its ack arrives, which is what lets the DeliveryManager's
# retry/breaker/spill semantics and the dedup keys survive unchanged.

STREAM_FRAME_MAGIC = b"VSF1"  # 'V'-leading, versioned, like VDE1
STREAM_ACK_OK = 0
STREAM_ACK_FAILED = 1  # receiver could not merge this frame (permanent)
STREAM_ACK_BUSY = 2    # receiver full, frame NOT taken (transient: the
#                        sender retries under the same dedup key — this
#                        is how streamed ingest backpressure reaches the
#                        delivery layer instead of shedding server-side)

_SEQ_OFF = len(STREAM_FRAME_MAGIC)
_BODY_OFF = _SEQ_OFF + 8


def encode_stream_frame_py(seq: int, body: bytes) -> bytes:
    """Pinned Python reference for the VSF1 frame wire bytes."""
    return STREAM_FRAME_MAGIC + int(seq).to_bytes(8, "little") + body


def encode_stream_frame(seq: int, body: bytes) -> bytes:
    """One stream frame: magic + u64 LE seq + unary-shaped body."""
    n = _native_codec()
    if (n is not None and isinstance(seq, int)
            and isinstance(body, bytes)):
        out = n.stream_frame_encode(seq, body)
        if out is not None:
            return out
    return encode_stream_frame_py(seq, body)


def decode_stream_frame_py(blob: bytes) -> tuple[int, bytes]:
    """Pinned Python reference for the VSF1 frame split."""
    if not blob.startswith(STREAM_FRAME_MAGIC) or len(blob) < _BODY_OFF:
        raise ValueError("bad stream frame")
    return (int.from_bytes(blob[_SEQ_OFF:_BODY_OFF], "little"),
            blob[_BODY_OFF:])


def decode_stream_frame(blob: bytes) -> tuple[int, bytes]:
    """Split a stream frame into (seq, body); ValueError on garbage."""
    n = _native_codec()
    if n is not None and isinstance(blob, bytes):
        res = n.stream_frame_decode(blob)
        if res is None:  # codec loaded, so None means a non-frame blob
            raise ValueError("bad stream frame")
        return res
    return decode_stream_frame_py(blob)


def _ack_status(ok) -> int:
    if ok is True:
        return STREAM_ACK_OK
    if ok is False:
        return STREAM_ACK_FAILED
    return int(ok)


def encode_stream_ack_py(seq: int, ok=True) -> bytes:
    """Pinned Python reference for the 9-byte ack wire bytes."""
    return int(seq).to_bytes(8, "little") + bytes((_ack_status(ok),))


def encode_stream_ack(seq: int, ok=True) -> bytes:
    """Ack one frame. `ok` is a bool (True/False -> OK/FAILED, the
    common sink-callback shape) or an explicit STREAM_ACK_* status."""
    n = _native_codec()
    if n is not None and isinstance(seq, int):
        out = n.stream_ack_encode(seq, _ack_status(ok))
        if out is not None:
            return out
    return encode_stream_ack_py(seq, ok)


def decode_stream_ack_py(blob: bytes) -> tuple[int, int]:
    """Pinned Python reference for the ack split."""
    if len(blob) != 9:
        raise ValueError("bad stream ack")
    return int.from_bytes(blob[:8], "little"), blob[8]


def decode_stream_ack(blob: bytes) -> tuple[int, int]:
    """Split an ack into (seq, STREAM_ACK_* status)."""
    n = _native_codec()
    if n is not None and isinstance(blob, bytes):
        res = n.stream_ack_decode(blob)
        if res is None:
            raise ValueError("bad stream ack")
        return res
    return decode_stream_ack_py(blob)


def frame_groups(parts: "list[tuple[bytes, int]]",
                 target_bytes: int) -> "list[tuple[bytes, int]]":
    """Group (blob, metric_count) pairs into frames of ~target_bytes.

    Consecutive blobs concatenate (serialized MetricBatch blobs merge
    by concatenation — repeated `metrics` fields append) until adding
    the next blob would cross the target; a single oversize blob stays
    its own frame, never split. ONLY valid for bare MetricBatch blobs:
    a VDE1-enveloped payload carries its own dedup identity and must
    stay one frame (the local→proxy and local→global hops qualify —
    envelopes are minted proxy-side)."""
    groups: list[tuple[bytes, int]] = []
    cur: list[bytes] = []
    cur_bytes = 0
    cur_n = 0
    for blob, n in parts:
        if cur and cur_bytes + len(blob) > target_bytes:
            groups.append((b"".join(cur), cur_n))
            cur, cur_bytes, cur_n = [], 0, 0
        cur.append(blob)
        cur_bytes += len(blob)
        cur_n += n
    if cur:
        groups.append((b"".join(cur), cur_n))
    return groups


def metric_key(m: pb.Metric) -> MetricKey:
    return MetricKey(
        name=m.name,
        type=_KIND_TO_TYPE[m.kind],
        joined_tags=",".join(m.tags),
    )


def apply_to_worker(worker, m: pb.Metric) -> None:
    """Merge one received metric into a DeviceWorker (the global tier's
    ingest; reference ImportMetricGRPC, worker.go:438-495: counters/gauges
    are forced global, local scope is rejected)."""
    key = metric_key(m)
    tags = list(m.tags)
    which = m.WhichOneof("value")
    if which == "counter":
        worker.import_counter(key, tags, m.counter.value)
    elif which == "gauge":
        worker.import_gauge(key, tags, m.gauge.value)
    elif which == "hll":
        regs = np.frombuffer(m.hll.registers, dtype=np.int8)
        worker.import_hll(key, tags, ScopeClass.MIXED, regs)
    elif which == "digest":
        scope = _SCOPE_FROM_PB.get(m.scope, ScopeClass.MIXED)
        if scope == ScopeClass.LOCAL:
            raise ValueError("import does not accept local metrics")
        means = np.asarray(m.digest.centroids.means, np.float32)
        weights = np.asarray(m.digest.centroids.weights, np.float32)
        worker.import_digest(
            key, tags, key.type, scope, means, weights,
            m.digest.min, m.digest.max, m.digest.reciprocal_sum,
        )
    else:
        raise ValueError("metric with no value")


def routing_digest(m: pb.Metric) -> int:
    """Worker-routing digest of a received metric. Computed exactly like
    the parse-time digest (utils/hashing.metric_digest), so a series lands
    on the same worker shard whether it arrived raw or forwarded
    (reference importsrv hashes the same identity, importsrv/server.go:
    141-148)."""
    from veneur_tpu.utils.hashing import metric_digest

    key = metric_key(m)
    return metric_digest(key.name, key.type, key.joined_tags)
