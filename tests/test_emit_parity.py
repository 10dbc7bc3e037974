"""Golden-byte parity: the native emit serializers (native/emit.cpp)
against the sinks' Python formatters.

The native emit tier's contract is bit-identical output — a flush must
produce the same wire bytes whether or not libveneur_native.so is
present. Pinned here for every serializer (Datadog JSON series bodies
incl. deflate, prometheus statsd lines, exposition text, DogStatsD
forward lines) across all metric classes, empty batches, UTF-8
names/tags, and NaN/±Inf values, plus the negotiation fallback with
the native library masked out.
"""

from __future__ import annotations

import json
import zlib

import numpy as np
import pytest

from veneur_tpu import native as native_mod
from veneur_tpu.core.columnar import (
    ColumnarMetrics,
    ColumnGroup,
    MetricFamily,
)
from veneur_tpu.core.directory import build_frag
from veneur_tpu.core.metrics import InterMetric, MetricType

requires_native = pytest.mark.skipif(
    not native_mod.emit_available(),
    reason="native emit tier unavailable")

NAN = float("nan")
INF = float("inf")

# rows covering the awkward cases: UTF-8 names and tag values, value-
# bearing tags with extra colons, bare (valueless) tags, duplicate
# keys, host:/device: magic tags, droppable prefixes
ROWS = [
    ("service.latency", ["env:prod", "host:web-1", "device:sda",
                         "region:us-east"]),
    ("über.metric", ["dc:köln", "emoji:✨sparkle", "tab:a\tb"]),
    ("plain", []),
    ("dots.and-dashes", ["k:v:w", "bare", "dup:a", "dup:b",
                         "quote:say \"hi\"", "back:a\\b"]),
    ("drop.me.please", ["env:prod"]),
]

# family values across the numeric minefield: shortest-repr edge cases
# (1e5 and 1e15 print fixed in CPython, 1e16 flips to scientific),
# subnormals, huge magnitudes, negative zero, and non-finite values
VALS_A = [1.5, NAN, 0.1, float(2) / 3, 100000.0]
VALS_B = [1e15, 1e16, -INF, -0.0, 5e-324]
VALS_C = [20.0, -123.456, INF, 1e-310, 1.7976931348623157e308]


def make_batch(rows, fams_spec, ts=1700000000, extras=()):
    """A ColumnarMetrics batch shaped exactly like generate_columnar's
    output: one group, incremental frag arena, f64 family columns."""
    arena = bytearray()
    clean = True
    for r, (name, tags) in enumerate(rows):
        f = build_frag(name, tags)
        if f is None:
            clean = False
            break
        if r:
            arena += b"\x1e"
        arena += f
    fams = [MetricFamily(s, t, np.asarray(v, np.float64),
                         None if m is None else np.asarray(m, bool))
            for s, t, v, m in fams_spec]
    g = ColumnGroup(
        nrows=len(rows),
        meta_at=lambda i: (rows[i][0], rows[i][1], None),
        families=fams,
        frag_at=lambda i: build_frag(*rows[i]),
        blob_of=lambda: arena if clean else None,
    )
    return ColumnarMetrics(timestamp=ts, groups=[g], extras=list(extras))


def standard_batch(extras=()):
    return make_batch(ROWS, [
        ("", MetricType.COUNTER, VALS_A, None),
        (".count", MetricType.COUNTER, VALS_B, [1, 0, 1, 1, 1]),
        (".p99", MetricType.GAUGE, VALS_C, [1, 1, 1, 0, 1]),
    ], extras=extras)


# ---------------------------------------------------------------------------
# line formats: byte-identical blobs


@requires_native
@pytest.mark.parametrize("excl", [None, {"env", "dup", "host"}])
def test_forward_lines_parity(excl):
    from veneur_tpu.sinks.forward_statsd import ForwardStatsdSink

    sink = ForwardStatsdSink("127.0.0.1:9125")
    sent = []
    sink._send = sent.append
    batch = standard_batch()
    sink.flush_columnar(batch, excluded_tags=excl)
    assert sink.flush_columnar_native(batch, excluded_tags=excl)
    py_lines, native_entries = sent
    assert b"\n".join(py_lines) == b"\n".join(native_entries)
    assert py_lines  # non-trivial comparison


@requires_native
@pytest.mark.parametrize("excl", [None, {"env", "dup"}])
def test_prometheus_lines_parity(excl):
    from veneur_tpu.sinks.prometheus import PrometheusMetricSink

    sink = PrometheusMetricSink("127.0.0.1:9125")
    sent = []
    sink._send = sent.append
    batch = standard_batch()
    sink.flush_columnar(batch, excluded_tags=excl)
    assert sink.flush_columnar_native(batch, excluded_tags=excl)
    py_lines, native_entries = sent
    assert b"\n".join(py_lines) == b"\n".join(native_entries)
    assert py_lines


@requires_native
@pytest.mark.parametrize("excl", [None, {"dup", "emoji"}])
def test_exposition_parity(excl):
    from veneur_tpu.sinks.prometheus import PrometheusExpositionSink

    sink = PrometheusExpositionSink("http://127.0.0.1:9091/metrics/job/v")
    posted = []
    sink._post = lambda body, count: posted.append((body, count))
    batch = standard_batch()
    sink.flush_columnar(batch, excluded_tags=excl)
    assert sink.flush_columnar_native(batch, excluded_tags=excl)
    (py_body, py_n), (native_body, native_n) = posted
    assert py_body == native_body
    assert py_n == native_n
    assert py_n  # non-trivial comparison


@requires_native
def test_exposition_label_rules():
    """Sanitized-key dedup keeps the first position and the last value;
    exclusion matches the raw key; UTF-8 keys collapse per character."""
    from veneur_tpu.sinks.prometheus import PrometheusExpositionSink

    rows = [("m", ["a.b:1", "a_b:2", "k:v", "ümläut:x", "gone:y"])]
    batch = make_batch(rows, [("", MetricType.GAUGE, [2.0], None)])
    sink = PrometheusExpositionSink("http://127.0.0.1:9091/x")
    posted = []
    sink._post = lambda body, count: posted.append(body)
    sink.flush_columnar(batch, excluded_tags={"gone"})
    assert sink.flush_columnar_native(batch, excluded_tags={"gone"})
    assert posted[0] == posted[1]
    assert posted[0] == b'm{a_b="2",k="v",_ml_ut="x"} 2.0\n'


# ---------------------------------------------------------------------------
# datadog: identical series payloads, native bodies pre-deflated


@requires_native
@pytest.mark.parametrize("excl", [None, {"env", "host"}])
def test_datadog_series_parity(excl):
    from veneur_tpu.sinks.datadog import DatadogMetricSink

    status = InterMetric("svc.up", 1700000000, 0.0, ["env:prod"],
                         MetricType.STATUS, message="ok")
    batch = standard_batch(extras=[status])
    posted = []

    def capture(dd_metrics, checks, raw_bodies=None, raw_count=0,
                precompressed=False):
        posted.append((dd_metrics, checks, raw_bodies or [], raw_count,
                       precompressed))

    sink = DatadogMetricSink(
        interval=10.0, flush_max_per_body=4, hostname="agg-1",
        tags=["common:tag", "secret:x"], dd_hostname="https://dd",
        api_key="k", metric_name_prefix_drops=["drop."],
        excluded_tags=["secret"])
    sink._post_all = capture
    sink.flush_columnar(batch, excluded_tags=excl)
    assert sink.flush_columnar_native(batch, excluded_tags=excl)
    (py_series, py_checks, py_raw, _, _), \
        (nat_series, nat_checks, nat_raw, nat_n, nat_pre) = posted
    assert not py_raw and nat_pre

    native_entries = list(nat_series)  # the extras' python-path dicts
    for body in nat_raw:
        raw = zlib.decompress(body)
        # deflate parity: the native tier's compressor is byte-identical
        # to Python zlib.compress
        assert zlib.compress(raw) == body
        parsed = json.loads(raw)
        assert len(parsed["series"]) <= 4  # chunking respected
        native_entries.extend(parsed["series"])

    # JSON-value parity, order included: the native body parses to
    # exactly the dicts the Python formatter builds (nonfinite -> null
    # on both sides)
    assert native_entries == py_series
    assert nat_checks == py_checks and py_checks
    assert nat_n == len(native_entries) - len(nat_series)
    nulls = [e for e in py_series for (_, v) in e["points"] if v is None]
    assert nulls, "nonfinite values must serialize as null"


@requires_native
def test_signalfx_body_parity():
    from veneur_tpu.sinks.signalfx import SignalFxMetricSink

    # signalfx drops non-finite the same way on both paths only via
    # json value equality; keep values finite here (its body emitter
    # predates this PR and is pinned by test_columnar.py too)
    batch = make_batch(ROWS, [
        ("", MetricType.COUNTER, [1.5, 2.0, 0.25, 4.0, 8.0], None),
        (".p50", MetricType.GAUGE, [9.0, -1.0, 0.5, 7.0, 3.0],
         [1, 1, 0, 1, 1]),
    ])
    sink = SignalFxMetricSink(api_key="k", hostname="h0")
    posted = []
    sink._post_buckets = lambda by_key, raw_bodies=None: posted.append(
        (by_key, raw_bodies or []))
    sink.flush_columnar(batch)
    assert sink.flush_columnar_native(batch)
    (py_buckets, py_raw), (nat_buckets, nat_raw) = posted
    assert not py_raw and not nat_buckets

    def points(buckets_or_raw):
        out = {"counter": [], "gauge": []}
        for kind in out:
            for pts in [b.get(kind, []) for b in buckets_or_raw]:
                out[kind].extend(pts)
        return out

    nat_parsed = [json.loads(body) for body, _n in nat_raw]
    assert points(nat_parsed) == points(list(py_buckets.values()))


# ---------------------------------------------------------------------------
# empty batches and unsupported rows


@requires_native
def test_empty_batch_all_serializers():
    from veneur_tpu.sinks.datadog import DatadogMetricSink
    from veneur_tpu.sinks.forward_statsd import ForwardStatsdSink
    from veneur_tpu.sinks.prometheus import (
        PrometheusExpositionSink,
        PrometheusMetricSink,
    )

    empty = ColumnarMetrics(timestamp=1)
    norows = make_batch([], [("", MetricType.COUNTER, [], None)])
    for batch in (empty, norows):
        fwd = ForwardStatsdSink("127.0.0.1:9125")
        sent = []
        fwd._send = sent.append
        assert fwd.flush_columnar_native(batch)
        assert b"".join(b"".join(e) for e in sent) == b""

        rep = PrometheusMetricSink("127.0.0.1:9125")
        rep._send = sent.append
        assert rep.flush_columnar_native(batch)

        expo = PrometheusExpositionSink("http://127.0.0.1:9091/x")
        bodies = []
        expo._post = lambda body, count: bodies.append((body, count))
        assert expo.flush_columnar_native(batch)
        assert all(b == b"" for b, _ in bodies)

        dd = DatadogMetricSink(
            interval=10.0, flush_max_per_body=100, hostname="h",
            tags=[], dd_hostname="https://dd", api_key="k")
        dd_posted = []
        dd._post_all = (lambda *a, **kw: dd_posted.append((a, kw)))
        assert dd.flush_columnar_native(batch)
        (dd_metrics, checks, raw, n), _kw = dd_posted[-1]
        assert not dd_metrics and not checks and not raw and not n


@requires_native
def test_separator_laden_rows_fall_back_per_group():
    """A row whose name/tags contain the arena separators poisons the
    group's frag arena; the native flush must still emit it, through
    the Python formatter, identically to the pure-Python flush."""
    from veneur_tpu.sinks.forward_statsd import ForwardStatsdSink

    rows = [("weird\x1fname", []), ("fine", ["k:v"])]
    batch = make_batch(rows, [("", MetricType.GAUGE, [1.0, 2.0], None)])
    assert batch.groups[0].meta_blob is None
    assert batch.emit_plan() == [None]
    sink = ForwardStatsdSink("127.0.0.1:9125")
    sent = []
    sink._send = sent.append
    sink.flush_columnar(batch)
    assert sink.flush_columnar_native(batch)  # handled, via fallback
    assert sent[0] == sent[1]
    assert len(sent[0]) == 2


# ---------------------------------------------------------------------------
# negotiation fallback with the native tier masked out


def test_emit_masked_by_env(monkeypatch):
    monkeypatch.setenv("VENEUR_EMIT_NATIVE", "0")
    assert not native_mod.emit_available()


def test_sinks_refuse_native_when_masked(monkeypatch):
    from veneur_tpu.sinks.datadog import DatadogMetricSink
    from veneur_tpu.sinks.forward_statsd import ForwardStatsdSink
    from veneur_tpu.sinks.prometheus import (
        PrometheusExpositionSink,
        PrometheusMetricSink,
    )

    monkeypatch.setenv("VENEUR_EMIT_NATIVE", "0")
    batch = standard_batch()
    dd = DatadogMetricSink(
        interval=10.0, flush_max_per_body=100, hostname="h", tags=[],
        dd_hostname="https://dd", api_key="k")
    assert not dd.flush_columnar_native(batch)
    assert not ForwardStatsdSink("127.0.0.1:9125") \
        .flush_columnar_native(batch)
    assert not PrometheusMetricSink("127.0.0.1:9125") \
        .flush_columnar_native(batch)
    assert not PrometheusExpositionSink("http://127.0.0.1:9091/x") \
        .flush_columnar_native(batch)


def test_server_negotiation_falls_back(monkeypatch):
    """The server's per-sink negotiation: native first, Python columnar
    formatter when the sink refuses — the flush is never lost."""
    from veneur_tpu.core.config import Config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks import MetricSink

    calls = []

    class ProbeSink(MetricSink):
        supports_columnar = True
        supports_native_emit = True
        native_ok = False

        def name(self):
            return "probe"

        def flush(self, metrics):
            calls.append(("flush", len(metrics)))

        def flush_columnar(self, batch, excluded_tags=None):
            calls.append(("python", batch.count()))

        def flush_columnar_native(self, batch, excluded_tags=None):
            if not self.native_ok:
                return False
            calls.append(("native", batch.count()))
            return True

    sink = ProbeSink()
    cfg = Config(interval="10s", percentiles=[], aggregates=["count"])
    srv = Server(cfg, metric_sinks=[sink])
    try:
        srv.process_metric_packet(b"x:3|ms")
        srv.flush()
        assert calls == [("python", 1)]
        sink.native_ok = True
        srv.process_metric_packet(b"x:3|ms")
        srv.flush()
        assert calls == [("python", 1), ("native", 1)]
        # config off forces the python path even for willing sinks
        srv.flush_emit_native = False
        srv.process_metric_packet(b"x:3|ms")
        srv.flush()
        assert calls[-1][0] == "python"
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# deflate


@requires_native
def test_deflate_matches_zlib():
    payloads = [b"", b"x", b'{"series":[]}' * 500,
                bytes(range(256)) * 64]
    for p in payloads:
        assert native_mod.deflate(p) == zlib.compress(p)
