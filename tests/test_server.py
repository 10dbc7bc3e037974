"""End-to-end server tests over loopback sockets.

Mirrors the reference's in-process fixture style (server_test.go:61-169):
a full Server on ephemeral ports, a channel sink capturing flushes, and
deterministic input vectors with value assertions
(TestLocalServerMixedMetrics, server_test.go:299).
"""

import threading
import socket
import time

import pytest

from veneur_tpu.core.config import Config, load_config, parse_duration, redacted_dict
from veneur_tpu.core.metrics import MetricType
from veneur_tpu.core.server import Server, calculate_tick_delay
from veneur_tpu.sinks.channel import ChannelMetricSink


def _server(**cfg_kwargs) -> tuple[Server, ChannelMetricSink, dict]:
    base = dict(
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        num_workers=2,
        num_readers=1,
        interval="10s",
        percentiles=[0.5, 0.99],
    )
    base.update(cfg_kwargs)
    cfg = Config(**base)
    sink = ChannelMetricSink()
    srv = Server(cfg, metric_sinks=[sink])
    ports = srv.start()
    return srv, sink, ports


def _send_udp(port: int, payload: bytes) -> None:
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.sendto(payload, ("127.0.0.1", port))
    s.close()


def _wait_for(predicate, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


def test_udp_ingest_to_flush():
    srv, sink, ports = _server()
    try:
        port = next(iter(ports.values()))
        for v in range(1, 101):
            _send_udp(port, f"e2e.timer:{v}|ms".encode())
        _send_udp(port, b"e2e.count:3|c\ne2e.count:4|c")  # multi-line datagram
        _send_udp(port, b"e2e.gauge:1.5|g")
        assert _wait_for(lambda: srv.packets_received >= 102)
        assert _wait_for(
            lambda: sum(w.processed for w in srv.workers) >= 103)
        metrics = srv.flush()
        by_key = {(m.name, m.type): m for m in metrics}
        assert by_key[("e2e.count", MetricType.COUNTER)].value == 7.0
        assert by_key[("e2e.gauge", MetricType.GAUGE)].value == 1.5
        # local instance: aggregates only for the mixed timer
        assert by_key[("e2e.timer.min", MetricType.GAUGE)].value == 1.0
        assert by_key[("e2e.timer.max", MetricType.GAUGE)].value == 100.0
        assert by_key[("e2e.timer.count", MetricType.COUNTER)].value == 100.0
        # channel sink received the same flush
        flushed = sink.queue.get(timeout=2)
        assert len(flushed) == len(metrics)
    finally:
        srv.shutdown()


def test_local_vs_global_percentiles():
    # a server WITHOUT forward_address is global: percentiles emitted
    srv, sink, ports = _server(forward_address="")
    try:
        port = next(iter(ports.values()))
        for v in range(1, 101):
            _send_udp(port, f"lat:{v}|h".encode())
        assert _wait_for(lambda: sum(w.processed for w in srv.workers) >= 100)
        metrics = srv.flush()
        names = {m.name for m in metrics}
        assert "lat.50percentile" in names
        assert "lat.99percentile" in names
    finally:
        srv.shutdown()

    # with forward_address set, it's local: no percentiles for mixed scope
    srv2, _, ports2 = _server(forward_address="http://upstream:8127")
    try:
        port2 = next(iter(ports2.values()))
        for v in range(1, 101):
            _send_udp(port2, f"lat:{v}|h".encode())
        assert _wait_for(lambda: sum(w.processed for w in srv2.workers) >= 100)
        metrics = srv2.flush()
        names = {m.name for m in metrics}
        assert "lat.50percentile" not in names
        assert "lat.min" in names
    finally:
        srv2.shutdown()


def test_overlong_datagram_dropped():
    srv, _, ports = _server()
    try:
        port = next(iter(ports.values()))
        _send_udp(port, b"x" * 5000)
        _send_udp(port, b"ok:1|c")
        assert _wait_for(lambda: srv.packets_received >= 2)
        assert srv.parse_errors >= 1
        metrics = srv.flush()
        assert any(m.name == "ok" for m in metrics)
    finally:
        srv.shutdown()


def test_events_flow_to_other_samples():
    srv, sink, ports = _server()
    try:
        port = next(iter(ports.values()))
        _send_udp(port, b"_e{5,4}:title|text|t:warning")
        _send_udp(port, b"_sc|svc|0|m:all good")
        assert _wait_for(lambda: srv.packets_received >= 2)
        metrics = srv.flush()
        samples = sink.other_samples.get(timeout=2)
        assert samples[0].name == "title"
        by_key = {(m.name, m.type): m for m in metrics}
        assert by_key[("svc", MetricType.STATUS)].value == 0.0
    finally:
        srv.shutdown()


def test_tcp_listener():
    cfg = Config(
        statsd_listen_addresses=["tcp://127.0.0.1:0"],
        interval="10s",
    )
    sink = ChannelMetricSink()
    srv = Server(cfg, metric_sinks=[sink])
    ports = srv.start()
    try:
        port = next(iter(ports.values()))
        c = socket.create_connection(("127.0.0.1", port))
        c.sendall(b"tcp.counter:5|c\ntcp.counter:6|c\n")
        c.close()
        assert _wait_for(lambda: sum(w.processed for w in srv.workers) >= 2)
        metrics = srv.flush()
        by_key = {(m.name, m.type): m for m in metrics}
        assert by_key[("tcp.counter", MetricType.COUNTER)].value == 11.0
    finally:
        srv.shutdown()


def test_tcp_lifecycle_self_metrics():
    """tcp.connects / tcp.disconnects mirror the reference's TCP
    listener telemetry (server.go:1254-1335) on both the Python handler
    and the C++ stream-reader path."""
    from veneur_tpu import scopedstatsd

    cfg = Config(statsd_listen_addresses=["tcp://127.0.0.1:0"],
                 interval="10s")
    sink = ChannelMetricSink()
    srv = Server(cfg, metric_sinks=[sink])
    cap = scopedstatsd.CaptureSender()
    srv.stats = scopedstatsd.ScopedClient(cap, namespace="veneur.")
    ports = srv.start()
    try:
        port = next(iter(ports.values()))
        for _ in range(2):
            c = socket.create_connection(("127.0.0.1", port))
            c.sendall(b"tcplc.counter:5|c\n")
            c.close()
        assert _wait_for(lambda: sum(
            1 for line in cap.lines if "tcp.connects" in line) >= 2)
        # disconnects surface either immediately (Python handler) or at
        # the pump's reap (native stream readers)
        assert _wait_for(lambda: sum(
            1 for line in cap.lines if "tcp.disconnects" in line) >= 2,
            timeout=5)
    finally:
        srv.shutdown()


def test_flush_ticker_runs():
    cfg = Config(
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        interval="200ms",
    )
    sink = ChannelMetricSink()
    srv = Server(cfg, metric_sinks=[sink])
    ports = srv.start()
    try:
        port = next(iter(ports.values()))
        _send_udp(port, b"tick:1|c")
        assert _wait_for(lambda: sum(w.processed for w in srv.workers) >= 1)
        flushed = sink.queue.get(timeout=5)
        assert any(m.name == "tick" for m in flushed)
    finally:
        srv.shutdown()


def test_sink_routing_and_excluded_tags():
    srv, sink, ports = _server()
    other = ChannelMetricSink()
    other.name = lambda: "othersink"  # type: ignore[method-assign]
    srv.metric_sinks.append(other)
    srv.sink_excluded_tags["channel"] = {"secret"}
    try:
        port = next(iter(ports.values()))
        _send_udp(port, b"routed:1|c|#veneursinkonly:othersink")
        _send_udp(port, b"tagged:1|c|#secret:x,keep:y")
        assert _wait_for(lambda: sum(w.processed for w in srv.workers) >= 2)
        srv.flush()
        channel_metrics = sink.queue.get(timeout=2)
        other_metrics = other.queue.get(timeout=2)
        ch_names = {m.name for m in channel_metrics}
        assert "routed" not in ch_names  # routed exclusively to othersink
        assert "routed" in {m.name for m in other_metrics}
        tagged = [m for m in channel_metrics if m.name == "tagged"][0]
        assert tagged.tags == ["keep:y"]  # excluded tag stripped
        tagged_other = [m for m in other_metrics if m.name == "tagged"][0]
        assert "secret:x" in tagged_other.tags
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# Config


def test_parse_duration():
    assert parse_duration("10s") == 10.0
    assert parse_duration("500ms") == 0.5
    assert parse_duration("2m30s") == 150.0
    assert parse_duration("1h") == 3600.0
    with pytest.raises(ValueError):
        parse_duration("xyz")


def test_load_config_yaml_env_overlay(tmp_path):
    p = tmp_path / "cfg.yaml"
    p.write_text(
        "interval: 5s\n"
        "percentiles: [0.5, 0.9]\n"
        "forward_address: http://global:8127\n"
        "datadog_api_key: sekrit\n"
        "unknown_key_xyz: 1\n"
    )
    cfg = load_config(str(p), env={"VENEUR_HOSTNAME": "h1",
                                   "VENEUR_NUMWORKERS": "3"})
    assert cfg.interval_seconds() == 5.0
    assert cfg.percentiles == [0.5, 0.9]
    assert cfg.is_local()
    assert cfg.hostname == "h1"
    assert cfg.num_workers == 3
    red = redacted_dict(cfg)
    assert red["datadog_api_key"] == "REDACTED"


def test_load_config_deprecated_aliases(tmp_path):
    """ssf_buffer_size / flush_max_per_body are deprecated aliases for the
    datadog_* knobs (reference config_parse.go:172-183); they fill the new
    key only when it was left at its default."""
    p = tmp_path / "cfg.yaml"
    p.write_text("ssf_buffer_size: 999\nflush_max_per_body: 1234\n")
    cfg = load_config(str(p))
    assert cfg.datadog_span_buffer_size == 999
    assert cfg.datadog_flush_max_per_body == 1234
    # explicit new-key value wins over the alias
    p.write_text("ssf_buffer_size: 999\ndatadog_span_buffer_size: 777\n")
    cfg = load_config(str(p))
    assert cfg.datadog_span_buffer_size == 777


def test_load_config_strict_rejects_unknown(tmp_path):
    p = tmp_path / "cfg.yaml"
    p.write_text("no_such_key: true\n")
    with pytest.raises(ValueError):
        load_config(str(p), strict=True)


@pytest.mark.parametrize("strict", [False, True], ids=["loose", "strict"])
@pytest.mark.parametrize("key", ["flush_pipeline", "flush_pipeline_backlog"])
def test_the_removed_flush_executor_keys_are_unknown_keys(
        tmp_path, caplog, key, strict):
    """The stage-parallel flush went with PR 31: a config that still
    names it gets what any unknown key gets, and no alias."""
    p = tmp_path / "cfg.yaml"
    p.write_text(f"interval: 5s\n{key}: 1\n")
    if strict:
        with pytest.raises(ValueError, match=key):
            load_config(str(p), strict=True)
        return
    with caplog.at_level("WARNING"):
        cfg = load_config(str(p))
    assert not hasattr(cfg, key)
    assert any("unknown config keys" in r.getMessage()
               and key in r.getMessage() for r in caplog.records)


def test_the_ticker_has_one_branch_and_outlives_a_flush_that_raises(
        monkeypatch, caplog):
    """_flush_loop: flush, then adapt the spill caps to its duration. A
    flush that raises is logged, owes the caps nothing, leaves no
    _tick_due behind, and the next tick still fires."""
    # not started: this test's thread is the only ticker
    srv = Server(Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                        interval="50ms", tpu_native_ingest=False),
                 metric_sinks=[ChannelMetricSink()])
    calls, adapted, due_seen = [], [], []

    def flush():
        due_seen.append(srv._tick_due)
        calls.append(len(calls))
        if len(calls) == 1:
            raise RuntimeError("first flush fails")
        if len(calls) == 3:
            srv._shutdown.set()

    monkeypatch.setattr(srv, "flush", flush)
    monkeypatch.setattr(srv, "_adapt_spill_caps", adapted.append)
    try:
        with caplog.at_level("ERROR"):
            t = threading.Thread(target=srv._flush_loop, daemon=True)
            t.start()
            t.join(timeout=10)
        assert not t.is_alive()
        assert len(calls) == 3
        assert len(adapted) == 2 and all(0 <= d < 1 for d in adapted)
        assert all(d is not None for d in due_seen)
        assert srv._tick_due is None
        (err,) = [r for r in caplog.records if r.levelname == "ERROR"]
        assert err.getMessage() == "flush failed"
        assert "first flush fails" in str(err.exc_info[1])
    finally:
        srv.shutdown()


def test_calculate_tick_delay():
    assert calculate_tick_delay(10.0, 103.0) == pytest.approx(7.0)
    assert calculate_tick_delay(10.0, 100.0) == pytest.approx(10.0)


def test_unixgram_statsd_flock_and_abstract(tmp_path):
    """Unixgram listener: flock exclusivity (networking.go:289-306 analog)
    plus abstract-socket ingest."""
    path = str(tmp_path / "statsd.sock")
    srv, sink, _ = _server(statsd_listen_addresses=[f"unixgram://{path}"])
    try:
        # a second server on the same path must refuse to start
        cfg2 = Config(statsd_listen_addresses=[f"unixgram://{path}"],
                      num_workers=1, num_readers=1, interval="10s")
        srv2 = Server(cfg2, metric_sinks=[])
        with pytest.raises(RuntimeError, match="locked"):
            srv2.start()
        srv2.shutdown()

        tx = socket.socket(socket.AF_UNIX, socket.SOCK_DGRAM)
        tx.sendto(b"ug.count:5|c", path)
        tx.close()
        assert _wait_for(lambda: sum(w.processed for w in srv.workers) >= 1)
        metrics = srv.flush()
        assert {(m.name, m.value) for m in metrics} == {("ug.count", 5.0)}
    finally:
        srv.shutdown()

    # abstract socket: no filesystem entry, no lock file
    srv3, _, _ = _server(statsd_listen_addresses=["unixgram://@vtpu-test-abs"])
    try:
        tx = socket.socket(socket.AF_UNIX, socket.SOCK_DGRAM)
        tx.sendto(b"abs.count:2|c", "\0vtpu-test-abs")
        tx.close()
        assert _wait_for(lambda: sum(w.processed for w in srv3.workers) >= 1)
        # abstract sockets have no filesystem presence: no lock fds taken
        assert srv3._socket_locks == []
    finally:
        srv3.shutdown()


def test_lock_released_after_shutdown(tmp_path):
    """Shutdown releases the flock so a successor instance can bind."""
    path = str(tmp_path / "reuse.sock")
    srv, _, _ = _server(statsd_listen_addresses=[f"unixgram://{path}"])
    srv.shutdown()
    srv2, _, _ = _server(statsd_listen_addresses=[f"unixgram://{path}"])
    srv2.shutdown()


def test_ssf_unixgram(tmp_path):
    """SSF spans over a unix datagram socket."""
    from veneur_tpu.gen import ssf_pb2

    path = str(tmp_path / "ssf.sock")
    srv, _, _ = _server(ssf_listen_addresses=[f"unixgram://{path}"])
    try:
        span = ssf_pb2.SSFSpan(id=7, trace_id=7, service="svc",
                               start_timestamp=1, end_timestamp=2)
        tx = socket.socket(socket.AF_UNIX, socket.SOCK_DGRAM)
        tx.sendto(span.SerializeToString(), path)
        tx.close()
        assert _wait_for(lambda: srv.ssf_spans_received.get("svc", 0) >= 1)
    finally:
        srv.shutdown()


def test_enable_profiling_writes_xla_trace(tmp_path):
    """enable_profiling starts a JAX profiler trace on start and flushes
    it on shutdown (reference profile.Start(), server.go:1392-1399)."""
    from veneur_tpu.core.config import load_config
    from veneur_tpu.core.factory import build_server

    prof = tmp_path / "prof"
    cfg = load_config(data={
        "statsd_listen_addresses": [],
        "interval": "60s",
        "enable_profiling": True,
        "profile_dir": str(prof),
    })
    srv = build_server(cfg)
    srv.start()
    srv.flush()
    srv.shutdown()
    files = list(prof.rglob("*"))
    assert any(f.is_file() for f in files), "no profiler artifacts written"


def test_mid_epoch_series_sync_preserves_flush_output():
    """New-series adoption can run any number of times mid-epoch
    (Server._series_sync_loop does it on a sub-interval cadence so the
    per-series Python work doesn't all land in swap, under the ingest
    lock) without changing what the flush emits or double-adopting."""
    srv, sink, ports = _server(num_workers=2, interval="600s")
    try:
        if not srv.native_mode:
            pytest.skip("native library unavailable")
        for i in range(200):
            srv._native_router.ingest(
                f"sync.t{i}:{i % 31}|ms\nsync.c{i}:2|c".encode())
            if i % 40 == 0:
                srv.sync_native_series_once()
        srv.sync_native_series_once()
        srv.sync_native_series_once()  # idempotent when nothing pending
        adopted_before = sum(
            w.directory.num_histo_rows for w in srv.workers)
        assert adopted_before == 200  # all series visible pre-flush
        final = srv.flush()
        ms = {m.name: m for m in
              (final.materialize() if hasattr(final, "materialize")
               else final)}
        # one .count per timer series + one counter series each
        assert sum(1 for n in ms if n.endswith(".count")) == 200
        assert ms["sync.c7"].value == 2.0
        assert ms["sync.t7.max"].value == 7.0
    finally:
        srv.shutdown()


def test_ingest_not_blocked_during_flush_extraction():
    """SURVEY §7 latency budget: next-interval ingest must keep flowing
    while the flush extracts. Routed native ingest takes no Python lock
    and the C++ context lock only covers the raw drain, so reader
    commits proceed while the device runs extraction."""
    srv, sink, ports = _server(num_workers=2, interval="600s")
    try:
        if not srv.native_mode:
            pytest.skip("native library unavailable")
        # enough series+samples that flush extraction takes real time
        payload = b"\n".join(
            f"iflush.s{i}:{i % 97}|ms".encode() for i in range(64))
        for i in range(3000):
            srv._native_router.ingest(payload
                                      .replace(b"iflush", b"is%d" % (i % 50)))

        flush_done = threading.Event()

        def run_flush():
            srv.flush()
            flush_done.set()

        t = threading.Thread(target=run_flush, daemon=True)
        t.start()
        accepted_during = 0
        probes = 0
        while not flush_done.is_set() and probes < 20000:
            accepted_during += srv._native_router.ingest(payload)
            probes += 1
        t.join(timeout=60)
        assert flush_done.is_set()
        # ingest kept flowing while the flush thread ran
        assert accepted_during > 0
        # and everything ingested during the flush lands in the NEW epoch
        post = sum(w.processed for w in srv.workers)
        assert post > 0
    finally:
        srv.shutdown()


def test_listener_fd_handoff_keeps_datagrams():
    """Zero-downtime restart (reference einhorn handoff,
    server.go:1401-1429): datagrams sent between the old server's
    quiesce and the new server's start must queue in the kernel socket
    buffer and be delivered to the successor, not dropped."""
    srv_a, _sink_a, ports = _server(num_workers=1, interval="600s")
    spec = next(iter(ports))
    port = ports[spec]
    try:
        _send_udp(port, b"gen1.c:1|c")
        assert _wait_for(lambda: sum(w.processed for w in srv_a.workers) >= 1)

        manifest = srv_a.prepare_handoff()
        assert manifest[spec]  # the udp listener fd is in the manifest
        # readers are quiesced: these datagrams queue in the kernel buffer
        for i in range(5):
            _send_udp(port, b"gen2.c:1|c")
        srv_a.shutdown()

        cfg = Config(statsd_listen_addresses=[spec], num_workers=1,
                     interval="600s", num_readers=1)
        srv_b = Server(cfg, inherited_fds=manifest)
        ports_b = srv_b.start()
        try:
            assert ports_b[spec] == port  # same socket, same port
            assert _wait_for(
                lambda: sum(w.processed for w in srv_b.workers) >= 5)
        finally:
            srv_b.shutdown()
    finally:
        srv_a.shutdown()


def test_canonical_self_telemetry_names():
    """The canonical telemetry surface (reference README.md:282-296,
    sinks/sinks.go constants) must appear in a flush's self-metrics."""
    from veneur_tpu import scopedstatsd
    from veneur_tpu.sinks.blackhole import BlackholeSpanSink

    srv, sink, ports = _server(num_workers=2, interval="600s")
    try:
        span_sink = BlackholeSpanSink()
        srv.span_sinks.append(span_sink)
        cap = scopedstatsd.CaptureSender()
        srv.stats = scopedstatsd.ScopedClient(cap, namespace="veneur.")
        port = next(iter(ports.values()))
        for i in range(20):
            _send_udp(port, f"tele.h:{i}|ms\ntele.c:1|c\ntele.s:x{i}|s"
                      .encode())
        _send_udp(port, b"tele.g:2|g")
        assert _wait_for(lambda: sum(w.processed for w in srv.workers) >= 61)
        srv.flush()
        lines = "\n".join(cap.lines)
        for name in (
            "veneur.worker.metrics_processed_total",
            "veneur.worker.metrics_flushed_total",
            "veneur.worker.metrics_imported_total",
            "veneur.flush.post_metrics_total",
            "veneur.flush.total_duration_ns",
            "veneur.packet.error_total",
            "veneur.sink.metrics_flushed_total",
            "veneur.sink.metric_flush_total_duration_ns",
            "veneur.gc.number",
            "veneur.mem.rss_bytes",
        ):
            assert name in lines, f"missing {name}"
        assert "metric_type:histogram" in lines
        assert "worker:0" in lines
    finally:
        srv.shutdown()


def test_adoption_spans_and_gauge_tell_known_from_first_seen():
    """`adopt` / `swap.adopt` keep their names and their `series`
    attribute (bench/layer_metrics/adopt_ms.flush.json reads them) and
    say how many of the series were known; `directory.interned` gauges
    what is kept for the series' lifetimes."""
    from veneur_tpu import scopedstatsd

    srv, sink, ports = _server(num_workers=1, interval="600s")
    try:
        if not srv.native_mode:
            pytest.skip("native ingest library unavailable")
        cap = scopedstatsd.CaptureSender()
        srv.stats = scopedstatsd.ScopedClient(cap, namespace="veneur.")
        port = next(iter(ports.values()))
        lines = [b"ad.t%d:1|ms" % i for i in range(30)]
        lines += [b"ad.c%d:1|c" % i for i in range(10)]
        for interval in range(2):
            extra = [b"ad.late%d:1|g" % i for i in range(5 * interval)]
            _send_udp(port, b"\n".join(lines + extra))
            assert _wait_for(
                lambda: sum(c.processed
                            for c in srv.workers[0]._all_ctxs())
                >= len(lines + extra))
            srv.flush()
            spans = [s for s in srv.rec.closed()
                     if s.name in ("adopt", "swap.adopt")
                     and s.flush == srv.workers[0].flight_epoch - 1]
            assert spans, [s.name for s in srv.rec.closed()]
            for sp in spans:
                assert sp.attrs["series"] == (sp.attrs["known"]
                                              + sp.attrs["first_seen"])
            total = {k: sum(sp.attrs[k] for sp in spans)
                     for k in ("series", "known", "first_seen")}
            if interval == 0:
                assert total == {"series": 40, "known": 0, "first_seen": 40}
            else:
                assert total == {"series": 45, "known": 40, "first_seen": 5}
        assert "veneur.directory.interned:45" in "\n".join(cap.lines)
    finally:
        srv.shutdown()


@pytest.mark.parametrize("disturbed", [False, True],
                         ids=["all-by-id", "index-and-blob-asked"])
def test_the_span_record_says_how_the_row_books_were_kept(disturbed):
    """`adopt` / `swap.adopt` carry `by_id`, the series that took their
    row as an integer; `flush.begin` carries, for the epoch it closed,
    `books_materialised` and `frag_blob_builds`. Through one native
    context into sinks that read the columns: by_id = series, 0 and 0.
    Ask a live book for its index and let a sink ask for the frag blobs:
    one book materialised (its arena joined then) and one join for each
    of the other pools that held rows."""
    from veneur_tpu.sinks import MetricSink

    class BlobSink(MetricSink):
        def name(self):
            return "blob"

        def flush(self, metrics):
            pass

        def flush_columnar(self, batch, excluded_tags=None):
            self.blobs = [g.meta_blob for g in batch.groups]

    srv, sink, ports = _server(num_workers=1, interval="600s")
    try:
        if not srv.native_mode:
            pytest.skip("native ingest library unavailable")
        blob_sink = BlobSink()
        if disturbed:
            srv.metric_sinks.append(blob_sink)
        port = next(iter(ports.values()))
        lines = [b"bk.t%d:1|ms" % i for i in range(30)]
        lines += [b"bk.c%d:1|c" % i for i in range(10)]
        lines += [b"bk.g%d:1|g" % i for i in range(5)]
        w = srv.workers[0]
        for interval in range(2):
            _send_udp(port, b"\n".join(lines))
            assert _wait_for(lambda: sum(c.processed for c in w._all_ctxs())
                             >= len(lines))
            if disturbed:
                with srv._worker_locks[0]:
                    w.sync_native_series()
                    assert len(w.directory.histo.index) == 30
            srv.flush()
            ordinal = w.flight_epoch - 1
            adopts = [s for s in srv.rec.closed() if s.flush == ordinal
                      and s.name in ("adopt", "swap.adopt")]
            assert sum(s.attrs["series"] for s in adopts) == 45
            assert sum(s.attrs["by_id"] for s in adopts) == 45
            begin = [s for s in srv.last_flush_phases["spans"]
                     if s[1] == "flush.begin"]
            assert len(begin) == 1 and begin[0][5] == ordinal
            attrs = begin[0][6]
            assert attrs["books_materialised"] == (1 if disturbed else 0)
            assert attrs["frag_blob_builds"] == (3 if disturbed else 0)
        if disturbed:
            assert [b is not None for b in blob_sink.blobs] == [True] * 3
    finally:
        srv.shutdown()


def test_the_heap_is_frozen_once_the_series_table_has_grown(monkeypatch):
    """What adoption keeps lives as long as the series: once a flush
    ends with the table a quarter (and 4,096 series) past what was last
    frozen, gc.freeze() takes it out of the collector's walk; a steady
    table freezes nothing again, a dropped one starts over."""
    import gc

    srv, sink, ports = _server(num_workers=1, interval="600s")
    try:
        calls = []
        monkeypatch.setattr(gc, "freeze", lambda: calls.append(1))
        for interned, frozen_calls in ((100, 0), (4096, 0), (5000, 1),
                                       (5000, 1), (9000, 1), (11000, 2),
                                       (300, 2), (4500, 3)):
            srv._freeze_series(interned)
            assert len(calls) == frozen_calls, (interned, calls)
        # and the flush is what calls it, with the workers' count
        seen = []
        monkeypatch.setattr(srv, "_freeze_series", seen.append)
        srv.process_metric_packet(b"fz.t:1|ms")
        srv.flush()
        assert seen == [srv.workers[0].interned_series]
    finally:
        srv.shutdown()


def test_listener_fd_handoff_ssf_listener():
    """SSF UDP listeners ride the handoff too."""
    cfg = Config(ssf_listen_addresses=["udp://127.0.0.1:0"],
                 interval="600s", num_workers=1)
    srv_a = Server(cfg)
    ports = srv_a.start()
    spec = "udp://127.0.0.1:0"
    port = ports[spec]
    try:
        manifest = srv_a.prepare_handoff()
        assert manifest.get("ssf:" + spec), manifest
        # queued while no reader is consuming
        from veneur_tpu import ssf
        from veneur_tpu.protocol import ssf_wire

        span = ssf.SSFSpan(trace_id=1, id=2, start_timestamp=1,
                           end_timestamp=2, service="hs", name="n")
        _send_udp(port, ssf_wire.encode_datagram(span))
        srv_a.shutdown()

        srv_b = Server(Config(ssf_listen_addresses=[spec],
                              interval="600s", num_workers=1),
                       inherited_fds=manifest)
        ports_b = srv_b.start()
        try:
            assert ports_b[spec] == port
            assert _wait_for(
                lambda: srv_b.ssf_spans_received.get("hs", 0) >= 1
                or sum(w.processed for w in srv_b.workers) >= 1)
        finally:
            srv_b.shutdown()
    finally:
        srv_a.shutdown()


def test_flush_ingest_soak_no_loss_no_crash():
    """Race-strategy soak (the §5.2 analog of running under -race): rapid
    flushes concurrent with multi-threaded UDP ingest; every counter
    increment sent before the final flush must be accounted for exactly
    once across all flush outputs — the two-phase swap/extract must not
    lose or double-count an epoch boundary."""
    import threading

    srv, sink, ports = _server(num_workers=2, interval="600s")
    try:
        port = next(iter(ports.values()))
        stop = threading.Event()
        sent = [0, 0]

        def blaster(idx):
            # throttled: the point is racing epoch boundaries, not
            # saturating the box (flushes must actually get CPU time)
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            while not stop.is_set():
                for _ in range(20):
                    s.sendto(b"soak.count:1|c\nsoak.h:5|ms",
                             ("127.0.0.1", port))
                    sent[idx] += 1
                time.sleep(0.02)
            s.close()

        threads = [threading.Thread(target=blaster, args=(i,), daemon=True)
                   for i in range(2)]
        for t in threads:
            t.start()
        # first flush compiles; keep flushing until several epoch
        # boundaries have raced the blasters (or a generous time cap on
        # slow single-core runners)
        flushes = 0
        deadline = time.time() + 30.0
        while flushes < 3 and time.time() < deadline:
            srv.flush()
            flushes += 1
        if flushes < 3:
            pytest.fail(f"only {flushes} flushes completed inside the 30s "
                        "cap: runner too slow to race epoch boundaries")
        stop.set()
        for t in threads:
            t.join(5.0)
        # UDP may drop under blast; the invariant is ingested == flushed:
        # wait for the readers to drain the kernel buffer (received count
        # stabilizes), then final-flush and account for every ingested
        # increment exactly once across all flushes
        def _stable():
            before = srv.packets_received
            time.sleep(0.4)
            return srv.packets_received == before

        assert _wait_for(_stable, timeout=15.0)
        srv.flush()

        total_ingested = srv.packets_received
        got = 0.0
        while not sink.queue.empty():
            got += sum(m.value for m in sink.queue.get_nowait()
                       if m.name == "soak.count")
        assert sum(sent) > 0 and total_ingested > 0
        assert got == total_ingested, (got, total_ingested, flushes)
    finally:
        srv.shutdown()


def test_flush_ingest_soak_columnar_no_loss():
    """The soak invariant through the COLUMNAR flush path: with only
    columnar sinks, rapid flushes racing multi-threaded ingest must
    still account for every ingested increment exactly once (the batch
    references the swapped epoch's directory/arrays — no copy — so this
    guards it against the live epoch mutating underneath)."""
    import threading

    from veneur_tpu.sinks.blackhole import BlackholeMetricSink

    class CountingColumnarSink(BlackholeMetricSink):
        def __init__(self):
            self.count_values = []

        def flush_columnar(self, batch, excluded_tags=None):
            for name, value, _tags, _t, _ts in batch.iter_rows(
                    self.name()):
                if name == "soak.count":
                    self.count_values.append(value)

    sink = CountingColumnarSink()
    cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                 num_workers=2, num_readers=1, interval="600s",
                 aggregates=["count"])
    srv = Server(cfg, metric_sinks=[sink])
    ports = srv.start()
    try:
        port = next(iter(ports.values()))
        stop = threading.Event()
        sent = [0, 0]

        def blaster(idx):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            while not stop.is_set():
                for _ in range(20):
                    s.sendto(b"soak.count:1|c\nsoak.h:5|ms",
                             ("127.0.0.1", port))
                    sent[idx] += 1
                time.sleep(0.02)
            s.close()

        threads = [threading.Thread(target=blaster, args=(i,), daemon=True)
                   for i in range(2)]
        for t in threads:
            t.start()
        # three flushes of an idle server can be over before a loaded
        # runner has let either blaster send: race them against traffic
        assert _wait_for(lambda: all(sent), timeout=10.0)
        flushes = 0
        deadline = time.time() + 30.0
        while flushes < 3 and time.time() < deadline:
            srv.flush()
            flushes += 1
        if flushes < 3:
            pytest.fail("runner too slow to race epoch boundaries")
        stop.set()
        for t in threads:
            t.join(5.0)

        def _stable():
            before = srv.packets_received
            time.sleep(0.4)
            return srv.packets_received == before

        assert _wait_for(_stable, timeout=15.0)
        srv.flush()
        total_ingested = srv.packets_received
        got = sum(sink.count_values)
        assert sum(sent) > 0 and total_ingested > 0
        assert got == total_ingested, (got, total_ingested, flushes)
    finally:
        srv.shutdown()


@pytest.mark.parametrize(
    "num_workers,num_readers,n_blasters",
    [
        (1, 2, 4),   # many readers + blasters racing one worker's epoch
        (4, 1, 2),   # one reader fanning packets across many workers
    ])
def test_flush_ingest_stress_matrix(num_workers, num_readers, n_blasters):
    """Threading stress matrix over the flush/ingest overlap (VERDICT r3
    item 5): the no-loss/no-double-count invariant of the two-phase
    swap/extract must hold at every point of the reader x worker x
    ingest-thread topology, not just the 2x1x2 shape the fixed soaks
    use. Native C++ commit path included when built (the same topology
    runs under ThreadSanitizer in native/tsan_soak.cpp)."""
    import threading

    srv, sink, ports = _server(num_workers=num_workers,
                               num_readers=num_readers, interval="600s")
    try:
        port = next(iter(ports.values()))
        stop = threading.Event()
        sent = [0] * n_blasters

        def blaster(idx):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            seq = 0
            while not stop.is_set():
                for _ in range(20):
                    # rotate names so digest%num_workers provably reaches
                    # every worker, whatever the matrix's worker count
                    s.sendto(b"soak.m%d.%d:1|c\nsoak.h%d:5|ms"
                             % (idx, seq % 16, idx), ("127.0.0.1", port))
                    sent[idx] += 1
                    seq += 1
                time.sleep(0.02)
            s.close()

        threads = [threading.Thread(target=blaster, args=(i,), daemon=True)
                   for i in range(n_blasters)]
        for t in threads:
            t.start()
        # three flushes of empty epochs can be over before a blaster
        # thread has run once: race the epochs only once all send
        assert _wait_for(lambda: all(sent), timeout=15.0)
        flushes = 0
        deadline = time.time() + 30.0
        while flushes < 3 and time.time() < deadline:
            srv.flush()
            flushes += 1
        if flushes < 3:
            pytest.fail("runner too slow to race epoch boundaries")
        stop.set()
        for t in threads:
            t.join(5.0)

        def _stable():
            before = srv.packets_received
            time.sleep(0.4)
            return srv.packets_received == before

        assert _wait_for(_stable, timeout=15.0)
        srv.flush()
        total_ingested = srv.packets_received
        got = 0.0
        while not sink.queue.empty():
            got += sum(m.value for m in sink.queue.get_nowait()
                       if m.name.startswith("soak.m"))
        assert sum(sent) > 0 and total_ingested > 0
        assert got == total_ingested, (got, total_ingested, flushes)
    finally:
        srv.shutdown()


def test_flush_is_self_traced():
    """Every flush emits an internal span that rejoins the server's own
    span pipeline (reference flusher.go:29 StartSpan("flush") via the
    internal SpanChan client, server.go:310-317)."""
    captured = []

    class _CapSpanSink:
        def name(self):
            return "cap"

        def start(self, trace_client=None):
            pass

        def ingest(self, span):
            captured.append(span)

        def flush(self):
            pass

    srv, sink, ports = _server(interval="600s")
    try:
        srv.span_worker.span_sinks.append(_CapSpanSink())
        srv.flush()
        assert _wait_for(
            lambda: any(s.name == "flush" for s in captured))
        span = [s for s in captured if s.name == "flush"][0]
        assert span.service == "veneur-tpu"
        assert span.end_timestamp > span.start_timestamp
    finally:
        srv.shutdown()


@pytest.mark.parametrize("native_readers", [True, False])
def test_udp_reader_modes_equivalent(native_readers):
    """The C++ reader thread (vn_reader_start) and the Python recv loop
    deliver identical flush results — and the Python path stays covered
    now that native readers are the default."""
    srv, sink, ports = _server(tpu_native_readers=native_readers)
    try:
        if native_readers:
            if not srv.native_mode:
                pytest.skip("native library unavailable")
            assert srv._native_readers, "native reader thread not started"
        port = next(iter(ports.values()))
        for v in range(1, 51):
            _send_udp(port, b"rm.t:%d|ms" % v)
        _send_udp(port, b"rm.c:2|c\nrm.c:3|c")
        _send_udp(port, b"x" * 5000)  # overlong: counted, dropped
        assert _wait_for(lambda: srv.packets_received >= 52)
        assert _wait_for(lambda: srv.parse_errors >= 1)
        metrics = srv.flush()
        by_key = {(m.name, m.type): m for m in metrics}
        assert by_key[("rm.c", MetricType.COUNTER)].value == 5.0
        assert by_key[("rm.t.count", MetricType.COUNTER)].value == 50.0
        assert by_key[("rm.t.max", MetricType.GAUGE)].value == 50.0
    finally:
        received = srv.packets_received
        srv.shutdown()
        # counters survive reader stop (folded into the stopped tally)
        assert srv.packets_received >= received


def test_sampled_timers_weighted_through_native_plane():
    """|@rate timers flow through the native staging plane with their
    1/rate weights (the non-unit-weights upload branch): count reflects
    the estimated population, not the sample count."""
    srv, _, ports = _server(num_workers=1)
    try:
        port = next(iter(ports.values()))
        for v in range(1, 41):
            _send_udp(port, b"sr.t:%d|ms|@0.5" % v)
        assert _wait_for(lambda: srv.packets_received >= 40)
        assert _wait_for(
            lambda: sum(w.processed for w in srv.workers) >= 40)
        metrics = srv.flush()
        by_key = {(m.name, m.type): m for m in metrics}
        # 40 samples at rate 0.5 -> weight 2 each -> estimated count 80
        assert by_key[("sr.t.count", MetricType.COUNTER)].value == 80.0
        assert by_key[("sr.t.max", MetricType.GAUGE)].value == 40.0
    finally:
        srv.shutdown()


def test_pool_growth_under_native_staging():
    """Series count far past tpu_initial_histo_rows: the device pool and
    the C++ staging plane grow on their own pow2 schedules and the
    extract reconciles them (slice/pad) without losing samples."""
    srv, _, ports = _server(num_workers=1, tpu_initial_histo_rows=256)
    try:
        port = next(iter(ports.values()))
        n_series = 2000
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        for i in range(n_series):
            s.sendto(b"gr.t%d:%d|ms" % (i, i % 100), ("127.0.0.1", port))
        s.close()
        assert _wait_for(lambda: srv.packets_received >= n_series, 10.0)
        assert _wait_for(
            lambda: sum(w.processed for w in srv.workers) >= n_series, 10.0)
        metrics = srv.flush()
        counts = [m for m in metrics if m.name.endswith(".count")]
        assert len(counts) == n_series
        assert all(m.value == 1.0 for m in counts)
    finally:
        srv.shutdown()


def test_native_reader_survives_garbage_fuzz():
    """Random bytes straight into the C++ reader: no crash, every
    datagram accounted (accepted or counted as parse error), server
    flushes normally afterwards."""
    import os as _os

    srv, _, ports = _server(num_workers=1)
    try:
        port = next(iter(ports.values()))
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rng = __import__("random").Random(7)
        n = 300
        for i in range(n):
            size = rng.choice((0, 1, 7, 63, 512, 1400))
            s.sendto(bytes(rng.getrandbits(8) for _ in range(size)),
                     ("127.0.0.1", port))
        s.sendto(b"fz.ok:1|c", ("127.0.0.1", port))
        s.close()
        assert _wait_for(lambda: srv.packets_received >= n + 1, 10.0)
        metrics = srv.flush()
        assert any(m.name == "fz.ok" for m in metrics)
        # garbage was counted, not silently swallowed (newline-split
        # lines can each count, so >= is the right bound)
        assert srv.parse_errors >= 1
    finally:
        srv.shutdown()


def test_tcp_native_stream_reader_fragmentation():
    """The C++ stream reader reassembles lines across arbitrary send
    boundaries, drops overlong lines whole (counted), and its reader is
    reaped after the peer closes."""
    srv, _, ports = _server(
        statsd_listen_addresses=["tcp://127.0.0.1:0"], num_workers=1)
    try:
        if not srv.native_mode:
            pytest.skip("native library unavailable")
        port = next(iter(ports.values()))
        c = socket.create_connection(("127.0.0.1", port))
        # a line split across three sends
        c.sendall(b"frag.c")
        time.sleep(0.05)
        c.sendall(b":4")
        time.sleep(0.05)
        c.sendall(b"|c\n")
        # two lines in one send + an overlong line + a good trailer
        c.sendall(b"frag.c:1|c\nfrag.t:9|ms\n")
        c.sendall(b"x" * 5000 + b"\n")
        c.sendall(b"frag.c:2|c\n")
        c.close()
        assert _wait_for(
            lambda: sum(w.processed for w in srv.workers) >= 4, 10.0)
        assert _wait_for(lambda: srv.parse_errors >= 1, 10.0)
        metrics = srv.flush()
        by_key = {(m.name, m.type): m for m in metrics}
        assert by_key[("frag.c", MetricType.COUNTER)].value == 7.0
        assert by_key[("frag.t.count", MetricType.COUNTER)].value == 1.0
        # reap: the closed connection's reader is joined by the pump
        assert _wait_for(lambda: not srv._native_stream_readers, 5.0)
    finally:
        srv.shutdown()


def test_shutdown_with_live_tcp_connection_is_prompt():
    """shutdown() must join an ACTIVE C++ stream reader promptly (the
    500ms recv timeout polls the stop flag) without waiting for the
    peer to close."""
    srv, _, ports = _server(
        statsd_listen_addresses=["tcp://127.0.0.1:0"], num_workers=1)
    port = next(iter(ports.values()))
    c = socket.create_connection(("127.0.0.1", port))
    c.sendall(b"live.c:1|c\n")
    assert _wait_for(lambda: sum(w.processed for w in srv.workers) >= 1)
    t0 = time.time()
    srv.shutdown()  # connection still open, reader mid-recv
    assert time.time() - t0 < 5.0
    c.close()


def test_high_cardinality_all_types_cross_pool_boundaries():
    """600 series of EVERY metric class through the packet path in one
    worker: counters and gauges cross the scalar pools' 256/512
    capacity boundaries (the soak-caught adopt_row bug lived exactly
    there), histos/sets cross the device-pool growth schedule, and the
    flush must still be exact."""
    srv, _, ports = _server(num_workers=1)
    try:
        port = next(iter(ports.values()))
        n = 600
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        lines = []
        for i in range(n):
            lines.append(b"hc.c%d:3|c" % i)
            lines.append(b"hc.g%d:%d|g" % (i, i))
            lines.append(b"hc.t%d:%d|ms" % (i, i % 250))
            lines.append(b"hc.s%d:member%d|s" % (i, i))
        # ~8 lines per datagram keeps packets under the default max
        for off in range(0, len(lines), 8):
            s.sendto(b"\n".join(lines[off:off + 8]), ("127.0.0.1", port))
        s.close()
        assert _wait_for(lambda: srv.packets_received >= len(lines) // 8,
                         15.0)
        assert _wait_for(
            lambda: sum(w.processed for w in srv.workers) >= 4 * n, 15.0)
        metrics = srv.flush()
        by_key = {(m.name, m.type): m for m in metrics}
        from veneur_tpu.core.metrics import MetricType
        for i in range(n):
            assert by_key[(f"hc.c{i}", MetricType.COUNTER)].value == 3.0
            assert by_key[(f"hc.g{i}", MetricType.GAUGE)].value == float(i)
        t_counts = [m for m in metrics
                    if m.name.startswith("hc.t") and
                    m.name.endswith(".count")]
        assert len(t_counts) == n
        assert all(m.value == 1.0 for m in t_counts)
        set_gauges = [m for m in metrics
                      if m.name.startswith("hc.s") and
                      m.type == MetricType.GAUGE and "." not in
                      m.name[len("hc.s"):]]
        assert len(set_gauges) == n
        # HLL small-range estimate of a single member is ~1.00003
        assert all(abs(m.value - 1.0) < 0.01 for m in set_gauges)
    finally:
        srv.shutdown()
