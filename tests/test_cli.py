"""CLI binary tests: emit, prometheus poller, config validation."""

import json
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from veneur_tpu.cli import emit, prometheus_poller
from veneur_tpu.cli.veneur_main import main as veneur_main
from veneur_tpu.core.config import load_proxy_config
from veneur_tpu.protocol import ssf_wire
from veneur_tpu.protocol.dogstatsd import parse_metric, parse_event


def _udp_receiver():
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    sock.settimeout(3)
    return sock, sock.getsockname()[1]


def test_emit_statsd_metrics():
    sock, port = _udp_receiver()
    rc = emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                    "-name", "cli.counter", "-count", "3",
                    "-tag", "env:dev,team:x"])
    assert rc == 0
    data = sock.recv(4096)
    m = parse_metric(data)
    assert m.name == "cli.counter"
    assert m.value == 3.0
    assert m.tags == ["env:dev", "team:x"]
    sock.close()


def test_emit_event():
    sock, port = _udp_receiver()
    rc = emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                    "-mode", "event",
                    "-e_title", "deploy", "-e_text", "done",
                    "-e_alert_type", "info"])
    assert rc == 0
    e = parse_event(sock.recv(4096))
    assert e.name == "deploy"
    sock.close()


def test_emit_service_check():
    sock, port = _udp_receiver()
    rc = emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                    "-mode", "sc", "-sc_name", "svc", "-sc_status", "2",
                    "-sc_msg", "broken"])
    assert rc == 0
    from veneur_tpu.protocol.dogstatsd import parse_service_check
    sc = parse_service_check(sock.recv(4096))
    assert sc.name == "svc"
    sock.close()


def test_emit_command_mode_ssf_span():
    sock, port = _udp_receiver()
    rc = emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                    "-ssf", "-name", "cmd.duration",
                    "-command", "true"])
    assert rc == 0
    span = ssf_wire.parse_ssf(sock.recv(65536))
    assert span.name == "cmd.duration"
    assert not span.error
    assert span.metrics[0].name == "cmd.duration"
    sock.close()


def test_emit_command_failure_propagates_exit():
    sock, port = _udp_receiver()
    rc = emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                    "-ssf", "-name", "cmd.duration",
                    "-command", "false"])
    assert rc == 1
    span = ssf_wire.parse_ssf(sock.recv(65536))
    assert span.error
    sock.close()


# ---------------------------------------------------------------------------
# prometheus poller


PROM_BODY = """\
# HELP http_requests_total Requests.
# TYPE http_requests_total counter
http_requests_total{code="200"} 100
http_requests_total{code="500"} 5
# TYPE temp_gauge gauge
temp_gauge 21.5
# TYPE req_latency histogram
req_latency_bucket{le="0.1"} 50
req_latency_bucket{le="+Inf"} 60
req_latency_sum 12.5
req_latency_count 60
"""


def test_prometheus_text_parsing():
    types, samples = prometheus_poller.parse_prometheus_text(PROM_BODY)
    assert types["http_requests_total"] == "counter"
    assert types["req_latency"] == "histogram"
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    assert ({"code": "200"}, 100.0) in by_name["http_requests_total"]
    assert by_name["temp_gauge"][0][1] == 21.5


def test_prometheus_counter_dedupe():
    cache = prometheus_poller.CountCache()
    types, samples = prometheus_poller.parse_prometheus_text(PROM_BODY)
    # first scrape establishes baselines; only gauges emitted
    lines1 = prometheus_poller.translate(types, samples, cache, [])
    assert any(b"temp_gauge:21.5|g" in ln for ln in lines1)
    assert not any(b"http_requests_total" in ln for ln in lines1)
    # second scrape with +10 on the 200 counter
    body2 = PROM_BODY.replace('code="200"} 100', 'code="200"} 110')
    types2, samples2 = prometheus_poller.parse_prometheus_text(body2)
    lines2 = prometheus_poller.translate(types2, samples2, cache, ["x:y"])
    counter_lines = [ln for ln in lines2 if b"http_requests_total" in ln]
    assert counter_lines == [b"http_requests_total:10.0|c|#code:200,x:y"]


def test_prometheus_poller_end_to_end():
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = PROM_BODY.encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    sock, port = _udp_receiver()
    try:
        rc = prometheus_poller.main([
            "-h", f"http://127.0.0.1:{httpd.server_port}/metrics",
            "-s", f"127.0.0.1:{port}", "-p", "svc.", "-once"])
        assert rc == 0
        data = sock.recv(65536)
        assert b"svc.temp_gauge:21.5|g" in data
    finally:
        httpd.shutdown()
        sock.close()


# ---------------------------------------------------------------------------
# config CLIs


def test_veneur_main_validate_config(tmp_path):
    p = tmp_path / "ok.yaml"
    p.write_text("interval: 5s\npercentiles: [0.5]\n")
    assert veneur_main(["-f", str(p), "-validate-config"]) == 0
    bad = tmp_path / "bad.yaml"
    bad.write_text("interval: nonsense\n")
    assert veneur_main(["-f", str(bad), "-validate-config"]) == 1


def test_load_proxy_config(tmp_path):
    p = tmp_path / "proxy.yaml"
    p.write_text(
        "consul_forward_service_name: veneur-global\n"
        "grpc_address: 127.0.0.1:8128\n"
    )
    cfg = load_proxy_config(str(p))
    assert cfg.consul_forward_service_name == "veneur-global"
    assert cfg.grpc_address == "127.0.0.1:8128"


def test_tdigest_analysis_harness(tmp_path):
    """The offline accuracy harness (tools/tdigest_analysis.py, the
    reference tdigest/analysis analog) meets the q-space error budget."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "tdigest_analysis",
        os.path.join(os.path.dirname(__file__), "..", "tools",
                     "tdigest_analysis.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    r = mod.analyze("gamma", mod.DISTRIBUTIONS["gamma"], 20_000, 100.0,
                    str(tmp_path))
    assert r["max_q_err"] < 0.01
    assert (tmp_path / "gamma.csv").exists()


def test_veneur_main_sighup_graceful_restart(tmp_path):
    """SIGHUP drains and re-execs in place (reference einhorn-style
    graceful restart, server.go:1401-1429) — the supervised PID survives
    and the restarted server answers on the same ports."""
    import os
    import signal
    import socket
    import subprocess
    import sys
    import time
    import urllib.request

    def _free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    udp_port = _free_port()
    http_port = _free_port()
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(
        f"statsd_listen_addresses: [udp://127.0.0.1:{udp_port}]\n"
        f"http_address: 127.0.0.1:{http_port}\n"
        "http_quit: true\n"
        "interval: 60s\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "veneur_tpu.cli.veneur_main",
         "-f", str(cfg)], env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/healthcheck", timeout=1)
                break
            except Exception:
                time.sleep(0.3)
        else:
            raise AssertionError("server never became healthy")
        proc.send_signal(signal.SIGHUP)
        # same PID re-execs: it must go unhealthy (drain) then healthy again
        deadline = time.time() + 45
        ok = False
        saw_down = False
        while time.time() < deadline:
            assert proc.poll() is None, \
                "process exited instead of re-exec'ing"
            try:
                r = urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/healthcheck", timeout=1)
                if saw_down and r.status == 200:
                    ok = True
                    break
            except Exception:
                saw_down = True
            time.sleep(0.3)
        assert ok, "restarted server never became healthy"
        # /quitquitquit must terminate the restarted process for real
        urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{http_port}/quitquitquit",
                method="POST"), timeout=5)
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()


def test_example_configs_load_strict():
    """example.yaml / example_proxy.yaml must stay loadable under strict
    parsing (the reference generates config.go FROM example.yaml; here the
    example files are generated from Config and validated in CI)."""
    import os

    from veneur_tpu.core.config import load_config, load_proxy_config

    root = os.path.join(os.path.dirname(__file__), "..")
    cfg = load_config(os.path.join(root, "example.yaml"), strict=True)
    assert cfg.interval == "10s"
    pcfg = load_proxy_config(os.path.join(root, "example_proxy.yaml"))
    assert pcfg is not None


def test_emit_mode_specific_tags_and_span_times():
    """Mode-specific tag flags and explicit span times (reference
    cmd/veneur-emit/main.go: -e_event_tags/-sc_tags/-span_tags,
    -span_starttime/-span_endtime)."""
    from veneur_tpu.protocol import ssf_wire
    from veneur_tpu.protocol.dogstatsd import parse_service_check

    sock, port = _udp_receiver()
    rc = emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                    "-mode", "sc", "-sc_name", "db.ok", "-sc_status", "0",
                    "-tag", "env:dev", "-sc_tags", "shard:3"])
    assert rc == 0
    sc = parse_service_check(sock.recv(4096))
    assert sorted(sc.tags) == ["env:dev", "shard:3"]

    rc = emit.main(["-hostport", f"udp://127.0.0.1:{port}", "-ssf",
                    "-name", "op", "-span_service", "svc",
                    "-span_tags", "widget:a",
                    "-span_starttime", "100", "-span_endtime", "101.5"])
    assert rc == 0
    span = ssf_wire.parse_ssf(sock.recv(65536))
    assert span.tags.get("widget") == "a"
    assert span.start_timestamp == 100 * 10**9
    assert span.end_timestamp == int(101.5 * 10**9)
    sock.close()


def test_prometheus_poller_label_filter_and_unix_socket(tmp_path):
    """-ignored-labels drops matching label names from tags;
    -socket scrapes over a unix stream (reference -socket transport)."""
    import socketserver

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            # a gauge: emitted on every scrape (counters need two scrapes
            # within one process to produce a delta)
            body = (b"# TYPE req_depth gauge\n"
                    b'req_depth{path="/x",internal_id="abc"} 5\n')
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    class UDSServer(socketserver.ThreadingUnixStreamServer):
        pass

    sock_path = str(tmp_path / "prom.sock")
    httpd = UDSServer(sock_path, Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    rx, port = _udp_receiver()
    try:
        argv = ["-h", "http://prom/metrics", "-s", f"127.0.0.1:{port}",
                "-socket", sock_path, "-ignored-labels", "internal_.*",
                "-once"]
        assert prometheus_poller.main(argv) == 0
        data = rx.recv(65536)
        assert b"req_depth:5" in data
        assert b"path:/x" in data
        assert b"internal_id" not in data
    finally:
        httpd.shutdown()
        rx.close()
