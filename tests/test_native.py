"""Native C++ ingest pipeline tests: parity with the Python parser and
native-mode server end-to-end."""

import os
import socket
import time

import numpy as np
import pytest

pytest.importorskip("ctypes")

from veneur_tpu import native as native_mod

if not native_mod.available():  # pragma: no cover - toolchain missing
    pytest.skip("native library unavailable", allow_module_level=True)

from veneur_tpu.core.config import Config
from veneur_tpu.core.metrics import MetricType
from veneur_tpu.core.server import Server
from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.protocol.dogstatsd import ParseError, parse_metric
from veneur_tpu.utils.hashing import hll_hash

# the served native/ directory, whatever a test points the loader at
NATIVE_DIR = native_mod._NATIVE_DIR


def test_lock_stats_instrumentation():
    """Commit-path mutex timing: always on, one record a lock hold of
    the chunk commit, resettable (tools/bench_lock_contention.py relies
    on this API)."""
    ctxs = [native_mod.NativeIngest() for _ in range(2)]
    router = native_mod.NativeRouter(ctxs)
    router.ingest(b"lk.a:1|c\nlk.b:2|ms")
    router.ingest(b"lk.a:1|c\nlk.b:2|ms\nlk.c:3|g")
    for s, ctx in enumerate(ctxs):
        st = router.lock_stats(s)
        assert st["acquisitions"] == ctx.commit_counters()["commit_batches"]
        assert len(st["hold_ns_samples"]) == st["acquisitions"]
        assert all(h > 0 for h in st["hold_ns_samples"])
        assert st["hold_ns_total"] == sum(st["hold_ns_samples"])
        assert st["contended"] == 0  # single thread never blocks
        assert st["wait_ns_total"] == 0
    total = sum(router.lock_stats(s)["acquisitions"] for s in range(2))
    assert 2 <= total <= 4  # a buffer locks each context it has lines for
    router.reset_lock_stats()
    assert router.lock_stats(0)["acquisitions"] == 0


@pytest.mark.parametrize("n_buffers", [1, 7, 64])
def test_lock_record_is_one_entry_a_commit_batch(n_buffers):
    """With no switch anywhere: after N buffers the lock's record holds
    N acquisitions, as many as commit_batches, none contended."""
    ni = native_mod.NativeIngest()
    for k in range(n_buffers):
        ni.ingest(b"\n".join(b"lk.t%d:%d|ms" % (j, k) for j in range(40)))
    st = ni.lock_stats()
    assert st["acquisitions"] == n_buffers \
        == ni.commit_counters()["commit_batches"]
    assert st["contended"] == 0 and st["wait_ns_total"] == 0
    assert st["hold_ns_total"] > 0
    totals = ni.lock_stats(samples=False)
    assert "hold_ns_samples" not in totals
    assert totals["acquisitions"] == n_buffers


def test_lock_record_sees_a_python_hold_of_the_context_lock():
    """The other end: a thread that holds ctx.lock() for 50 ms while
    another ingests leaves the wait in the committer's record."""
    import threading

    ni = native_mod.NativeIngest()
    ni.ingest(b"lk.w:1|c")
    holding, done = threading.Event(), threading.Event()

    def hold() -> None:
        ni.lock()
        try:
            holding.set()
            time.sleep(0.05)
        finally:
            ni.unlock()
        done.set()

    th = threading.Thread(target=hold)
    th.start()
    assert holding.wait(5.0)
    ni.ingest(b"lk.w:1|c")  # blocks until the holder lets go
    th.join(5.0)
    assert done.is_set() and not th.is_alive()
    st = ni.lock_stats()
    assert st["acquisitions"] == 2
    assert st["contended"] >= 1
    assert st["wait_ns_total"] >= 40_000_000
    assert max(st["wait_ns_samples"]) >= 40_000_000


def test_library_matches_source():
    """The loaded .so's build stamp equals the sha256 prefix of the
    current sources (dogstatsd.cpp + emit.cpp + forward_codec.cpp, the
    three TUs of the library) — a stale committed binary (library no
    longer built from the checked-in source) fails here instead of
    silently testing old code."""
    import hashlib
    import os

    ndir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native")
    h = hashlib.sha256()
    for fn in ("dogstatsd.cpp", "emit.cpp", "forward_codec.cpp"):
        h.update(open(os.path.join(ndir, fn), "rb").read())
    assert native_mod.source_hash() == h.hexdigest()[:16]


# -- the contract with the library: this tree's build, or none --------------

_REFUSED = "is not this tree's build"


def _c_library(path, stamp=None, extra=""):
    """A library that is not the served one: `stamp` gives it a
    vn_source_hash, `extra` is more C."""
    import subprocess

    src = path.with_suffix(".cpp")
    body = extra
    if stamp is not None:
        body += (f'extern "C" const char* vn_source_hash() '
                 f'{{ return "{stamp}"; }}\n')
    src.write_text(body or "int vn_unused;\n")
    subprocess.run(["g++", "-shared", "-fPIC", "-o", str(path), str(src)],
                   check=True, capture_output=True)


def _foreign_native_dir(tmp_path, case):
    """A native/ directory as a checkout could come to hold it, with the
    three sources and (but for one case) a library that is not their
    build. `make` there does nothing: the library that stands is what
    the loader meets."""
    import shutil

    ndir = tmp_path / "native"
    ndir.mkdir()
    for name in native_mod._LIB_SOURCES:
        shutil.copy(os.path.join(NATIVE_DIR, name), ndir / name)
    lib = ndir / "libveneur_native.so"
    if case == "build failed and no library":
        return ndir  # no Makefile either: make fails
    (ndir / "Makefile").write_text("all:\n")
    if case == "stamp differs":
        # the served library beside sources edited since it was built
        shutil.copy(native_mod._LIB_PATH, lib)
        with open(ndir / "emit.cpp", "a") as f:
            f.write("// edited after the build\n")
    elif case == "no stamp":
        _c_library(lib, extra='extern "C" int vn_ingest() { return 0; }\n')
    elif case == "a symbol is missing":
        _c_library(lib, stamp=native_mod._sources_stamp(
            native_mod._LIB_SOURCES))
    return ndir


def _python_path_output(w):
    from veneur_tpu.core.flusher import (
        device_quantiles, generate_inter_metrics)
    from veneur_tpu.core.metrics import HistogramAggregates

    aggs = HistogramAggregates.from_names(["min", "max", "count"])
    for i in range(40):
        for line in (b"nl.t:%d|ms|#a:b" % i, b"nl.c:2|c", b"nl.g:%d|g" % i,
                     b"nl.s:u%d|s" % (i % 7)):
            w.process_metric(parse_metric(line))
    snap = w.flush(device_quantiles([0.5, 0.99], aggs))
    return sorted(
        (m.name, m.type, tuple(m.tags), repr(m.value))
        for m in generate_inter_metrics(snap, True, [0.5, 0.99], aggs,
                                        now=1000))


@pytest.mark.parametrize("case", [
    "stamp differs", "no stamp", "a symbol is missing",
    "build failed and no library"])
def test_a_library_that_is_not_this_trees_is_refused_whole(
        case, tmp_path, monkeypatch, caplog):
    from veneur_tpu.core.worker import DeviceWorker

    never_had_one = _python_path_output(DeviceWorker(stage_depth=16))
    served_stamp = native_mod.source_hash()
    ndir = _foreign_native_dir(tmp_path, case)
    monkeypatch.setattr(native_mod, "_NATIVE_DIR", str(ndir))
    monkeypatch.setattr(native_mod, "_LIB_PATH",
                        str(ndir / "libveneur_native.so"))
    monkeypatch.setattr(native_mod, "_loaded", {})
    with caplog.at_level("WARNING", logger="veneur_tpu.native"):
        assert native_mod.load_library() is None
        # decided once: asking again neither builds nor warns again
        assert native_mod.load_library() is None
        assert not native_mod.available()
        assert not native_mod.emit_available()
        assert native_mod.source_hash() == ""
    (warning,) = [r.getMessage() for r in caplog.records]
    if case == "build failed and no library":
        assert "native build failed" in warning
    else:
        assert _REFUSED in warning
        want = native_mod._sources_stamp(native_mod._LIB_SOURCES)
        assert f"the sources' is {want}" in warning
        found = {"stamp differs": served_stamp, "no stamp": "none",
                 "a symbol is missing": want}[case]
        assert (found != want) == (case != "a symbol is missing")
        assert f"stamp {found}," in warning
    w = DeviceWorker(stage_depth=16)
    assert w.attach_native() is False
    assert w._native is None
    assert _python_path_output(w) == never_had_one


def _exported(source):
    """The names a source defines inside its extern "C" blocks."""
    import re

    names, inside = [], False
    with open(os.path.join(NATIVE_DIR, source)) as f:
        for line in f:
            if line.startswith('extern "C" {'):
                inside = True
            elif line.startswith('}  // extern "C"'):
                inside = False
            elif inside:
                m = re.match(r"[A-Za-z_][\w \*]*?\b(vn_\w+)\s*\(", line)
                if m:
                    names.append(m.group(1))
    return set(names)


# exported for a tool alone, and so bound by no loader: none today
TOOL_ONLY: set = set()


@pytest.mark.parametrize("source", [
    "dogstatsd.cpp", "emit.cpp", "forward_codec.cpp", "loadgen.cpp"])
def test_every_exported_name_is_bound_at_load(source):
    """What the loader does not bind it cannot miss: a name left out of
    _bind_* would come back as a call-time AttributeError."""
    lib = (native_mod.load_loadgen_library() if source == "loadgen.cpp"
           else native_mod.load_library())
    names = _exported(source)
    assert len(names) >= 6, names
    assert names - set(vars(lib)) - TOOL_ONLY == set()


def test_parser_parity_property():
    """Every accepted line must produce the same (type, tags, scope, value)
    as the Python parser; every rejected line must be rejected by both."""
    ni = native_mod.NativeIngest()
    packets = [
        b"a.b.c:1|c",
        b"a.b.c:2.5|g",
        b"t:3|ms|@0.5|#b:2,a:1",
        b"h:4.25|h|#veneurlocalonly,x",
        b"d:5|d|#veneurglobalonly:true",
        b"s:member|s|#k:v",
        b"neg:-42.5|g",
        b"exp:1e3|c",
        b"plus:+4|g",
        # malformed — both should reject
        b"aaa|bbb:1|c",  # '|' before the first ':' (pipe-split order)
        b"a|b:1|ms",
        b"foo",
        b":1|c",
        b"foo:1",
        b"foo:1||",
        b"foo:bar|c",
        b"foo:nan|c",
        b"foo:1|z",
        b"foo:1|c|x",
        b"foo:1|c|@0",
        b"foo:1|c|@2",
        b"foo:1|c|@0.1|@0.2",
        b"foo:1|c|#a|#b",
        b"foo:1 |c",
        b"foo:1_0|c",
    ]
    for pkt in packets:
        try:
            py = parse_metric(pkt)
            py_ok = True
        except ParseError:
            py_ok = False
        before = ni.processed
        ni.ingest(pkt)
        native_ok = ni.processed > before
        assert native_ok == py_ok, pkt

    # new-series records carry the normalized identity; compare against
    # the python parser's view
    records = {
        (name, native_mod.NativeIngest.TYPE_BY_KIND[kind]): (joined, scope)
        for _, _, kind, scope, name, joined
        in ni.drain_new_series().first_records()
    }
    py_t = parse_metric(b"t:3|ms|@0.5|#b:2,a:1")
    assert records[("t", "timer")] == ("a:1,b:2", 0)
    assert py_t.joined_tags == "a:1,b:2"
    py_h = parse_metric(b"h:4.25|h|#veneurlocalonly,x")
    assert records[("h", "histogram")] == ("x", 1)
    assert py_h.scope == 1 and py_h.tags == ["x"]
    assert records[("d", "histogram")] == ("", 2)


def test_native_values_and_weights():
    ni = native_mod.NativeIngest()
    ni.ingest(b"t:3|ms|@0.5")
    ni.ingest(b"t:7|ms")
    rows, vals, wts = ni.drain_histo(16)
    assert list(rows) == [0, 0]
    assert list(vals) == [3.0, 7.0]
    assert list(wts) == [2.0, 1.0]  # weight = 1/sample_rate


def test_native_counter_truncation():
    ni = native_mod.NativeIngest()
    ni.ingest(b"c:2.7|c")  # int(2.7) = 2
    ni.ingest(b"c:1|c|@0.3")  # 1 * int(1/0.3)=3
    rows, contribs = ni.drain_counter(16)
    assert contribs.sum() == 5.0


def test_native_hll_split_matches_python():
    ni = native_mod.NativeIngest()
    values = [f"member-{i}" for i in range(200)]
    for v in values:
        ni.ingest(f"s:{v}|s".encode())
    rows, idx, rank = ni.drain_set(1024)
    hashes = np.array([hll_hash(v.encode()) for v in values],
                      dtype=np.uint64)
    py_idx, py_rank = hll_ops.split_hashes(hashes)
    np.testing.assert_array_equal(idx, py_idx)
    np.testing.assert_array_equal(rank, py_rank)


def test_native_shared_directory_with_python_upsert():
    ni = native_mod.NativeIngest()
    ni.ingest(b"x:1|ms|#a:1")  # row 0 via parsing
    row = ni.upsert("x", "timer", "a:1", 0)  # same series via python path
    assert row == 0
    row2 = ni.upsert("y", "timer", "", 0)
    assert row2 == 1


def test_native_mode_server_end_to_end():
    cfg = Config(
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        num_workers=1,
        interval="10s",
        percentiles=[0.5],
        tpu_native_ingest=True,
    )
    srv = Server(cfg)
    assert srv.native_mode
    ports = srv.start()
    try:
        port = next(iter(ports.values()))
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        for v in range(1, 101):
            s.sendto(f"nat.timer:{v}|ms|#env:prod".encode(),
                     ("127.0.0.1", port))
        s.sendto(b"nat.count:3|c\nnat.count:4|c", ("127.0.0.1", port))
        s.sendto(b"nat.gauge:1.5|g\nnat.gauge:9.5|g", ("127.0.0.1", port))
        for i in range(300):
            s.sendto(f"nat.set:u{i}|s".encode(), ("127.0.0.1", port))
        s.sendto(b"_sc|natsvc|0|m:fine", ("127.0.0.1", port))
        s.close()
        deadline = time.time() + 5
        while time.time() < deadline:
            if srv.packets_received >= 404:
                break
            time.sleep(0.02)
        metrics = srv.flush()
        by_key = {(m.name, m.type): m for m in metrics}
        assert by_key[("nat.count", MetricType.COUNTER)].value == 7.0
        assert by_key[("nat.gauge", MetricType.GAUGE)].value == 9.5
        assert by_key[("nat.timer.min", MetricType.GAUGE)].value == 1.0
        assert by_key[("nat.timer.max", MetricType.GAUGE)].value == 100.0
        timer_meta = by_key[("nat.timer.max", MetricType.GAUGE)]
        assert timer_meta.tags == ["env:prod"]
        assert by_key[("natsvc", MetricType.STATUS)].value == 0.0
        # set estimate (global server without forward address)
        est = by_key[("nat.set", MetricType.GAUGE)].value
        assert abs(est - 300) / 300 < 0.05
        # percentiles present (no forward address → global)
        assert ("nat.timer.50percentile", MetricType.GAUGE) in by_key
    finally:
        srv.shutdown()


def test_native_mode_epoch_reset():
    cfg = Config(num_workers=1, interval="10s", tpu_native_ingest=True)
    srv = Server(cfg)
    assert srv.native_mode
    srv.process_metric_packet(b"e.c:1|c")
    m1 = srv.flush()
    assert any(m.name == "e.c" for m in m1)
    m2 = srv.flush()
    assert not any(m.name == "e.c" for m in m2)
    # same series again in the new epoch gets a fresh row cleanly
    srv.process_metric_packet(b"e.c:5|c")
    m3 = srv.flush()
    by = {m.name: m for m in m3}
    assert by["e.c"].value == 5.0
    srv.shutdown()


def test_native_parse_errors_counted():
    cfg = Config(num_workers=1, interval="10s", tpu_native_ingest=True)
    srv = Server(cfg)
    srv.process_metric_packet(b"bad::packet|q")
    srv.process_metric_packet(b"ok:1|c")
    srv.flush()
    assert srv.workers[0].parse_errors >= 1
    srv.shutdown()


# ---------------------------------------------------------------------------
# Native SSF span fast path


def _make_span_bytes(**kw):
    from veneur_tpu.gen import ssf_pb2

    pb = ssf_pb2.SSFSpan()
    for k, v in kw.pop("tags", {}).items():
        pb.tags[k] = v
    for s in kw.pop("metrics", []):
        m = pb.metrics.add()
        for f, fv in s.items():
            if f == "tags":
                for tk, tv in fv.items():
                    m.tags[tk] = tv
            else:
                setattr(m, f, fv)
    for k, v in kw.items():
        setattr(pb, k, v)
    return pb.SerializeToString()


def test_native_ssf_extraction_matches_python():
    """The C++ span→metric extraction must produce the same series
    (names, tags, scope, values) as the Python MetricExtractionSink."""
    from veneur_tpu.core.spans import (
        convert_indicator_metrics, convert_metrics)
    from veneur_tpu.protocol.ssf_wire import parse_ssf

    payload = _make_span_bytes(
        trace_id=42, id=43, start_timestamp=10**9,
        end_timestamp=10**9 + 5_000_000, service="api", name="req",
        indicator=True, error=True,
        tags={"ssf_objective": "checkout"},
        metrics=[
            {"metric": 0, "name": "hits", "value": 3.0,
             "tags": {"env": "prod"}},
            {"metric": 2, "name": "lat", "value": 12.5, "sample_rate": 0.5},
            {"metric": 3, "name": "users", "message": "u1",
             "tags": {"veneurglobalonly": "true"}},
            {"metric": 1, "name": "temp", "value": 20.0},
        ])

    ni = native_mod.NativeIngest()
    rc = ni.ingest_ssf(payload, b"ind.timer", b"obj.timer")
    assert rc == 1
    assert ni.ssf_spans == 1
    assert ni.ssf_invalid == 0

    got = {(p, k, name, joined, scope)
           for p, _row, k, scope, name, joined
           in ni.drain_new_series().first_records()}

    # expected series via the Python path
    span = parse_ssf(payload)
    pymetrics, invalid = convert_metrics(span)
    assert invalid == 0
    pymetrics += convert_indicator_metrics(span, "ind.timer", "obj.timer")
    from veneur_tpu.core.directory import classify as pyclassify
    want = set()
    pool_by_type = {"histogram": 0, "timer": 0, "set": 1, "counter": 2,
                    "gauge": 3}
    for m in pymetrics:
        cls = int(pyclassify(m.key.type, m.scope))
        want.add((pool_by_type[m.key.type],
                  native_mod.NativeIngest.KIND_BY_TYPE[m.key.type],
                  m.key.name, m.key.joined_tags, cls))
    assert got == want

    # values: counter contribution, histo batch, set registers
    rows, contribs = ni.drain_counter(16)
    assert list(contribs) == [3.0]
    rows, vals, wts = ni.drain_histo(16)
    # lat (rate .5 => weight 2) + two derived 5ms indicator timers
    assert sorted(zip(vals.tolist(), wts.tolist())) == [
        (12.5, 2.0), (5e6, 1.0), (5e6, 1.0)]
    srv = ni.drain_ssf_services()
    assert srv == {"api": 1}


def test_native_ssf_status_sample_falls_back():
    payload = _make_span_bytes(
        trace_id=1, id=1, start_timestamp=1, end_timestamp=2,
        service="s", name="n",
        metrics=[{"metric": 4, "name": "check", "status": 2,
                  "message": "bad"}])
    ni = native_mod.NativeIngest()
    assert ni.ingest_ssf(payload, b"", b"") == -1
    assert ni.ssf_spans == 0  # nothing ingested


def test_native_ssf_decode_error():
    ni = native_mod.NativeIngest()
    assert ni.ingest_ssf(b"\xff\xff\xff\xff", b"", b"") == 0


def test_native_ssf_name_tag_fallback():
    """Empty span name falls back to the 'name' tag (wire normalization,
    protocol/ssf_wire.normalize_span)."""
    payload = _make_span_bytes(
        trace_id=7, id=8, start_timestamp=1, end_timestamp=2_000_001,
        service="svc", indicator=True, tags={"name": "from-tag"})
    ni = native_mod.NativeIngest()
    assert ni.ingest_ssf(payload, b"ind.t", b"obj.t") == 1
    series = ni.drain_new_series().first_records()
    objs = [(name, joined) for _p, _r, _k, _s, name, joined in series
            if name == "obj.t"]
    assert objs and "objective:from-tag" in objs[0][1]


def test_server_native_ssf_end_to_end():
    """Server with native mode: SSF datagram → native extraction →
    flushed metrics, matching a Python-path server's output."""
    payload = _make_span_bytes(
        trace_id=9, id=10, start_timestamp=10**9,
        end_timestamp=10**9 + 2_000_000, service="web", name="h",
        indicator=True,
        metrics=[{"metric": 2, "name": "spanlat", "value": 7.0}])

    def run(native: bool):
        cfg = Config(interval="10s", num_workers=1,
                     tpu_native_ingest=native,
                     indicator_span_timer_name="ind.t",
                     percentiles=[0.5])
        srv = Server(cfg)
        if native and not srv.native_mode:
            pytest.skip("native library unavailable")
        srv.handle_trace_packet(payload)
        if not native:
            # Python path goes through the async span worker; pump it
            # until the extracted metrics land (a fixed sleep flakes
            # under CPU contention from parallel jobs)
            srv.span_worker.start()
            deadline = time.time() + 10
            while (sum(w.processed for w in srv.workers) < 2
                   and time.time() < deadline):
                time.sleep(0.02)
            srv.span_worker.stop()
        out = srv.flush()
        return {(m.name, round(m.value, 3)) for m in out}

    got_native = run(True)
    got_python = run(False)
    assert got_native == got_python
    assert any(n == "spanlat.50percentile" for n, _ in got_native)
    assert any(n.startswith("ind.t") for n, _ in got_native)


def test_native_ssf_non_ascii_tag_order_matches_python():
    """Tag bytes >= 0x80 must sort identically in C++ (unsigned compare)
    and Python (code-point sort) or one series would get two digests."""
    from veneur_tpu.protocol.dogstatsd import parse_metric_ssf
    from veneur_tpu import ssf as ssf_model

    tags = {"Ωmega": "1", "alpha": "2", "zz": "3"}
    payload = _make_span_bytes(
        trace_id=1, id=2, start_timestamp=1, end_timestamp=2,
        service="s", name="n",
        metrics=[{"metric": 2, "name": "m", "value": 1.0, "tags": tags}])
    ni = native_mod.NativeIngest()
    assert ni.ingest_ssf(payload, b"", b"") == 1
    (_, _, _, _, _name, joined), = ni.drain_new_series().first_records()

    pym = parse_metric_ssf(ssf_model.SSFSample(
        metric=ssf_model.SSFMetricType.HISTOGRAM, name="m", value=1.0,
        tags=dict(tags)))
    assert joined == pym.key.joined_tags


def test_native_ssf_hostile_service_name():
    """Tabs/newlines in an untrusted service name must not corrupt the
    service-counter drain framing or inject statsd lines."""
    payload = _make_span_bytes(
        trace_id=1, id=2, start_timestamp=1, end_timestamp=2,
        service="evil\tsvc\nx", name="n",
        metrics=[{"metric": 0, "name": "c", "value": 1.0}])
    ni = native_mod.NativeIngest()
    assert ni.ingest_ssf(payload, b"", b"") == 1
    counts = ni.drain_ssf_services()
    assert counts == {"evil_svc_x": 1}


def test_scopedstatsd_injection_sanitized():
    from veneur_tpu import scopedstatsd

    cap = scopedstatsd.CaptureSender()
    cli = scopedstatsd.ScopedClient(cap, namespace="v.")
    cli.count("m", 1, tags=["service:x|#fake\nforged:999|g"])
    assert len(cap.lines) == 1
    assert "\n" not in cap.lines[0]
    assert cap.lines[0].count("|#") == 1


# ---------------------------------------------------------------------------
# sharded router (vn_ingest_routed)


def test_router_shards_by_digest():
    """Series must land on shard digest % N — the same shard the Python
    parser would route to — so mixed native/Python ingest of one series
    always shares a row."""
    from veneur_tpu.protocol.dogstatsd import parse_metric

    ctxs = [native_mod.NativeIngest() for _ in range(4)]
    router = native_mod.NativeRouter(ctxs)
    lines = [f"shard.m{i}:1|c|#t:{i % 7}" for i in range(200)]
    for ln in lines:
        router.ingest(ln.encode())
    assert sum(c.processed for c in ctxs) == 200

    per_shard = [0] * 4
    for ln in lines:
        m = parse_metric(ln.encode())
        per_shard[m.digest % 4] += 1
    got = [c.processed for c in ctxs]
    assert got == per_shard


def test_router_concurrent_ingest_exact_totals():
    import threading

    ctxs = [native_mod.NativeIngest() for _ in range(4)]
    router = native_mod.NativeRouter(ctxs)
    n_threads, per_thread = 4, 2000

    def work(t):
        for i in range(per_thread):
            # same series set from every thread → heavy cross-shard traffic
            router.ingest(
                f"conc.c{i % 50}:2|c\nconc.h{i % 31}:{i}|ms".encode())

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    total = n_threads * per_thread
    assert sum(c.processed for c in ctxs) == 2 * total
    assert sum(c.errors for c in ctxs) == 0
    csum = 0.0
    hcount = 0
    for c in ctxs:
        rows, contribs = c.drain_counter(1 << 20)
        csum += contribs.sum()
        r, v, w = c.drain_histo(1 << 20)
        hcount += len(r)
    assert csum == 2.0 * total
    assert hcount == total


def test_router_events_and_errors_land_on_shard_zero():
    ctxs = [native_mod.NativeIngest() for _ in range(2)]
    router = native_mod.NativeRouter(ctxs)
    router.ingest(b"_e{5,5}:title|hello\nnot-a-metric\nok.c:1|c")
    assert ctxs[0].drain_other() == [b"_e{5,5}:title|hello"]
    assert ctxs[0].errors + ctxs[1].errors == 1
    assert ctxs[0].processed + ctxs[1].processed == 1


def test_ingest_ssf_many_matches_single():
    payloads = [
        _make_span_bytes(
            trace_id=i + 1, id=i + 1, start_timestamp=100 + i,
            end_timestamp=200 + i * 3, service=f"s{i % 3}", name="op",
            indicator=True)
        for i in range(50)
    ]
    single = native_mod.NativeIngest()
    for p in payloads:
        assert single.ingest_ssf(p, b"ind", b"obj") == 1
    batched = native_mod.NativeIngest()
    ok, errs, fallbacks = batched.ingest_ssf_many(payloads, b"ind", b"obj")
    assert (ok, errs, fallbacks) == (50, 0, [])
    r1 = single.drain_histo(1 << 16)
    r2 = batched.drain_histo(1 << 16)
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a, b)


def test_ingest_ssf_many_mixed_outcomes():
    good = _make_span_bytes(trace_id=1, id=2, start_timestamp=1,
                            end_timestamp=5, service="s", name="n",
                            indicator=True)
    status = _make_span_bytes(
        trace_id=3, id=4, start_timestamp=1, end_timestamp=5, service="s",
        name="n", metrics=[{"metric": 4, "name": "chk", "value": 0.0}])
    ni = native_mod.NativeIngest()
    ok, errs, fallbacks = ni.ingest_ssf_many(
        [good, b"\xff\xff garbage", status], b"i", b"o")
    assert ok == 1
    assert errs == 1
    assert fallbacks == [status]  # STATUS spans come back for Python
    assert ni.ingest_ssf_many([], b"", b"") == (0, 0, [])


def test_ingest_ssf_many_empty_frame_is_error():
    ni = native_mod.NativeIngest()
    good = _make_span_bytes(trace_id=1, id=2, start_timestamp=1,
                            end_timestamp=5, service="s", name="n",
                            indicator=True)
    ok, errs, fallbacks = ni.ingest_ssf_many([b"", good], b"i", b"o")
    assert (ok, errs, fallbacks) == (1, 1, [])


def test_wire_decoder_fuzz_never_crashes():
    """The network-facing MetricBatch wire decoder must survive
    arbitrary and mutated bytes: every input either parses (and then
    agrees with the Python protobuf parser on the metric count) or is
    rejected, never a crash/hang. Seeded, mirrors the HLL/gob decoder
    fuzzes."""
    import numpy as np

    from veneur_tpu.gen import veneur_tpu_pb2 as pb

    rng = np.random.default_rng(0xFEED)

    # a valid seed blob to mutate
    batch = pb.MetricBatch()
    for i in range(8):
        m = batch.metrics.add()
        m.name = f"fz{i}"
        m.tags.extend([f"a:{i}", "b:2"])
        m.kind = pb.KIND_TIMER
        m.scope = pb.SCOPE_MIXED
        m.digest.centroids.means.extend([1.0, 2.0, 3.0])
        m.digest.centroids.weights.extend([1.0, 1.0, 2.0])
        m.digest.min = 1.0
        m.digest.max = 3.0
        m.digest.compression = 100.0
    seed = bytearray(batch.SerializeToString())

    def check(blob: bytes):
        d = native_mod.decode_metric_batch(bytes(blob))
        if d is None:
            return
        # if the native decoder accepted it, the python parser must
        # accept it too and agree on the count
        try:
            ref = pb.MetricBatch.FromString(bytes(blob))
        except Exception:
            # native is stricter about e.g. trailing garbage the python
            # parser also rejects — acceptance without python agreement
            # would be the bug
            raise AssertionError("native accepted what protobuf rejects")
        assert d.n == len(ref.metrics)

    # pure random garbage
    for _ in range(300):
        n = int(rng.integers(0, 200))
        check(rng.integers(0, 256, n, dtype=np.uint8).tobytes())
    # single-byte mutations of the valid blob
    for _ in range(500):
        b = bytearray(seed)
        pos = int(rng.integers(0, len(b)))
        b[pos] = int(rng.integers(0, 256))
        check(b)
    # truncations
    for cut in range(0, len(seed), 7):
        check(seed[:cut])
    # duplications / splices
    for _ in range(100):
        a = int(rng.integers(0, len(seed)))
        b2 = int(rng.integers(a, len(seed)))
        check(bytes(seed[:b2]) + bytes(seed[a:]))


def test_parser_parity_fuzz():
    """Seeded random fuzz over generated + mutated DogStatsD lines: the
    C++ and Python parsers must agree on accept/reject for every input
    (the property behind parser_test.go's exhaustive malformed table,
    checked over a much wider space)."""
    import random

    rng = random.Random(0xC0FFEE)
    types = [b"c", b"g", b"ms", b"h", b"d", b"s", b"zz", b""]
    names = [b"a.b.c", b"x", b"", b"with space", b"uni\xc3\xa9"]
    values = [b"1", b"2.5", b"-3", b"+4", b"1e3", b"nan", b"bar", b"",
              b"0x1f", b"1_0"]
    rates = [b"", b"|@0.5", b"|@1", b"|@0", b"|@2", b"|@x"]
    tagsets = [b"", b"|#a:1", b"|#b:2,a:1", b"|#veneurlocalonly",
               b"|#veneursinkonly:kafka", b"|#", b"|#a:1|#b:2"]

    ni = native_mod.NativeIngest()
    checked = 0
    for _ in range(2500):
        line = (rng.choice(names) + b":" + rng.choice(values) + b"|"
                + rng.choice(types) + rng.choice(rates)
                + rng.choice(tagsets))
        if rng.random() < 0.3 and line:
            # byte-level mutation
            pos = rng.randrange(len(line))
            line = (line[:pos]
                    + bytes([rng.randrange(33, 127)])
                    + line[pos + 1:])
        try:
            parse_metric(line)
            py_ok = True
        except ParseError:
            py_ok = False
        before = ni.processed
        ni.ingest(line)
        native_ok = ni.processed > before
        assert native_ok == py_ok, line
        checked += 1
    assert checked == 2500


def test_native_ssf_decode_fuzz_agrees_with_python():
    """Seeded fuzz over valid, mutated, and random SSF payloads: the
    hand-written C++ proto decoder and the Python wire parser must agree
    on accept/reject, and neither may crash. (Acceptance for the native
    path = decodes AND is a valid trace span with samples to extract —
    rc 1/-1; Python's parse_ssf accepts any decodable proto, so only
    native-accepts-what-python-rejects is a divergence.)"""
    import random

    from veneur_tpu.protocol import ssf_wire

    rng = random.Random(0xBEEF)
    seeds = []
    for i in range(40):
        metrics = []
        for j in range(i % 3):
            sample = {"name": f"m{j}", "value": float(j) + 0.5,
                      "sample_rate": 1.0, "message": f"msg{j}",
                      "unit": "ms", "tags": {"a": "b"}}
            metrics.append(sample)
        seeds.append(_make_span_bytes(
            trace_id=rng.randrange(1, 1 << 60),
            id=rng.randrange(1, 1 << 60),
            start_timestamp=rng.randrange(1, 1 << 60),
            end_timestamp=rng.randrange(1, 1 << 60),
            service=f"svc{i}", name=f"op{i}",
            indicator=bool(i % 2),
            metrics=metrics,
            tags={f"k{j}": f"v{j}" for j in range(i % 4)}))

    ni = native_mod.NativeIngest()
    checked = 0
    for _ in range(3000):
        base = bytearray(rng.choice(seeds))
        roll = rng.random()
        if roll < 0.35 and base:
            # point mutation
            for _ in range(rng.randrange(1, 4)):
                base[rng.randrange(len(base))] = rng.randrange(256)
        elif roll < 0.5:
            # truncation
            del base[rng.randrange(len(base)):]
        elif roll < 0.6:
            base = bytearray(rng.randbytes(rng.randrange(0, 80)))
        payload = bytes(base)

        try:
            span = ssf_wire.parse_ssf(payload)
            py_ok = True
        except Exception:
            py_ok = False
        rc = ni.ingest_ssf(payload, b"ind.t", b"obj.t")
        assert rc in (-1, 0, 1), (rc, payload)
        if rc in (1, -1):
            # native accepted: python must also decode it
            assert py_ok, payload
        checked += 1
    assert checked == 3000


def test_drain_new_series_hands_every_record_in_one_call():
    """One drain empties the queue however many bytes of strings it
    holds (the drain that filled a 1MB scratch a round at a time left
    records stranded when one did not fit) — stranded records would
    leave device rows without directory metadata."""
    ni = native_mod.NativeIngest()
    long_tag = "env:" + "x" * 400
    n = 4000  # ~1.6MB of packed records
    for i in range(n):
        ni.upsert(f"long.series.{i}", "histogram", long_tag, 0)
    assert ni.pending_new_series == n
    records = ni.drain_new_series().first_records()
    assert len(records) == n
    assert ni.pending_new_series == 0
    assert records[0][4] == "long.series.0"
    assert records[-1][4] == f"long.series.{n - 1}"
    assert records[0][5] == long_tag


def test_drain_new_series_one_record_larger_than_a_megabyte():
    """A single record past the old 1MB scratch was stranded until the
    reset; the queue has no scratch now and hands it over."""
    ni = native_mod.NativeIngest()
    huge = "k:" + "v" * (2 << 20)
    ni.upsert("huge.series", "counter", huge, 0)
    ni.upsert("after", "counter", "", 0)
    batch = ni.drain_new_series()
    assert batch.first_records() == [
        (2, 0, 0, 0, "huge.series", huge), (2, 1, 0, 0, "after", "")]
    assert ni.pending_new_series == 0


# -- lifetime series ids (Ctx::interned) ------------------------------------


def _lines(names, fmt=b"%s:1|ms|#a:1"):
    return b"\n".join(fmt % n for n in names)


def test_series_keeps_its_sid_across_reset_and_rows_restart():
    """reset() forgets rows, not series: the row follows this
    interval's first-seen order, the sid is the one given at first
    sight, per (kind, scope class, name, tags)."""
    ni = native_mod.NativeIngest()
    ni.ingest(_lines([b"a", b"b", b"c"]) + b"\na:1|c\nb:2|ms|#a:1")
    first = ni.drain_new_series()
    assert first.rows.tolist() == [0, 1, 2, 0]
    assert first.pools.tolist() == [0, 0, 0, 2]
    assert first.sids.tolist() == [0, 1, 2, 3]
    assert first.pool_slices() == [(0, 0, 3), (2, 3, 4)]
    sid = dict(zip([r[2:] for r in first.first_records()],
                   first.sids.tolist()))
    ni.reset()
    # another order, one new series, one series gone, a scope twin
    ni.ingest(b"a:1|c\n" + _lines([b"c", b"new", b"a"])
              + b"\nc:1|ms|#a:1,veneurlocalonly")
    second = ni.drain_new_series()
    # grouped by pool, a pool's records in the order their rows went out
    assert second.pools.tolist() == [0, 0, 0, 0, 2]
    assert second.rows.tolist() == [0, 1, 2, 3, 0]
    assert second.sids.tolist() == [
        sid[(3, 0, "c", "a:1")], 4, sid[(3, 0, "a", "a:1")], 5,
        sid[(0, 0, "a", "")]]
    assert second.pool_slices() == [(0, 0, 4), (2, 4, 5)]
    assert second.first_records() == [
        (0, 1, 3, 0, "new", "a:1"), (0, 3, 3, 1, "c", "a:1")]
    assert second.generation == first.generation


@pytest.mark.parametrize("known", [False, True],
                         ids=["first-seen", "known"])
def test_a_drain_comes_grouped_by_pool_with_its_strings_in_step(known):
    """Lines of the four kinds interleaved: the drain hands each pool's
    records as one slice with consecutive rows, in the order the pool
    gave the rows out, and a first-seen record's kind, scope and strings
    still find it there."""
    ni = native_mod.NativeIngest()
    lines = []
    for i in range(40):
        lines += [b"g%d:1|g" % i, b"t%d:1|ms|#k:%d" % (i, i),
                  b"s%d:m|s" % i, b"c%d:1|c" % i, b"h%d:1|h" % i]
    ni.ingest(b"\n".join(lines))
    batch = ni.drain_new_series()
    sid_of = dict(zip(batch.first_names, batch.sids[batch.first_at].tolist()))
    if known:
        ni.reset()
        ni.ingest(b"\n".join(reversed(lines)))
        batch = ni.drain_new_series()
        assert len(batch.first_at) == 0
    assert batch.pool_slices() == [(0, 0, 80), (1, 80, 120), (2, 120, 160),
                                   (3, 160, 200)]
    for pool, start, stop in batch.pool_slices():
        assert set(batch.pools[start:stop].tolist()) == {pool}
        assert batch.rows[start:stop].tolist() == list(range(stop - start))
    order = range(39, -1, -1) if known else range(40)
    want = ([n % i for i in order for n in (("h%d", "t%d") if known
                                            else ("t%d", "h%d"))]
            + ["s%d" % i for i in order] + ["c%d" % i for i in order]
            + ["g%d" % i for i in order])
    assert batch.sids.tolist() == [sid_of[name] for name in want]
    if not known:
        records = batch.first_records()
        assert len(records) == 200
        pool_of = {"t": 0, "h": 0, "s": 1, "c": 2, "g": 3}
        for pool, row, kind, _scope, name, joined in records:
            assert pool == pool_of[name[0]] and row < 80
            assert kind == {"t": 3, "h": 2, "s": 4, "c": 0, "g": 1}[name[0]]
            assert joined == ("k:" + name[1:] if name[0] == "t" else "")


def test_two_drainers_of_one_context_never_overlap():
    """The drain's pointers are good until the context's next drain and
    its out-parameters are shared, so a second drainer waits for the
    first one's copy (a Python lock; the context's mutex is held inside
    the call only). Four threads drain while a feeder registers series,
    and each is held up between the call and its copy, as a thread that
    waits for the interpreter is: every series is handed over exactly
    once, and every batch is whole (grouped, a pool's rows consecutive,
    its strings its own)."""
    import sys
    import threading

    class SlowToCopy:
        def __init__(self, lib):
            self._lib = lib

        def __getattr__(self, name):
            return getattr(self._lib, name)

        def vn_drain_new_series(self, *args):
            n = self._lib.vn_drain_new_series(*args)
            time.sleep(0.0005)
            return n

    ni = native_mod.NativeIngest()
    ni._lib = SlowToCopy(ni._lib)
    total, batches, done = 6000, [], threading.Event()

    def feed():
        for at in range(0, total, 10):
            ni.ingest(b"\n".join(
                b"d%d:1|%s" % (i, (b"ms", b"c", b"g")[i % 3])
                for i in range(at, at + 10)))
        done.set()

    def drain(out):
        while True:
            last = done.is_set()
            batch = ni.drain_new_series()
            if len(batch):
                out.append(batch)
            if last:
                return

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        outs = [[] for _ in range(4)]
        threads = [threading.Thread(target=drain, args=(out,))
                   for out in outs] + [threading.Thread(target=feed)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    batches = [b for out in outs for b in out]
    names, sids = [], []
    for batch in batches:
        assert len(batch.first_at) == len(batch) == len(batch.first_names)
        for pool, start, stop in batch.pool_slices():
            assert set(batch.pools[start:stop].tolist()) == {pool}
            rows = batch.rows[start:stop]
            assert (rows[1:] - rows[:-1] == 1).all()
        for pool, _row, kind, _scope, name, joined in batch.first_records():
            assert (pool, kind) == {0: (0, 3), 1: (2, 0), 2: (3, 1)}[
                int(name[1:]) % 3] and joined == ""
        names += batch.first_names
        sids += batch.sids.tolist()
    assert sorted(names) == sorted("d%d" % i for i in range(total))
    assert sorted(sids) == list(range(total))


def test_known_series_cross_the_drain_as_integers():
    """From the second interval on a known series brings no string
    with it: nothing is decoded, split or hashed for it in Python."""
    ni = native_mod.NativeIngest()
    names = [b"s%d" % i for i in range(500)]
    ni.ingest(_lines(names))
    batch = ni.drain_new_series()
    assert len(batch) == 500 and len(batch.first_names) == 500
    sids = dict(zip(batch.first_names, batch.sids.tolist()))
    for interval in range(2):
        ni.reset()
        order = names[::-1] if interval else names[250:] + names[:250]
        ni.ingest(_lines(order))
        batch = ni.drain_new_series()
        assert len(batch) == 500
        assert batch.first_names == [] and batch.first_tags == []
        assert len(batch.first_at) == 0
        assert batch.rows.tolist() == list(range(500))
        assert batch.sids.tolist() == [sids[n.decode()] for n in order]


def test_a_first_seen_record_lost_to_reset_is_handed_over_again():
    """Strings queued but never drained go with the reset; the sid is
    not marked handed, so the series brings them again."""
    ni = native_mod.NativeIngest()
    ni.ingest(_lines([b"x", b"y"]))
    ni.reset()  # nobody drained
    ni.ingest(_lines([b"y", b"z", b"x"]))
    batch = ni.drain_new_series()
    assert batch.sids.tolist() == [1, 2, 0]
    assert batch.first_names == ["y", "z", "x"]
    ni.reset()
    ni.ingest(_lines([b"x", b"y", b"z"]))
    batch = ni.drain_new_series()
    assert batch.sids.tolist() == [0, 1, 2] and batch.first_names == []


def test_separators_in_a_name_or_tag_do_not_break_the_framing():
    """Wire input is untrusted: \\x1e/\\x1f in a name or tag are
    substituted in the strings handed over; the two spellings stay two
    series (two rows, two sids) as they always were."""
    ni = native_mod.NativeIngest()
    ni.ingest(b"a\x1eb:1|c|#t:\x1fv\na_b:1|c|#t:_v\nplain:1|c")
    batch = ni.drain_new_series()
    assert batch.first_records() == [
        (2, 0, 0, 0, "a_b", "t:_v"), (2, 1, 0, 0, "a_b", "t:_v"),
        (2, 2, 0, 0, "plain", "")]
    assert batch.sids.tolist() == [0, 1, 2]
    # the Python-side upsert substitutes before the call and lands on
    # the second spelling's series
    assert ni.upsert("a\x1eb", "counter", "t:\x1fv", 0) == 1
    assert ni.pending_new_series == 0


def test_intern_table_is_dropped_at_a_reset_past_its_bound():
    """Past the bound (4,000,000; 8 here) the table goes at the next
    reset, never mid-interval: a drain holds one generation's sids."""
    ni = native_mod.NativeIngest()
    ni.set_intern_cap(8)
    ni.ingest(_lines([b"n%d" % i for i in range(6)]))
    assert ni.drain_new_series().generation == 0
    ni.reset()  # 6 < 8: kept
    ni.ingest(_lines([b"n%d" % i for i in range(3, 12)]))
    batch = ni.drain_new_series()
    assert batch.generation == 0  # over the bound now, dropped later
    assert batch.sids.tolist() == list(range(3, 12))
    assert batch.first_names == ["n%d" % i for i in range(6, 12)]
    ni.reset()
    ni.ingest(_lines([b"n11", b"n0"]))
    batch = ni.drain_new_series()
    assert batch.generation == 1
    assert batch.sids.tolist() == [0, 1]
    assert batch.first_names == ["n11", "n0"]


# -- the chunk commit (ingest_buffer) against the per-line path ---------------


def corpus_lines(seed: int, n: int = 1500) -> list:
    """A seeded corpus of all five kinds over a few hundred series: tags
    in any order, magic scope tags, sample rates, hot timers that spill
    past the staging depth, gauges written many times (last write wins),
    events, service checks and malformed lines."""
    rng = np.random.default_rng(seed)
    tagsets = [[], [b"a:1"], [b"b:2", b"a:1"], [b"z", b"env:prod", b"a:1"],
               [b"a:1", b"veneurlocalonly"], [b"veneurglobalonly:x", b"k:v"]]
    bad = [b"foo", b":1|c", b"a|b:1|ms", b"x:nan|g", b"x:1|q", b"x:1|c|@2",
           b"x:1|c|#a|#b", b"x:1 |c"]
    out = []
    for _ in range(n):
        k = int(rng.integers(0, 100))
        sid = int(rng.integers(0, 40))
        tags = list(tagsets[int(rng.integers(0, len(tagsets)))])
        rng.shuffle(tags)
        suffix = (b"|#" + b",".join(tags)) if tags else b""
        rate = [b"", b"|@0.5", b"|@0.25"][int(rng.integers(0, 3))]
        if k < 30:
            # few timer series, many samples each: rows fill and spill
            ln = b"c.t%d:%.2f|ms%s%s" % (sid % 6, rng.integers(0, 4000) / 4,
                                         rate, suffix)
        elif k < 40:
            ln = b"c.h%d:%d|%s%s" % (sid, rng.integers(0, 99),
                                     [b"h", b"d"][sid % 2], suffix)
        elif k < 58:
            ln = b"c.c%d:%d|c%s%s" % (sid, rng.integers(1, 9), rate, suffix)
        elif k < 76:
            ln = b"c.g%d:%.2f|g%s" % (sid % 10, rng.integers(0, 999) / 4,
                                      suffix)
        elif k < 88:
            ln = b"c.s%d:u%d|s%s" % (sid % 8, rng.integers(0, 50), suffix)
        elif k < 91:
            ln = b"_e{5,2}:title|hi|#t:%d" % sid
        elif k < 93:
            ln = b"_sc|check.%d|0|#t:1" % sid
        else:
            ln = bad[int(rng.integers(0, len(bad)))]
        out.append(ln)
    return out


def feed(ingest, lines: list, how: str, rng) -> None:
    """The lines through `ingest` (one call takes newline-joined bytes):
    whole, one line a call, or split at random line boundaries."""
    if how == "whole":
        cuts = [0, len(lines)]
    elif how == "by_line":
        cuts = list(range(len(lines) + 1))
    else:
        inner = np.unique(rng.integers(1, len(lines), 40)).tolist()
        cuts = [0] + inner + [len(lines)]
    for a, b in zip(cuts, cuts[1:]):
        ingest(b"\n".join(lines[a:b]))


def interval_record(ni) -> dict:
    """Everything a flush takes out of a context, then the reset."""
    st = ni.detach_stage()
    plane = None
    if st is not None:
        vals, wts, counts, unit, free = st
        plane = (vals.copy(), wts.copy(), counts.copy(), unit)
        free()
    batch = ni.drain_new_series()
    rec = {
        "processed": ni.processed, "errors": ni.errors,
        "series": (batch.pools.tolist(), batch.rows.tolist(),
                   batch.sids.tolist(), batch.first_records(),
                   batch.generation),
        "plane": plane,
        "histo": [a.tolist() for a in ni.drain_histo(1 << 16)],
        "set": [a.tolist() for a in ni.drain_set(1 << 16)],
        "counter": [a.tolist() for a in ni.drain_counter(1 << 16)],
        "gauge": [a.tolist() for a in ni.drain_gauge(1 << 16)],
        "other": ni.drain_other(), "rows": ni.num_rows(),
    }
    ni.reset()
    return rec


def assert_same_record(got: dict, want: dict) -> None:
    for key in want:
        if key != "plane":
            assert got[key] == want[key], key
    assert (got["plane"] is None) == (want["plane"] is None)
    if want["plane"] is not None:
        for g, w in zip(got["plane"][:3], want["plane"][:3]):
            np.testing.assert_array_equal(g, w)
        assert got["plane"][3] == want["plane"][3]


def run_intervals(n_ctx: int, how: str, seed: int):
    """Three intervals of the corpus (the second leaves series out and
    reverses the order, the third brings them back) through n_ctx
    contexts fed `how`; one record per context per interval, then each
    context's commit counters with its lock's acquisitions beside them."""
    ctxs = [native_mod.NativeIngest() for _ in range(n_ctx)]
    for ni in ctxs:
        ni.set_stage_depth(8)
    router = native_mod.NativeRouter(ctxs)
    ingest = router.ingest if n_ctx > 1 else ctxs[0].ingest
    lines = corpus_lines(seed)
    rng = np.random.default_rng(seed + 1)
    out = []
    for part in (lines, lines[900:300:-1], lines[200:]):
        feed(ingest, part, how, rng)
        out.append([interval_record(ni) for ni in ctxs])
    return out, [{**ni.commit_counters(), "lock_acquisitions":
                  ni.lock_stats(samples=False)["acquisitions"]}
                 for ni in ctxs]


@pytest.mark.parametrize("how", ["whole", "splits", "by_line"])
@pytest.mark.parametrize("seed", [11, 12])
def test_chunk_commit_matches_line_by_line(how, seed):
    """However a buffer is cut, the context ends where one line a call
    leaves it: counts, the drained series queue (pools, rows, sids,
    first-seen strings), the staged plane and the SoA batches, over
    three intervals with resets; and the lock's record has one entry a
    lock hold, so one a line fed where a call is a line."""
    want, _ = run_intervals(1, "by_line", seed)
    got, (cc,) = run_intervals(1, how, seed)
    for g, w in zip(got, want):
        assert_same_record(g[0], w[0])
    assert cc["lock_acquisitions"] == cc["commit_batches"]
    if how == "by_line":
        assert cc["lock_acquisitions"] == cc["commit_lines"] \
            == sum(r[0]["processed"] for r in got)
    assert want[0][0]["errors"] > 0 and want[0][0]["other"]
    assert want[0][0]["histo"][0]  # timers spilled past the depth


@pytest.mark.parametrize("how", ["whole", "splits", "by_line"])
def test_commit_counters_add_up(how):
    """Every accepted metric line is a hit, a restamp or a first sight;
    the chunk commit took every one of them."""
    records, (cc,) = run_intervals(1, how, 21)
    processed = sum(r[0]["processed"] for r in records)
    queued = sum(len(r[0]["series"][0]) for r in records)
    firsts = sum(len(r[0]["series"][3]) for r in records)
    assert cc["dir_hits"] + cc["dir_restamped"] + cc["dir_first_seen"] \
        == processed == cc["commit_lines"]
    assert cc["dir_restamped"] + cc["dir_first_seen"] == queued
    assert cc["dir_first_seen"] == firsts
    assert cc["dir_restamped"] > 0 and cc["dir_hits"] > 0
    if how == "whole":
        assert cc["commit_batches"] == 3
    elif how == "by_line":
        assert cc["commit_batches"] == processed


def test_stream_reader_matches_whole_buffer():
    """The TCP reader cuts the stream wherever recv returns: partial
    lines carried over, an overlong line dropped (one error) with the
    line after it intact, blank lines ignored."""
    lines = corpus_lines(31, 600)
    lines[100:100] = [b"", b"long." + b"x" * 300 + b":1|c", b"after.long:1|c"]
    want_ni = native_mod.NativeIngest()
    want_ni.set_stage_depth(8)
    want_ni.ingest(b"\n".join(ln for ln in lines if len(ln) <= 256))
    want = interval_record(want_ni)
    want["errors"] += 1  # the overlong line

    ni = native_mod.NativeIngest()
    ni.set_stage_depth(8)
    router = native_mod.NativeRouter([ni])
    a, b = socket.socketpair()
    handle = router.start_stream_reader(a.detach(), 256)
    data = b"\n".join(lines) + b"\n"
    rng = np.random.default_rng(5)
    cuts = [0] + np.unique(rng.integers(1, len(data), 60)).tolist() + [
        len(data)]
    for lo, hi in zip(cuts, cuts[1:]):
        b.sendall(data[lo:hi])
        time.sleep(0.002)  # let recv see the cut
    b.close()
    deadline = time.time() + 10
    while not router.stream_reader_done(handle) and time.time() < deadline:
        time.sleep(0.01)
    n_lines = router.stop_stream_reader(handle)
    assert n_lines == sum(1 for ln in lines if 0 < len(ln) <= 256)
    assert_same_record(interval_record(ni), want)


# -- epochs: one lifetime directory, rows stamped per interval ----------------


def test_a_series_not_written_in_an_interval_has_no_row_in_it():
    """Rows are the interval's own, from 0 in first-seen order; a
    series left out of an interval is not in its queue, and comes back
    under its sid."""
    ni = native_mod.NativeIngest()
    ni.ingest(_lines([b"a", b"b", b"c"]))
    first = ni.drain_new_series()
    ni.reset()
    ni.ingest(_lines([b"c", b"a"]))
    second = ni.drain_new_series()
    assert second.sids.tolist() == [2, 0] and second.rows.tolist() == [0, 1]
    assert ni.num_rows() == (2, 0, 0, 0)
    ni.reset()
    ni.ingest(_lines([b"b", b"b", b"c"]))
    third = ni.drain_new_series()
    assert third.sids.tolist() == [1, 2] and third.rows.tolist() == [0, 1]
    assert third.first_names == [] and first.first_names == ["a", "b", "c"]
    assert ni.commit_counters()["dir_hits"] == 1


@pytest.mark.parametrize("line_first", [True, False])
def test_an_upsert_and_a_line_of_one_identity_meet_in_one_row(line_first):
    ni = native_mod.NativeIngest()
    for interval in range(2):
        ni.ingest(b"other:1|ms")
        if line_first:
            ni.ingest(b"u.t:1|ms|#b:2,a:1")
            row = ni.upsert("u.t", "timer", "a:1,b:2", 0)
        else:
            row = ni.upsert("u.t", "timer", "a:1,b:2", 0)
            ni.ingest(b"u.t:1|ms|#b:2,a:1")
        assert row == 1
        batch = ni.drain_new_series()
        assert batch.rows.tolist() == [0, 1]
        assert batch.sids.tolist() == [0, 1]
        assert len(batch.first_at) == (2 if interval == 0 else 0)
        # the scope twin is another series, in the same pool
        assert ni.upsert("u.t", "timer", "a:1,b:2", 1) == 2
        ni.reset()


def test_upsert_many_shares_the_directory():
    ni = native_mod.NativeIngest()
    ni.ingest(b"m.a:1|c\nm.b:1|c")
    meta = b"m.b\x1f\x1em.new\x1ft:1\x1em.a\x1f"
    kinds = np.zeros(3, np.uint8)  # counters
    rows = native_mod.upsert_many(ni, meta, kinds, np.zeros(3, np.uint8),
                                  np.ones(3, np.uint8))
    assert rows.tolist() == [1, 2, 0]
    assert ni.drain_new_series().sids.tolist() == [0, 1, 2]


def test_the_table_drop_mixes_no_generations():
    """After the drop at intern_cap every slot is gone with its stamp:
    rows and sids restart together, and a series of the old generation
    is first-seen again."""
    ni = native_mod.NativeIngest()
    ni.set_intern_cap(4)
    ni.ingest(_lines([b"g%d" % i for i in range(6)]))
    assert ni.drain_new_series().generation == 0
    ni.reset()  # 6 >= 4: dropped here
    ni.ingest(_lines([b"g5", b"g0", b"g5"]))
    batch = ni.drain_new_series()
    assert batch.generation == 1
    assert batch.rows.tolist() == [0, 1] and batch.sids.tolist() == [0, 1]
    assert batch.first_names == ["g5", "g0"]
    cc = ni.commit_counters()
    assert (cc["dir_first_seen"], cc["dir_restamped"], cc["dir_hits"]) \
        == (8, 0, 1)


# -- raw-sample staging plane (vn_set_stage_depth / vn_stage_detach) --------


def test_native_staging_plane_detach():
    """Staged samples land in the [rows, depth] plane in commit order;
    detach hands the plane over and installs a fresh one."""
    ni = native_mod.NativeIngest()
    ni.set_stage_depth(4)
    ni.ingest(b"st.a:1|ms\nst.a:2|ms\nst.b:7|ms|@0.5")
    assert ni.stage_total == 3
    assert ni.pending_histo == 0  # nothing spilled
    st = ni.detach_stage()
    assert st is not None
    vals, wts, counts, unit, free = st
    assert not unit  # the @0.5 sample makes weights non-unit
    try:
        assert vals.shape == wts.shape and vals.shape[1] == 4
        assert counts[0] == 2 and counts[1] == 1
        assert vals[0, 0] == 1.0 and vals[0, 1] == 2.0
        assert wts[0, 0] == 1.0
        assert vals[1, 0] == 7.0 and wts[1, 0] == 2.0  # 1/0.5
        assert wts[0, 2] == 0.0  # unused slot stays zero-weight
    finally:
        free()
    # fresh plane: nothing staged until new samples arrive
    assert ni.stage_total == 0
    assert ni.detach_stage() is None
    ni.ingest(b"st.a:9|ms")
    assert ni.stage_total == 1


def test_native_staging_spills_past_depth():
    """Slots past the depth spill into the SoA batch (the direct-fold
    path) — no sample is dropped either side."""
    ni = native_mod.NativeIngest()
    ni.set_stage_depth(2)
    for v in range(5):
        ni.ingest(b"sp.hot:%d|ms" % v)
    assert ni.stage_total == 2
    assert ni.pending_histo == 3
    rows, vals, wts = ni.drain_histo(16)
    assert list(vals) == [2.0, 3.0, 4.0]
    st = ni.detach_stage()
    vals2, _wts2, counts, unit, free = st
    assert unit  # every sample unweighted
    try:
        assert counts[0] == 2 and vals2[0, 0] == 0.0 and vals2[0, 1] == 1.0
    finally:
        free()


def test_native_spill_cap_sheds_with_exact_count():
    """Beyond the pending-batch cap the sample is dropped and counted
    (overload shedding, drop-don't-block): an overloaded host must stay
    memory-bounded like the reference's fixed worker channels
    (worker.go:31-48), never OOM. Counter/gauge/set batches cap too;
    drains and later ingest keep working after shedding."""
    ni = native_mod.NativeIngest()
    ni.set_stage_depth(2)
    ni.set_spill_cap(4)
    # one hot histo row: 2 staged + 4 spilled + 3 shed
    for v in range(9):
        ni.ingest(b"cap.hot:%d|ms" % v)
    assert ni.stage_total == 2
    assert ni.pending_histo == 4
    assert ni.overload_dropped == 3
    # counters shed beyond the cap too (value preserved up to the cap)
    for v in range(6):
        ni.ingest(b"cap.c:1|c")
    assert ni.pending_counter == 4
    assert ni.overload_dropped == 5
    # sets: cap applies per sample
    for v in range(6):
        ni.ingest(b"cap.s:%d|s" % v)
    assert ni.pending_set == 4
    assert ni.overload_dropped == 7
    # gauges are last-write-wins: at the cap, a row already in the
    # batch UPDATES in place (a shed gauge would flush an actively
    # wrong early-interval value); only rows absent from the capped
    # batch shed
    for v in range(4):
        ni.ingest(b"cap.g:%d|g" % v)  # fills the batch to the cap
    ni.ingest(b"cap.gnew:1|g")  # new row while capped: sheds
    assert ni.overload_dropped == 8
    ni.ingest(b"cap.g:99|g")  # known row while capped: in-place update
    assert ni.pending_gauge == 4
    assert ni.overload_dropped == 8
    _rows, gvals = ni.drain_gauge(8)
    assert 99.0 in list(gvals)
    # shedding is not sticky: a drain frees the batch and ingest resumes
    rows, vals, _wts = ni.drain_histo(16)
    assert list(vals) == [2.0, 3.0, 4.0, 5.0]
    ni.ingest(b"cap.hot:42|ms")
    assert ni.pending_histo == 1
    assert ni.overload_dropped == 8
    # the in-place gauge index is invalidated by the drain: the same
    # row appends fresh entries afterwards (no stale-index writes)
    ni.ingest(b"cap.g:7|g")
    assert ni.pending_gauge == 1
    # epoch reset clears the tally (per-interval self-metric semantics)
    ni.reset()
    assert ni.overload_dropped == 0


def test_native_spill_cap_raise_rebuilds_gauge_index():
    """Raising the cap mid-overload invalidates the onset-built gauge
    last-write index: rows appended after the raise must win LWW over
    their pre-raise duplicates at the next overload onset (a stale
    index would update the older-positioned entry, so the newer batch
    entry — holding an older value — wins the fold)."""
    ni = native_mod.NativeIngest()
    ni.set_stage_depth(2)
    ni.set_spill_cap(2)
    ni.ingest(b"rg.a:1|g")
    ni.ingest(b"rg.b:2|g")          # batch at cap
    ni.ingest(b"rg.a:10|g")         # onset: index built, in-place update
    assert ni.pending_gauge == 2
    ni.set_spill_cap(4)             # raise: push_back resumes
    ni.ingest(b"rg.c:3|g")
    ni.ingest(b"rg.a:20|g")         # duplicate row, later position
    assert ni.pending_gauge == 4    # back at (new) cap
    ni.ingest(b"rg.a:30|g")         # 2nd onset: index must be rebuilt
    dropped_before = ni.overload_dropped
    ni.ingest(b"rg.d:9|g")          # genuinely absent row: sheds
    assert ni.overload_dropped == dropped_before + 1
    _rows, gvals = ni.drain_gauge(8)
    # the LAST entry for row a carries 30 — with a stale index the 30
    # lands at position 0 and the stale 20 wins the positional LWW fold
    assert list(gvals) == [10.0, 2.0, 3.0, 30.0]


def test_native_staging_reset_drops_plane():
    """vn_ctx_reset must not leak staged samples into the next epoch."""
    ni = native_mod.NativeIngest()
    ni.set_stage_depth(4)
    ni.ingest(b"rs.x:3|ms")
    assert ni.stage_total == 1
    ni.reset()
    assert ni.stage_total == 0
    assert ni.detach_stage() is None
    # staging stays enabled across epochs
    ni.ingest(b"rs.x:5|ms")
    assert ni.stage_total == 1


@pytest.mark.parametrize("close", ["detach", "reset"])
def test_a_new_plane_is_sized_for_the_last_interval_and_zero(close):
    """The plane after a detach or a reset starts at the row count the
    interval before it needed (no growth in a steady interval), every
    slot zero whatever the plane before it held (a freed plane is wiped
    and used again), and still grows past that."""
    ni = native_mod.NativeIngest()
    ni.set_stage_depth(4)
    names = [b"p%d" % i for i in range(5000)]
    ni.ingest(_lines(names, b"%s:7|ms|@0.5"))
    assert len(ni.drain_stage_delta(64)[0]) == 64  # a watermark to wipe
    grows = ni.commit_counters()["plane_grows"]
    assert grows == 1  # 4,096 -> 8,192
    first_at = None
    if close == "detach":
        vals, wts, counts, unit, free = ni.detach_stage()
        assert vals.shape == (8192, 4) and counts.sum() == 5000
        assert not unit and wts[4999, 0] == 2.0
        first_at = vals.ctypes.data
        free()
    ni.reset()
    ni.ingest(b"p0:3|ms")
    assert ni.drain_stage_delta(64)[2].tolist() == [3.0]
    vals, wts, counts, unit, free = ni.detach_stage()
    try:
        assert first_at in (None, vals.ctypes.data)  # the same memory
        assert vals.shape == (8192, 4) and unit
        assert counts.tolist() == [1] + [0] * 8191
        assert vals[0, 0] == 3.0 and wts[0, 0] == 1.0
        vals[0, 0] = wts[0, 0] = 0.0
        assert not vals.any() and not wts.any()
    finally:
        free()
    assert ni.commit_counters()["plane_grows"] == grows
    ni.reset()
    # the interval before this one needed one row: back to the least
    ni.ingest(_lines(names + [b"q%d" % i for i in range(4000)], b"%s:1|ms"))
    vals, _wts, counts, _unit, free = ni.detach_stage()
    try:
        assert vals.shape == (16384, 4) and counts.sum() == 9000
        assert vals[:9000, 0].tolist() == [1.0] * 9000
        assert not vals[9000:].any() and not vals[:, 1:].any()
    finally:
        free()
    assert ni.commit_counters()["plane_grows"] == grows + 2


def test_native_ssf_reader_end_to_end():
    """The C++ SSF datagram reader (vn_ssf_reader_start): indicator
    spans extract in C++ with no Python on the path; STATUS spans ride
    the fallback buffer to the Python pipeline — nothing lost."""
    cfg = Config(ssf_listen_addresses=["udp://127.0.0.1:0"],
                 interval="600s", num_workers=1,
                 indicator_span_timer_name="ind.t", percentiles=[0.5])
    srv = Server(cfg)
    if not srv.native_mode:
        srv.shutdown()
        pytest.skip("native library unavailable")
    ports = srv.start()
    try:
        assert srv._native_ssf_readers, "native SSF reader not started"
        port = next(iter(ports.values()))
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        # indicator span: fully native
        s.sendto(_make_span_bytes(
            trace_id=5, id=6, start_timestamp=10**9,
            end_timestamp=10**9 + 3_000_000, service="rdr", name="op",
            indicator=True), ("127.0.0.1", port))
        # STATUS span: must fall back to Python
        s.sendto(_make_span_bytes(
            trace_id=7, id=8, start_timestamp=10**9,
            end_timestamp=10**9 + 1, service="rdr", name="op",
            metrics=[{"metric": 4, "name": "svc.ok", "value": 0.0}]),
            ("127.0.0.1", port))
        deadline = time.time() + 10
        while time.time() < deadline:
            if sum(w.processed for w in srv.workers) >= 2:
                break
            time.sleep(0.05)
        metrics = srv.flush()
        names = {m.name for m in metrics}
        assert any(n.startswith("ind.t") for n in names), names
        by_key = {(m.name, m.type): m for m in metrics}
        assert by_key[("svc.ok", MetricType.STATUS)].value == 0.0
    finally:
        srv.shutdown()


def test_wire_decoder_strictness_matches_python_pb():
    """Three malformation classes the round-4 decoder fuzz caught the
    C++ wire decoder ACCEPTING where the protobuf spec (and the Python
    parser) reject — each must now reject, or a half-corrupt forward
    body would silently decode garbage into the global tier instead of
    falling back / erroring visibly:
      1. tag varints exceeding 32 bits (field numbers cap at 2^29-1),
      2. the same inside nested submessages (counter/gauge/digest/hll),
      3. invalid UTF-8 in proto3 `string` fields (name, tags)."""
    from veneur_tpu.gen import veneur_tpu_pb2 as vpb

    # oversized tag varint at the top level: 5 bytes, bits past 2^32
    assert native_mod.decode_metric_batch(
        b"\xfd\x17\xf4\xb7a'\xc5\xe9\xd8\xc8:\xe7\xaf\x0br") is None

    # 10-byte varint whose final byte overflows uint64: every spec
    # parser rejects; the SSF decoder must too (round-4 deep fuzz)
    ni = native_mod.NativeIngest()
    overflow_tid = b"\x10" + b"\xa1\xdd\x9f\x99\x8a\xba\x8e\xbc\xd5\x18"
    assert ni.ingest_ssf(overflow_tid + b"J\x02ssR\x07\x12\x02m0\x1d\x00\x00\x00?",
                         b"i", b"o") == 0

    # TAG varints cap at 5 bytes: a zero-padded 6-byte tag encoding is
    # malformed even though its value fits uint32 (round-4 deep fuzz)
    six_byte_tag = b"\x9d\xa5\xbb\x9f\x81\x00" + b"\xa5\xfc:P"
    assert ni.ingest_ssf(b"\x10\x07" + six_byte_tag + b"J\x02ss",
                         b"i", b"o") == 0
    assert native_mod.decode_metric_batch(six_byte_tag) is None

    # oversized tag varint inside a counter submessage
    bad_inner = bytes.fromhex("0a120a054b7a2e6d0d2a09cdfaffff40ff82ffff")
    assert native_mod.decode_metric_batch(bad_inner) is None

    # invalid UTF-8 in the name string field
    good = vpb.MetricBatch()
    m = good.metrics.add()
    m.name = "ok.name"
    m.kind = vpb.KIND_COUNTER
    m.counter.value = 3
    blob = bytearray(good.SerializeToString())
    idx = bytes(blob).find(b"ok.name")
    blob[idx] = 0xD8  # lead byte with no continuation
    assert native_mod.decode_metric_batch(bytes(blob)) is None
    # the unmutated batch still decodes
    d = native_mod.decode_metric_batch(bytes(good.SerializeToString()))
    assert d is not None and d.n == 1
