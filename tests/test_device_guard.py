"""Device fault domain: taxonomy, breaker, host failover, probe.

The contract under test (ops/device_guard.py + ops/host_engine.py +
worker failover wiring): a device fault anywhere on the guarded path —
batch fold, micro-fold scatter, spill fold, staged-plane fold, flush
extract, set ops, pool growth — must never lose an epoch. The worker
completes the flush on the host engine, and because that engine is
pinned bit-identical to the device programs for every metric class, a
faulted flush produces byte-for-byte the snapshot a healthy device
would have (only the ``degraded`` flag differs). A consecutive-failure
streak trips the per-worker breaker, quarantining the device path
entirely; a compile+fold+extract probe re-admits it, after which
flushes are bitwise back to normal.

CI runs the parity matrix twice — default and VENEUR_DEVICE_GUARD=0
(tools/ci.sh device-fault lane) — so the escape hatch provably restores
the unguarded path.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from veneur_tpu.core.flusher import device_quantiles
from veneur_tpu.core.metrics import HistogramAggregates
from veneur_tpu.core.worker import DeviceWorker
from veneur_tpu.ops import device_guard as dg
from veneur_tpu.protocol.dogstatsd import parse_metric
from veneur_tpu.utils import faults as fl

AGGS = HistogramAggregates.from_names(["min", "max", "sum", "count"])
PCTS = [0.5, 0.9, 0.99]
QS = device_quantiles(PCTS, AGGS)

# one always-open injection window per flush-path op (dispatch-index
# window [0, 1e6) covers any realistic test run)
ALWAYS = [(0, 10**6, "oom")]
FLUSH_OPS = ("fold", "spill", "staged", "micro", "extract", "sets",
             "grow", "import")


def _need_devices(n: int) -> None:
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, have {jax.device_count()}")


def _assert_snapshots_identical(a, b, path):
    """Bitwise snapshot equality, ``degraded`` excluded (it is the one
    field a host-completed flush is SUPPOSED to change)."""
    for f in dataclasses.fields(a):
        if f.name == "degraded":
            continue
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, np.ndarray) or isinstance(vb, np.ndarray):
            assert va is not None and vb is not None, (path, f.name)
            assert va.dtype == vb.dtype and va.shape == vb.shape, (
                path, f.name, getattr(va, "dtype", None),
                getattr(vb, "dtype", None))
            assert va.tobytes() == vb.tobytes(), (path, f.name, va, vb)
        elif isinstance(va, (int, float)) or va is None:
            assert va == vb, (path, f.name, va, vb)


def _mk_worker(shards=0, micro=False, **kw):
    kw.setdefault("compression", 100)
    kw.setdefault("stage_depth", 32)
    kw.setdefault("batch_size", 8)
    kw.setdefault("initial_histo_rows", 8)
    kw.setdefault("initial_set_rows", 8)
    return DeviceWorker(micro_fold=micro, micro_fold_rows=1,
                        micro_fold_max_age_s=1e9, series_shards=shards,
                        **kw)


def _feed_interval(w, seed, micro=False):
    """One interval of mixed workload: t-digest timers past the initial
    pool (growth runs), HLL sets, counters, gauges; micro-folds at
    offsets so a fault can land mid-stream."""
    rng = np.random.default_rng(seed)
    for batch in range(8):
        for i in range(10):
            k = (batch * 10 + i) % 17
            w.process_metric(parse_metric(
                f"h{k}:{rng.normal():.6f}|ms|#a:{k % 3}".encode()))
            w.process_metric(parse_metric(f"c{k}:{1 + k % 4}|c".encode()))
            w.process_metric(parse_metric(
                f"g{k}:{rng.normal():.6f}|g".encode()))
            w.process_metric(parse_metric(
                f"s{k}:v{rng.integers(200)}|s".encode()))
        if micro and batch % 2 == 0 and w.micro_fold_due():
            w.micro_fold_once()


# -- taxonomy ---------------------------------------------------------------


class XlaRuntimeError(RuntimeError):
    """Stand-in named like jaxlib's — classify matches by MRO name."""


def test_classify_taxonomy():
    assert dg.classify(XlaRuntimeError("RESOURCE_EXHAUSTED: oom")) == "oom"
    assert dg.classify(XlaRuntimeError("Out of memory: 128GiB")) == "oom"
    assert dg.classify(
        XlaRuntimeError("Mosaic lowering failed")) == "compile"
    assert dg.classify(XlaRuntimeError("UNAVAILABLE: device lost")) == "lost"
    assert dg.classify(XlaRuntimeError("something else entirely")) == "other"
    # an OOM that also mentions compilation is still an OOM
    assert dg.classify(
        XlaRuntimeError("RESOURCE_EXHAUSTED during compilation")) == "oom"
    # injected faults carry their kind
    assert dg.classify(fl.InjectedDeviceFault("lost", "fold")) == "lost"
    # python-level bugs are NOT device faults
    assert dg.classify(ValueError("bad arg")) is None
    assert dg.classify(TypeError("nope")) is None
    # already-classified errors pass through
    err = dg.DeviceFaultError("oom", "fold", RuntimeError("x"))
    assert dg.classify(err) == "oom"


# -- breaker unit behavior --------------------------------------------------


def _fake_clock(t0=0.0):
    state = {"t": t0}

    def clock():
        return state["t"]

    return clock, state


def test_streak_trips_breaker():
    g = dg.DeviceGuard(streak_limit=3, clock=_fake_clock()[0])

    def boom():
        raise fl.InjectedDeviceFault("oom", "fold")

    for i in range(2):
        with pytest.raises(dg.DeviceFaultError):
            g.call("fold", boom)
        assert not g.quarantined, i
    # a success between faults resets the streak
    assert g.call("fold", lambda: 42) == 42
    for i in range(2):
        with pytest.raises(dg.DeviceFaultError):
            g.call("fold", boom)
        assert not g.quarantined
    with pytest.raises(dg.DeviceFaultError):
        g.call("fold", boom)
    assert g.quarantined
    assert "oom" in g.trip_reason and "fold" in g.trip_reason
    c = g.counters()
    assert c["device.fault.oom"] == 5
    assert c["device.guard.trips"] == 1
    assert g.last_fault == "oom:fold"


def test_retryable_retries_once():
    g = dg.DeviceGuard(streak_limit=3)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise fl.InjectedDeviceFault("lost", "extract")
        return "ok"

    assert g.call("extract", flaky, retryable=True) == "ok"
    c = g.counters()
    assert c["device.fault.retries"] == 1
    assert c["device.fault.retry_success"] == 1
    assert c["device.fault.lost"] == 1
    assert not g.quarantined

    # non-retryable: the first fault surfaces immediately
    calls["n"] = 0
    with pytest.raises(dg.DeviceFaultError):
        g.call("fold", flaky)
    assert calls["n"] == 1


def test_python_errors_reraise_unclassified():
    g = dg.DeviceGuard()

    def bug():
        raise ValueError("host-side bug")

    with pytest.raises(ValueError):
        g.call("fold", bug)
    assert g.counters() == {}
    assert not g.quarantined


def test_probe_schedule_half_open():
    clock, state = _fake_clock()
    g = dg.DeviceGuard(streak_limit=1, probe_interval_s=30.0, clock=clock)
    with pytest.raises(dg.DeviceFaultError):
        g.call("fold", lambda: (_ for _ in ()).throw(
            fl.InjectedDeviceFault("oom", "fold")))
    assert g.quarantined
    # the first probe waits a full interval from the trip
    assert not g.probe_due()
    state["t"] = 29.0
    assert not g.probe_due()
    state["t"] = 30.0
    assert g.probe_due()
    # a failed probe re-arms the timer
    g.note_probe(False)
    assert not g.probe_due()
    state["t"] = 60.0
    assert g.probe_due()
    g.note_probe(True)
    g.readmit()
    assert not g.quarantined and g.trip_reason is None
    c = g.counters()
    assert c["device.guard.probes"] == 2
    assert c["device.guard.probe_failures"] == 1
    assert c["device.guard.readmissions"] == 1


def test_disabled_guard_is_passthrough():
    g = dg.DeviceGuard(enabled=False)

    def boom():
        raise fl.InjectedDeviceFault("oom", "fold")

    # no classification, no counters, the raw exception surfaces
    with pytest.raises(fl.InjectedDeviceFault):
        g.call("fold", boom)
    assert g.counters() == {}
    assert not g.quarantined


# -- failover parity matrix -------------------------------------------------


@pytest.mark.parametrize("shards", [0, 2], ids=["unsharded", "sharded"])
@pytest.mark.parametrize("micro", [False, True], ids=["batch", "micro"])
def test_fault_failover_bitwise(shards, micro):
    """Every flush under persistent injected faults — including the
    quarantined flush that runs entirely on the host engine — is
    byte-for-byte the snapshot a healthy worker produces, for all three
    metric classes, micro-folds on and off, sharded and not."""
    _need_devices(max(1, shards))
    base = _mk_worker(shards, micro)
    clean = [(_feed_interval(base, s, micro), base.flush(QS))[1]
             for s in (1, 2, 3)]

    w = _mk_worker(shards, micro, device_fault_streak=2)
    plan = fl.DeviceFaultPlan(
        seed=9, op_windows={op: ALWAYS for op in FLUSH_OPS})
    got = []
    with fl.DeviceFaultInjector(plan) as inj:
        _feed_interval(w, 1, micro)
        got.append(w.flush(QS))
        _feed_interval(w, 2, micro)
        got.append(w.flush(QS))
    assert sum(inj.injected[k] for k in dg.FAULT_KINDS) > 0, \
        "no fault injected — matrix would compare healthy to healthy"
    assert w.guard.quarantined
    # third interval: device healthy again but still quarantined — the
    # live epoch runs start-to-finish on the host engine
    _feed_interval(w, 3, micro)
    got.append(w.flush(QS))
    for n, (a, b) in enumerate(zip(clean, got)):
        _assert_snapshots_identical(a, b, f"interval={n}")
        assert b.degraded, f"interval={n} should be flagged degraded"
        assert not a.degraded
    assert w.host_fallback_flushes >= 2


def _native_micro_interval(w, seed):
    """One interval through the C++ plane with the micro-fold on: timers
    (one past the staging depth, so the spill fold runs too), counters,
    gauges, sets; two micro-folds, then lines the swap's residual drain
    finds. Every staged sample is mirrored at the tick, so the swap hands
    the detached plane over as the mirror's replay copy."""
    rng = np.random.default_rng(seed)
    for part in range(3):
        lines = []
        for i in range(40):
            k = (part * 40 + i) % 17
            lines += [f"h{k}:{rng.normal():.6f}|ms|#a:{k % 3}",
                      f"c{k}:{1 + k % 4}|c", f"g{k}:{rng.normal():.6f}|g",
                      f"s{k}:v{rng.integers(200)}|s"]
        lines += [f"hot:{rng.normal():.6f}|ms" for _ in range(20)]
        w.ingest_datagram("\n".join(lines).encode())
        if part < 2:
            assert w.micro_fold_once() > 0


# where the fault lands -> does the failover read the replay copy?
REPLAY_FAULTS = {
    "micro": True,     # the residual feed: the mirror never completes
    "staged": True,    # the mirror's fold
    "extract": False,  # after the fold has landed: the copy is spent
}


@pytest.mark.parametrize("op", sorted(REPLAY_FAULTS))
def test_native_replay_plane_failover_bitwise(op):
    """Native ingest + micro-fold: the mirror's replay copy is the
    detached C++ plane itself, handed over uncompacted (`replay: plane`
    on `swap.handoff`); a fault before the mirror's fold has landed
    compacts it once (`extract.replay_compact`) and folds it on the host
    engine, a fault after it reads nothing; either way the flush is the
    clean run's bit for bit, and so is the next one."""
    base = _mk_worker(0, True)
    if not base.attach_native():
        pytest.skip("native ingest library unavailable")
    clean = []
    for seed in (1, 2):
        _native_micro_interval(base, seed)
        clean.append(base.flush(QS))

    w = _mk_worker(0, True, device_fault_streak=100)
    assert w.attach_native()
    plan = fl.DeviceFaultPlan(seed=9, op_windows={op: ALWAYS})
    _native_micro_interval(w, 1)
    with fl.DeviceFaultInjector(plan) as inj:
        got = [w.flush(QS)]
    assert sum(inj.injected[k] for k in dg.FAULT_KINDS) > 0
    faulted = [s for s in w.rec.closed() if s.name.startswith(
        ("swap.handoff", "extract.replay_"))]
    _native_micro_interval(w, 2)
    got.append(w.flush(QS))
    for n, (a, b) in enumerate(zip(clean, got)):
        _assert_snapshots_identical(a, b, f"op={op} interval={n}")
        assert b.degraded == (n == 0) and not a.degraded
    handoff = [s for s in faulted if s.name == "swap.handoff"]
    assert [s.attrs["replay"] for s in handoff] == ["plane"]
    assert handoff[0].attrs["plane_rows"] >= 18
    compact = [s for s in faulted if s.name == "extract.replay_compact"]
    assert len(compact) == REPLAY_FAULTS[op]
    if compact:
        # 3 x 40 samples over 17 rows + the hot row's first 32
        assert compact[0].attrs["samples"] == 120 + 32
        assert "wait" not in compact[0].attrs
    # the clean flushes: the plane held, released once, never compacted
    names = [s.name for s in base.rec.closed()]
    assert names.count("extract.replay_release") == 2
    assert names.count("extract.replay_compact") == 0
    assert {s.attrs["replay"] for s in base.rec.closed()
            if s.name == "swap.handoff"} == {"plane"}


def _count_plane_frees(w) -> dict:
    """Wrap the native context's detach_stage: every detached plane's
    address, and how often its free() ran."""
    real = w._native.detach_stage
    log = {"addrs": [], "frees": []}

    def detach_stage():
        st = real()
        if st is None:
            return None
        k = len(log["addrs"])
        log["addrs"].append(int(st[0].ctypes.data))
        log["frees"].append(0)

        def free(_free=st[4], _k=k):
            log["frees"][_k] += 1
            _free()

        return (*st[:4], free)

    w._native.detach_stage = detach_stage
    return log


def _raise(exc):
    def boom(*a, **kw):
        raise exc
    return boom


RELEASE_PATHS = ("clean", "failover_before_mirror_fold",
                 "failover_after_mirror_fold", "exception_before_mirror_fold",
                 "exception_after_mirror_fold", "no_histogram_rows",
                 "dropped_unextracted")


@pytest.mark.parametrize("path", RELEASE_PATHS)
def test_replay_plane_is_released_exactly_once(path, monkeypatch):
    """However a swapped epoch dies, the detached C++ plane it holds as
    the mirror's replay copy gets one free(): none would leak a plane an
    interval, two would hand one plane to the reader twice."""
    w = _mk_worker(0, True, device_fault_streak=100)
    if not w.attach_native():
        pytest.skip("native ingest library unavailable")
    log = _count_plane_frees(w)
    _native_micro_interval(w, 1)
    if path == "clean":
        w.flush(QS)
        assert w.rec.last("extract.replay_release").attrs["rows"] == 4096
    elif path.startswith("failover"):
        op = "staged" if "before" in path else "extract"
        with fl.DeviceFaultInjector(fl.DeviceFaultPlan(
                seed=9, op_windows={op: ALWAYS})):
            assert w.flush(QS).degraded
    elif path.startswith("exception"):
        # not a device fault: the server logs it and goes on
        name = "_fold_staged" if "before" in path else "_note_fold_widths"
        monkeypatch.setattr(w, name, _raise(ValueError("not the device's")))
        with pytest.raises(ValueError):
            w.flush(QS)
        monkeypatch.undo()
    elif path == "no_histogram_rows":
        sw = w.swap(QS)
        assert sw.micro_replay.free is not None
        sw.histo = None
        w.extract_snapshot(sw, QS)
    else:
        sw = w.swap(QS)
        assert log["frees"] == [0]  # the swap reads and frees nothing
        sw.release()
        sw.release()
    assert log["frees"] == [1]
    # and the worker goes on: the next interval's plane, once as well
    _native_micro_interval(w, 2)
    assert not w.flush(QS).degraded
    assert log["frees"] == [1, 1]


def test_two_planes_take_turns():
    """The release still wipes and shelves: with the reader's next
    interval under way while a flush extracts, as in the server, the
    context allocates no plane after its second; every interval starts
    on the spare the flush before last gave back."""
    w = _mk_worker(0, True)
    if not w.attach_native():
        pytest.skip("native ingest library unavailable")
    log = _count_plane_frees(w)
    for seed in range(5):
        _native_micro_interval(w, seed)
        sw = w.swap(QS)
        w.ingest_datagram(b"h0:1.5|ms")
        assert log["frees"][-1] == 0
        w.extract_snapshot(sw, QS)
    a, b = log["addrs"][:2]
    assert a != b and log["addrs"] == [a, b, a, b, a]
    assert log["frees"] == [1] * 5
    assert [s.attrs["plane_rows"] for s in w.rec.closed()
            if s.name == "swap.handoff"] == [4096] * 5


@pytest.mark.parametrize("dies_in", ["swap", "extract"])
def test_a_flush_cut_short_gives_its_planes_back(dies_in, monkeypatch):
    """A shutdown's interrupt between a worker's swap and its extraction
    (here a KeyboardInterrupt out of the second worker's swap, or out of
    the extract phase before any worker was reached): the epochs already
    closed die with the flush, and the server gives their planes back."""
    from veneur_tpu.core.config import Config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.channel import ChannelMetricSink

    srv = Server(Config(statsd_listen_addresses=["tcp://127.0.0.1:0"],
                        num_workers=2, num_readers=1, interval="10s",
                        percentiles=[0.5], tpu_native_ingest=True),
                 metric_sinks=[ChannelMetricSink()])
    try:
        if not srv.native_mode:
            pytest.skip("native ingest library unavailable")
        logs = [_count_plane_frees(w) for w in srv.workers]
        for k in range(2):
            srv._native_router.ingest("\n".join(
                f"cut.t{i}:{i}.5|ms" for i in range(64)).encode())
            for i, w in enumerate(srv.workers):
                srv._micro_fold(i, w)
            if k:
                break
            if dies_in == "swap":
                monkeypatch.setattr(srv.workers[1], "swap",
                                    _raise(KeyboardInterrupt()))
            else:
                monkeypatch.setattr(srv, "_flush_extract_workers",
                                    _raise(KeyboardInterrupt()))
            with pytest.raises(KeyboardInterrupt):
                srv.flush()
            monkeypatch.undo()
            want = [[1], [] if dies_in == "swap" else [1]]
            assert [lg["frees"] for lg in logs] == want
        srv.flush()
        assert all(lg["frees"] == [1] * len(lg["frees"]) for lg in logs)
        assert sum(len(lg["frees"]) for lg in logs) == (
            3 if dies_in == "swap" else 4)
    finally:
        srv.shutdown()


@pytest.mark.parametrize("shards", [0, 2], ids=["unsharded", "sharded"])
def test_probe_readmits_and_restores_device_path(shards):
    """quarantine → probe → re-admission: the post-readmit flush runs on
    device (not degraded) and is bitwise a healthy worker's."""
    _need_devices(max(1, shards))
    w = _mk_worker(shards, device_fault_streak=1)
    plan = fl.DeviceFaultPlan(
        seed=3, op_windows={op: [(0, 10**6, "lost")]
                            for op in ("staged", "extract", "spill")})
    with fl.DeviceFaultInjector(plan):
        _feed_interval(w, 5)
        s_fault = w.flush(QS)
    assert s_fault.degraded and w.guard.quarantined

    w.guard.probe_interval_s = 0.0
    w.device_guard_tick()
    assert not w.guard.quarantined and not w._host_live
    c = w.guard.counters()
    assert c["device.guard.probes"] == 1
    assert c["device.guard.readmissions"] == 1

    _feed_interval(w, 6)
    s_after = w.flush(QS)
    assert not s_after.degraded

    base = _mk_worker(shards)
    _feed_interval(base, 5)
    b_first = base.flush(QS)
    _feed_interval(base, 6)
    b_after = base.flush(QS)
    _assert_snapshots_identical(b_first, s_fault, "faulted-interval")
    _assert_snapshots_identical(b_after, s_after, "post-readmit")


def test_failed_probe_stays_quarantined():
    w = _mk_worker(device_fault_streak=1)
    plan = fl.DeviceFaultPlan(
        seed=4, op_windows={"staged": [(0, 10**6, "lost")],
                            "extract": [(0, 10**6, "lost")]})
    with fl.DeviceFaultInjector(plan):
        _feed_interval(w, 5)
        w.flush(QS)
    assert w.guard.quarantined
    w.guard.probe_interval_s = 0.0
    # the probe itself faults → still quarantined, timer re-armed
    probe_plan = fl.DeviceFaultPlan(
        seed=5, op_windows={"probe": [(0, 10**6, "lost")]})
    with fl.DeviceFaultInjector(probe_plan):
        w.device_guard_tick()
    assert w.guard.quarantined
    c = w.guard.counters()
    assert c["device.guard.probe_failures"] == 1
    # next interval still flushes, conserved, on the host
    _feed_interval(w, 6)
    assert w.flush(QS).degraded


def test_transient_fault_window_conserves():
    """A fault window that OPENS mid-run (transient burst, then heals):
    some device ops succeed before the fault, the host engine completes
    the rest — still bitwise."""
    base = _mk_worker()
    _feed_interval(base, 11)
    clean = base.flush(QS)

    w = _mk_worker(device_fault_streak=10)  # streak never trips
    # burst scoped to fold ops — a grow fault would (by design) trip the
    # HBM valve's immediate breaker regardless of streak
    plan = fl.DeviceFaultPlan(seed=6, op_windows={
        "staged": [(0, 2, "oom")], "spill": [(0, 2, "oom")]})
    with fl.DeviceFaultInjector(plan) as inj:
        _feed_interval(w, 11)
        got = w.flush(QS)
    assert inj.injected["oom"] > 0
    assert not w.guard.quarantined, "burst should not trip a streak of 10"
    _assert_snapshots_identical(clean, got, "transient-burst")
    assert got.degraded
    # the burst healed: the next interval is a healthy device flush
    _feed_interval(base, 12)
    _feed_interval(w, 12)
    after = w.flush(QS)
    assert not after.degraded
    _assert_snapshots_identical(base.flush(QS), after, "post-burst")


def test_escape_hatch_disables_guard(monkeypatch):
    """VENEUR_DEVICE_GUARD=0 restores the unguarded path: no dispatch
    seam, so injection never fires and flushes are healthy-identical."""
    monkeypatch.setenv("VENEUR_DEVICE_GUARD", "0")
    w = _mk_worker()
    assert not w.guard.enabled
    plan = fl.DeviceFaultPlan(
        seed=7, op_windows={op: ALWAYS for op in FLUSH_OPS})
    with fl.DeviceFaultInjector(plan) as inj:
        _feed_interval(w, 13)
        snap = w.flush(QS)
    assert sum(inj.injected.values()) == 0, \
        "guarded dispatch ran despite the escape hatch"
    assert not snap.degraded and w.guard.counters() == {}

    monkeypatch.delenv("VENEUR_DEVICE_GUARD")
    base = _mk_worker()
    assert base.guard.enabled
    _feed_interval(base, 13)
    _assert_snapshots_identical(base.flush(QS), snap, "hatch")


def test_grow_oom_valve_degrades_not_faults():
    """OOM on pool growth: the HBM valve's pre-flight eats the fault,
    trips the breaker, and the epoch continues (and flushes, exact) on
    the host-grown pool."""
    base = _mk_worker(initial_histo_rows=4)
    _feed_interval(base, 21)
    clean = base.flush(QS)

    w = _mk_worker(initial_histo_rows=4)
    plan = fl.DeviceFaultPlan(seed=8, op_windows={"grow": ALWAYS})
    with fl.DeviceFaultInjector(plan) as inj:
        _feed_interval(w, 21)  # 17 series >> 4 rows → growth must run
        got = w.flush(QS)
    assert inj.injected["oom"] > 0, "growth never ran — widen the workload"
    assert w.guard.quarantined
    assert w.guard.counters().get("device.valve.grow_oom", 0) >= 1
    _assert_snapshots_identical(clean, got, "grow-valve")
    assert got.degraded


# -- one served flush against the failover engine, class by class -----------

T0 = 1_700_000_000
CLASSES = {"counter": "sf.count", "gauge": "sf.gauge", "timer": "sf.timer",
           "histogram": "sf.histo", "set": "sf.set"}


def _interval_lines(i: int) -> list[bytes]:
    """One interval of traffic in every metric class, tagged and bare,
    varied per interval so the streams are distinguishable."""
    lines = [
        b"sf.count:%d|c" % (i + 1),
        b"sf.count:%d|c|#env:prod,team:obs" % (2 * i + 3),
        b"sf.gauge:%.2f|g" % (1.5 * (i + 1)),
        b"sf.gauge:%d|g|#env:prod" % (10 * i),
    ]
    for v in range(1, 21):
        lines.append(b"sf.timer:%d|ms" % (v * (i + 1)))
        lines.append(b"sf.histo:%d|h|#env:prod" % (v + i))
    for j in range(12 + i):
        lines.append(b"sf.set:user%d|s" % j)
        lines.append(b"sf.set:user%d|s|#env:prod" % (j * 7))
    return lines


def _served_streams(faulted: bool) -> list[list]:
    """Three intervals through a whole Server (two workers, the flush
    the ticker would run, a channel sink): on the device, or with every
    flush-path dispatch faulting, so that the second and third flushes
    run start to finish on ops/host_engine.py."""
    from veneur_tpu.core.config import Config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.channel import ChannelMetricSink

    sink = ChannelMetricSink()
    srv = Server(Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                        num_workers=2, num_readers=1, interval="10s",
                        percentiles=[0.5, 0.99], device_fault_streak=1,
                        tpu_native_ingest=False),
                 metric_sinks=[sink])
    plan = fl.DeviceFaultPlan(
        seed=5, op_windows={op: ALWAYS for op in FLUSH_OPS} if faulted
        else {})
    out = []
    try:
        with fl.DeviceFaultInjector(plan) as inj:
            for i in range(3):
                for line in _interval_lines(i):
                    srv.handle_metric_packet(line)
                srv.flush(now=T0 + 10 * i)
                out.append(sink.queue.get(timeout=30))
        if faulted:
            assert sum(inj.injected[k] for k in dg.FAULT_KINDS) > 0
            assert all(w.guard.quarantined for w in srv.workers)
            assert sum(w.host_fallback_flushes for w in srv.workers) >= 3
        else:
            assert not any(w.host_fallback_flushes for w in srv.workers)
    finally:
        srv.shutdown()
    return out


@pytest.fixture(scope="module")
def served_and_failover():
    return _served_streams(False), _served_streams(True)


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_a_served_flush_is_the_failover_engines_answer(
        served_and_failover, cls):
    """test_fault_failover_bitwise holds a worker's snapshot to the host
    engine for timers, counters, gauges and sets; this holds what a
    sink is handed, through generation and across two workers, and adds
    the |h class and bare-beside-tagged series."""
    served, failover = served_and_failover

    def of_class(metrics):
        return sorted(
            (m.name, m.timestamp, repr(m.value), tuple(m.tags), m.type,
             m.hostname)
            for m in metrics if m.name.startswith(CLASSES[cls]))

    for i, (a, b) in enumerate(zip(served, failover)):
        assert of_class(a), (cls, i)
        assert of_class(a) == of_class(b), (cls, i)
