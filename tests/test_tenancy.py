"""Per-tenant QoS layer (core/tenancy.py + the worker/flusher/server
wiring): series-budget admission, honest per-tenant tallies surviving
the epoch swap, rejected-row parity between the object and columnar
emit paths, the tenant-aware shed ordering, and config validation."""

import numpy as np
import pytest

from veneur_tpu.core.config import Config, load_config, validate_config
from veneur_tpu.core.flusher import (
    device_quantiles,
    forwardable_rows,
    generate_columnar,
    generate_inter_metrics,
)
from veneur_tpu.core.metrics import (
    DEFAULT_TENANT,
    HistogramAggregates,
    tenant_of,
)
from veneur_tpu.core.tenancy import TenantLedger, TenantTallies
from veneur_tpu.core.worker import DeviceWorker
from veneur_tpu.health.policy import shed_spill_keep
from veneur_tpu.protocol.dogstatsd import parse_metric

AGGS = HistogramAggregates.from_names(["min", "max", "count"])


def _worker(default_budget=0, budgets=None) -> DeviceWorker:
    w = DeviceWorker()
    w.tenancy = TenantLedger(default_budget=default_budget,
                             budgets=budgets or {})
    return w


# -- tenant_of -------------------------------------------------------------


def test_tenant_of_extraction():
    assert tenant_of(["env:prod", "tenant:acme"], "tenant") == "acme"
    assert tenant_of(["tenantx:no", "env:prod"], "tenant") == DEFAULT_TENANT
    assert tenant_of([], "tenant") == DEFAULT_TENANT
    assert tenant_of(["tenant:"], "tenant") == DEFAULT_TENANT
    assert tenant_of(["team:x"], "team") == "x"


# -- TenantLedger ----------------------------------------------------------


def test_ledger_budget_and_idempotence():
    led = TenantLedger(default_budget=2, budgets={"vip": 0, "tiny": 1})
    assert led.admit("a", "s1") and led.admit("a", "s2")
    assert not led.admit("a", "s3")
    assert led.admit("a", "s1")  # admitted stays admitted
    # re-admission never re-consumes budget
    assert led.live("a") == 2
    # per-tenant override: 0 = unlimited
    for i in range(50):
        assert led.admit("vip", f"v{i}")
    assert led.admit("tiny", "t1")
    assert not led.admit("tiny", "t2")
    assert led.over_budget() == frozenset({"a", "tiny"})
    # distinct-series rejection counts deduplicate
    led.admit("a", "s3")
    led.admit("a", "s3")
    assert led.series_rejected_counts()["a"] == 1


def test_ledger_zero_budget_never_rejects():
    led = TenantLedger(default_budget=0)
    for i in range(100):
        assert led.admit("anyone", f"s{i}")
    assert led.over_budget() == frozenset()
    assert led.series_rejected_counts() == {}


# -- TenantTallies ---------------------------------------------------------


def test_tallies_accumulate_and_conserve():
    epoch, total = TenantTallies(), TenantTallies()
    epoch.accepted["a"] = 10
    epoch.kept["a"] = 7
    epoch.rejected["a"] = 2
    epoch.dropped["a"] = 1
    assert epoch.conservation_gaps() == {"a": 0}
    epoch.accumulate_into(total)
    epoch.reset()
    assert epoch.accepted == {}
    assert total.accepted["a"] == 10
    merged = total.merged_with(epoch)
    assert merged["accepted"]["a"] == 10 and merged["dropped"]["a"] == 1


# -- worker end-to-end budget enforcement (Python path) --------------------


def test_worker_rejects_new_series_over_budget():
    w = _worker(default_budget=2)
    for i in range(5):
        w.process_metric(parse_metric(
            f"m{i}:1|c|#tenant:noisy".encode()))
    # existing series keep aggregating after the budget trips
    w.process_metric(parse_metric(b"m0:1|c|#tenant:noisy"))
    t = w.tenant_tallies
    assert t.accepted["noisy"] == 6
    assert t.kept["noisy"] == 3  # m0 twice + m1 once
    assert t.rejected["noisy"] == 3
    assert t.conservation_gaps() == {"noisy": 0}
    # rejection is TRUE rejection on the Python path: no row exists
    assert w.scalars.counters.used == 2
    assert w.scalars.counters.rejected_rows == 0


def test_worker_budget_spans_metric_types():
    w = _worker(default_budget=3)
    w.process_metric(parse_metric(b"h:1|ms|#tenant:x"))
    w.process_metric(parse_metric(b"s:a|s|#tenant:x"))
    w.process_metric(parse_metric(b"c:1|c|#tenant:x"))
    w.process_metric(parse_metric(b"g:1|g|#tenant:x"))  # 4th series
    t = w.tenant_tallies
    assert t.kept["x"] == 3 and t.rejected["x"] == 1
    assert w.tenancy.live("x") == 3


def test_untagged_samples_use_default_tenant():
    w = _worker(default_budget=1)
    w.process_metric(parse_metric(b"a:1|c"))
    w.process_metric(parse_metric(b"b:1|c"))
    t = w.tenant_tallies
    assert t.kept[DEFAULT_TENANT] == 1
    assert t.rejected[DEFAULT_TENANT] == 1


def test_lifetime_tallies_survive_pipelined_intervals():
    """Regression for the swap-time accounting: per-tenant tallies must
    accumulate into lifetime totals BEFORE the epoch reset, exactly like
    Worker.processed_total, so counts pin across >= 3 intervals."""
    w = _worker(default_budget=2)
    qs = device_quantiles([], AGGS)
    expect_acc = 0
    for interval in range(3):
        for i in range(4):  # 2 kept series + 2 rejected per interval
            w.process_metric(parse_metric(
                f"im{i}:1|c|#tenant:rt".encode()))
        expect_acc += 4
        life = w.tenant_lifetime()
        assert life["accepted"]["rt"] == expect_acc
        sw = w.swap(qs)
        # epoch tallies reset at swap; lifetime view is unchanged
        assert w.tenant_tallies.accepted == {}
        life = w.tenant_lifetime()
        assert life["accepted"]["rt"] == expect_acc
        assert life["kept"]["rt"] + life["rejected"]["rt"] == expect_acc
        w.extract_snapshot(sw, qs, 10.0)
    life = w.tenant_lifetime()
    assert life["accepted"]["rt"] == 12
    assert life["kept"]["rt"] == 6  # 2 series x 1 sample... per interval
    assert life["rejected"]["rt"] == 6
    gaps = {t: life["accepted"].get(t, 0) - life["kept"].get(t, 0)
            - life["rejected"].get(t, 0) - life["dropped"].get(t, 0)
            for t in life["accepted"]}
    assert gaps == {"rt": 0}


# -- rejected-row flush parity (object vs columnar) ------------------------


def _mark_rejected(pool, row):
    if hasattr(pool, "rows"):
        pool.rows[row].admitted = False
    pool.admit_codes[row] = 0
    pool.rejected_rows += 1


def test_rejected_rows_skip_both_emit_paths():
    """The native path adopts rows in C++ before the ledger runs, so a
    rejected series lands WITH a row (admitted=False) and both emit
    paths must skip it identically — including percentile families and
    the forward split."""
    w = DeviceWorker()
    for i in range(4):
        for v in (1.0, 2.0, 3.0):
            w.process_metric(parse_metric(f"h{i}:{v}|ms".encode()))
        w.process_metric(parse_metric(f"s{i}:x{i}|s".encode()))
        w.process_metric(parse_metric(f"c{i}:2|c".encode()))
        w.process_metric(parse_metric(f"g{i}:7|g".encode()))
    for i in range(4):  # mixed sets forward-only: add local ones to emit
        w.process_metric(parse_metric(
            f"sl{i}:y{i}|s|#veneurlocalonly".encode()))
    # simulate native-path rejection of one row per pool (sets: one
    # mixed row for the forward split, one local row for the emit path)
    _mark_rejected(w.directory.histo, 1)
    _mark_rejected(w.directory.sets, 2)
    _mark_rejected(w.directory.sets, 5)
    _mark_rejected(w.scalars.counters, 0)
    _mark_rejected(w.scalars.gauges, 3)
    qs = device_quantiles([0.5], AGGS)
    snap = w.flush(qs, interval_s=10.0)

    objs = generate_inter_metrics(snap, True, [0.5], AGGS, now=77)
    batch = generate_columnar(snap, True, [0.5], AGGS, now=77)
    mats = batch.materialize()

    def key(m):
        return (m.name, m.type, round(m.value, 9), tuple(m.tags))

    assert sorted(map(key, mats)) == sorted(map(key, objs))
    names = {m.name for m in objs}
    for gone in ("h1", "sl1", "c0", "g3"):
        assert not any(n.startswith(gone + ".") or n == gone
                       for n in names), gone
    for kept in ("h0", "sl0", "c1", "g0"):
        assert any(n.startswith(kept + ".") or n == kept
                   for n in names), kept
    # rejected rows must not ride the forward path either (they would
    # re-spend the tenant's budget on the global tier)
    fwd_names = {item[1].name for item in forwardable_rows(snap)}
    assert "h0" in fwd_names and "s0" in fwd_names
    assert "h1" not in fwd_names and "s2" not in fwd_names


# -- tenant-aware shed ordering --------------------------------------------


def test_shed_spill_keep_innocents_first():
    keep = shed_spill_keep([True, False, True, False, True], 3)
    assert keep.tolist() == [1, 3, 4]  # both innocents + newest abusive


def test_shed_spill_keep_no_abusive_matches_blanket_rule():
    flags = np.zeros(10, bool)
    keep = shed_spill_keep(flags, 4)
    assert keep.tolist() == [6, 7, 8, 9]  # exactly a[-budget:]


def test_shed_spill_keep_under_budget_keeps_all():
    assert shed_spill_keep([True, False], 5).tolist() == [0, 1]


def test_shed_spill_keep_all_abusive():
    keep = shed_spill_keep(np.ones(6, bool), 2)
    assert keep.tolist() == [4, 5]  # newest abusive fill the budget


def test_governor_tenant_shed_attribution():
    from veneur_tpu.health.governor import FlushDeadlineGovernor

    gov = FlushDeadlineGovernor(interval_s=10.0)
    assert gov.tenant_shed_counts() == {}
    gov.note_tenant_shed("evil", 7)
    gov.note_tenant_shed("evil", 3)
    gov.note_tenant_shed("other", 1)
    counts = gov.tenant_shed_counts()
    assert counts == {"evil": 10, "other": 1}
    counts["evil"] = 0  # the view is a copy, not the live dict
    assert gov.tenant_shed_counts()["evil"] == 10


# -- tenant-aware delivery spill eviction ----------------------------------


def test_spill_buffer_evicts_abusive_first():
    from veneur_tpu.sinks.delivery import SpillBuffer, _SpillEntry

    buf = SpillBuffer(max_bytes=1 << 20, max_payloads=3)
    mk = lambda t: _SpillEntry(lambda _: None, 10, None, t)  # noqa: E731
    order = ["good", "evil", "good", "evil"]
    evicted = []
    for t in order:
        evicted += buf.push(mk(t), abusive=frozenset({"evil"}))
    assert [e.tenant for e in evicted] == ["evil"]  # oldest abusive
    assert [e.tenant for e in buf.pop_all()] == ["good", "good", "evil"]
    # no abusive set: plain FIFO eviction, bitwise the old behavior
    buf2 = SpillBuffer(max_bytes=1 << 20, max_payloads=1)
    ev = buf2.push(mk("a"))
    assert ev == []
    ev = buf2.push(mk("b"))
    assert [e.tenant for e in ev] == ["a"]


# -- config ----------------------------------------------------------------


def test_config_tenant_validation():
    validate_config(Config())
    validate_config(Config(tenant_default_budget=100,
                           tenant_budgets={"vip": 0, "x": 5}))
    for bad in (dict(tenant_default_budget=-1),
                dict(tenant_tag_key=""),
                dict(tenant_budgets={"a": -2}),
                dict(tenant_sketch_depth=0),
                dict(tenant_sketch_depth=9),
                dict(tenant_sketch_width=1000),
                dict(tenant_sketch_width=32),
                dict(tenant_topk=0),
                dict(loadgen_tenant_count=0),
                dict(loadgen_tenant_abusive_frac=1.5),
                dict(loadgen_tenant_zipf_s=-1.0),
                dict(loadgen_tenant_churn_keys=-1)):
        with pytest.raises(ValueError):
            validate_config(Config(**bad))


def test_config_tenant_budgets_env_overlay():
    cfg = load_config(data={"tenant_default_budget": 10},
                      env={"VENEUR_TENANT_BUDGETS": "vip:0,noisy:25"})
    assert cfg.tenant_budgets == {"vip": 0, "noisy": 25}
    assert cfg.tenant_default_budget == 10


def test_server_installs_ledger_only_when_budgeted():
    from veneur_tpu.core.server import Server

    cfg = load_config(data={"interval": "10s"})
    s = Server(cfg)
    try:
        assert s.tenant_ledger is None
        assert s.workers[0].tenancy is None
    finally:
        s.shutdown()
    cfg2 = load_config(data={"interval": "10s",
                             "tenant_budgets": {"noisy": 4}})
    s2 = Server(cfg2)
    try:
        assert s2.tenant_ledger is not None
        assert s2.workers[0].tenancy is s2.tenant_ledger
        assert s2.workers[0].tenant_sketch is not None
    finally:
        s2.shutdown()


# -- the full isolation soak (slow-marked out of tier-1) --------------------


@pytest.mark.slow
def test_tenant_isolation_soak_quick_run(tmp_path):
    """End-to-end miniature soak run as a subprocess, the ci.sh lane's
    shape: every isolation check must hold and the artifact must carry
    the baseline-vs-abuse evidence."""
    import json
    import os
    import subprocess
    import sys

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", VENEUR_ARTIFACT_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(tools, "soak_tenant_isolation.py"),
         "--quick"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    art = json.load(open(tmp_path / "TENANT_ISOLATION_SOAK.json"))
    assert art["failures"] == []
    assert all(art["checks"].values())
    assert (art["baseline"]["innocent_hashes"]
            == art["abuse"]["innocent_hashes"])
    assert art["abuse"]["ledger_live"]["evil"] == art["budget"]
