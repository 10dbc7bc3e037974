#!/bin/sh
# One-command local CI: native build from source (stale-.so check via the
# stamp test), full suite on the virtual 8-device CPU mesh, multi-chip
# dryrun. Mirrors .github/workflows/ci.yml; the reference's analog is
# `go test -race ./...` (.circleci/config.yml:104-112).
set -e
cd "$(dirname "$0")/.."

echo "== native build =="
make -C native clean all

echo "== race-detection gate (ThreadSanitizer soak) =="
make -C native tsan

# Two fuzz modes (VERDICT r4 item 6 — a 10s fixed-seed pass is a
# regression tripwire, not a fuzzer):
#  - CI gate: fixed seed 7 (deterministic tripwire for the known repros)
#    PLUS a fresh-seed pass so every CI run also hunts, recorded in the
#    standing tally artifact FUZZ_TALLY.json.
#  - Long-run: VENEUR_FUZZ_LONG=1 tools/ci.sh (or run directly:
#    tools/fuzz_differential.py --seconds 30 --rounds 20 --tally
#    FUZZ_TALLY.json) — ≥30 min fresh-seed campaign; commit the tally.
echo "== differential codec fuzz (fixed-seed tripwire + fresh-seed hunt) =="
JAX_PLATFORMS=cpu \
  python tools/fuzz_differential.py --seconds 10 --seed 7
JAX_PLATFORMS=cpu \
  python tools/fuzz_differential.py --seconds 10 --tally FUZZ_TALLY.json
if [ -n "${VENEUR_FUZZ_LONG:-}" ]; then
  echo "== long-run fuzz campaign (~40 min) =="
  JAX_PLATFORMS=cpu \
    python tools/fuzz_differential.py --seconds 30 --rounds 20 \
      --tally FUZZ_TALLY.json
fi

# Tier-1 lane: the flush-deadline governor contract and the O(samples)
# transfer-diet regression pin (tests/test_health_ledger.py asserts the
# staged upload is ~ samples*4 + counts*4 bytes independent of depth —
# a silent dense-upload regression is a 268 MB/flush mistake at 1M
# series that no value-equality test can see). Runs first and alone so
# a transfer or watchdog regression is named by its lane, not buried in
# the full-suite output.
echo "== tier-1 health lane (governor + transfer ledger) =="
python -m pytest tests/test_health_governor.py tests/test_health_ledger.py \
  -q -m 'not slow'

# Emit-parity lane: the native emit serializers (native/emit.cpp) must
# be byte-identical to the sinks' Python formatters (statsd lines,
# exposition text, forward lines) and JSON-value-identical for the
# datadog/signalfx bodies, deflate included. Runs twice: with the .so
# live (parity pins) and with it masked (fallback negotiation pins) —
# a drifted serializer or a broken fallback is named by this lane.
echo "== emit parity lane (native on + native masked) =="
JAX_PLATFORMS=cpu \
  python -m pytest tests/test_emit_parity.py -q -m 'not slow'
JAX_PLATFORMS=cpu VENEUR_EMIT_NATIVE=0 \
  python -m pytest tests/test_emit_parity.py -q -m 'not slow'

# Micro-fold parity lane: the always-hot flush path (ops/microfold.py)
# must be BIT-identical to the once-per-interval batch fold for every
# metric class, cost identical H2D bytes, and hold the epoch-swap fence.
# Runs twice, mirroring the emit lane: default (micro-folds on) and with
# the escape hatch thrown (VENEUR_MICRO_FOLD=0) — a parity drift is
# named by the first pass, a broken disable path by the second.
echo "== micro-fold parity lane (always-hot on + escape hatch) =="
JAX_PLATFORMS=cpu \
  python -m pytest tests/test_microfold.py -q -m 'not slow'
JAX_PLATFORMS=cpu VENEUR_MICRO_FOLD=0 \
  python -m pytest tests/test_microfold.py -q -m 'not slow'

# Series-sharding parity lane: the device-sharded series axis
# (ops/series_shard.py) must be BIT-identical to the single-device
# path for every metric class, spills and imports included, with
# micro-folds on and off. Runs twice, mirroring the micro-fold lane:
# default (tests/conftest.py forces an 8-device virtual CPU platform,
# so the sharded golden matrix executes for real; XLA_FLAGS here is
# belt-and-braces for a stripped environment) and with the escape
# hatch thrown (VENEUR_SERIES_SHARDS=0) — a parity drift is named by
# the first pass, a broken disable path by the second.
echo "== series-sharding parity lane (sharded on + escape hatch) =="
JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -m pytest tests/test_series_shard.py -q -m 'not slow'
JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  VENEUR_SERIES_SHARDS=0 \
  python -m pytest tests/test_series_shard.py -q -m 'not slow'

# Live-query lane: the read path (veneur_tpu/query/) must answer from
# exactly one committed epoch and agree with the flush bit-for-bit at
# the fence — tests/test_query.py pins query==flush parity (unsharded
# AND sharded), snapshot isolation under concurrent ingest, the
# heavy-hitter fenced-read no-mutation regression, and both serving
# fronts. The bench smoke then validates the QUERY_BENCH artifact
# schema and the sub-second latency claim on live cells with
# concurrent ingest. (The query differential fuzz target rides the
# codec fuzz lane above — it is in the default target set.)
echo "== live-query lane (epoch-fence parity + bench smoke) =="
JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -m pytest tests/test_query.py -q -m 'not slow'
timeout -k 10 600 python tools/bench_query.py --smoke \
  --out "${TMPDIR:-/tmp}/QUERY_BENCH_SMOKE.json"
python - <<'PYGATE'
import json, os
with open(os.path.join(os.environ.get("TMPDIR", "/tmp"),
                       "QUERY_BENCH_SMOKE.json")) as f:
    a = json.load(f)
cells = {(c["series"], c["shards"], c["concurrent_ingest"])
         for c in a["grid"]}
assert (128, 0, True) in cells and (128, 4, True) in cells, \
    f"smoke grid must cover unsharded+sharded under ingest: {cells}"
for c in a["grid"]:
    for op, s in c["ops"].items():
        assert 0 < s["p50_ms"] <= s["p99_ms"] < 1000, \
            f"sub-second claim broken: {op} {s} in cell {c}"
assert a["sustained_ab"]["ratio"] > 0.5, \
    f"ingest rate under query load: {a['sustained_ab']}"
print("query bench artifact OK")
PYGATE

# Delivery chaos lane: a server flushing into HTTP sinks whose
# openers inject seeded faults (utils/faults.py) — refusals, 5xx, slow
# responses, mid-body resets, payload rejections, and a deterministic
# outage window. Gates the delivery layer's three contracts
# (sinks/delivery.py): exact payload conservation, flush deadlines held
# under retry pressure, and a full breaker open→half-open→closed cycle.
# Artifact: FAULT_SOAK.json.
echo "== delivery chaos lane (seeded fault soak) =="
timeout -k 10 120 env JAX_PLATFORMS=cpu \
  python tools/soak_faults.py --quick

# Ring-churn chaos lane: local → proxy → 3 globals over real gRPC while
# a scripted schedule kills/restarts a member (breaker cycle on the
# revival), reshards the ring twice through the discovery-refresh path,
# and flaps discovery — under seeded transient forward faults. Gates
# the live-membership tier's contracts (distributed/proxy.py): exact
# tier-wide conservation, zero drops/sheds, spill fully settled, and a
# full breaker open→half-open→closed cycle — and, with seeded
# duplicate injection active, the exactly-once contract:
# duplicates == 0 with the dedup window provably engaged. Artifact:
# RING_CHURN_SOAK.json (committed copy is the full 36-interval run; the
# lane redirects its miniature artifact to /tmp so quick never
# clobbers it).
echo "== ring-churn chaos lane (seeded membership soak) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu \
  VENEUR_ARTIFACT_DIR="${TMPDIR:-/tmp}" \
  python tools/soak_ring_churn.py --quick
# Hard duplicates==0 gate, independent of the soak's own pass bar: the
# artifact's counter/histogram excess over exact expected totals must
# be zero AND the dedup window must have absorbed at least one injected
# replay (a zero that never faced a duplicate proves nothing).
python - "${TMPDIR:-/tmp}/RING_CHURN_SOAK.json" <<'PYGATE'
import json, sys
a = json.load(open(sys.argv[1]))
assert a["duplicates_observed"] == 0, \
    f"duplicates observed: {a['duplicates_observed']}"
assert a["dedup_stats"]["hits"] >= 1, "dedup window never engaged"
print(f"duplicates==0 gate: OK (hits={a['dedup_stats']['hits']}, "
      f"deduped={a['dedup_stats']['metrics_deduped']} metrics)")
PYGATE

# Autoscale chaos lane: the elastic tier end to end — a watched
# membership file (members + standby pool), the HealthGate probing and
# quarantining on the refresh path, and the ElasticController scaling
# on the tier's own pressure signals. The scripted run doubles the
# offered load against capacity-throttled real import servers (scale
# 2 -> 4 under hysteresis + cooldown), halves it back (graceful-drain
# scale-in to 2, retire only when idle), then kills a member cold
# (breaker-streak quarantine -> ring 1 -> probed re-admission). Gates:
# exact conservation and duplicates == 0 through every reshard, the
# calm phase never scales, scale-out AND quarantine actually happened.
# Artifact: AUTOSCALE_SOAK.json (committed copy is the full run; the
# lane redirects its miniature artifact to /tmp).
echo "== autoscale chaos lane (elastic tier soak) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  VENEUR_ARTIFACT_DIR="${TMPDIR:-/tmp}" \
  python tools/soak_autoscale.py --quick
# Hard gates, independent of the soak's own pass bar: conservation
# must be exact with zero duplicate excess, and the elastic story must
# have actually run (reached 4 members, quarantined the sick one).
python - "${TMPDIR:-/tmp}/AUTOSCALE_SOAK.json" <<'PYGATE'
import json, sys
a = json.load(open(sys.argv[1]))
assert a["duplicates_observed"] == 0, \
    f"duplicates observed: {a['duplicates_observed']}"
assert a["counter_total_observed"] == a["counter_total_expected"], \
    "counter conservation not exact"
assert a["histo_count_observed"] == a["histo_count_expected"], \
    "histogram conservation not exact"
assert a["max_ring_members"] == 4, "tier never scaled out to 4"
assert a["gate"]["quarantined_total"] >= 1, "sick member never quarantined"
print(f"autoscale gate: OK (max_ring={a['max_ring_members']}, "
      f"quarantined={a['gate']['quarantined_total']}, duplicates=0)")
PYGATE

# Tenant-isolation lane: two seeded runs sharing bit-identical innocent
# traffic — baseline vs an abusive tenant exploding series cardinality
# against a per-tenant budget (core/tenancy.py). Gates the QoS layer's
# contracts: innocents emit bit-for-bit what the baseline emits, the
# abuser is capped at exactly its budget (reject-new, never evict-live),
# per-tenant conservation is exact, and the heavy-hitter sketch names
# the abuser's hot key. Artifact: TENANT_ISOLATION_SOAK.json (committed
# copy is the full 12-interval run; the lane redirects its miniature
# artifact to /tmp so quick never clobbers it).
echo "== tenant-isolation lane (seeded adversarial QoS soak) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu \
  VENEUR_ARTIFACT_DIR="${TMPDIR:-/tmp}" \
  python tools/soak_tenant_isolation.py --quick

# Crash-recovery lane: a supervised real server journaling its delivery
# spill (utils/journal.py) is SIGKILLed at seeded adversarial points
# under load — mid-outage, before a recovered backlog delivers
# (double-restart replay), after a scripted partial drain — restarted,
# and finally SIGTERMed. Gates the durability contracts: every kill's
# read-only journal census equals the next incarnation's replay count,
# cross-incarnation conservation is exact against the receiver's own
# 2xx ledger, zero drops/evictions, and the graceful drain exits with
# an empty spill and an empty journal. Artifact: CRASH_RECOVERY_SOAK
# .json (committed copy is the full run; the lane redirects its
# miniature artifact to /tmp so quick never clobbers it).
echo "== crash-recovery lane (kill-9 durability soak) =="
timeout -k 10 420 env JAX_PLATFORMS=cpu \
  VENEUR_ARTIFACT_DIR="${TMPDIR:-/tmp}" \
  python tools/soak_crash_recovery.py --quick
# Hard duplicates==0 gate: every successful sink POST was replayed
# (p_duplicate=1.0) under its journal-minted Idempotency-Key, so the
# receiver must have absorbed a nonzero replay count while its 2xx
# ledger stays exactly equal to the delivered sum (zero double-counts).
python - "${TMPDIR:-/tmp}/CRASH_RECOVERY_SOAK.json" <<'PYGATE'
import json, sys
a = json.load(open(sys.argv[1]))["dedup"]
assert a["receiver_double_counts"] == 0, f"double counts: {a}"
assert a["duplicates_injected"] >= 1, "duplicate injection never engaged"
assert a["receiver_replays_absorbed"] >= 1, "receiver absorbed no replays"
print(f"duplicates==0 gate: OK ({a['duplicates_injected']} injected, "
      f"{a['receiver_replays_absorbed']} absorbed)")
PYGATE

# Device-fault lane: the guarded TPU execution domain (ops/device_guard
# .py + ops/host_engine.py) — fault classification taxonomy, breaker
# streak, host-mirror failover bit-identical for every metric class
# (sharded and unsharded, micro-folds on and off), probe re-admission,
# and the HBM grow valve. The guard-mechanics suite runs with the guard
# on (its tests inject seeded device faults); the escape-hatch pass
# then re-runs the micro-fold parity suite under VENEUR_DEVICE_GUARD=0
# — a failover drift is named by the first pass, a hatch that perturbs
# the healthy flush path by the second. The seeded chaos soak drives
# scripted fault shapes (transient OOM burst, hard outage → quarantine
# → probe readmission, mid-micro-fold, mid-extract) against a clean
# twin. (The device_fallback differential fuzz target rides the codec
# fuzz lane at the top — it is in the default target set.) Artifact:
# DEVICE_FAULT_SOAK.json (committed copy is the full run; the lane
# redirects its miniature artifact to /tmp so quick never clobbers it).
echo "== device-fault lane (guarded execution + escape hatch + chaos) =="
JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -m pytest tests/test_device_guard.py -q -m 'not slow'
JAX_PLATFORMS=cpu VENEUR_DEVICE_GUARD=0 \
  python -m pytest tests/test_microfold.py -q -m 'not slow'
timeout -k 10 240 env JAX_PLATFORMS=cpu \
  VENEUR_ARTIFACT_DIR="${TMPDIR:-/tmp}" \
  python tools/soak_device_faults.py --quick
# Hard gate on the committed full-run artifact: parity bitwise, exact
# conservation, the complete breaker cycle, healthy overhead <= 1%.
python - <<'PYGATE'
import json
a = json.load(open("DEVICE_FAULT_SOAK.json"))
assert a["ok"] and not a["failures"], a["failures"]
assert a["parity_bitwise_all"], "host failover drifted from device path"
assert a["conservation_exact_all"], "a faulted flush lost samples"
cyc = a["scenarios"]["hard_outage_readmission"]["breaker_cycle"]
assert all(cyc.values()), f"incomplete breaker cycle: {cyc}"
ab = a["healthy_ab"]
assert ab["ok"] and ab["overhead_pct"] <= ab["rel_limit_pct"], ab
print(f"device-fault gate: OK (breaker cycle complete, parity bitwise, "
      f"healthy overhead {ab['overhead_pct']}% <= {ab['rel_limit_pct']}%)")
PYGATE

# Streaming congestion lane: the adaptive ack window (AIMD controller,
# distributed/rpc.py) under scripted busy-ack storms and ack-delay
# windows (utils/faults.py FaultyStreamSink) — collapse to the floor,
# recovery after the storm, duplicates == 0 across a reconnect landing
# mid-collapse, and the native VSF1/VDE1 codec parity matrix. Runs
# twice, mirroring the micro-fold lane: default (adaptive on) and with
# the escape hatch thrown (VENEUR_STREAM_ADAPTIVE=0, which must
# reproduce the PR 15 fixed-window wire shape) — a controller
# regression is named by the first pass, a broken hatch by the second.
echo "== streaming congestion lane (adaptive on + escape hatch) =="
JAX_PLATFORMS=cpu \
  python -m pytest tests/test_stream_forward.py -q -m 'not slow'
JAX_PLATFORMS=cpu VENEUR_STREAM_ADAPTIVE=0 \
  python -m pytest tests/test_stream_forward.py -q -m 'not slow'

# Forward-codec parity lane: the native frame/ack/dedup-envelope codec
# (native/forward_codec.cpp) must be byte-identical to the pinned
# Python encoders and reject-identical on corrupt input. The native-on
# pass rides the congestion lane above; this pass masks the .so so a
# broken fallback negotiation is named here. (The forward_codec
# differential fuzz target rides the codec fuzz lane at the top — it
# is in the default target set.)
echo "== forward codec parity lane (native masked) =="
JAX_PLATFORMS=cpu VENEUR_CODEC_NATIVE=0 \
  python -m pytest tests/test_stream_forward.py -q -m 'not slow' \
    -k 'codec or parity'

# Ring-sustained smoke: the whole-ring harness (paced senders → proxy
# → 3 globals over real gRPC, tools/bench_ring_sustained.py) at a
# fixed offered rate on the streaming forward path — adaptive window
# by default, plus a fixed-window (--no-adaptive, the PR 15 shape)
# A/B cell at the same rate. Gates the transport end to end: frames
# pipelined under the ack window, server-side coalescing engaged,
# exact ring conservation (ingested == proxied + drops at quiescence)
# and duplicates == 0 in BOTH cells at a rate (15k metrics/s) well
# under the rig's measured A/B cliff so host noise never flakes the
# lane, and the adaptive cell at least matching the fixed cell.
# Artifacts go to /tmp — the committed RING_SUSTAINED.json is the
# full --ab --ab-axis stream-window search, gated below.
echo "== ring-sustained smoke (adaptive + fixed-window A/B) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu \
  VENEUR_ARTIFACT_DIR="${TMPDIR:-/tmp}" \
  python tools/bench_ring_sustained.py --smoke --mode streaming \
    --rate 15000 --out "${TMPDIR:-/tmp}/RING_SUSTAINED_SMOKE.json"
timeout -k 10 240 env JAX_PLATFORMS=cpu \
  VENEUR_ARTIFACT_DIR="${TMPDIR:-/tmp}" \
  python tools/bench_ring_sustained.py --smoke --mode streaming \
    --rate 15000 --no-adaptive \
    --out "${TMPDIR:-/tmp}/RING_SUSTAINED_SMOKE_FIXED.json"
python - "${TMPDIR:-/tmp}/RING_SUSTAINED_SMOKE.json" \
         "${TMPDIR:-/tmp}/RING_SUSTAINED_SMOKE_FIXED.json" <<'PYGATE'
import json, sys
ad = json.load(open(sys.argv[1]))
fx = json.load(open(sys.argv[2]))
assert ad["adaptive"] and not fx["adaptive"], (ad["adaptive"],
                                               fx["adaptive"])
for cell in (ad, fx):
    w = "adaptive" if cell["adaptive"] else "fixed"
    assert cell["passed"], f"{w} smoke cell failed"
    assert cell["duplicates_observed"] == 0, f"{w}: duplicates"
    assert cell["conservation_exact"], f"{w}: conservation broken"
# both cells attain the same paced offered rate; the adaptive window
# must not cost throughput (0.95 absorbs scheduler jitter on 1 core)
assert ad["value"] >= 0.95 * fx["value"], \
    f"adaptive smoke rate {ad['value']} << fixed {fx['value']}"
assert ad["window_current"] >= 1, "adaptive window gauge missing"
print(f"stream-window smoke A/B: OK (adaptive {ad['value']:.0f}/s "
      f"window={ad['window_current']} vs fixed {fx['value']:.0f}/s, "
      f"dups 0/0)")
PYGATE

# Sharded-tier smoke: the same ring with spread senders over M=1 and
# M=2 proxies. Gates the proxy-tier spreading path end to end: exact
# conservation and duplicates == 0 through the SpreadForwarder, and
# the 2-proxy fleet's capacity (sum of per-proxy metrics per proxy
# CPU-second) at least that of 1 proxy — the co-scheduled 1-core rig
# can't scale wall-clock throughput, so the capacity metric is the
# honest scaling signal (see RING_PROXY_SCALING.json for the full
# M=1/2/4 cells + chaos run).
echo "== sharded proxy tier smoke (spread senders, M=1 vs M=2) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu \
  VENEUR_ARTIFACT_DIR="${TMPDIR:-/tmp}" \
  python tools/bench_ring_sustained.py --smoke --mode streaming \
    --rate 15000 --spread --proxies 1 \
    --out "${TMPDIR:-/tmp}/RING_SPREAD_SMOKE_1.json"
timeout -k 10 240 env JAX_PLATFORMS=cpu \
  VENEUR_ARTIFACT_DIR="${TMPDIR:-/tmp}" \
  python tools/bench_ring_sustained.py --smoke --mode streaming \
    --rate 15000 --proxies 2 \
    --out "${TMPDIR:-/tmp}/RING_SPREAD_SMOKE_2.json"
python - "${TMPDIR:-/tmp}/RING_SPREAD_SMOKE_1.json" \
         "${TMPDIR:-/tmp}/RING_SPREAD_SMOKE_2.json" <<'PYGATE'
import json, sys
one = json.load(open(sys.argv[1]))
two = json.load(open(sys.argv[2]))
for cell in (one, two):
    m = cell["proxies"]
    assert cell["passed"], f"{m}-proxy spread smoke failed"
    assert cell["duplicates_observed"] == 0, f"{m}-proxy: duplicates"
    assert cell["conservation_exact"], f"{m}-proxy: conservation broken"
    assert cell["spread_senders"], f"{m}-proxy: spread path not engaged"
cap1 = one["proxy_tier_capacity_metrics_per_s"]
cap2 = two["proxy_tier_capacity_metrics_per_s"]
assert cap2 >= cap1, f"2-proxy capacity {cap2} < 1-proxy {cap1}"
# co-scheduled guard: spreading must not cost wall-clock throughput
assert two["value"] >= 0.85 * one["value"], \
    f"2-proxy co-scheduled rate {two['value']} << 1-proxy {one['value']}"
print(f"sharded-tier smoke: OK (capacity {cap1:.0f} -> {cap2:.0f} "
      f"metrics/cpu-s, dups 0/0, conservation exact)")
PYGATE

# Committed-artifact gates: the repo-root soak/bench artifacts are the
# full runs' evidence — re-parse them so a regeneration that silently
# lost the exactly-once or streaming-wins property fails CI even if
# nobody reran the quick lanes' miniature twins.
python - <<'PYGATE'
import json
a = json.load(open("RING_CHURN_SOAK.json"))
assert a["duplicates_observed"] == 0, \
    f"committed churn soak: duplicates {a['duplicates_observed']}"
assert a["checks"]["streaming_engaged"], \
    "committed churn soak: streaming never engaged"
b = json.load(open("AUTOSCALE_SOAK.json"))
assert b["duplicates_observed"] == 0, \
    f"committed autoscale soak: duplicates {b['duplicates_observed']}"
assert b["checks"]["streaming_engaged"], \
    "committed autoscale soak: streaming never engaged"
r = json.load(open("RING_SUSTAINED.json"))
assert not r["failures"], f"committed ring A/B failed: {r['failures']}"
assert r["checks"]["streaming_ge_unary"], \
    "committed ring A/B: streaming slower than unary"
for mode, m in r["modes"].items():
    assert m["duplicates_observed"] == 0, \
        f"committed ring A/B: {mode} duplicates"
assert "stream_window_ab" in r, \
    "committed ring A/B missing the stream-window axis (regenerate with" \
    " --ab --ab-axis stream-window)"
assert r["checks"]["adaptive_ge_fixed_saturated"], \
    "committed ring A/B: adaptive window slower than fixed at saturation"
assert r["checks"]["adaptive_ge_fixed_calm"], \
    "committed ring A/B: adaptive window slower than fixed at the calm point"
s = json.load(open("RING_PROXY_SCALING.json"))
assert not s["failures"], f"committed proxy scaling failed: {s['failures']}"
for m, c in s["cells"].items():
    assert c["duplicates_observed"] == 0, f"scaling cell {m}: duplicates"
    assert c["conservation_exact"], f"scaling cell {m}: conservation"
assert s["checks"]["capacity_scaling_near_linear"], \
    "committed proxy scaling: capacity not near-linear"
ch = s["chaos"]
assert ch and not ch["failures"], \
    f"committed proxy scaling chaos cell: {ch and ch['failures']}"
print("committed-artifact gates: OK (churn dup=0, autoscale dup=0, "
      f"ring streaming {r['sustained_ring_metrics_per_s']}/s >= "
      f"unary {r['modes']['unary']['sustained_ring_metrics_per_s']}/s, "
      f"proxy capacity x{max(s['cells'])}/x{min(s['cells'])} "
      f"{[v for k, v in s['capacity_scaling'].items() if k.startswith('x')][0]})")
PYGATE

# Sustained-rate floor: the loadgen harness drives a live server's UDP
# socket at a fixed offered rate for 5 flush intervals and fails on
# loss or broken flush cadence. 50k lines/s is deliberately well under
# half the 1-core dev rig's measured rate (110k confirmed,
# SUSTAINED_PIPELINE.json, platform cpu) so host noise doesn't flake
# the lane, while a real regression (parse slowdown, flush stall, shed
# storm) still trips it; min-cadence 0.7 tolerates one straggler flush
# in 5 (XLA-CPU occasionally recompiles mid-run on this rig), two fail.
# --keys 2000 (~10k series) keeps per-flush XLA work well inside the
# 2s interval on one core — the default 10k-key workload's ~50k series
# cost 2-4s per flush here, which gates the rig's flush latency, not
# the packet path this lane is for. Bounded: warmup + 5×2s intervals
# under a hard cap.
echo "== sustained-rate smoke (loadgen floor gate) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  python tools/bench_sustained.py --smoke --rate 50000 --intervals 5 \
    --interval 2s --min-cadence 0.7 --keys 2000

# Span-parity lane: the columnar SSF pipeline (veneur_tpu/spans/) must
# derive metrics BIT-identical to the per-span Python reference for
# every metric class, with series shards and micro-folds on and off.
# Runs twice, mirroring the micro-fold lane: default (columnar on) and
# with the escape hatch thrown (VENEUR_SPAN_COLUMNAR=0) — a derivation
# drift is named by the first pass, a broken per-span fallback (the
# SpanWorker lanes the columnar path replaced as default) by the
# second, which also re-runs the SSF suite on the legacy path.
echo "== span-parity lane (columnar on + escape hatch) =="
JAX_PLATFORMS=cpu \
  python -m pytest tests/test_spans_columnar.py -q -m 'not slow'
JAX_PLATFORMS=cpu VENEUR_SPAN_COLUMNAR=0 \
  python -m pytest tests/test_spans_columnar.py tests/test_ssf.py \
    -q -m 'not slow'

# Reader-shard parity lane: the shared-nothing multi-reader ingest
# (core/worker.attach_reader_shards) must produce the same keyed flush
# output as the legacy digest-routed path for every metric class, with
# exact conservation and per-reader attribution. Runs the server /
# ingest / micro-fold suites twice, mirroring the micro-fold lane:
# once with the env hatch forcing reader_shards=4 (every qualifying
# server in the suites boots sharded; non-qualifying configs degrade
# to legacy by the resolve gates) and once pinned legacy
# (VENEUR_READER_SHARDS=0) — a shard-mode drift is named by the first
# pass, a broken escape hatch by the second.
echo "== reader-shard parity lane (sharded num_readers=4 + legacy) =="
JAX_PLATFORMS=cpu VENEUR_READER_SHARDS=4 \
  python -m pytest tests/test_reader_shards.py tests/test_server.py \
    tests/test_native.py tests/test_microfold.py -q -m 'not slow'
JAX_PLATFORMS=cpu VENEUR_READER_SHARDS=0 \
  python -m pytest tests/test_reader_shards.py tests/test_server.py \
    tests/test_native.py tests/test_microfold.py -q -m 'not slow'

# SSF sustained-rate floor: mixed statsd+SSF traffic (10% spans) with
# the columnar pipeline deriving span metrics on the flush path; gates
# the SSF packet path (zero loss), spans actually arriving, and exact
# span conservation (received == derived + dropped + pending) at a
# rate well under the rig's measured headroom. The cadence floor is
# deliberately loose here: span-derived series perturb XLA shapes for
# the first few intervals on the 1-core rig, so late-tick noise is
# expected — the statsd lane above owns the strict cadence gate.
# Artifact stays in /tmp — the committed SPAN_SUSTAINED.json is the
# full search run.
echo "== SSF sustained-rate smoke (span workload + conservation gate) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  python tools/bench_sustained.py --smoke --workload ssf --rate 20000 \
    --intervals 4 --interval 2s --min-cadence 0.25 --keys 1000 \
    --out "${TMPDIR:-/tmp}/SPAN_SUSTAINED_SMOKE.json"

# Archive round-trip lane: the flush archive (veneur_tpu/archive/) must
# capture a real factory-wired server's flush bit-identically (raw
# IEEE-754 value planes in VMB1 frames), replay it through the import
# path into a fresh server bit-identically, and absorb a SECOND dedup
# replay without double-counting — with the sink's sample ledger and
# the delivery manager's payload ledger exact. The VMB1 corruption
# matrix (torn tails, bit flips, truncated sections, unknown kinds)
# and the SigV4 blob-egress vectors run first so a codec or signer
# drift is named by its test, not by the soak. The soak's miniature
# artifact goes to /tmp — the committed ARCHIVE_REPLAY_SOAK.json is
# the full-workload run.
echo "== archive round-trip lane (capture -> replay -> dedup) =="
JAX_PLATFORMS=cpu \
  python -m pytest tests/test_archive.py tests/test_plugins.py \
    -q -m 'not slow'
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  VENEUR_ARTIFACT_DIR="${TMPDIR:-/tmp}" \
  python tools/soak_archive_replay.py --quick
python - <<PYGATE
import json, os
p = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                 "ARCHIVE_REPLAY_SOAK.json")
d = json.load(open(p))
bi = d["bit_identical"]
assert bi["archive"], "archived frames drifted from the flush"
assert bi["replay"], "replayed flush drifted from the original"
assert bi["dedup_twice"], "double dedup-replay double-counted"
assert d["conservation"]["exact"], d["conservation"]
assert d["ok"] and not d["failures"], d["failures"]
print("archive round-trip gate: bit-identical x3, conservation exact")
PYGATE

echo "== test suite =="
python -m pytest tests/ -q

echo "== multi-chip dryrun (8 virtual devices) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "CI GREEN"
