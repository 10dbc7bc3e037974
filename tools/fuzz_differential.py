"""Differential fuzz harness: every hand-written codec vs its oracle.

Four targets, each bounded-time, each a property the round-4 campaign
used to find real bugs (3 fixed: SSF unknown-enum rejection in the
Python decoder; 32-bit tag-bound and proto3-UTF-8 acceptance gaps in
the C++ MetricBatch decoder):

  dogstatsd  C++ parser vs Python parser — accept/reject parity per
             LINE (newline-free inputs; the datagram API splits lines)
  ssf        C++ decoder accepts => Python decodes (rc 1/-1 => parse)
  metricpb   C++ wire decoder accepts => generated protobuf parses,
             and metric counts agree
  gob        round-trip identity + clean bounded-time GobError on
             mutated bytes (untrusted peer input on /import)

Later rounds added ssf_stream (framed-stream recoverability), loadgen
(generated traffic must parse in both codecs), reader_commit
(shared-nothing per-reader owned contexts vs one legacy context over
the same per-reader streams — keyed fold parity), query (live-query
device kernels vs independent numpy references on randomized pools),
and forward_codec (native VSF1/VDE1 stream-frame codec vs the pinned
Python reference: byte-identical encodes, round-trip decodes, same
typed verdict on corrupted blobs).

Usage: python tools/fuzz_differential.py [--seconds 30] [--seed N]
Exit 0 = no divergence; 1 = divergence (repro printed with seed).
"""

from __future__ import annotations

import argparse
import os
import random
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

import numpy as np


def fuzz_dogstatsd(rng, t_end) -> int:
    from veneur_tpu import native as native_mod
    from veneur_tpu.protocol.dogstatsd import parse_metric, ParseError

    types = [b"c", b"g", b"ms", b"h", b"d", b"s", b"zz", b"", b"cg", b"mss"]
    names = [b"a.b.c", b"x", b"", b"with space", b"uni\xc3\xa9", b"a" * 64,
             b"a:b"]
    values = [b"1", b"2.5", b"-3", b"+4", b"1e3", b"nan", b"inf", b"bar",
              b"", b"0x1f", b"1_0", b"9" * 30, b"1.2.3", b" 1"]
    rates = [b"", b"|@0.5", b"|@1", b"|@0", b"|@2", b"|@x", b"|@-1"]
    tagsets = [b"", b"|#a:1", b"|#b:2,a:1", b"|#veneurlocalonly", b"|#",
               b"|#a:1|#b:2", b"|#" + b"t" * 200, b"|#a:1,a:1", b"|#,"]
    ni = native_mod.NativeIngest()
    n = 0
    while time.time() < t_end:
        for _ in range(2000):
            line = (rng.choice(names) + b":" + rng.choice(values) + b"|"
                    + rng.choice(types) + rng.choice(rates)
                    + rng.choice(tagsets))
            if rng.random() < 0.4 and line:
                pos = rng.randrange(len(line))
                b = rng.randrange(0, 256)  # NULs included
                if b == 0x0A:  # newline splits datagrams; per-line scope
                    b = 0x0B
                line = line[:pos] + bytes([b]) + line[pos + 1:]
            try:
                parse_metric(line)
                py_ok = True
            except ParseError:
                py_ok = False
            before = ni.processed
            ni.ingest(line)
            if (ni.processed > before) != py_ok:
                print(f"dogstatsd DIVERGE py={py_ok}: {line!r}")
                return -1
            n += 1
    return n


def fuzz_ssf(rng, t_end) -> int:
    from test_native import _make_span_bytes
    from veneur_tpu import native as native_mod
    from veneur_tpu.protocol import ssf_wire

    seeds = []
    for i in range(60):
        metrics = [{"name": f"m{j}", "value": j + 0.5, "sample_rate": 1.0,
                    "message": "msg" * j, "unit": "ms",
                    "tags": {f"t{k}": "v" * k for k in range(j)}}
                   for j in range(i % 5)]
        seeds.append(_make_span_bytes(
            trace_id=rng.randrange(0, 1 << 63), id=rng.randrange(0, 1 << 63),
            start_timestamp=rng.randrange(0, 1 << 63),
            end_timestamp=rng.randrange(0, 1 << 63),
            service=f"s{i}", name=f"op{i}", indicator=bool(i % 2),
            metrics=metrics, tags={f"k{j}": f"v{j}" for j in range(i % 6)}))
    ni = native_mod.NativeIngest()
    n = 0
    while time.time() < t_end:
        for _ in range(2000):
            base = bytearray(rng.choice(seeds))
            roll = rng.random()
            if roll < 0.4 and base:
                for _ in range(rng.randrange(1, 8)):
                    base[rng.randrange(len(base))] = rng.randrange(256)
            elif roll < 0.55:
                del base[rng.randrange(max(1, len(base))):]
            elif roll < 0.65:
                base = bytearray(rng.randbytes(rng.randrange(0, 300)))
            payload = bytes(base)
            try:
                ssf_wire.parse_ssf(payload)
                py_ok = True
            except Exception:
                py_ok = False
            rc = ni.ingest_ssf(payload, b"ind.t", b"obj.t")
            if rc not in (-1, 0, 1) or (rc in (1, -1) and not py_ok):
                print(f"ssf DIVERGE rc={rc} py={py_ok}: {payload!r}")
                return -1
            n += 1
    return n


def fuzz_metricpb(rng, t_end) -> int:
    from veneur_tpu import native as native_mod
    from veneur_tpu.gen import veneur_tpu_pb2 as mpb

    def make_batch(i):
        b = mpb.MetricBatch()
        for j in range(i % 5):
            m = b.metrics.add()
            m.name = f"fz.m{j}" * (1 + j % 3)
            m.tags.extend([f"t{k}:v{k}" for k in range(j % 4)])
            m.kind = [mpb.KIND_COUNTER, mpb.KIND_GAUGE, mpb.KIND_HISTOGRAM,
                      mpb.KIND_SET, mpb.KIND_TIMER][j % 5]
            m.scope = [mpb.SCOPE_MIXED, mpb.SCOPE_LOCAL,
                       mpb.SCOPE_GLOBAL][j % 3]
            if m.kind == mpb.KIND_COUNTER:
                m.counter.value = int(j * 3 - 2)
            elif m.kind == mpb.KIND_GAUGE:
                m.gauge.value = float(j) * 1.5 - 2
            elif m.kind in (mpb.KIND_HISTOGRAM, mpb.KIND_TIMER):
                m.digest.compression = 100.0
                m.digest.min = -1.0
                m.digest.max = 99.0
                m.digest.centroids.means.extend(
                    [float(k) for k in range(j + 1)])
                m.digest.centroids.weights.extend(
                    [1.0 + k for k in range(j + 1)])
            elif m.kind == mpb.KIND_SET:
                m.hll.registers = bytes(range(16 + j))
                m.hll.precision = 14
        return b.SerializeToString()

    seeds = [make_batch(i) for i in range(50)]
    n = 0
    while time.time() < t_end:
        for _ in range(2000):
            base = bytearray(rng.choice(seeds))
            roll = rng.random()
            if roll < 0.4 and base:
                for _ in range(rng.randrange(1, 8)):
                    base[rng.randrange(len(base))] = rng.randrange(256)
            elif roll < 0.55 and base:
                del base[rng.randrange(len(base)):]
            elif roll < 0.65:
                base = bytearray(rng.randbytes(rng.randrange(0, 200)))
            blob = bytes(base)
            d = native_mod.decode_metric_batch(blob)
            if d is not None:
                try:
                    pb = mpb.MetricBatch.FromString(blob)
                except Exception:
                    print(f"metricpb DIVERGE C++ n={d.n} py=rej: {blob!r}")
                    return -1
                if d.n != len(pb.metrics):
                    print(f"metricpb COUNT {d.n} != {len(pb.metrics)}: "
                          f"{blob!r}")
                    return -1
            n += 1
    return n


def fuzz_gob(rng, t_end) -> int:
    from veneur_tpu.distributed import gob

    seeds = []
    for i in range(20):
        k = 1 + i % 15
        means = np.sort(np.array([rng.uniform(-1e3, 1e3) for _ in range(k)]))
        weights = np.array([1.0 + rng.random() * 5 for _ in range(k)])
        blob = gob.encode_merging_digest(
            means, weights, 100.0, float(means.min()), float(means.max()),
            0.5)
        d = gob.decode_merging_digest(blob)
        assert np.allclose(d.means, means)
        seeds.append(blob)
    n = 0
    while time.time() < t_end:
        for _ in range(2000):
            base = bytearray(rng.choice(seeds))
            roll = rng.random()
            if roll < 0.5 and base:
                for _ in range(rng.randrange(1, 6)):
                    base[rng.randrange(len(base))] = rng.randrange(256)
            elif roll < 0.65 and base:
                del base[rng.randrange(len(base)):]
            elif roll < 0.75:
                base = bytearray(rng.randbytes(rng.randrange(0, 150)))
            blob = bytes(base)
            t0 = time.process_time()  # CPU time: wall time flags false
            # positives whenever the (niced, background) fuzzer is
            # descheduled under host load — observed in round 5
            try:
                gob.decode_merging_digest(blob)
            except gob.GobError:
                pass
            except Exception as e:
                print(f"gob CRASH {type(e).__name__}: {e} on {blob!r}")
                return -1
            if time.process_time() - t0 > 1.0:
                print(f"gob SLOW on {len(blob)}B")
                return -1
            n += 1
    return n


def fuzz_ssf_stream(rng, t_end) -> int:
    """Framed-stream reader invariants (round-5 semantics: an
    unmarshalable payload inside a well-formed frame is RECOVERABLE —
    reference ReadSSFStreamSocket continues on non-framing errors):

      1. SSFUnmarshalError must consume exactly its frame: a valid
         frame appended after a bad-payload frame always decodes.
      2. Any byte stream terminates in bounded reads with FramingError,
         SSFUnmarshalError, clean EOF (None), or decoded spans — no
         other exception, no infinite loop.
    """
    import io
    import struct

    from test_native import _make_span_bytes
    from veneur_tpu.protocol import ssf_wire

    good_payload = _make_span_bytes(
        trace_id=7, id=8, start_timestamp=1, end_timestamp=2,
        service="fz", name="op")
    good_frame = struct.pack(">BI", 0, len(good_payload)) + good_payload
    n = 0
    while time.time() < t_end:
        for _ in range(2000):
            roll = rng.random()
            if roll < 0.5:
                # bad payload in a well-formed frame + a good frame:
                # the recoverability property
                bad = rng.randbytes(rng.randrange(0, 64))
                stream = (struct.pack(">BI", 0, len(bad)) + bad
                          + good_frame)
                f = io.BytesIO(stream)
                try:
                    first = ssf_wire.read_ssf(f)
                    first_ok = True
                except ssf_wire.SSFUnmarshalError:
                    first_ok = False
                except ssf_wire.FramingError:
                    print("ssf_stream DIVERGE: well-formed frame raised "
                          f"non-recoverable FramingError: {bad!r}")
                    return -1
                span = ssf_wire.read_ssf(f)
                if span is None or span.service != "fz":
                    print(f"ssf_stream DIVERGE: good frame lost after "
                          f"{'decoded' if first_ok else 'unmarshal-err'} "
                          f"frame: {bad!r}")
                    return -1
            else:
                # arbitrary bytes: bounded reads, bounded error surface
                base = bytearray(good_frame * rng.randrange(1, 3))
                for _ in range(rng.randrange(1, 6)):
                    base[rng.randrange(len(base))] = rng.randrange(256)
                f = io.BytesIO(bytes(base))
                for _ in range(8):  # > frames in the stream
                    try:
                        if ssf_wire.read_ssf(f) is None:
                            break
                    except ssf_wire.FramingError:
                        break  # SSFUnmarshalError subclasses it: both ok
                    except Exception as e:
                        print(f"ssf_stream CRASH {type(e).__name__}: {e} "
                              f"on {bytes(base)!r}")
                        return -1
                else:
                    print(f"ssf_stream UNBOUNDED on {bytes(base)!r}")
                    return -1
            n += 1
    return n


def fuzz_loadgen(rng, t_end) -> int:
    """Generated-traffic differential (the loadgen ring synthesizer is
    a third codec): every DogStatsD line a randomized WorkloadSpec
    synthesizes must be ACCEPTED by both the Python reference parser
    and the C++ ingest parser, and the three line tallies — ring
    metadata, Python parses, native processed — must agree exactly.
    Same for SSF span rings through parse_ssf and the native span fast
    path. A generator that emits unparseable traffic would silently
    deflate every sustained-pipeline number (loss would be synthetic)."""
    from veneur_tpu import native as native_mod
    from veneur_tpu.core.metrics import DEFAULT_TENANT, tenant_of
    from veneur_tpu.loadgen.spec import WorkloadSpec
    from veneur_tpu.protocol import ssf_wire
    from veneur_tpu.protocol.dogstatsd import parse_metric, ParseError

    if not native_mod.loadgen_available():
        print("loadgen: native library unavailable — 0 cases")
        return 0
    ni = native_mod.NativeIngest()
    n = 0
    while time.time() < t_end:
        mix = [rng.random() for _ in range(5)]
        mix[rng.randrange(5)] += 0.2  # guarantee a positive sum
        tenants = rng.choice([1, 1, 2, 5, 16])
        spec = WorkloadSpec(
            seed=rng.randrange(1 << 30),
            num_keys=rng.choice([1, 3, 97, 1000]),
            zipf_s=rng.choice([0.0, 0.7, 1.1, 2.5]),
            type_mix=mix,
            num_tags=rng.randrange(0, 7),
            tag_cardinality=rng.choice([1, 5, 50]),
            prefix=rng.choice(["lg", "fz.deep.prefix", "a"]),
            datagram_bytes=rng.choice([64, 512, 1400, 8192]),
            ring_lines=2000,
            tenant_count=tenants,
            tenant_abusive_frac=(
                0.0 if tenants == 1 else rng.choice([0.0, 0.3, 1.0])),
            tenant_zipf_s=rng.choice([0.0, 1.0]),
            tenant_churn_keys=rng.choice([0, 500]))
        valid_tenants = {f"t{i}" for i in range(tenants)}
        ring = spec.build_ring()
        py_total = native_total = 0
        for i in range(len(ring)):
            dgram = ring.datagram(i)
            for line in dgram.split(b"\n"):
                try:
                    m = parse_metric(line)
                except ParseError as e:
                    print(f"loadgen DIVERGE py rejects generated line "
                          f"({e}): {line!r} spec={spec.to_dict()}")
                    return -1
                if not m.key.name.startswith(spec.prefix + "."):
                    print(f"loadgen DIVERGE name outside prefix: "
                          f"{m.key.name!r} spec={spec.to_dict()}")
                    return -1
                # tenant stamping property: multi-tenant specs put a
                # valid tenant:tN tag on EVERY line, single-tenant
                # specs on none (tenant_of sees only the default)
                t = tenant_of(m.tags, "tenant")
                if tenants == 1 and t != DEFAULT_TENANT:
                    print(f"loadgen DIVERGE tenant tag on single-tenant"
                          f" line: {line!r} spec={spec.to_dict()}")
                    return -1
                if tenants > 1 and t not in valid_tenants:
                    print(f"loadgen DIVERGE bad tenant {t!r}: {line!r} "
                          f"spec={spec.to_dict()}")
                    return -1
                py_total += 1
            before = ni.processed
            ni.ingest(dgram)
            native_total += ni.processed - before
        if not (py_total == native_total == ring.total_lines):
            print(f"loadgen TALLY py={py_total} native={native_total} "
                  f"ring={ring.total_lines} spec={spec.to_dict()}")
            return -1
        ssf_ring = spec.build_ssf_ring(n_spans=50)
        for i in range(len(ssf_ring)):
            payload = ssf_ring.datagram(i)
            try:
                ssf_wire.parse_ssf(payload)
            except Exception as e:
                print(f"loadgen DIVERGE py rejects generated span "
                      f"({type(e).__name__}: {e}): {payload!r}")
                return -1
            rc = ni.ingest_ssf(payload, b"ind.t", b"obj.t")
            if rc != 1:
                print(f"loadgen DIVERGE native rc={rc} on generated "
                      f"span: {payload!r}")
                return -1
        n += py_total + len(ssf_ring)
    return n


def fuzz_reader_commit(rng, t_end) -> int:
    """Shared-nothing reader-commit differential (the reader-shard line
    path): R private owned contexts (vn_ingest_home, one per reader)
    vs ONE legacy context processing the same per-reader streams
    serialized in reader order. Everything keyed must agree exactly:
    processed/error tallies and the per-series folds — counter
    contribution sums, timer/histogram (value, weight) multisets, set
    HLL (index, rank) updates, and last-value gauges. Gauge keys are
    per-reader-disjoint: cross-reader last-writer ordering is not part
    of the contract (same ground truth as tests/test_reader_shards.py);
    counters, timers, and sets DO overlap across readers."""
    from veneur_tpu import native as native_mod

    R = 3
    owned = [native_mod.NativeIngest() for _ in range(R)]
    legacy = native_mod.NativeIngest()
    for ctx in owned + [legacy]:
        ctx.set_spill_cap(1 << 20)

    # (pool, row) -> key maps persist for a context's lifetime;
    # drain_new_series only reports rows created since the last drain
    name_maps = {id(c): {} for c in owned + [legacy]}

    def drain_keyed(ctx):
        names = name_maps[id(ctx)]
        names.update({(p, r): (nm, tg) for p, r, _k, _s, nm, tg
                      in ctx.drain_new_series().first_records()})
        out = {"h": {}, "c": {}, "g": {}, "s": {}}
        while True:
            hr, hv, hw = ctx.drain_histo(4096)
            for r, v, w in zip(hr.tolist(), hv.tolist(), hw.tolist()):
                out["h"].setdefault(names[(0, r)], []).append((v, w))
            sr, si, sk = ctx.drain_set(4096)
            for r, i, k in zip(sr.tolist(), si.tolist(), sk.tolist()):
                out["s"].setdefault(names[(1, r)], set()).add((i, k))
            cr, cc = ctx.drain_counter(4096)
            for r, c in zip(cr.tolist(), cc.tolist()):
                key = names[(2, r)]
                out["c"][key] = out["c"].get(key, 0.0) + c
            gr, gv = ctx.drain_gauge(4096)
            for r, v in zip(gr.tolist(), gv.tolist()):
                out["g"][names[(3, r)]] = v
            if not (ctx.pending_histo or ctx.pending_set
                    or ctx.pending_counter or ctx.pending_gauge):
                break
        for v in out["h"].values():
            v.sort()
        return out

    n = 0
    seen = [0] * (2 * (R + 1))  # processed/errors offsets per context
    while time.time() < t_end:
        keys = [b"fz.k%d" % j for j in range(rng.randrange(1, 40))]
        streams = []
        for r in range(R):
            lines = []
            for _ in range(rng.randrange(20, 200)):
                roll = rng.random()
                if roll < 0.08:
                    lines.append(rng.choice(
                        [b"bad line", b":|c", b"fz.x:|g", b"fz.x:1|zz",
                         b"fz.x:nope|c", b""]))
                    continue
                name = rng.choice(keys)
                if roll < 0.30:
                    line = name + b":%d|c" % rng.randrange(-50, 50)
                    if rng.random() < 0.3:
                        line += b"|@0.5"
                elif roll < 0.55:
                    line = name + b":%d.%d|ms" % (rng.randrange(500),
                                                  rng.randrange(100))
                elif roll < 0.75:
                    line = name + b":u%d|s" % rng.randrange(200)
                else:  # per-reader-disjoint gauge namespace
                    line = b"fz.g%d.%s:%d|g" % (r, name, rng.randrange(999))
                if rng.random() < 0.4:
                    line += b"|#t:%d" % rng.randrange(4)
                lines.append(line)
            dgrams = [b"\n".join(lines[i:i + 20])
                      for i in range(0, len(lines), 20)]
            streams.append(dgrams)

        for r in range(R):
            for d in streams[r]:
                owned[r].ingest_owned(d)
        for r in range(R):  # reader (context) order — the parity contract
            for d in streams[r]:
                legacy.ingest(d)

        tallies = []
        for i, ctx in enumerate(owned + [legacy]):
            p = int(ctx.processed) - seen[2 * i]
            e = int(ctx.errors) - seen[2 * i + 1]
            seen[2 * i], seen[2 * i + 1] = int(ctx.processed), int(ctx.errors)
            if int(ctx.overload_dropped):
                print("reader_commit spill cap hit — raise cap")
                return -1
            tallies.append((p, e))
        sp = sum(t[0] for t in tallies[:R])
        se = sum(t[1] for t in tallies[:R])
        if (sp, se) != tallies[R]:
            print(f"reader_commit TALLY sharded=({sp},{se}) "
                  f"legacy={tallies[R]}")
            return -1

        got = {"h": {}, "c": {}, "g": {}, "s": {}}
        for ctx in owned:  # fold per-reader drains in reader order
            part = drain_keyed(ctx)
            for key, vw in part["h"].items():
                got["h"].setdefault(key, []).extend(vw)
            for key, pairs in part["s"].items():
                got["s"].setdefault(key, set()).update(pairs)
            for key, c in part["c"].items():
                got["c"][key] = got["c"].get(key, 0.0) + c
            got["g"].update(part["g"])
        for v in got["h"].values():
            v.sort()
        want = drain_keyed(legacy)
        if got != want:
            for cls in ("h", "c", "g", "s"):
                if got[cls] != want[cls]:
                    diff = (set(got[cls]) ^ set(want[cls])) or {
                        k for k in got[cls]
                        if got[cls][k] != want[cls].get(k)}
                    print(f"reader_commit DIVERGE class={cls} "
                          f"keys={sorted(diff)[:5]}")
            return -1
        n += sp + se
    return n


def fuzz_query(rng, t_end) -> int:
    """Live-query differential (veneur_tpu/query/): the device query
    kernels vs their independent numpy references on randomized pools —

      quantile_rows  vs np_quantile      (f32 vs f64, tolerance)
      hll.estimate   vs np_hll_estimate  (random register fields, both
                     the linear-counting and raw-harmonic branches)
      heavyhitter.query vs np_cms_query  (exact: same int32 counters)
                     + CMS upper-bound and read_totals-exact properties
      SpaceSavingTopK with capacity >= distinct keys vs exact Counter

    Fixed pool shapes keep the jit cache at one compile per kernel."""
    from collections import Counter

    import jax.numpy as jnp

    from veneur_tpu.ops import heavyhitter as hh
    from veneur_tpu.ops import hll
    from veneur_tpu.ops import query as qops

    nprng = np.random.default_rng(rng.randrange(1 << 30))
    S, C = 16, 32
    n = 0
    while time.time() < t_end:
        for _ in range(10):
            # t-digest quantiles: left-packed digests (k live centroids,
            # zero-weight tail), one always-empty row for the NaN path
            means = np.sort(nprng.uniform(-1e3, 1e3, (S, C)),
                            axis=1).astype(np.float32)
            weights = nprng.uniform(0.1, 8.0, (S, C)).astype(np.float32)
            for i in range(S):
                weights[i, nprng.integers(0 if i == 0 else 1, C + 1):] = 0.0
            dmin = means[:, 0] - nprng.uniform(0, 10, S).astype(np.float32)
            kmax = np.maximum((weights > 0).sum(axis=1) - 1, 0)
            dmax = (means[np.arange(S), kmax]
                    + nprng.uniform(0, 10, S).astype(np.float32))
            qs = np.sort(nprng.uniform(0.0, 1.0, rng.choice([1, 3, 5, 8])))
            if rng.random() < 0.3:
                qs[0], qs[-1] = 0.0, 1.0
            qpad, norig = qops.pad_quantiles(qs)
            rows, nrows = qops.pad_rows(
                nprng.integers(0, S, rng.choice([3, 4, 7, 8])))
            dev = np.asarray(qops.quantile_rows(
                jnp.asarray(means), jnp.asarray(weights), jnp.asarray(dmin),
                jnp.asarray(dmax), jnp.asarray(rows), jnp.asarray(qpad)))
            ref = qops.np_quantile(means, weights, dmin, dmax,
                                   qpad)[rows]
            if not np.allclose(dev[:nrows, :norig], ref[:nrows, :norig],
                               rtol=1e-3, atol=1e-2, equal_nan=True):
                print(f"query QUANTILE DIVERGE rows={rows[:nrows]} "
                      f"qs={qs!r}\n dev={dev[:nrows, :norig]!r}\n "
                      f"ref={ref[:nrows, :norig]!r}")
                return -1

            # HLL estimate: random register fields, forcing both branches
            p = rng.choice([6, 10])
            m = 1 << p
            regs = nprng.integers(0, 64 - p + 2, (8, m)).astype(np.int8)
            regs[0, :] = 0  # empty row: pure linear counting
            regs[1, nprng.random(m) < 0.99] = 0  # sparse: zeros > 0
            dev_e = np.asarray(hll.estimate(jnp.asarray(regs), p))
            ref_e = qops.np_hll_estimate(regs, p)
            if not np.allclose(dev_e, ref_e, rtol=1e-3):
                print(f"query HLL DIVERGE p={p}\n dev={dev_e!r}\n "
                      f"ref={ref_e!r}")
                return -1

            # CMS: device point query is bit-equal to the reference and
            # upper-bounds the truth; totals are exact
            T, D, W = 4, 4, 256
            keys = [f"qk{j}" for j in range(rng.randrange(1, 60))]
            nins = rng.randrange(1, 200)
            ins_rows = nprng.integers(0, T, nins).astype(np.int32)
            ins_keys = [rng.choice(keys) for _ in range(nins)]
            counts = nprng.integers(1, 1000, nins).astype(np.int32)
            cols = hh.split_hashes(hh.hash_keys(ins_keys), D, W)
            pool = hh.insert_chunked(hh.init_pool(T, D, W), ins_rows, cols,
                                     counts, chunk=256)
            qrows = np.repeat(np.arange(T, dtype=np.int32), len(keys))
            qcols = np.tile(hh.split_hashes(hh.hash_keys(keys), D, W), T)
            dev_c = np.asarray(hh.query(pool, jnp.asarray(qrows),
                                        jnp.asarray(qcols)))
            ref_c = qops.np_cms_query(np.asarray(pool), qrows, qcols)
            if not np.array_equal(dev_c, ref_c):
                print(f"query CMS DIVERGE keys={len(keys)} nins={nins}")
                return -1
            truth = Counter()
            for t, k, c in zip(ins_rows.tolist(), ins_keys,
                               counts.tolist()):
                truth[(t, k)] += c
            est = dev_c.reshape(T, len(keys))
            for t in range(T):
                for j, k in enumerate(keys):
                    if est[t, j] < truth[(t, k)]:
                        print(f"query CMS UNDER-estimate t={t} key={k}: "
                              f"{est[t, j]} < {truth[(t, k)]}")
                        return -1
            tot = np.asarray(hh.read_totals(pool))
            want_tot = np.bincount(ins_rows, weights=counts,
                                   minlength=T).astype(np.int64)
            if not np.array_equal(tot, want_tot):
                print(f"query TOTALS DIVERGE {tot!r} != {want_tot!r}")
                return -1

            # space-saving with room for every distinct key == exact
            ss = hh.SpaceSavingTopK(capacity=len(keys))
            stream = Counter()
            for _ in range(rng.randrange(1, 300)):
                k = rng.choice(keys)
                c = rng.randrange(1, 20)
                ss.offer(k, c)
                stream[k] += c
            got = {k: (c, e) for k, c, e in ss.items()}
            want = {k: (c, 0) for k, c in stream.items()}
            if got != want:
                print(f"query TOPK DIVERGE {got!r} != {want!r}")
                return -1
            n += 1
    return n


def fuzz_forward_codec(rng, t_end) -> int:
    """Native VSF1/VDE1 forward-frame codec vs the pinned Python
    reference: encoded bytes identical, decodes round-trip through both
    paths, and corrupted blobs draw the same typed verdict (accept with
    equal value, or ValueError) from both. Runs against whatever
    dispatch is live — with VENEUR_CODEC_NATIVE=0 it degrades to a
    Python self-consistency sweep (CI runs it both ways)."""
    from veneur_tpu.distributed import codec

    if codec._native_codec() is None:
        print("forward_codec: native codec not loaded "
              "(Python self-consistency only)")

    def rand_sender() -> str:
        chars = []
        for _ in range(rng.randrange(0, 14)):
            r = rng.random()
            if r < 0.55:
                chars.append(chr(rng.randrange(0x20, 0x7F)))
            elif r < 0.70:   # controls + DEL: the \u00xx escape path
                chars.append(chr(rng.choice(
                    list(range(0x00, 0x20)) + [0x7F])))
            elif r < 0.85:   # BMP non-ASCII: \uxxxx escapes
                chars.append(chr(rng.randrange(0x80, 0x3000)))
            elif r < 0.95:   # astral: surrogate-pair escapes
                chars.append(chr(rng.randrange(0x10000, 0x10400)))
            else:            # lone surrogate: native must decline,
                chars.append(chr(rng.randrange(0xD800, 0xE000)))
        return "".join(chars)  # ... and fall back per-call

    def verdict(fn, blob):
        try:
            return ("ok", fn(blob))
        except ValueError:
            return ("reject", None)

    n = 0
    while time.time() < t_end:
        for _ in range(1500):
            seq = rng.randrange(0, 1 << 64)
            body = bytes(rng.randrange(256)
                         for _ in range(rng.randrange(0, 48)))
            frame = codec.encode_stream_frame(seq, body)
            if frame != codec.encode_stream_frame_py(seq, body):
                print(f"forward_codec FRAME ENC DIVERGE seq={seq}")
                return -1
            if (codec.decode_stream_frame(frame) != (seq, body)
                    or codec.decode_stream_frame_py(frame) != (seq, body)):
                print(f"forward_codec FRAME DEC DIVERGE seq={seq}")
                return -1
            status = rng.randrange(0, 256)
            ack = codec.encode_stream_ack(seq, status)
            if (ack != codec.encode_stream_ack_py(seq, status)
                    or codec.decode_stream_ack(ack)
                    != codec.decode_stream_ack_py(ack)):
                print(f"forward_codec ACK DIVERGE seq={seq} st={status}")
                return -1
            sender = rand_sender()
            did = rng.randrange(-(1 << 66), 1 << 66)  # straddles i64
            cnt = rng.randrange(0, 1 << 40)
            env = codec.encode_dedup_envelope(sender, did, cnt, body)
            if env != codec.encode_dedup_envelope_py(
                    sender, did, cnt, body):
                print(f"forward_codec ENV ENC DIVERGE {sender!r} {did}")
                return -1
            # ground truth is the JSON escape round-trip: two adjacent
            # lone surrogates re-merge into one astral char on decode
            # (a Python-reference property the native path must match)
            import json as _json
            want = ((_json.loads(_json.dumps(sender)), did, cnt), body)
            if (codec.decode_dedup_envelope(env) != want
                    or codec.decode_dedup_envelope_py(env) != want):
                print(f"forward_codec ENV DEC DIVERGE {sender!r} {did}")
                return -1
            # corruption: one mutated byte must draw the same verdict
            # (and value, when accepted) from both decode paths
            blob = env if rng.random() < 0.5 else frame
            pos = rng.randrange(len(blob))
            mutated = (blob[:pos]
                       + bytes([blob[pos] ^ (1 << rng.randrange(8))])
                       + blob[pos + 1:])
            for pub, ref in ((codec.decode_dedup_envelope,
                              codec.decode_dedup_envelope_py),
                             (codec.decode_stream_frame,
                              codec.decode_stream_frame_py),
                             (codec.decode_stream_ack,
                              codec.decode_stream_ack_py)):
                if verdict(pub, mutated) != verdict(ref, mutated):
                    print(f"forward_codec CORRUPT DIVERGE {pub.__name__}"
                          f" pos={pos} blob={mutated!r}")
                    return -1
            n += 1
    return n


def fuzz_device_fallback(rng, t_end) -> int:
    """Device fault-domain differential (ops/device_guard +
    ops/host_engine): a worker under a randomized seeded
    DeviceFaultPlan — random fault kind, random per-op dispatch windows,
    random breaker streak, micro-folds on or off — must flush
    byte-identical snapshots to a clean worker fed the same stream, for
    every metric class. This is the no-epoch-lost contract: whatever
    subset of device ops fault, and whether or not the breaker trips,
    failover to the host engine conserves everything, bitwise (only the
    ``degraded`` flag may differ)."""
    import dataclasses

    from veneur_tpu.core.flusher import device_quantiles
    from veneur_tpu.core.metrics import HistogramAggregates
    from veneur_tpu.core.worker import DeviceWorker
    from veneur_tpu.protocol.dogstatsd import parse_metric
    from veneur_tpu.utils import faults as fl

    qs = device_quantiles(
        [0.5, 0.9, 0.99], HistogramAggregates.from_names(
            ["min", "max", "sum", "count"]))
    ops_all = ("fold", "spill", "staged", "micro", "extract", "sets",
               "grow", "import")

    # fixed shapes: one jit specialization set for the whole run
    def mk(streak, micro):
        return DeviceWorker(compression=100, stage_depth=32, batch_size=8,
                            initial_histo_rows=8, initial_set_rows=8,
                            micro_fold=micro, micro_fold_rows=1,
                            micro_fold_max_age_s=1e9,
                            device_fault_streak=streak)

    def drive(w, lines, micro):
        for ln in lines:
            if ln is None:
                if micro and w.micro_fold_due():
                    w.micro_fold_once()
                continue
            w.process_metric(parse_metric(ln.encode()))
        return w.flush(qs)

    n = 0
    while time.time() < t_end:
        seed = rng.randrange(1 << 30)
        nprng = np.random.default_rng(seed)
        micro = rng.random() < 0.5
        streak = rng.choice([1, 2, 3])
        nser = rng.randrange(3, 20)
        lines = []
        for _ in range(rng.randrange(3, 9)):
            for _ in range(rng.randrange(4, 14)):
                k = int(nprng.integers(nser))
                t = rng.random()
                if t < 0.4:
                    lines.append(f"h{k}:{nprng.normal():.6f}|ms|#a:{k % 3}")
                elif t < 0.6:
                    lines.append(f"c{k}:{1 + k % 5}|c")
                elif t < 0.8:
                    lines.append(f"s{k}:v{nprng.integers(50)}|s")
                else:
                    lines.append(f"g{k}:{nprng.normal():.6f}|g")
            lines.append(None)  # micro-fold point
        kind = rng.choice(["oom", "compile", "lost", "other"])
        ops = rng.sample(ops_all, rng.randrange(1, len(ops_all) + 1))
        start = rng.randrange(0, 8)
        width = rng.randrange(1, 12)
        plan = fl.DeviceFaultPlan(seed=seed, op_windows={
            op: [(start, start + width, kind)] for op in ops})

        base = drive(mk(streak, micro), lines, micro)
        w = mk(streak, micro)
        with fl.DeviceFaultInjector(plan) as inj:
            got = drive(w, lines, micro)
        injected = sum(inj.injected[k]
                       for k in ("oom", "compile", "lost", "other"))
        ctx = (f"seed={seed} kind={kind} ops={ops} "
               f"window=({start},{start + width}) micro={micro} "
               f"streak={streak} injected={injected} "
               f"quarantined={w.guard.quarantined}")
        for f in dataclasses.fields(base):
            if f.name == "degraded":
                continue
            va, vb = getattr(base, f.name), getattr(got, f.name)
            if not (isinstance(va, np.ndarray)
                    or isinstance(vb, np.ndarray)):
                continue
            if (va is None or vb is None or va.dtype != vb.dtype
                    or va.shape != vb.shape
                    or va.tobytes() != vb.tobytes()):
                print(f"device_fallback DIVERGE field={f.name} {ctx}\n"
                      f" base={va!r}\n got={vb!r}")
                return -1
        if got.degraded and not injected:
            print(f"device_fallback PHANTOM degraded flush {ctx}")
            return -1
        n += 1
    return n


TARGETS = {"dogstatsd": fuzz_dogstatsd, "ssf": fuzz_ssf,
           "metricpb": fuzz_metricpb, "gob": fuzz_gob,
           "ssf_stream": fuzz_ssf_stream, "loadgen": fuzz_loadgen,
           "reader_commit": fuzz_reader_commit, "query": fuzz_query,
           "forward_codec": fuzz_forward_codec,
           "device_fallback": fuzz_device_fallback}


def _git_rev() -> str:
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO,
            capture_output=True, text=True, timeout=10).stdout.strip()
    except Exception:
        return "unknown"


def _update_tally(path: str, seed: int, per_target: dict[str, int],
                  divergences: list[str]) -> None:
    """Accumulate a round's results into the standing tally artifact
    (the long-run campaign is a standing gate, its tally committed so
    codec parity keeps being hunted after every codec change, not just
    pinned at a fixed seed)."""
    import json

    tally = {"total_cases": 0, "runs": 0, "seeds": [], "per_target": {},
             "divergences_found": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                loaded = json.load(f)
            if isinstance(loaded, dict) and isinstance(
                    loaded.get("per_target"), dict):
                tally = loaded
        except Exception:
            pass
    tally["runs"] = tally.get("runs", 0) + 1
    tally["seeds"] = (tally.get("seeds", []) + [seed])[-50:]
    for name, n in per_target.items():
        tally["per_target"][name] = tally["per_target"].get(name, 0) + n
    tally["total_cases"] = sum(tally["per_target"].values())
    tally["divergences_found"] = (
        tally.get("divergences_found", []) + divergences)
    tally["last_run_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime())
    tally["last_rev"] = _git_rev()
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(tally, f, indent=1)
    os.replace(tmp, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=30.0,
                    help="budget per target")
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--targets",
                    default="dogstatsd,ssf,metricpb,gob,ssf_stream,"
                            "loadgen,reader_commit,query,forward_codec")
    ap.add_argument("--tally", default=None, metavar="PATH",
                    help="accumulate results into this JSON artifact")
    ap.add_argument("--rounds", type=int, default=1,
                    help="repeat the whole target sweep N times with a "
                         "fresh seed each round (long-run mode)")
    args = ap.parse_args()
    failed = False
    for rnd in range(args.rounds):
        seed = (args.seed + rnd if args.seed is not None
                else int(time.time()))
        print(f"round {rnd + 1}/{args.rounds} seed {seed}", flush=True)
        per_target: dict[str, int] = {}
        divergences: list[str] = []
        for name in args.targets.split(","):
            rng = random.Random(seed)
            n = TARGETS[name](rng, time.time() + args.seconds)
            if n < 0:
                failed = True
                divergences.append(f"{name} seed={seed}")
                print(f"{name}: DIVERGENCE (seed {seed})", flush=True)
            else:
                per_target[name] = n
                print(f"{name}: {n} cases clean", flush=True)
        if args.tally:
            _update_tally(args.tally, seed, per_target, divergences)
        if failed:
            break
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
