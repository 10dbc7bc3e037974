"""Device-mesh aggregation: the distributed tier as XLA collectives.

The reference's distributed design (SURVEY.md §2.3/§5.7-5.8): N local
instances each aggregate a shard of traffic, then forward mergeable sketches
over gRPC to global instances that reduce them per series. Veneur's
parallelism strategies map onto the device mesh as:

  axis "series" — the reference's in-process worker sharding
                  (Digest % N, server.go:1039): each device owns a
                  contiguous shard of series rows. No communication is
                  needed on this axis: metric identity → row → shard is
                  deterministic, like the consistent-hash ring of the proxy
                  tier (proxy.go:587-628).
  axis "hosts"  — the local→global aggregation tier (importsrv →
                  worker.go:438-495): each host-rank aggregates its own
                  traffic for the *same* series space, and the global
                  reduce becomes collectives over ICI instead of per-series
                  Go loops: all_gather of digest centroid rows + one batched
                  compress for t-digests, psum-style max for HLL registers,
                  psum for counters.

When real deployments span machines, the host boundary still speaks the
protobuf sketch codec (distributed/codec.py); this module covers the
single-process multi-chip mesh where the whole reduce rides ICI.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from veneur_tpu.ops import tdigest as td
from veneur_tpu.ops import hll as hll_ops


def make_mesh(n_devices: Optional[int] = None, hosts: Optional[int] = None
              ) -> Mesh:
    """Build a (hosts, series) mesh over the first n devices.

    hosts defaults to 2 when the device count is even (so the cross-host
    reduce path is exercised), else 1.
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    devs = devs[:n]
    if hosts is None:
        hosts = 2 if n % 2 == 0 and n >= 2 else 1
    if n % hosts:
        raise ValueError(f"{n} devices not divisible by hosts={hosts}")
    arr = np.array(devs).reshape(hosts, n // hosts)
    return Mesh(arr, ("hosts", "series"))


def make_series_mesh(shards: int) -> Mesh:
    """1-D mesh over the first `shards` devices for the within-host
    series-axis split (ops/series_shard.py). Named "series" so it
    composes with make_mesh's (hosts, series) convention: the global
    tier reduces over "hosts", the local pools partition over
    "series" — the same axis name means the same ownership rule
    (row -> shard by r % D) at both tiers."""
    devs = jax.devices()
    if shards > len(devs):
        raise ValueError(
            f"series_shards={shards} exceeds {len(devs)} visible devices")
    return Mesh(np.array(devs[:shards]), ("series",))


def _local_aggregate_step(means, weights, dmin, dmax, drecip,
                          rows, values, wts, qs, compression):
    """Per-device block: ingest this host-shard's batch into its series
    rows, then reduce digests across the hosts axis and extract quantiles.

    Shapes inside shard_map (leading mesh dims stripped to 1):
      means/weights: [1, s_loc, C]; dmin/dmax/drecip: [1, s_loc]
      rows/values/wts: [1, n_loc]; qs: [P] (replicated)
    """
    m = means[0]
    w = weights[0]
    mn = dmin[0]
    mx = dmax[0]
    rc = drecip[0]

    n_m, n_w, n_mn, n_mx, n_rc, _stats = td.add_batch(
        m, w, mn, mx, rc, rows[0], values[0], wts[0],
        compression=compression,
    )

    # cross-host digest reduce over ICI: gather every host's centroid rows
    # for the series this device owns, merge in one batched compress
    g_means = jax.lax.all_gather(n_m, "hosts")  # [H, s_loc, C]
    g_w = jax.lax.all_gather(n_w, "hosts")
    g_mn = jax.lax.pmin(n_mn, "hosts")
    g_mx = jax.lax.pmax(n_mx, "hosts")
    g_rc = jax.lax.psum(n_rc, "hosts")

    h, s_loc, c = g_means.shape
    cat_means = jnp.transpose(g_means, (1, 0, 2)).reshape(s_loc, h * c)
    cat_w = jnp.transpose(g_w, (1, 0, 2)).reshape(s_loc, h * c)
    mg_means, mg_w = td.compress_rows(cat_means, cat_w, compression, c)

    quant = td.quantile(mg_means, mg_w, g_mn, g_mx, qs)  # [s_loc, P]

    return (n_m[None], n_w[None], n_mn[None], n_mx[None], n_rc[None],
            quant[None])


def build_sharded_flush_step(mesh: Mesh,
                             compression: float = td.DEFAULT_COMPRESSION):
    """Jit the fused multi-chip aggregation+reduce+extract step.

    Logical shapes:
      means/weights: f32[H, S, C]   sharded (hosts, series, -)
      dmin/dmax/drecip: f32[H, S]   sharded (hosts, series)
      rows: i32[H, N] values/wts: f32[H, N]  sharded (hosts, series)
        — each (host, series-shard) device gets its own batch slice whose
          row ids are LOCAL to its series shard
      qs: f32[P] replicated
    Returns (updated per-host state..., quantiles f32[H', S, P]) where the
    quantile output's host dim is the per-device copy of the merged result.
    """
    spec_state2 = P("hosts", "series", None)
    spec_state1 = P("hosts", "series")
    spec_batch = P("hosts", "series")
    spec_q = P(None)

    fn = shard_map(
        functools.partial(_local_aggregate_step, compression=compression),
        mesh=mesh,
        in_specs=(spec_state2, spec_state2, spec_state1, spec_state1,
                  spec_state1, spec_batch, spec_batch, spec_batch, spec_q),
        out_specs=(spec_state2, spec_state2, spec_state1, spec_state1,
                   spec_state1, P("hosts", "series", None)),
        check_vma=False,
    )
    return jax.jit(fn)


def make_example_state(mesh: Mesh, s_per_shard: int = 8, n_per_shard: int = 64,
                       capacity: int = td.DEFAULT_CAPACITY, p: int = 3):
    """Tiny sharded example inputs for the sharded flush step."""
    hosts = mesh.shape["hosts"]
    series_shards = mesh.shape["series"]
    s = s_per_shard * series_shards
    n = n_per_shard * series_shards

    def shard(arr, spec):
        return jax.device_put(arr, NamedSharding(mesh, spec))

    rng = np.random.default_rng(0)
    means = shard(np.full((hosts, s, capacity), np.inf, np.float32),
                  P("hosts", "series", None))
    weights = shard(np.zeros((hosts, s, capacity), np.float32),
                    P("hosts", "series", None))
    dmin = shard(np.full((hosts, s), np.inf, np.float32), P("hosts", "series"))
    dmax = shard(np.full((hosts, s), -np.inf, np.float32),
                 P("hosts", "series"))
    drecip = shard(np.zeros((hosts, s), np.float32), P("hosts", "series"))
    # per-device-local row ids in [0, s_per_shard)
    rows = shard(
        rng.integers(0, s_per_shard, (hosts, n)).astype(np.int32),
        P("hosts", "series"))
    values = shard(rng.uniform(1, 100, (hosts, n)).astype(np.float32),
                   P("hosts", "series"))
    wts = shard(np.ones((hosts, n), np.float32), P("hosts", "series"))
    qs = jnp.asarray(np.linspace(0.25, 0.99, p, dtype=np.float32))
    return (means, weights, dmin, dmax, drecip, rows, values, wts, qs)


# ---------------------------------------------------------------------------
# Product path: the mesh-sharded histogram pool for the global tier.
#
# A global veneur-tpu terminates forwarded digests from many locals. With
# a mesh configured (config tpu_mesh_devices / tpu_mesh_hosts), histogram
# state shards over the (hosts, series) mesh: imported centroids are
# re-ingested as weighted samples — the exact semantics of the
# reference's shuffled re-Add merge (tdigest/merging_digest.go:374-389):
# min/max evolve from centroid means, reciprocalSum is carried exactly
# (the oldReciprocalSum line) via a host-side f64 accumulator. Flush runs
# the cross-host all_gather + batched compress + quantile extraction on
# the mesh (ICI collectives replace worker.go:438-495 per-series loops).


def build_mesh_ingest_step(mesh: Mesh,
                           compression: float = td.DEFAULT_COMPRESSION,
                           carry_recip: bool = True):
    """Per-device ingest of a (rows, values, weights) batch slice into
    sharded digest state. No collectives — series live on their home
    shard. carry_recip=False is the import variant: re-ingested centroid
    means must not pollute reciprocalSum (it travels on the wire)."""

    def _step(means, weights, dmin, dmax, drecip, rows, values, wts):
        m, w, mn, mx, rc, _ = td.add_batch(
            means[0], weights[0], dmin[0], dmax[0], drecip[0],
            rows[0], values[0], wts[0], compression=compression)
        if not carry_recip:
            rc = drecip[0]
        return m[None], w[None], mn[None], mx[None], rc[None]

    spec2 = P("hosts", "series", None)
    spec1 = P("hosts", "series")
    return jax.jit(shard_map(
        _step, mesh=mesh,
        in_specs=(spec2, spec2, spec1, spec1, spec1, spec1, spec1, spec1),
        out_specs=(spec2, spec2, spec1, spec1, spec1),
        check_vma=False,
    ))


def build_mesh_extract_step(mesh: Mesh,
                            compression: float = td.DEFAULT_COMPRESSION):
    """Cross-host merge + quantile/scalar extraction over the mesh.

    Returns (quant [H,S,P], dmin, dmax, dsum, dcount, drecip — each
    [H,S], identical along the hosts axis; callers slice host 0)."""

    def _step(means, weights, dmin, dmax, drecip, qs):
        g_means = jax.lax.all_gather(means[0], "hosts")  # [H, s_loc, C]
        g_w = jax.lax.all_gather(weights[0], "hosts")
        mn = jax.lax.pmin(dmin[0], "hosts")
        mx = jax.lax.pmax(dmax[0], "hosts")
        rc = jax.lax.psum(drecip[0], "hosts")
        h, s_loc, c = g_means.shape
        cat_m = jnp.transpose(g_means, (1, 0, 2)).reshape(s_loc, h * c)
        cat_w = jnp.transpose(g_w, (1, 0, 2)).reshape(s_loc, h * c)
        mg_m, mg_w = td.compress_rows(cat_m, cat_w, compression, c)
        quant = td.quantile(mg_m, mg_w, mn, mx, qs)
        dsum = td.row_sum(mg_m, mg_w)
        dcount = td.row_count(mg_w)
        return (quant[None], mn[None], mx[None], dsum[None], dcount[None],
                rc[None])

    spec2 = P("hosts", "series", None)
    spec1 = P("hosts", "series")
    return jax.jit(shard_map(
        _step, mesh=mesh,
        in_specs=(spec2, spec2, spec1, spec1, spec1, P(None)),
        out_specs=(P("hosts", "series", None), spec1, spec1, spec1, spec1,
                   spec1),
        check_vma=False,
    ))


def _next_pow2(n: int, floor: int) -> int:
    v = floor
    while v < n:
        v *= 2
    return v


class MeshHistoPool:
    """Mesh-sharded histogram aggregation state for one flush epoch.

    Global rows come from the owning worker's series directory; row r
    lives on series-shard ``r % D`` at local index ``r // D`` (interleaved
    so shards fill evenly as series appear). Raw samples and imported
    centroids buffer host-side per (host-slot, shard) and stream to the
    mesh in batches; flush merges across the hosts axis and extracts.
    """

    def __init__(self, mesh: Mesh,
                 compression: float = td.DEFAULT_COMPRESSION,
                 capacity: int = td.DEFAULT_CAPACITY,
                 initial_rows_per_shard: int = 256,
                 batch_size: int = 65536) -> None:
        self.mesh = mesh
        self.hosts = mesh.shape["hosts"]
        self.shards = mesh.shape["series"]
        self.compression = compression
        self.capacity = capacity
        self.initial_rows = initial_rows_per_shard
        self.batch_size = batch_size
        self._ingest_raw = build_mesh_ingest_step(mesh, compression, True)
        self._ingest_imp = build_mesh_ingest_step(mesh, compression, False)
        self._extract = build_mesh_extract_step(mesh, compression)
        self.reset()

    def reset(self) -> None:
        self._state = None  # (means, weights, dmin, dmax, drecip)
        self._rows_per_shard = 0
        # pending [host][shard] SoA buffers: (local_row, value, weight)
        self._pend = [[([], [], []) for _ in range(self.shards)]
                      for _ in range(self.hosts)]
        self._pend_imp = [[([], [], []) for _ in range(self.shards)]
                         for _ in range(self.hosts)]
        self._pend_n = 0
        self._recip_extra: dict[int, float] = {}  # global row → wire recip
        self._max_row = -1
        self._imp_rr = 0  # round-robin host slot for imports

    # -- ingestion ----------------------------------------------------------

    def add_sample(self, row: int, value: float, weight: float,
                   host_slot: int) -> None:
        d, l = row % self.shards, row // self.shards
        b = self._pend[host_slot % self.hosts][d]
        b[0].append(l)
        b[1].append(value)
        b[2].append(weight)
        self._max_row = max(self._max_row, row)
        self._pend_n += 1
        if self._pend_n >= self.batch_size:
            self._flush_pending()

    def add_samples_bulk(self, rows: np.ndarray, values: np.ndarray,
                         weights: np.ndarray) -> None:
        """Vectorized ingest of a drained native batch: samples group by
        (host-slot, shard) with one lexsort instead of a per-sample
        Python loop (the native drain holds the worker lock — readers
        block on it, so this path must stay near numpy speed)."""
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return
        values = np.asarray(values)
        weights = np.asarray(weights)
        h = rows % self.hosts
        d = rows % self.shards
        loc = rows // self.shards
        key = h * self.shards + d
        order = np.argsort(key, kind="stable")
        key_s = key[order]
        bounds = np.flatnonzero(
            np.r_[True, key_s[1:] != key_s[:-1]])
        bounds = np.r_[bounds, key_s.size]
        loc_s = loc[order]
        val_s = values[order]
        wt_s = weights[order]
        for i in range(len(bounds) - 1):
            a, b = int(bounds[i]), int(bounds[i + 1])
            hi, di = int(key_s[a]) // self.shards, int(key_s[a]) % self.shards
            buf = self._pend[hi][di]
            buf[0].extend(loc_s[a:b].tolist())
            buf[1].extend(val_s[a:b].tolist())
            buf[2].extend(wt_s[a:b].tolist())
        self._max_row = max(self._max_row, int(rows.max()))
        self._pend_n += int(rows.size)
        if self._pend_n >= self.batch_size:
            self._flush_pending()

    def add_centroids(self, row: int, means, weights, recip: float) -> None:
        """Merge one imported digest: centroids re-ingested as weighted
        samples (reference Merge semantics); wire reciprocalSum carried
        exactly in f64 host-side."""
        slot = self._imp_rr % self.hosts
        self._imp_rr += 1
        d, l = row % self.shards, row // self.shards
        b = self._pend_imp[slot][d]
        for m, w in zip(means, weights):
            if w > 0:
                b[0].append(l)
                b[1].append(float(m))
                b[2].append(float(w))
                self._pend_n += 1
        self._recip_extra[row] = self._recip_extra.get(row, 0.0) + recip
        self._max_row = max(self._max_row, row)
        if self._pend_n >= self.batch_size:
            self._flush_pending()

    # -- device movement ----------------------------------------------------

    def _shard_state(self, arr: np.ndarray, spec: P):
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def _ensure_rows(self) -> None:
        need = (self._max_row // self.shards) + 1
        if self._state is not None and need <= self._rows_per_shard:
            return
        new_rps = _next_pow2(need, self.initial_rows)
        h, d, c = self.hosts, self.shards, self.capacity
        s = new_rps * d
        means = np.full((h, s, c), np.inf, np.float32)
        weights = np.zeros((h, s, c), np.float32)
        dmin = np.full((h, s), np.inf, np.float32)
        dmax = np.full((h, s), -np.inf, np.float32)
        drecip = np.zeros((h, s), np.float32)
        if self._state is not None:
            old = [np.asarray(a) for a in self._state]
            # old state: [h, old_rps * d, ...] — per-shard blocks relocate
            old_rps = self._rows_per_shard
            for di in range(d):
                means[:, di * new_rps:di * new_rps + old_rps] = \
                    old[0][:, di * old_rps:(di + 1) * old_rps]
                weights[:, di * new_rps:di * new_rps + old_rps] = \
                    old[1][:, di * old_rps:(di + 1) * old_rps]
                dmin[:, di * new_rps:di * new_rps + old_rps] = \
                    old[2][:, di * old_rps:(di + 1) * old_rps]
                dmax[:, di * new_rps:di * new_rps + old_rps] = \
                    old[3][:, di * old_rps:(di + 1) * old_rps]
                drecip[:, di * new_rps:di * new_rps + old_rps] = \
                    old[4][:, di * old_rps:(di + 1) * old_rps]
        self._rows_per_shard = new_rps
        s2 = P("hosts", "series", None)
        s1 = P("hosts", "series")
        self._state = (
            self._shard_state(means, s2), self._shard_state(weights, s2),
            self._shard_state(dmin, s1), self._shard_state(dmax, s1),
            self._shard_state(drecip, s1),
        )

    def _build_batch(self, pend) -> Optional[tuple]:
        widest = max((len(pend[h][d][0]) for h in range(self.hosts)
                      for d in range(self.shards)), default=0)
        if widest == 0:
            return None
        nd = _next_pow2(widest, 64)
        h, d = self.hosts, self.shards
        rows = np.zeros((h, d * nd), np.int32)
        vals = np.ones((h, d * nd), np.float32)
        wts = np.zeros((h, d * nd), np.float32)  # 0 ⇒ padding
        for hi in range(h):
            for di in range(d):
                lr, lv, lw = pend[hi][di]
                n = len(lr)
                if n:
                    rows[hi, di * nd:di * nd + n] = lr
                    vals[hi, di * nd:di * nd + n] = lv
                    wts[hi, di * nd:di * nd + n] = lw
                pend[hi][di] = ([], [], [])
        s1 = P("hosts", "series")
        return (self._shard_state(rows, s1), self._shard_state(vals, s1),
                self._shard_state(wts, s1))

    def _flush_pending(self) -> None:
        if self._pend_n == 0:
            return
        self._ensure_rows()
        raw = self._build_batch(self._pend)
        if raw is not None:
            self._state = self._ingest_raw(*self._state, *raw)
        imp = self._build_batch(self._pend_imp)
        if imp is not None:
            self._state = self._ingest_imp(*self._state, *imp)
        self._pend_n = 0

    # -- flush --------------------------------------------------------------

    def extract(self, quantiles: np.ndarray, num_rows: int):
        """Merge across hosts and extract; returns dict of np arrays in
        global-row order [num_rows], or None if nothing was ingested."""
        self._flush_pending()
        if self._max_row >= 0:
            # rows can be known without any positive-weight sample queued
            # (e.g. an imported digest whose centroids were all empty):
            # state must still cover them or the gather below goes OOB
            self._ensure_rows()
        if self._state is None:
            return None
        qs = jnp.asarray(np.asarray(quantiles, np.float32))
        quant, mn, mx, dsum, dcount, drecip = self._extract(
            *self._state, qs)
        # host 0's copy; invert row interleave: global row r = shard-major
        # position (r % D) * rps + r // D
        rps, d = self._rows_per_shard, self.shards
        r = np.arange(num_rows)
        pos = (r % d) * rps + r // d
        out = {
            "quant": np.asarray(quant)[0][pos],
            "dmin": np.asarray(mn)[0][pos],
            "dmax": np.asarray(mx)[0][pos],
            "dsum": np.asarray(dsum)[0][pos].astype(np.float64),
            "dcount": np.asarray(dcount)[0][pos].astype(np.float64),
            "drecip": np.asarray(drecip)[0][pos].astype(np.float64),
        }
        for row, extra in self._recip_extra.items():
            if row < num_rows:
                out["drecip"][row] += extra
        return out


# ---------------------------------------------------------------------------
# Standalone collective merges (used by the global tier when local+global
# shards share a pod)


def build_hll_merge(mesh: Mesh):
    """HLL register merge across hosts: elementwise max collective."""

    def _merge(regs):  # [1, s_loc, m]
        return jax.lax.pmax(regs[0], "hosts")[None]

    return jax.jit(shard_map(
        _merge, mesh=mesh,
        in_specs=(P("hosts", "series", None),),
        out_specs=P("hosts", "series", None),
        check_vma=False,
    ))


def build_sharded_staged_fold(mesh: Mesh, compression: float = 100.0):
    """The round-4 local-tier flush program over a device mesh: digest
    pool rows AND the raw-sample staging plane shard over every device
    (hosts × series — the local tier's series space is flat over the
    mesh), each shard folding its own [S_loc, B] plane independently.
    Embarrassingly parallel: no collectives; cross-host digest MERGING
    is the global tier's job (build_sharded_flush_step).

    Returns fn(fields14..., svals, swts) -> fields14, all arrays row-
    sharded."""
    from veneur_tpu.core.worker import _histo_fold_staged

    spec2 = P(("hosts", "series"), None)
    fields = tuple([spec2] * 2 + [P(("hosts", "series"))] * 12)

    def _local(*args):
        # per shard, its wide rows told from its narrow ones there
        return _histo_fold_staged.__wrapped__(
            *args, compression=compression)[:14]

    return jax.jit(shard_map(_local, mesh=mesh,
                             in_specs=fields + (spec2, spec2),
                             out_specs=fields, check_vma=False))


def build_counter_merge(mesh: Mesh):
    """Counter sum across hosts (the trivial segment-sum analog)."""

    def _merge(vals):  # [1, s_loc]
        return jax.lax.psum(vals[0], "hosts")[None]

    return jax.jit(shard_map(
        _merge, mesh=mesh,
        in_specs=(P("hosts", "series"),),
        out_specs=P("hosts", "series"),
        check_vma=False,
    ))
