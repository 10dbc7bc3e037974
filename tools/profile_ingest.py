"""Stage-by-stage TPU timing of the t-digest ingest path (add_batch).

Run on hardware: python tools/profile_ingest.py
Each stage is jitted separately with a scalar force-read so the timing
reflects real execution, not dispatch.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from veneur_tpu.ops import segments, tdigest as td

S = 16384
N = 1 << 22
C = td.DEFAULT_CAPACITY
ITERS = 10

rng = np.random.default_rng(0)
rows = jnp.asarray(rng.integers(0, S, N).astype(np.int32))
vals = jnp.asarray(rng.gamma(2.0, 50.0, N).astype(np.float32))
wts = jnp.ones(N, np.float32)
pool = td.init_pool(S, C)


def bench(name, fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    # force: pull one scalar
    def scalar(o):
        leaves = jax.tree_util.tree_leaves(o)
        return float(jnp.sum(leaves[0].astype(jnp.float32).ravel()[:1])[None][0])
    scalar(out)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = fn(*args)
    scalar(out)
    dt = (time.perf_counter() - t0) / ITERS
    print(f"{name:34s} {dt*1e3:9.2f} ms   {N/dt/1e6:8.1f} Msamp/s")
    return out


@jax.jit
def full(pool, rows, vals, wts):
    return td.add_batch(pool.means, pool.weights, pool.min, pool.max,
                        pool.recip, rows, vals, wts)


@jax.jit
def sort3(rows, vals, wts):
    return jax.lax.sort((rows, vals, wts), dimension=0, num_keys=2)


@jax.jit
def sort_single_key(keys, wts):
    return jax.lax.sort((keys, wts), dimension=0, num_keys=1)


@jax.jit
def segcum(sw, starts):
    return segments.segmented_cumsum(sw, starts)


@jax.jit
def compress(means, weights):
    cat_m = jnp.concatenate([means, means], axis=-1)
    cat_w = jnp.concatenate([weights, weights], axis=-1)
    return td._compress_rows(cat_m, cat_w, 100.0, C)


@jax.jit
def quant(means, weights, dmin, dmax, qs):
    return td.quantile(means, weights, dmin, dmax, qs)


print("device:", jax.devices()[0])
out = bench("add_batch (full)", full, pool, rows, vals, wts)

# larger batches amortize the [K, C]-shaped fixed cost (gathers + final
# compress scale with series, not samples)
N4 = N * 4
rows4 = jnp.asarray(np.random.default_rng(7).integers(0, S, N4)
                    .astype(np.int32))
vals4 = jnp.asarray(np.random.default_rng(8).gamma(2.0, 50.0, N4)
                    .astype(np.float32))
wts4 = jnp.ones(N4, np.float32)


@jax.jit
def full4(pool, rows, vals, wts):
    return td.add_batch(pool.means, pool.weights, pool.min, pool.max,
                        pool.recip, rows, vals, wts)


_saveN = N
N = N4
bench("add_batch (4x batch)", full4, pool, rows4, vals4, wts4)
N = _saveN

srows, svals, sw = bench("lax.sort 2-key + payload", sort3, rows, vals, wts)

# single fused key: row in high bits, value-as-sortable-u32 in low bits,
# packed into f64 (53-bit mantissa holds 14+32 bits exactly? no — 46 bits)
v_bits = jax.lax.bitcast_convert_type(vals, jnp.uint32)
key64 = rows.astype(jnp.float64) * 4294967296.0 + v_bits.astype(jnp.float64)
bench("lax.sort 1 f64 key + payload", sort_single_key, key64, wts)

starts = jnp.concatenate([jnp.ones((1,), bool), srows[1:] != srows[:-1]])
bench("segmented_cumsum", segcum, sw, starts)


bench("_compress_rows (2C cand)", compress, pool.means, pool.weights)

qs = jnp.asarray(np.array([0.5, 0.9, 0.99], np.float32))
bench("quantile x3", quant, pool.means, pool.weights, pool.min, pool.max, qs)

# 1M-series shapes for the flush-latency budget
S2 = 1 << 20
pool2 = td.init_pool(S2, C)
N2 = N


@jax.jit
def compress_1m(means, weights):
    cat_m = jnp.concatenate([means, means], axis=-1)
    cat_w = jnp.concatenate([weights, weights], axis=-1)
    return td._compress_rows(cat_m, cat_w, 100.0, C)


bench("_compress_rows 1M series", compress_1m, pool2.means, pool2.weights)
bench("quantile x3 1M series", quant, pool2.means, pool2.weights,
      pool2.min, pool2.max, qs)

# The product's round-4 hot path: one staged-plane fold per interval
# (core/worker._histo_fold_staged). add_batch above remains the spill /
# import-merge path. (The fused Pallas scan kernel that used to be A/B'd
# here was deleted with the staged redesign — see _prefix_scans_xla's
# docstring in ops/tdigest.py.)
from veneur_tpu.core.worker import _histo_fold_staged  # noqa: E402

B = 64
sv = jnp.asarray(rng.gamma(2.0, 50.0, (S, B)).astype(np.float32))
sw_plane = jnp.asarray(np.ones((S, B), np.float32))


def staged_fold(pool, sv, sw_plane):
    def _full(v):
        return jnp.full((S,), v, jnp.float32)

    return _histo_fold_staged(
        jnp.array(pool.means), jnp.array(pool.weights),
        jnp.array(pool.min), jnp.array(pool.max), jnp.array(pool.recip),
        _full(0.0), _full(np.inf), _full(-np.inf), _full(0.0), _full(0.0),
        _full(0.0), _full(0.0), _full(0.0), _full(0.0), sv, sw_plane)


bench(f"staged fold [S={S}, B={B}] (={S * B} samples)", staged_fold,
      pool, sv, sw_plane)
