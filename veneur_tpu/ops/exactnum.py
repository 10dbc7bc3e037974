"""Order-pinned exact numerics: paired device (jnp) / host (np) kernels.

The device fault domain (ops/device_guard.py) fails a quarantined worker
over to a host NumPy sketch engine (ops/host_engine.py) whose flushes
must stay BYTE-identical to the device path — a degraded interval that
silently shifts every quantile would defeat the whole point of an
escape hatch. f32 arithmetic only delivers that when both sides execute
the *same sequence of IEEE-754 operations*, and three things normally
break it:

1. **Reductions/scans reassociate.** `jnp.sum`/`jnp.cumsum` lower to
   whatever tree XLA picks; NumPy runs strict left folds (with its own
   pairwise blocking). Fix: express every float reduction as an explicit
   Hillis-Steele scan (`cumsum`) or pairwise halving tree (`tsum`) whose
   loop structure is identical in both twins — then both sides perform
   literally the same adds in the same order.
2. **FMA contraction.** XLA/LLVM fuse `a*b + c` into one fused
   multiply-add; NumPy rounds the product first. `lax.optimization_barrier`
   does NOT stop it (verified: the barrier is stripped before fusion).
   Fix: `block(x) = where(x == x, x, 0)` — a NaN-semantics select the
   compiler cannot constant-fold or look through, so the product is
   rounded to f32 before it meets the add. The NumPy twin applies the
   same select (an identity for non-NaN values).
3. **Transcendentals differ per libm.** `arcsin`, `log`, `exp2` have no
   cross-implementation bit contract. Fix: precompute them on the host
   in f64, round once to f32, and ship the results as *tables* both
   sides read with exact integer gathers / comparison-exact
   searchsorted, or the count of the same comparisons
   (`kscale_boundaries` for the t-digest k-function,
   `EXP2_NEG_TABLE` / `hll_linear_table` for the HLL estimator).

Division, sqrt, min/max, comparisons, sorts (`lax.sort` is stable, like
`np.argsort(kind="stable")`), searchsorted, selects, and single add/sub
ops are IEEE-correctly-rounded on both sides and need no treatment.

A welcome side effect: with the transcendentals gone and every
reduction order pinned, the *device* path itself becomes reproducible
across backends (TPU f32 mul/add/div are IEEE) instead of merely within
one compiled executable.
"""

from __future__ import annotations

import functools
import math

import jax.numpy as jnp
import numpy as np


def next_pow2(n: int, floor: int = 1) -> int:
    v = max(int(n), floor)
    return 1 << (v - 1).bit_length()


# ---------------------------------------------------------------------------
# FMA contraction blocker


def block(x):
    """Round a product to f32 before it can contract into an add.

    `where(x == x, x, 0)` is an identity for every non-NaN value, but its
    NaN semantics stop XLA from folding it away — the multiply's result
    must materialize, so `block(a*b) + c` performs a rounded multiply
    then a rounded add on both device and host."""
    return jnp.where(x == x, x, jnp.zeros_like(x))


def np_block(x):
    x = np.asarray(x)
    return np.where(x == x, x, np.zeros_like(x))


# ---------------------------------------------------------------------------
# Order-pinned scans and reductions (last axis)


def cumsum(x):
    """Inclusive prefix sum along the last axis as a Hillis-Steele
    doubling scan: log2(n) vectorized adds in a fixed order. The np twin
    runs the identical loop, so results are bitwise equal."""
    n = x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 1)
    shift = 1
    while shift < n:
        x = x + jnp.pad(x, pad + [(shift, 0)])[..., :n]
        shift *= 2
    return x


def np_cumsum(x):
    x = np.asarray(x)
    n = x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 1)
    shift = 1
    while shift < n:
        x = x + np.pad(x, pad + [(shift, 0)])[..., :n]
        shift *= 2
    return x


def tsum(x):
    """Sum along the last axis as a pairwise halving tree (zero-padded
    to a power of two): the one fixed association both twins share."""
    n = x.shape[-1]
    p = next_pow2(n)
    if p != n:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, p - n)]
        x = jnp.pad(x, pad)
    while p > 1:
        x = x[..., 0::2] + x[..., 1::2]
        p //= 2
    return x[..., 0]


def np_tsum(x):
    x = np.asarray(x)
    n = x.shape[-1]
    p = next_pow2(n)
    if p != n:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, p - n)]
        x = np.pad(x, pad)
    while p > 1:
        x = x[..., 0::2] + x[..., 1::2]
        p //= 2
    return x[..., 0]


def tsum0(x):
    """Tree sum over axis 0 (stacked-pool merges)."""
    return tsum(jnp.moveaxis(x, 0, -1))


def np_tsum0(x):
    return np_tsum(np.moveaxis(np.asarray(x), 0, -1))


# ---------------------------------------------------------------------------
# t-digest k-function bucketing, table form
#
# The scale function k(q) = δ·(asin(2q−1)/π + ½) is only ever used as
# floor(k(q)) — a bucket id. Inverting it once on the host turns the
# device-side arcsin into a question about the δ bucket boundaries
# q_j = (sin(π(j/δ − ½)) + 1)/2, j = 1..⌊δ⌋: bucket(q) is the number of
# boundaries ≤ q, i.e. searchsorted(side="right"). Comparisons are
# exact and so are integer sums, so both twins agree bitwise. The host
# searches; the device counts: ⌊δ⌋ compares and adds an element, all
# elementwise, where a binary search is log2(δ) dependent gathers from
# the table (9.5 ns a step on a v5e, ops/segments.py).


@functools.lru_cache(maxsize=None)
def kscale_boundaries(compression: float) -> np.ndarray:
    """f32[⌊δ⌋] ascending bucket boundaries for floor(k1_δ(q)),
    computed in f64 and rounded once."""
    delta = float(compression)
    j = np.arange(1, int(math.floor(delta)) + 1, dtype=np.float64)
    q = (np.sin(np.pi * (j / delta - 0.5)) + 1.0) / 2.0
    return np.clip(q, 0.0, 1.0).astype(np.float32)


def kscale_bucket(q, compression: float):
    """floor(k1_δ(q)) for f32 q in [0, 1], table form (device): the
    count of boundaries ≤ q, one scalar boundary at a time, so the
    traced program is elementwise whatever the compiler fuses — no
    gather, no loop, no [⌊δ⌋, ...] intermediate."""
    btab = kscale_boundaries(compression)
    count = jnp.zeros(jnp.shape(q), jnp.int32)
    for b in btab:
        count = count + (q >= b).astype(jnp.int32)
    # NaN compares false with every boundary; NumPy sorts it past them all
    return jnp.where(q != q, jnp.int32(btab.size), count)


def np_kscale_bucket(q, compression: float):
    btab = kscale_boundaries(compression)
    return np.searchsorted(
        btab, np.asarray(q, np.float32), side="right").astype(np.int32)


# ---------------------------------------------------------------------------
# HLL estimator tables
#
# exp2(-rank) over int8 ranks 0..64 is a 65-entry gather; the linear-
# counting branch m·ln(m/z) is a (m+1)-entry gather by the integer
# zero-register count. Both tables are f64-computed, f32-rounded once.

_EXP2_NEG_TABLE = np.exp2(-np.arange(65, dtype=np.float64)).astype(np.float32)


def exp2_neg_table() -> np.ndarray:
    """f32[65]: exp2(-r) for register ranks r = 0..64."""
    return _EXP2_NEG_TABLE


@functools.lru_cache(maxsize=None)
def hll_linear_table(precision: int) -> np.ndarray:
    """f32[m+1]: m·ln(m / max(z, 1)) by zero-register count z."""
    m = float(1 << precision)
    z = np.maximum(np.arange((1 << precision) + 1, dtype=np.float64), 1.0)
    return (m * np.log(m / z)).astype(np.float32)


@functools.lru_cache(maxsize=None)
def hll_alpha_m2(precision: int) -> np.float32:
    """f32: α_m · m² for the harmonic-mean estimator, rounded once."""
    m = float(1 << precision)
    alpha = 0.7213 / (1.0 + 1.079 / m)
    return np.float32(alpha * m * m)
