"""Batched HyperLogLog on TPU.

Semantics spec: the reference's vendored axiomhq/hyperloglog sketch
(precision p=14 → 2^14 registers, used by samplers.Set,
samplers/samplers.go:367-463). Re-designed for SIMD execution:

* A pool of S sketches is one dense `int8[S, 2^p]` register array (p=14 ⇒
  16384 = 128×128 registers per row, one TPU tile-aligned panel): dense
  rows are what makes insert a single scatter and merge a single
  elementwise max, at 2^p bytes a series. The reference's sparse
  representation lives one level up, in ops/staged_sets.py: small sets
  stay on the host as (row, register, rank) triples and only a set past
  2^p/8 distinct registers takes a row of this pool.

* Values are hashed host-side (strings never touch the device); the 64-bit
  hash splits into a p-bit register index and the leading-zero rank of the
  remaining bits — see `split_hashes`.

* insert = one `scatter-max` per batch over the whole pool; cross-host
  merge = elementwise `maximum` (the associative reduce the global tier
  runs over ICI); estimate = one vectorized harmonic-mean reduction per
  flush with linear counting for the small-cardinality regime.

The estimator is classic HLL with linear counting below 2.5m (the 64-bit
hash needs no large-range correction). The reference's axiomhq sketch uses
the LogLog-Beta estimator; both sit within the same ~1.04/√m error envelope,
which is what the tests assert.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.ops import exactnum as exn

DEFAULT_PRECISION = 14  # matches reference (axiomhq) precision


def num_registers(precision: int = DEFAULT_PRECISION) -> int:
    return 1 << precision


def init_pool(num_rows: int, precision: int = DEFAULT_PRECISION) -> jax.Array:
    return jnp.zeros((num_rows, num_registers(precision)), dtype=jnp.int8)


def split_hashes(
    hashes: np.ndarray, precision: int = DEFAULT_PRECISION
) -> tuple[np.ndarray, np.ndarray]:
    """Split 64-bit hashes into (register index, rank) host-side.

    index = top p bits; rank = #leading zeros of the remaining 64-p bits,
    plus one (capped at 64-p+1 when those bits are all zero).
    """
    h = hashes.astype(np.uint64)
    idx = (h >> np.uint64(64 - precision)).astype(np.int32)
    w = (h << np.uint64(precision)) & np.uint64(0xFFFFFFFFFFFFFFFF)
    # clz via float64 exponent: highest set bit of w is frexp-exponent - 1.
    # w == 0 → rank = 64-p+1. Values within 2^-52 of a power of two can
    # round the exponent up by one; that's a 1-in-2^40 rank-off-by-one on a
    # random hash — far below HLL's intrinsic error.
    nonzero = w != 0
    _, exp = np.frexp(w.astype(np.float64))
    clz = 64 - exp
    rank = np.where(nonzero, clz + 1, 64 - precision + 1).astype(np.int8)
    rank = np.minimum(rank, np.int8(64 - precision + 1))
    return idx, rank


@jax.jit
def insert_batch(
    registers: jax.Array,
    rows: jax.Array,
    reg_idx: jax.Array,
    rank: jax.Array,
) -> jax.Array:
    """Batch-max a set of (row, register, rank) updates into the pool.

    rows: i32[N] sketch row per sample (padding: rank 0 — a no-op since
    registers are >= 0).

    TPU-first formulation: a raw scatter-max with duplicate (row, register)
    indices serializes on TPU. Instead, sort by (flat register slot, rank);
    the LAST element of each equal-slot run then holds that slot's max, so
    a scatter against the sorted index vector applies the whole batch.
    Non-run-end elements keep their (sorted, duplicate) index but have
    their rank zeroed — max with 0 is a no-op since registers are >= 0 —
    so the indices_are_sorted=True promise to XLA holds exactly
    (duplicates allowed, hence unique_indices=False).
    """
    s, m = registers.shape
    flat = rows * m + reg_idx  # fits i32 for s·m < 2^31 (s ≤ 2^17 at p=14)
    rank32 = rank.astype(jnp.int32)
    with jax.named_scope("hll.insert.sort"):
        sflat, srank = jax.lax.sort((flat, rank32), dimension=0,
                                    num_keys=2)
    with jax.named_scope("hll.insert.scatter_max"):
        is_end = jnp.concatenate(
            [sflat[1:] != sflat[:-1], jnp.ones((1,), bool)])
        vals = jnp.where(is_end, srank, 0)  # non-run-end → no-op max(·, 0)
        out = registers.reshape(-1).at[sflat].max(
            vals.astype(registers.dtype), mode="drop",
            indices_are_sorted=True, unique_indices=False)
    return out.reshape(s, m)


@jax.jit
def insert_batch_scatter(
    registers: jax.Array,
    rows: jax.Array,
    reg_idx: jax.Array,
    rank: jax.Array,
) -> jax.Array:
    """Plain duplicate-index scatter-max variant (kept for A/B against
    `insert_batch` on hardware)."""
    return registers.at[rows, reg_idx].max(rank, mode="drop")


@jax.jit
def merge(a: jax.Array, b: jax.Array) -> jax.Array:
    """Register-wise max — the associative cross-host reduce
    (reference Set.Combine, samplers/samplers.go:423-435)."""
    return jnp.maximum(a, b)


@functools.partial(jax.jit, static_argnames=("precision",))
def estimate(registers: jax.Array, precision: int = DEFAULT_PRECISION
             ) -> jax.Array:
    """Cardinality estimate per row: int8[S, m] → f32[S].

    Harmonic-mean estimator with linear counting below 2.5m.

    Order-pinned form (host fallback parity, see ops/exactnum.py): the
    transcendentals become host-precomputed f32 tables read by integer
    gathers (exp2(-rank) is 65 entries; the linear-counting m·ln(m/z)
    is indexed by the integer zero count), and the Σ 2^-reg reduction is
    a pairwise halving tree — so ops/host_engine.py reproduces every
    estimate bitwise.
    """
    m = float(num_registers(precision))
    with jax.named_scope("hll.estimate"):
        ranks = registers.astype(jnp.int32)
        ept = jnp.asarray(exn.exp2_neg_table())
        inv_sum = exn.tsum(ept[ranks])  # Σ 2^-reg, fixed association
        zeros = jnp.sum((registers == 0).astype(jnp.int32), axis=-1)
        raw = jnp.asarray(exn.hll_alpha_m2(precision)) / inv_sum
        linear = jnp.asarray(exn.hll_linear_table(precision))[zeros]
        use_linear = (raw <= jnp.float32(2.5 * m)) & (zeros > 0)
        return jnp.where(use_linear, linear, raw)


# ---------------------------------------------------------------------------
# Host-side helpers (codec / single-sketch use)


def registers_to_bytes(row: np.ndarray) -> bytes:
    """Dense register row → wire bytes (see distributed/codec.py)."""
    return np.asarray(row, dtype=np.int8).tobytes()


def registers_from_bytes(data: bytes, precision: int = DEFAULT_PRECISION
                         ) -> np.ndarray:
    arr = np.frombuffer(data, dtype=np.int8)
    if arr.shape[0] != num_registers(precision):
        raise ValueError(
            f"HLL payload has {arr.shape[0]} registers, expected"
            f" {num_registers(precision)}"
        )
    return arr
