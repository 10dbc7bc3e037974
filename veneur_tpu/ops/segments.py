"""Scatter-free segmented-scan primitives for TPU.

XLA lowers `segment_sum`/`segment_min` on TPU to scatters and large
`searchsorted` calls to gather-chain binary searches; both run far below
VPU peak (measured ~9ns/element on v5e; 9.5 ns a search step over 50.3M
elements against a 100-entry table, PERF_LEDGER.jsonl PR 25 — which is
why exactnum.kscale_bucket counts comparisons). These primitives keep
segmented reductions in cumsum/select territory instead:

* `segmented_cumsum` — chunked Hillis-Steele scan with a segmented
  cross-chunk carry stitch; no scatter, no per-segment loop.
* `last_marked_carry` — exclusive "value at the last marked position"
  scan, the building block that turns per-run sums into differences of
  prefix sums at run boundaries (ops/tdigest.py uses it for t-digest
  bucket accumulation).

Every float add here happens in a fixed, explicitly-coded order (the
doubling-shift loops), and each primitive has a NumPy twin running the
IDENTICAL loop — the bit-parity contract the host fallback engine
(ops/host_engine.py) is built on; see ops/exactnum.py for why. The
earlier cross-chunk stitch used `lax.associative_scan` over affine
maps, whose recursive association XLA owns and NumPy cannot mirror; the
carry is itself just a segmented scan over chunk totals, so it now runs
the same Hillis loop at the chunk level.

Used by the t-digest batch ingest (ops/tdigest.py); the reference's
equivalent inner loop is the per-centroid Go walk in
tdigest/merging_digest.go:140-224, which has no batched analog.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 128  # one TPU lane tile


def _pad_to_chunks(x: jax.Array, fill) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % CHUNK
    if pad:
        x = jnp.concatenate(
            [x, jnp.full((pad,), fill, dtype=x.dtype)])
    return x.reshape(-1, CHUNK)


def _np_pad_to_chunks(x: np.ndarray, fill) -> np.ndarray:
    n = x.shape[0]
    pad = (-n) % CHUNK
    if pad:
        x = np.concatenate(
            [x, np.full((pad,), fill, dtype=x.dtype)])
    return x.reshape(-1, CHUNK)


def _shift_right(x: jax.Array, fill) -> jax.Array:
    return jnp.concatenate(
        [jnp.full((1,), fill, dtype=x.dtype), x[:-1]])


def segmented_cumsum(values: jax.Array, starts: jax.Array) -> jax.Array:
    """Inclusive cumulative sum of `values` that restarts wherever
    `starts` is True (position 0 is implicitly a start).

    values: f32[N]; starts: bool[N]. Returns f32[N].
    """
    with jax.named_scope("segments.segmented_cumsum"):
        n = values.shape[0]
        v2 = _pad_to_chunks(values, 0.0)
        s2 = _pad_to_chunks(starts, False)
        s2 = s2.at[0, 0].set(True)
        g, l = v2.shape

        # Per-chunk segmented Hillis-Steele scan (col 0 treated as a reset;
        # the true cross-chunk carry is stitched below).
        v = v2
        f = s2.at[:, 0].set(True)
        shift = 1
        while shift < l:
            vs = jnp.pad(v, ((0, 0), (shift, 0)))[:, :l]
            fs = jnp.pad(f, ((0, 0), (shift, 0)), constant_values=True)[:, :l]
            v = jnp.where(f, v, v + vs)
            f = f | fs
            shift *= 2

        # Cross-chunk carry: the open-run total entering chunk g is itself a
        # segmented inclusive cumsum of the chunks' last-column values,
        # restarting at any chunk that contains a real start — the SAME
        # Hillis loop as above, run once at the chunk level.
        has_start = jnp.any(s2, axis=1)
        cv = v[:, -1]
        cf = has_start.at[0].set(True)
        shift = 1
        while shift < g:
            cvs = jnp.pad(cv, (shift, 0))[:g]
            cfs = jnp.pad(cf, (shift, 0), constant_values=True)[:g]
            cv = jnp.where(cf, cv, cv + cvs)
            cf = cf | cfs
            shift *= 2
        carry_in = _shift_right(cv, jnp.zeros((), values.dtype))
        # carry applies to the head run only: elements before the first real
        # start of the chunk. (Select, not multiply-by-mask: the add order
        # stays pinned and nothing invites contraction.)
        before_first = jnp.cumsum(s2.astype(jnp.int32), axis=1) == 0
        out = jnp.where(before_first, v + carry_in[:, None], v)
        return out.reshape(-1)[:n]


def np_segmented_cumsum(values: np.ndarray, starts: np.ndarray) -> np.ndarray:
    """NumPy twin of `segmented_cumsum`: the identical shift loops, so
    the result is bitwise equal to the device kernel's."""
    values = np.asarray(values)
    n = values.shape[0]
    v = _np_pad_to_chunks(values, values.dtype.type(0))
    s2 = _np_pad_to_chunks(np.asarray(starts, bool), False).copy()
    s2[0, 0] = True
    g, l = v.shape

    f = s2.copy()
    f[:, 0] = True
    shift = 1
    while shift < l:
        vs = np.pad(v, ((0, 0), (shift, 0)))[:, :l]
        fs = np.pad(f, ((0, 0), (shift, 0)), constant_values=True)[:, :l]
        v = np.where(f, v, v + vs)
        f = f | fs
        shift *= 2

    has_start = np.any(s2, axis=1)
    cv = v[:, -1]
    cf = has_start.copy()
    cf[0] = True
    shift = 1
    while shift < g:
        cvs = np.pad(cv, (shift, 0))[:g]
        cfs = np.pad(cf, (shift, 0), constant_values=True)[:g]
        cv = np.where(cf, cv, cv + cvs)
        cf = cf | cfs
        shift *= 2
    carry_in = np.concatenate(
        [np.zeros((1,), values.dtype), cv[:-1]])
    before_first = np.cumsum(s2.astype(np.int32), axis=1) == 0
    out = np.where(before_first, v + carry_in[:, None], v)
    return out.reshape(-1)[:n].astype(values.dtype)


def last_marked_carry(mask: jax.Array, *values: jax.Array
                      ) -> tuple[jax.Array, ...]:
    """Along the last axis, carry each payload forward from the most
    recent *strictly earlier* position where ``mask`` is True (exclusive
    scan; positions before any mark carry 0).

    mask: bool[..., L]; values: f32[..., L] each. Returns one array per
    payload. log2(L) elementwise select steps — no gathers, no scatters.
    (Hand-rolled Hillis-Steele jumps rather than lax.associative_scan:
    the scan's recursive slicing stalls the TPU compiler when fused into
    a larger program — observed >30min on v5e for _compress_rows — while
    this loop, the same shape as segmented_cumsum's, compiles in
    seconds.)
    """
    with jax.named_scope("segments.last_marked_carry"):
        pad = [(0, 0)] * (mask.ndim - 1) + [(1, 0)]
        m = jnp.pad(mask, pad)[..., :-1]
        vs = [jnp.pad(v, pad)[..., :-1] for v in values]
        n = m.shape[-1]

        def shift_right(x, k, fill=False):
            p = [(0, 0)] * (x.ndim - 1) + [(k, 0)]
            return jnp.pad(x, p, constant_values=fill)[..., :n]

        shift = 1
        while shift < n:
            # invariant: (m, vs) at i reflect the last mark in (i-2^k, i]
            m_s = shift_right(m, shift)
            vs = [jnp.where(m, v, shift_right(v, shift, 0))
                  for v in vs]
            m = m | m_s
            shift *= 2
        return tuple(vs)


def np_last_marked_carry(mask: np.ndarray, *values: np.ndarray
                         ) -> tuple[np.ndarray, ...]:
    """NumPy twin of `last_marked_carry` (selects and shifts only, in
    the identical order — bitwise equal by construction)."""
    mask = np.asarray(mask, bool)
    pad = [(0, 0)] * (mask.ndim - 1) + [(1, 0)]
    m = np.pad(mask, pad)[..., :-1]
    vs = [np.pad(np.asarray(v), pad)[..., :-1] for v in values]
    n = m.shape[-1]

    def shift_right(x, k, fill=False):
        p = [(0, 0)] * (x.ndim - 1) + [(k, 0)]
        return np.pad(x, p, constant_values=fill)[..., :n]

    shift = 1
    while shift < n:
        m_s = shift_right(m, shift)
        vs = [np.where(m, v, shift_right(v, shift, 0))
              for v in vs]
        m = m | m_s
        shift *= 2
    return tuple(vs)
