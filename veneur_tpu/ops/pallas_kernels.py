"""Pallas TPU kernels for the flush hot path.

The flush-time percentile extraction at high cardinality (BASELINE.md: p99
flush latency at 1M histogram series) reads the whole digest pool. The XLA
path materializes several intermediates ([S,C] bounds, [S,C,P] reach masks)
in HBM; this kernel fuses the entire extraction — cumulative weights,
centroid bounds, quantile interpolation, sum/count aggregates — into one
VMEM pass per row block:

* cumsum along the 128-wide centroid axis is a [B,C]×[C,C] lower-triangular
  matmul (MXU work instead of a serial scan),
* per-quantile slot selection is a one-hot mask-and-reduce (no gathers —
  dynamic per-lane gathers don't vectorize on TPU),
* all P quantiles and the sum/count aggregates come out of the single load
  of means/weights.

Off by default (see supported()); the XLA implementation
(ops/tdigest.quantile et al.) is the served path. Tests run the kernel
in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from veneur_tpu.ops import tdigest as td

DEFAULT_BLOCK_ROWS = 256


def _extract_kernel(means_ref, weights_ref, dmin_ref, dmax_ref, qs_ref,
                    tril_ref, quant_ref, dsum_ref, dcount_ref):
    # Mosaic lowering constraints (interpret mode can't see them;
    # tests/test_tpu_compile.py compiles the kernel for a v5e):
    #   * every ref is rank-2 — rank-1 memrefs don't tile onto the
    #     (sublane, lane) register layout
    #   * no negative static indices (x[:, -1] lowers to dynamic_slice,
    #     unimplemented) — use the explicit positive index
    #   * no argmax (int reductions unsupported) — one-hot via a float
    #     min-reduce over a lane iota instead
    #   * no sublane-axis iota inside the kernel — the lower-triangular
    #     cumsum matmul matrix arrives as an operand
    means = means_ref[...]  # [B, C]
    weights = weights_ref[...]  # [B, C]
    dmin = dmin_ref[...][:, 0]  # [B, 1] -> [B]
    dmax = dmax_ref[...][:, 0]
    qs = qs_ref[...][0, :]  # [1, P] -> [P]
    b, c = means.shape
    p = qs.shape[0]

    # cumulative weight via lower-triangular matmul (rides the MXU)
    w_cum = jnp.dot(weights, tril_ref[...],
                    preferred_element_type=jnp.float32)
    total = w_cum[:, c - 1]  # [B]

    nonempty = weights > 0
    count = jnp.sum(nonempty.astype(jnp.float32), axis=-1)  # [B]

    idx = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
    idxf = idx.astype(jnp.float32)  # tpu.iota only produces integers
    # next-slot means: shift left, +inf in the last lane
    next_means = jnp.concatenate(
        [means[:, 1:], jnp.full((b, 1), jnp.inf, means.dtype)], axis=-1)
    mid = (means + next_means) * 0.5
    is_last = idx == (count.astype(jnp.int32) - 1)[:, None]
    ub = jnp.where(is_last, dmax[:, None], mid)
    lb = jnp.concatenate([dmin[:, None], ub[:, :-1]], axis=-1)

    # aggregates from the same load
    dsum_ref[...] = jnp.sum(jnp.where(nonempty, means * weights, 0.0),
                            axis=-1, keepdims=True)
    dcount_ref[...] = total[:, None]

    w_before = w_cum - weights
    safe_w = jnp.maximum(weights, 1e-30)
    empty_row = (total <= 0) | (count <= 0)
    cols = []
    for j in range(p):
        target = qs[j] * total  # [B]
        reached = target[:, None] <= w_cum  # [B, C]
        # first reached slot, argmax-free: min lane index where reached
        first = jnp.min(jnp.where(reached, idxf, jnp.inf), axis=-1)  # [B]
        sel = idxf == first[:, None]  # one-hot [B, C]
        proportion = (target[:, None] - w_before) / safe_w
        val_all = lb + proportion * (ub - lb)
        val = jnp.sum(jnp.where(sel, val_all, 0.0), axis=-1)
        cols.append(jnp.where(empty_row, jnp.nan, val))
    quant_ref[...] = jnp.stack(cols, axis=-1)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def flush_extract(means, weights, dmin, dmax, qs,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: bool = False):
    """Fused flush extraction: (quantiles [S,P], dsum [S], dcount [S])."""
    s, c = means.shape
    p = qs.shape[0]
    if s % block_rows:
        block_rows = min(block_rows, s)
        while s % block_rows:
            block_rows //= 2
    grid = (s // block_rows,)
    # cum[j] = Σ_{i<=j} w_i as a [C,C] matmul operand (in-kernel sublane
    # iota fails Mosaic verification; see _extract_kernel header)
    tril = jnp.asarray(
        (np.arange(c)[:, None] <= np.arange(c)[None, :])
        .astype(np.float32))
    quant, dsum, dcount = pl.pallas_call(
        _extract_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, p), lambda i: (0, 0)),
            pl.BlockSpec((c, c), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, p), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, p), jnp.float32),
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
        ],
        interpret=interpret,
    )(means, weights, dmin[:, None], dmax[:, None], qs[None, :], tril)
    return quant, dsum[:, 0], dcount[:, 0]


def flush_extract_reference(means, weights, dmin, dmax, qs):
    """The XLA path producing identical outputs (test oracle)."""
    quant = td.quantile(means, weights, dmin, dmax, qs)
    return quant, td.row_sum(means, weights), td.row_count(weights)


def supported() -> bool:
    """The Pallas extract runs only where it is asked for: on a TPU with
    VENEUR_PALLAS=1. It is off by default because it has not been timed
    against the XLA program on a chip (ROADMAP D5 decides whether it
    stays). Where it is on and fails, DeviceWorker._extract raises."""
    import os

    from veneur_tpu.utils.backend import is_tpu_backend

    return os.environ.get("VENEUR_PALLAS") == "1" and is_tpu_backend()
