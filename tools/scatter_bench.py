#!/usr/bin/env python3
"""scatter_bench.py: what the micro-fold mirror's layout costs on the chip
(PRs 39, 41).

At each mirror size (--rows; the cells' 524,288 and 2,097,152) one
65,536-entry chunk, its tail padded with DROP_ROW as a flush's last chunk
is, goes three ways:

- `flat`: the program's scatter (`ops/microfold._scatter_chunk`) into the
  flat float32[M x 64] arrays the mirror keeps since PR 41;
- `planes`: the scatter into [M, 64] arrays that the mirror kept before,
  which lives on in this script alone: XLA copies each plane whole into a
  linear array, scatters there and converts it back;
- `dense`: `ops/microfold.mirror_dense`, flat -> [M/2, 64] (a pool half
  full, as the cells' is): the one change of layout the flat mirror pays,
  once per array per flush and not once per chunk.

It holds `dense` of the flat mirror to bitwise equality with the planes'
prefix on the device, and gives both sums.

    chiprun -- python tools/scatter_bench.py [--rows 524288,2097152]

One JSON line; times are host-clock milliseconds around a call that ends
in block_until_ready, least and median of --reps, after one call that
compiles.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from veneur_tpu.ops import microfold as mf  # noqa: E402

DEPTH = 64


@functools.partial(jax.jit, donate_argnums=(0, 1))
def planes_scatter(dvals, dwts, rows, slots, vals, wts):
    """The mirror's scatter up to PR 40."""
    return (dvals.at[rows, slots].set(vals, mode="drop"),
            dwts.at[rows, slots].set(wts, mode="drop"))


def timed(fn, state, reps):
    """([ms] of reps calls after one that compiles, the last result);
    `fn` maps the state to the next one (a donated mirror) or to a
    result that leaves it alone."""
    ms = []
    for i in range(reps + 1):
        t0 = time.perf_counter()
        out = fn(state)
        jax.block_until_ready(out)
        if i:
            ms.append((time.perf_counter() - t0) * 1e3)
        if isinstance(out, tuple):
            state = out
    return ms, out


def stats(ms):
    return {"ms_min": min(ms), "ms_median": statistics.median(ms)}


def bench(m, reps, rng):
    n = mf.MICRO_CHUNK
    real = n - n // 8
    # distinct (row, slot) pairs, as an epoch's are: rows anywhere in the
    # lower half, slots 0 and 1 (most timers hold two samples)
    at = rng.permutation(np.unique(rng.integers(0, m, 2 * n)))[:real]
    rows_np = np.full(n, mf.DROP_ROW, np.int32)
    slots_np = np.zeros(n, np.int32)
    rows_np[:real], slots_np[:real] = at // 2, at % 2
    coo = [jnp.asarray(a) for a in (
        rows_np, slots_np, rng.random(n, np.float32) + 0.5,
        np.ones(n, np.float32))]
    out = {}

    flat = tuple(jnp.zeros(m * DEPTH, jnp.float32) for _ in range(2))
    ms, flat = timed(
        lambda st: mf._scatter_chunk(*st, *coo, depth=DEPTH), flat, reps)
    out["flat"] = stats(ms)
    ms, dv = timed(lambda st: mf.mirror_dense(st[0], m // 2, DEPTH),
                   flat, reps)
    out["dense"] = stats(ms)
    dw = mf.mirror_dense(flat[1], m // 2, DEPTH)
    out["flat"].update(sum_vals=float(jnp.sum(dv)), sum_wts=float(jnp.sum(dw)))
    del flat

    planes = tuple(jnp.zeros((m, DEPTH), jnp.float32) for _ in range(2))
    ms, planes = timed(lambda st: planes_scatter(*st, *coo), planes, reps)
    out["planes"] = dict(stats(ms), sum_vals=float(jnp.sum(planes[0])),
                         sum_wts=float(jnp.sum(planes[1])))
    out["dense_equals_planes"] = bool(
        jnp.array_equal(dv, planes[0][:m // 2])
        & jnp.array_equal(dw, planes[1][:m // 2]))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="524288,2097152")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    dev = jax.devices()[0]
    out = {"device": dev.device_kind, "platform": dev.platform,
           "chunk": mf.MICRO_CHUNK, "depth": DEPTH, "reps": args.reps}
    rng = np.random.default_rng(args.seed)
    ok = True
    for m in (int(r) for r in args.rows.split(",")):
        out[str(m)] = got = bench(m, args.reps, rng)
        ok = ok and got["dense_equals_planes"]
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
