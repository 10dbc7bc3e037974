"""exactnum.kscale_bucket: the device's count of comparisons equals the
host's searchsorted bit for bit, and stays a count (no search, no
gather) in every t-digest program that calls it."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veneur_tpu.ops import exactnum as exn


def _probe(compression: float, seed: int) -> np.ndarray:
    """f32 q that a bucketing could get wrong: random values, every
    boundary and its two float neighbours, zeros, one, values outside
    [0, 1], infinities, NaN and a subnormal."""
    rng = np.random.default_rng(seed)
    btab = exn.kscale_boundaries(compression)
    special = np.array(
        [0.0, -0.0, 1.0, 1.5, -1.0, 2.0, -1e-3, np.inf, -np.inf, np.nan,
         1e-45, np.finfo(np.float32).tiny, np.nextafter(np.float32(1), 0)],
        np.float32)
    return np.concatenate([
        rng.random(20000, dtype=np.float32),
        rng.normal(0.5, 1.0, 2000).astype(np.float32),
        btab,
        np.nextafter(btab, np.float32(-np.inf)),
        np.nextafter(btab, np.float32(np.inf)),
        special,
    ])


@pytest.mark.parametrize("compression", [20.5, 50.0, 100.0, 200.0, 1000.0])
def test_kscale_bucket_equals_its_numpy_twin_bitwise(compression):
    bucket = jax.jit(exn.kscale_bucket, static_argnums=1)
    q = _probe(compression, seed=int(compression * 2))
    want = exn.np_kscale_bucket(q, compression)
    assert want.max() == int(np.floor(compression))  # NaN, +inf, q >= 1
    got = np.asarray(bucket(jnp.asarray(q), compression))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    # [S, M], as _compress_rows hands it over
    m = 64
    q2 = np.resize(q, (-(-q.size // m), m))
    got2 = np.asarray(bucket(jnp.asarray(q2), compression))
    np.testing.assert_array_equal(got2, exn.np_kscale_bucket(q2, compression))


#: what a search over the table, or any per-element read of it, is made of
_SEARCH_PRIMITIVES = {"while", "scan", "gather", "dynamic_slice", "sort"}


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its inner jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_kscale_bucket_is_elementwise():
    q = jnp.zeros((8, 192), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: exn.kscale_bucket(x, 100.0))(q).jaxpr
    eqns = list(_eqns(jaxpr))
    assert not {e.primitive.name for e in eqns} & _SEARCH_PRIMITIVES
    # and nothing shaped [⌊δ⌋, ...]: every value is a scalar or q's shape
    shapes = {tuple(v.aval.shape) for e in eqns for v in e.outvars}
    assert shapes <= {(), (8, 192)}, shapes


def _programs():
    from veneur_tpu.core import worker as wk

    f32, i32 = jnp.float32, jnp.int32
    rows, depth, k, n = 256, 8, 64, 256
    fields = ([jnp.zeros((rows, 128), f32)] * 2
              + [jnp.zeros((rows,), f32)] * 12)
    plane = jnp.zeros((rows, depth), f32)
    return {
        "fold_staged": lambda: wk._histo_fold_staged.lower(
            *fields, plane, plane, compression=100.0),
        "ingest_step": lambda: wk._histo_ingest_step.lower(
            *fields, jnp.zeros((k,), i32), jnp.zeros((n,), i32),
            jnp.zeros((n,), f32), jnp.zeros((n,), f32), compression=100.0),
    }


#: '%while.17 = (s32[], ...) while(...), ..., metadata={op_name="..."}'
_INSTR = re.compile(
    r'^\s*(?:ROOT )?%[\w.\-]+ = .*? ([a-z][a-z\-]*)\(.*op_name="([^"]*)"',
    re.M)


@pytest.mark.parametrize("program", ["fold_staged", "ingest_step"])
def test_no_search_under_the_k_bucket_scope(program):
    """The counter that says the mechanism engaged: in the compiled
    program, every instruction under `tdigest.k_bucket` is elementwise
    (the search was `.../tdigest.k_bucket/jit(searchsorted)/.../while`)."""
    text = _programs()[program]().compile().as_text()
    ops = {op for op, path in _INSTR.findall(text)
           if "tdigest.k_bucket" in path}
    assert {"compare", "add"} <= ops, ops
    search = {"while", "gather", "dynamic-slice", "sort", "reduce",
              "scatter", "call", "conditional"}
    assert not ops & search, ops & search
