"""The served path's device programs, compiled for a TPU v5e that is
described and not attached, at the size chip_smoke.py runs: S = 2^20
timer rows, C = 128 centroids, staging depth 64.

What this guards: the TPU compiler refuses a program (a kernel slice off
the tiling, a program that cannot fit 16 GiB) here, at no chip time,
before a chip run meets it. What it cannot say: anything about results
or times, or about what else the process keeps on the device beside one
program. A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold libtpu, and every xdist worker imports
every test file. All of these tests stay in this one file for the same
reason, and compile in the test's own process.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

S = 1 << 20          # timer rows (chip_smoke.py --series)
C = 128              # centroids per row (ops/tdigest.DEFAULT_CAPACITY)
DEPTH = 64           # tpu_stage_depth
P = 3                # percentiles 0.5, 0.75, 0.99
SETS = 1024          # chip_smoke.py's set count
HBM_BYTES = 16 << 30  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile can be written to the persistent cache but not read
    # back without a chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _fields(one_chip, rows=S):
    """The 14 pool arrays in HistoDeviceState.fields() order."""
    return ([_shape(one_chip, (rows, C))] * 2
            + [_shape(one_chip, (rows,))] * 12)


def _fits(name, compiled):
    """One program's own footprint must leave room on the chip."""
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: args {m.argument_size_in_bytes / 1e9:.2f} GB, "
          f"out {m.output_size_in_bytes / 1e9:.2f} GB, "
          f"temp {m.temp_size_in_bytes / 1e9:.2f} GB, "
          f"aliased {m.alias_size_in_bytes / 1e9:.2f} GB, "
          f"total {total / 1e9:.2f} GB")
    assert total < HBM_BYTES, f"{name} needs {total} bytes"
    return total


def test_fold_staged(one_chip):
    from veneur_tpu.core.worker import _histo_fold_staged

    plane = _shape(one_chip, (S, DEPTH))
    compiled = _histo_fold_staged.lower(
        *_fields(one_chip), plane, plane, compression=100.0).compile()
    _fits("_histo_fold_staged", compiled)


def test_flush_extract_tpu_branch(one_chip, monkeypatch):
    """The select+reduce slot pick (ops/tdigest.quantile's TPU branch),
    not the gather a CPU process would trace."""
    from veneur_tpu.core.worker import _histo_flush_extract
    from veneur_tpu.ops import tdigest as td
    from veneur_tpu.utils import backend

    monkeypatch.setattr(backend, "is_tpu_backend", lambda: True)
    picked = []
    impl = td._quantile_impl

    def spy(*args, use_gather):
        picked.append(use_gather)
        return impl(*args, use_gather=use_gather)

    monkeypatch.setattr(td, "_quantile_impl", spy)
    compiled = _histo_flush_extract.lower(
        *_fields(one_chip), _shape(one_chip, (P,))).compile()
    assert picked == [False], "the CPU gather branch was traced"
    _fits("_histo_flush_extract", compiled)


def test_expand_flat_planes(one_chip):
    from veneur_tpu.core.worker import _expand_flat_planes

    # 2 samples a series plus 64 staged per hot series, padded to a pow2
    flat = _shape(one_chip, (1 << 22,))
    compiled = _expand_flat_planes.lower(
        flat, flat, _shape(one_chip, (S,), jnp.int32),
        depth=DEPTH, unit=True).compile()
    _fits("_expand_flat_planes", compiled)


def test_micro_fold_scatter(one_chip):
    from veneur_tpu.ops import microfold as mf

    # the mirror of a 2^20-series pool: 2 x S rows, flat (PR 41)
    mirror = _shape(one_chip, (2 * S * DEPTH,))
    idx = _shape(one_chip, (mf.MICRO_CHUNK,), jnp.int32)
    val = _shape(one_chip, (mf.MICRO_CHUNK,))
    compiled = mf._scatter_chunk.lower(
        mirror, mirror, idx, idx, val, val, depth=DEPTH).compile()
    _fits("microfold._scatter_chunk", compiled)
    # it scatters in place: into [M, 64] planes XLA copied each plane
    # whole into a linear array and back, 1.5 GiB of temp a chunk
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == 2 * 2 * S * DEPTH * 4
    assert m.temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("mirror_rows", [2 * S, S, S // 2],
                         ids=["slice", "whole", "pad"])
def test_micro_fold_mirror_dense(one_chip, mirror_rows):
    """flat -> [s_eff, depth], once per array per flush: one program a
    (mirror_rows, s_eff) pair, whichever of the two is the larger."""
    from veneur_tpu.ops import microfold as mf

    compiled = mf.mirror_dense.lower(
        _shape(one_chip, (mirror_rows * DEPTH,)), S, DEPTH).compile()
    _fits("microfold.mirror_dense", compiled)
    assert compiled.memory_analysis().output_size_in_bytes == S * DEPTH * 4


def test_dense_hll_insert_and_estimate(one_chip):
    from veneur_tpu.ops import hll

    m = hll.num_registers(14)
    regs = _shape(one_chip, (SETS, m), jnp.int8)
    k = 1 << 16  # StagedSetStore.compact_every
    idx = _shape(one_chip, (k,), jnp.int32)
    ins = hll.insert_batch.lower(
        regs, idx, idx, _shape(one_chip, (k,), jnp.int8)).compile()
    _fits("hll.insert_batch", ins)
    est = hll.estimate.lower(regs, precision=14).compile()
    _fits("hll.estimate", est)


@pytest.mark.parametrize("length", [1 << 10, 1 << 12, 1 << 14])
def test_staged_set_insert_at_each_ladder_length(one_chip, length):
    """The staged store's dense tier as a cell with a few dozen promoted
    rows runs it (PR 46): every insert is one of three lengths into the
    least pool, 64 rows; a program each, none of them refused."""
    from veneur_tpu.ops import hll
    from veneur_tpu.ops import staged_sets as st

    assert length in st.INSERT_LENGTHS and len(st.INSERT_LENGTHS) == 3
    regs = _shape(one_chip, (st.POOL_MIN_ROWS, hll.num_registers(14)),
                  jnp.int8)
    idx = _shape(one_chip, (length,), jnp.int32)
    ins = hll.insert_batch.lower(
        regs, idx, idx, _shape(one_chip, (length,), jnp.int8)).compile()
    _fits(f"hll.insert_batch[{length}]", ins)


def test_staged_set_pool_growth_and_estimate(one_chip):
    from veneur_tpu.ops import hll
    from veneur_tpu.ops import staged_sets as st

    rows = st.POOL_MIN_ROWS
    regs = _shape(one_chip, (rows, hll.num_registers(14)), jnp.int8)
    grown = st._grow_pool.lower(regs, rows=2 * rows).compile()
    _fits("staged_sets._grow_pool", grown)
    assert grown.memory_analysis().output_size_in_bytes >= 2 * rows * (
        hll.num_registers(14))
    _fits("hll.estimate[pool]",
          hll.estimate.lower(regs, precision=14).compile())
