"""Compile rehearsal for TPU v5e at Table-1 sizes, with no chip attached.

Every Pallas kernel the tuner can enumerate on TPU, and one fused k-bucket
serving program, is lowered and compiled here for a *described* v5e:2x2
topology (one chip of it).  The TPU compiler refuses here what it would
refuse on the chip — an unsupported primitive, an unaligned slice, more
VMEM or SMEM than a core has — at no chip time.  Nothing runs: shapes come
from ``jax.ShapeDtypeStruct``, never arrays.

Sizes are Table 1's: ldoor (952,203 rows, max 49 nonzeros per row, so a
SELL width of 56) for the resident-x kernels, mesh_2048 (4,194,304 rows,
5-point stencil) for the column-slab kernel.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library at a time, so a test worker that
is not given this file must never load it.  Kernels get
``interpret=False`` explicitly, because ``kernels.ops.on_cpu()`` sees the
host CPU here.
"""
import math
import time
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

LDOOR_M = 952_203
LDOOR_W = 56  # Table 1 max nnz/row 49, 8-aligned
LDOOR_BAND = 1200
MESH2048_N = 4_194_304


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def compile_for_chip(fn, *shapes):
    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*shapes)
    compiled = lowered.compile()
    secs = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    print(f"compiled in {secs:.2f} s; {mem}")
    return lowered, compiled


def _chunks(m):
    from repro.kernels.sell_spmv import CHUNK_ALIGN

    n_chunks = -(-m // 8)
    return -(-n_chunks // CHUNK_ALIGN) * CHUNK_ALIGN


@pytest.mark.parametrize("chunk_tile", [8, 16])
def test_sell_spmv_resident_x_compiles_at_ldoor(spec, chunk_tile):
    from repro.kernels.sell_spmv import sell_spmv_pallas

    nch = _chunks(LDOOR_M)
    _, c = compile_for_chip(
        lambda cols, vals, x: sell_spmv_pallas(
            cols, vals, x, chunk_tile=chunk_tile, interpret=False
        ),
        spec((nch, 8, LDOOR_W), jnp.int32),
        spec((nch, 8, LDOOR_W), jnp.float32),
        spec((LDOOR_M,), jnp.float32),
    )
    assert "tpu_custom_call" in c.as_text()


def test_sell_spmv_column_slab_compiles_at_mesh_2048(spec):
    from repro.kernels.sell_spmv import sell_spmv_blocked_pallas

    n_slabs, nch = 2, _chunks(MESH2048_N)
    slab_n = MESH2048_N // n_slabs
    _, c = compile_for_chip(
        lambda cols, vals, x: sell_spmv_blocked_pallas(
            cols, vals, x, slab_n=slab_n, interpret=False
        ),
        spec((n_slabs, nch, 8, 8), jnp.int32),
        spec((n_slabs, nch, 8, 8), jnp.float32),
        spec((n_slabs * slab_n,), jnp.float32),
    )
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("k", [1, 16])
def test_bcsr_spmm_compiles_at_ldoor(spec, k):
    """Lane-dense (8, 128) blocks, ldoor's band-sized x window."""
    from repro.kernels.bcsr_spmm import TILE_ROWS, bcsr_spmm_pallas
    from repro.kernels.ops import BLOCK_TILE

    bm, bk = 8, 128
    gm, gn = -(-LDOOR_M // bm), -(-LDOOR_M // bk)
    n_blocks = gm * 4 // BLOCK_TILE * BLOCK_TILE
    window = TILE_ROWS + 2 * LDOOR_BAND + 2 * bk
    _, c = compile_for_chip(
        lambda rows, cols, blocks, xb: bcsr_spmm_pallas(
            rows, cols, blocks, xb, n_block_rows=gm, window=window,
            interpret=False,
        ),
        spec((n_blocks,), jnp.int32),
        spec((n_blocks,), jnp.int32),
        spec((n_blocks, bm, bk), jnp.float32),
        spec((gn, bk, k), jnp.float32),
    )
    assert "tpu_custom_call" in c.as_text()


def test_spmspv_scatter_compiles_at_ldoor(spec):
    from repro.kernels.spmspv import spmspv_scatter_pallas

    G = 4096 * 64
    _, c = compile_for_chip(
        lambda rows, prods: spmspv_scatter_pallas(
            rows, prods, m=LDOOR_M, slab=4096, interpret=False
        ),
        spec((G,), jnp.int32),
        spec((G,), jnp.float32),
    )
    assert "tpu_custom_call" in c.as_text()


def test_fused_csr_vector_bucket_program_compiles_at_ldoor(spec):
    """The engine's k=16 serving program at ldoor's full size, built as the
    engine builds it: ``fused_batch_executable`` over the csr/vector
    ``runner`` (on-device assembly + SpMM in one launch).  The prepared
    arrays enter as arguments: its HLO carries no matrix-sized constant,
    and the compile stays in seconds."""
    from repro.runtime.executable import fused_batch_executable
    from repro.tune.candidates import Candidate
    from repro.tune.operator import runner

    nnz, bucket = 21_723_010, 16
    dev = {
        "data": jnp.zeros((nnz,), jnp.float32),
        "indices": jnp.zeros((nnz,), jnp.int32),
        "rows": jnp.zeros((nnz,), jnp.int32),
    }
    # runner() reads only the shape of the matrix for csr/vector.
    shape_only = types.SimpleNamespace(shape=(LDOOR_M, LDOOR_M))
    run = runner(shape_only, Candidate("csr", "vector"), {"dev": dev}, k=bucket)
    prog = fused_batch_executable(run, bucket=bucket)

    t0 = time.perf_counter()
    lowered = prog.lower(*[spec((LDOOR_M,), jnp.float32)] * bucket)
    c = lowered.compile()
    mem = c.memory_analysis()
    print(f"compiled in {time.perf_counter() - t0:.2f} s; {mem}")
    assert len(lowered.as_text()) < 100_000  # no matrix-sized constants
    assert mem.argument_size_in_bytes >= 3 * nnz * 4
    assert mem.output_size_in_bytes >= LDOOR_M * bucket * 4  # tile-padded
    # Temporaries: the gathered (nnz, 16) product slab dominates.
    assert mem.temp_size_in_bytes < 16 * 1024**3
    assert math.isfinite(mem.temp_size_in_bytes)
