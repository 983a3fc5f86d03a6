"""Pallas TPU kernel: SELL-C-sigma SpMV — the ``vgatherd`` adaptation.

The paper's -O3 SpMV packs 8 consecutive nonzeros of one row into a 512-bit
register and gathers the 8 matching x elements with ``vgatherd``; throughput
is set by how few cachelines the gather touches (UCLD, Fig 5).

TPUs have no HBM gather, and Mosaic has no vector gather across vregs
either: ``xv[cols]`` on a VMEM-resident x is refused ("Only 2D gather is
supported" — ``tpu.dynamic_gather`` reaches within one (8, 128) vreg).  So
the gather runs on the scalar core: SELL-C-sigma sorts rows by length
inside windows of ``sigma`` rows (the analogue of the paper's
``dynamic,64`` chunk scheduling) and packs C = 8 rows of W slots each; the
kernel streams the packed (cols, vals) tiles into SMEM through the shared
:mod:`repro.kernels.pipeline` slab pipeline (double-buffered DMA — the
paper's software prefetching), keeps x lane-dense in VMEM, and per slot
loads x's row ``col >> 7`` with a dynamic sublane slice and picks lane
``col & 127`` with a mask.  One kernel serves both layouts:

:func:`sell_spmv_pallas` — x resident in VMEM (one slab):

    cols/vals : ANY (HBM) -> SMEM tiles of T chunks   # double-buffered DMA
    x         : (n/128, 128) VMEM                     # resident
    y_sorted  : (m/128, 128) VMEM                     # written once

:func:`sell_spmv_blocked_pallas` — cache blocking for x beyond the VMEM
  budget (Nishtala et al. in the paper's refs): A is pre-split into column
  slabs, one SELL packing per slab over a *shared* row permutation, stacked
  rectangular; x slab ``s`` is copied into VMEM before the slab's tiles
  stream, and slab partials accumulate positionally in the sorted output.

The scalar-core gather costs a few cycles per stored slot (padding
included); on TPU these kernels compile and compete in the measured
search against XLA's own gathers, which are no faster per element.
The UTD metric (core.metrics) predicts these kernels' win over the scalar
tier exactly as UCLD predicts the vgatherd win in Fig 5.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compat import CompilerParams as _CompilerParams

from .pipeline import (
    LANES,
    SMEM,
    VMEM_LIMIT_BYTES,
    add_to_elem,
    lane_dense,
    lane_iota,
    resolve_pipelined,
    slab_pipeline,
)

__all__ = ["sell_spmv_pallas", "sell_spmv_blocked_pallas", "smem_tile_slots",
           "CHUNK_ALIGN"]


# The SELL preparations pad the chunk count to this multiple (1024 rows).
CHUNK_ALIGN = 128

# Mosaic slices a 1-D HBM array only in whole (1024,) tiles, so every SMEM
# tile of the (cols, vals) streams holds a multiple of 1024 slots.
HBM_1D_TILE = 1024


def _tile_chunks(chunk_tile: int, C: int, W: int) -> int:
    """Chunks per SMEM tile: ``chunk_tile`` doubled until a tile of
    ``T * C * W`` slots is a whole number of 1-D HBM tiles.  The SELL
    preparations pad the chunk count to a multiple of 128 chunks, which
    every such T divides."""
    T = int(chunk_tile)
    while (T * C * W) % HBM_1D_TILE:
        T *= 2
    return T


def smem_tile_slots(chunk_tile: int, W: int, C: int = 8) -> int:
    """Slots in one SMEM tile of one stream (the SMEM size rule's input)."""
    W = -(-max(int(W), 1) // 8) * 8
    return _tile_chunks(chunk_tile, C, W) * C * W


def _sell_rows_call(cols, vals, x_rows, *, chunk_tile, interpret, pipelined):
    """Sorted-row sums of a stacked SELL packing, (n_chunks * C,).

    cols/vals: (S, n_chunks, C, W) slab-local packings over one row order;
    x_rows: (S * slab_rows, 128) lane-dense x slabs, slab ``s`` at rows
    ``[s * slab_rows, (s + 1) * slab_rows)``.
    """
    S, n_chunks, C, W = cols.shape
    assert W % 8 == 0, W
    T = _tile_chunks(chunk_tile, C, W)
    assert n_chunks % T == 0, (n_chunks, T)
    n_tiles = n_chunks // T
    L = T * C * W  # one tile of every stream
    slab_rows = x_rows.shape[0] // S
    n_sorted = n_chunks * C
    out_rows = lane_dense(jnp.zeros((n_sorted,), vals.dtype)).shape[0]
    pipe = resolve_pipelined(pipelined, interpret)

    def _kernel(cols_hbm, vals_hbm, x_hbm, o_ref, x_buf, x_sem):
        o_ref[...] = jnp.zeros_like(o_ref)
        lane = lane_iota()

        def tile(step, c_ref, v_ref):  # SMEM refs, L entries each
            s = step // n_tiles
            t = step - s * n_tiles

            @pl.when(t == 0)
            def _load_x_slab():
                src = x_hbm.at[pl.ds(s * slab_rows, slab_rows)]
                if pipe:
                    cp = pltpu.make_async_copy(src, x_buf, x_sem)
                    cp.start()
                    cp.wait()
                else:
                    x_buf[...] = src[...]

            def row(r, _):  # r-th sorted row of this tile
                base = r * W

                def slots(jj, acc):  # 8 slots per trip, unrolled
                    for u in range(8):
                        j = base + jj * 8 + u
                        c = c_ref[j]
                        xr = x_buf[pl.ds(c >> 7, 1), :]
                        hit = lane == (c & (LANES - 1))
                        acc = acc + jnp.where(hit, xr * v_ref[j], 0.0)
                    return acc

                acc = jax.lax.fori_loop(
                    0, W // 8, slots, jnp.zeros((1, LANES), jnp.float32)
                )
                total = jnp.sum(acc, axis=1, keepdims=True)
                add_to_elem(o_ref, t * (T * C) + r, total, lane)
                return 0

            jax.lax.fori_loop(0, T * C, row, 0)

        slab_pipeline(
            tile,
            [(cols_hbm, L, SMEM), (vals_hbm, L, SMEM)],
            S * n_tiles,
            pipelined=pipe,
        )

    out = pl.pallas_call(
        _kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # streamed by the pipeline
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),  # x slabs, copied per slab
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((out_rows, LANES), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((slab_rows, LANES), x_rows.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=_CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(cols.reshape(-1), vals.reshape(-1), x_rows)
    return out.reshape(-1)[:n_sorted].astype(vals.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk_tile", "interpret", "pipelined")
)
def sell_spmv_pallas(
    cols: jax.Array,  # (n_chunks, C, W) int32
    vals: jax.Array,  # (n_chunks, C, W)
    x: jax.Array,  # (n,)
    *,
    chunk_tile: int = 8,
    interpret: bool = False,
    pipelined: bool | None = None,
) -> jax.Array:
    """Returns per-sorted-row sums (n_chunks * C,); caller un-permutes."""
    return _sell_rows_call(
        cols[None], vals[None], lane_dense(x.astype(jnp.float32)),
        chunk_tile=chunk_tile, interpret=interpret, pipelined=pipelined,
    )


@functools.partial(
    jax.jit, static_argnames=("slab_n", "chunk_tile", "interpret", "pipelined")
)
def sell_spmv_blocked_pallas(
    cols: jax.Array,  # (n_slabs, n_chunks, C, W) int32, slab-local columns
    vals: jax.Array,  # (n_slabs, n_chunks, C, W)
    x: jax.Array,  # (n_slabs * slab_n,) zero-padded
    *,
    slab_n: int,
    chunk_tile: int = 8,
    interpret: bool = False,
    pipelined: bool | None = None,
) -> jax.Array:
    """Column-slab SELL SpMV: returns sorted partial sums (n_chunks * C,).

    Every slab is packed over the SAME row permutation (see
    ``ops.sell_prepare_blocked_stacked``), so slab partials accumulate
    positionally and the caller un-permutes once.  Slab ``s`` consumes
    ``x[s*slab_n:(s+1)*slab_n]`` — only one x slab occupies VMEM at a time,
    which is the whole point: x larger than the VMEM budget streams through
    instead of disqualifying the kernel.
    """
    n_slabs = cols.shape[0]
    assert x.shape[0] == n_slabs * slab_n, (x.shape, n_slabs, slab_n)
    slabs = x.astype(jnp.float32).reshape(n_slabs, slab_n)
    x_rows = jnp.concatenate([lane_dense(slabs[s]) for s in range(n_slabs)])
    return _sell_rows_call(
        cols, vals, x_rows,
        chunk_tile=chunk_tile, interpret=interpret, pipelined=pipelined,
    )
