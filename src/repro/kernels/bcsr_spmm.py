"""Pallas TPU kernel: block-sparse (BCSR) matrix x dense matrix.

The TPU adaptation of the paper's register blocking (§4.5, Table 2).  On the
Phi a "register block" is an 8x{1..8} dense patch streamed through FMA
registers; on TPU the natural patch is one MXU pass over a (bm, bk) tile.

The grid runs over *row tiles* of ``tile_rows`` output rows.  Per tile:

  X window  : (window, 128) VMEM     # the x rows this tile's blocks touch
  A blocks  : ANY (HBM) -> VMEM slabs of ``block_tile`` blocks  # pipelined
  block idx : ANY (HBM) -> SMEM slabs                           # pipelined
  Y tile    : (tile_rows, 128) VMEM  # accumulator, written back once

X and Y are lane-padded to 128 columns: Mosaic copies an HBM window only
in whole 128-lane tiles, so k <= 128 rides in one lane tile (at k = 16 the
window DMA moves 8x the useful bytes — the price of compiling at all).

Blocks are sorted by row, so a tile's blocks are one contiguous range of
the stream; the wrapper finds each tile's range and x-window start in XLA
(``searchsorted``/``segment_min``) and hands them over as scalar-prefetch
tables.  The block and index streams run through the shared
:mod:`repro.kernels.pipeline` slab pipeline (double-buffered DMA that
overlaps the MXU work on the previous slab — the paper's software
prefetching); the index slabs land in SMEM, where the scalar core resolves
the irregular gather at *addressing* time: a block's x tile is a dynamic,
8-aligned sublane slice of the window.

The window is what makes the kernel fit VMEM at Table-1 sizes: a whole X
strip (every column block, lane-padded to 128) is hundreds of MB for a
million-row matrix, while a banded matrix's row tile touches a window of
about ``tile_rows + 2 * bandwidth`` rows.  ``ops.bcsr_prepare`` measures the
window on the host; callers that cannot (traced block tables, e.g. the
sparse FFN) pass ``window=None`` and keep all of X resident.

The paper's Table 2 economics carry over verbatim: stored zeros cost
bandwidth, so the ops layer exposes ``fill_ratio`` and benchmarks sweep block
shapes exactly like Table 2.
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
    resolve_pipelined,
    slab_pipeline,
)

__all__ = ["bcsr_spmm_pallas", "TILE_ROWS"]

TILE_ROWS = 512  # output rows per grid step (a multiple of every bm)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_block_rows", "window", "tile_rows", "block_tile", "interpret",
        "out_dtype", "pipelined",
    ),
)
def bcsr_spmm_pallas(
    block_rows: jax.Array,  # (n_blocks,) int32, sorted
    block_cols: jax.Array,  # (n_blocks,) int32
    blocks: jax.Array,  # (n_blocks, bm, bk)
    x_blocked: jax.Array,  # (n_col_blocks, bk, k)
    *,
    n_block_rows: int,
    window: int | None = None,
    tile_rows: int = TILE_ROWS,
    block_tile: int = 1024,
    interpret: bool = False,
    out_dtype=jnp.float32,
    pipelined: bool | None = None,
) -> jax.Array:
    """Returns (n_block_rows, bm, k) = A @ X with A block-sparse.

    ``window`` (rows of X, static) bounds the x rows any row tile's blocks
    touch; None keeps all of X resident.  The block stream is padded with
    zero blocks to a multiple of ``block_tile`` (``ops.bcsr_prepare``
    already pads, so the tuned path never copies it per call).
    """
    n_blocks, bm, bk = blocks.shape
    n_col_blocks, bk2, k = x_blocked.shape
    assert bk == bk2, (bk, bk2)
    assert tile_rows % bm == 0 and bm % 8 == 0 and bk % 8 == 0, (bm, bk)
    pipe = resolve_pipelined(pipelined, interpret)
    R = tile_rows // bm  # block rows per tile
    n_tiles = max(1, -(-n_block_rows // R))

    BT = int(block_tile)
    pad = (-n_blocks) % BT
    if pad:  # zero blocks at the last row keep the stream sorted
        last = jnp.full((pad,), max(n_block_rows - 1, 0), block_rows.dtype)
        block_rows = jnp.concatenate([block_rows, last])
        block_cols = jnp.concatenate(
            [block_cols, jnp.zeros((pad,), block_cols.dtype)]
        )
        blocks = jnp.concatenate(
            [blocks, jnp.zeros((pad, bm, bk), blocks.dtype)]
        )
    n_rows_x = n_col_blocks * bk
    win = n_rows_x if window is None else min(int(window), n_rows_x)
    win = -(-max(win, bk) // 8) * 8
    kp = -(-k // LANES) * LANES
    x2d = jnp.zeros((max(n_rows_x, win), kp), x_blocked.dtype)
    x2d = x2d.at[:n_rows_x, :k].set(x_blocked.reshape(n_rows_x, k))

    # Per-tile block ranges and x-window starts (sorted stream => ranges).
    tile_of = block_rows // R
    bounds = jnp.searchsorted(
        tile_of, jnp.arange(n_tiles + 1, dtype=tile_of.dtype), side="left"
    ).astype(jnp.int32)
    first_col = jax.ops.segment_min(
        block_cols, tile_of, num_segments=n_tiles
    )
    win_start = jnp.clip(first_col * bk, 0, x2d.shape[0] - win).astype(
        jnp.int32
    )

    def _kernel(bounds_ref, ws_ref, rows_hbm, cols_hbm, blocks_hbm, x_hbm,
                o_ref, x_win, x_sem):
        i = pl.program_id(0)
        ws = ws_ref[i]
        src = x_hbm.at[pl.ds(ws, win)]
        if pipe:
            cp = pltpu.make_async_copy(src, x_win, x_sem)
            cp.start()
            cp.wait()
        else:
            x_win[...] = src[...]
        o_ref[...] = jnp.zeros_like(o_ref)
        lo = bounds_ref[i]
        hi = bounds_ref[i + 1]

        def slab(s, rows_t, cols_t, blocks_t):
            def one(t, _):
                g = s * BT + t

                @pl.when((g >= lo) & (g < hi))
                def _():
                    r0 = (rows_t[t] - i * R) * bm
                    c0 = cols_t[t] * bk - ws
                    xs = x_win[pl.ds(pl.multiple_of(c0, 8), bk), :]
                    o_ref[pl.ds(pl.multiple_of(r0, 8), bm), :] += jnp.dot(
                        blocks_t[t], xs,
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST,
                    )

                return 0

            jax.lax.fori_loop(0, BT, one, 0)

        slab_pipeline(
            slab,
            [(rows_hbm, BT, SMEM), (cols_hbm, BT, SMEM), (blocks_hbm, BT)],
            (hi + BT - 1) // BT,
            start=lo // BT,
            pipelined=pipe,
        )

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
            out_specs=pl.BlockSpec(
                (tile_rows, kp), lambda i, bounds, ws: (i, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((win, kp), x2d.dtype),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile_rows, kp), jnp.float32),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(bounds, win_start, block_rows, block_cols, blocks, x2d)
    out = out[: n_block_rows * bm, :k].astype(out_dtype)
    return out.reshape(n_block_rows, bm, k)
