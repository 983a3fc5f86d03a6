"""jit'd public wrappers around the Pallas kernels.

Handles everything the raw kernels don't: empty-block-row padding, x column
slabbing (cache blocking) for matrices whose x does not fit in VMEM, output
un-permutation for SELL, and interpret-mode selection (interpret=True on
CPU, where the kernels' numerics are tested).  The kernels themselves
stream their A (and x-slab) operands through the shared double-buffered
slab pipeline (kernels/pipeline.py).

On TPU every kernel here compiles for v5e at Table-1 sizes
(``tests/test_tpu_compile.py`` compiles each one for a described v5e
topology): SELL SpMV resident and column-slab, lane-row SELL SpMV, BCSR
SpMM at k = 1 and 16, and the SpMSpV scatter.  None is taken out of the
TPU search space for a compiler refusal;
``tune.candidates.enumerate_candidates`` enumerates each only where its
resident operands fit :data:`VMEM_BUDGET_BYTES` (and the SELL tiles fit
SMEM, and lane-row SELL's value rows a quarter of HBM), which is a size
rule, not a refusal.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import BCSRMatrix, SELLMatrix
from repro.core.formats import nnz_row_ids as formats_nnz_row_ids
from .bcsr_spmm import TILE_ROWS, bcsr_spmm_pallas
from .pipeline import LANES, VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES
from .sell_spmv import (
    CHUNK_ALIGN,
    sell_lr_spmv_pallas,
    sell_spmv_blocked_pallas,
    sell_spmv_pallas,
)

__all__ = [
    "on_cpu",
    "bcsr_prepare",
    "bcsr_spmm",
    "sell_prepare",
    "sell_spmv",
    "sell_prepare_blocked",
    "sell_prepare_blocked_stacked",
    "sell_spmv_blocked",
    "sell_spmv_blocked_stacked",
    "lanerow_counts",
    "sell_lr_prepare",
    "sell_lr_spmv",
    "VMEM_BUDGET_BYTES",
    "VMEM_LIMIT_BYTES",
]

# Stored blocks per pipeline slab.  The block-index streams ride to SMEM
# in slabs of this many entries, and Mosaic slices a 1-D HBM array only in
# whole 1024-entry tiles.
BLOCK_TILE = 1024


def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# BCSR
# ---------------------------------------------------------------------------
def bcsr_prepare(a: BCSRMatrix, tile_rows: int = TILE_ROWS) -> dict[str, Any]:
    """Host-side prep for :func:`bcsr_spmm`.

    * Empty block rows get one explicit zero block at column 0 (paper-style
      fill-in), so every output row has a stored block.
    * The row-sorted stream is padded with zero blocks (at the last block
      row, keeping it sorted) to a multiple of the kernel's slab, so no
      call re-pads it.
    * ``window``: the most x rows one ``tile_rows`` row tile's blocks span
      (8-aligned) — the kernel keeps only that window of X in VMEM.
    """
    gm, _ = a.grid_shape
    present = np.zeros(gm, dtype=bool)
    present[a.block_rows] = True
    missing = np.nonzero(~present)[0].astype(np.int32)
    bm, bk = a.block_shape
    block_rows = np.concatenate([a.block_rows, missing])
    block_cols = np.concatenate([a.block_cols, np.zeros_like(missing)])
    blocks = np.concatenate(
        [a.blocks, np.zeros((missing.shape[0], bm, bk), a.blocks.dtype)]
    )
    order = np.argsort(block_rows, kind="stable")
    block_rows, block_cols, blocks = (
        block_rows[order], block_cols[order], blocks[order]
    )
    pad = (-block_rows.shape[0]) % BLOCK_TILE
    block_rows = np.concatenate(
        [block_rows, np.full(pad, max(gm - 1, 0), block_rows.dtype)]
    )
    block_cols = np.concatenate([block_cols, np.zeros(pad, block_cols.dtype)])
    blocks = np.concatenate([blocks, np.zeros((pad, bm, bk), blocks.dtype)])
    tile = block_rows.astype(np.int64) // max(tile_rows // bm, 1)
    n_tiles = int(tile.max(initial=0)) + 1
    lo = np.full(n_tiles, np.iinfo(np.int64).max)
    hi = np.zeros(n_tiles, np.int64)
    np.minimum.at(lo, tile, block_cols.astype(np.int64))
    np.maximum.at(hi, tile, block_cols.astype(np.int64) + 1)
    span = int(((hi - np.minimum(lo, hi)) * bk).max(initial=1))
    return {
        "block_rows": jnp.asarray(block_rows),
        "block_cols": jnp.asarray(block_cols),
        "blocks": jnp.asarray(blocks),
        "grid_shape": a.grid_shape,
        "block_shape": a.block_shape,
        "shape": a.shape,
        "window": -(-span // 8) * 8,
        "tile_rows": int(tile_rows),
    }


def bcsr_spmm(
    prep: dict[str, Any],
    x: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Y = A @ X. x: (n, k) unblocked; returns (m, k) unpadded.

    The kernel keeps one row tile's Y and the prepared x window of X in
    VMEM (both lane-padded to 128); ``tune.candidates`` enumerates the
    kernel only where that window fits the VMEM budget.
    """
    if interpret is None:
        interpret = on_cpu()
    gm, gn = prep["grid_shape"]
    bm, bk = prep["block_shape"]
    m, n = prep["shape"]
    k = x.shape[-1]
    x_pad = jnp.zeros((gn * bk, k), x.dtype).at[:n].set(x)
    out = bcsr_spmm_pallas(
        prep["block_rows"],
        prep["block_cols"],
        prep["blocks"],
        x_pad.reshape(gn, bk, k),
        n_block_rows=gm,
        window=prep["window"],
        tile_rows=prep["tile_rows"],
        block_tile=BLOCK_TILE,
        interpret=interpret,
    )
    return out.reshape(gm * bm, k)[:m]


# ---------------------------------------------------------------------------
# SELL
# ---------------------------------------------------------------------------
def sell_prepare(a: SELLMatrix, chunk_tile: int = 8) -> dict[str, Any]:
    """Host-side prep: pad the chunk count to a multiple of CHUNK_ALIGN
    (itself a multiple of every chunk_tile), the kernel's tile rule."""
    n_chunks = a.n_chunks
    pad = (-n_chunks) % max(CHUNK_ALIGN, chunk_tile)
    cols, vals, row_perm = a.cols, a.vals, a.row_perm
    if pad:
        cols = np.concatenate([cols, np.zeros((pad,) + cols.shape[1:], cols.dtype)])
        vals = np.concatenate([vals, np.zeros((pad,) + vals.shape[1:], vals.dtype)])
        row_perm = np.concatenate(
            [row_perm, np.full(pad * a.C, -1, row_perm.dtype)]
        )
    return {
        "cols": jnp.asarray(cols),
        "vals": jnp.asarray(vals),
        "row_perm": jnp.asarray(row_perm),
        "shape": a.shape,
        "chunk_tile": chunk_tile,
    }


@functools.partial(
    jax.jit, static_argnames=("n_rows", "chunk_tile", "interpret")
)
def _sell_spmv_jit(
    prep_cols, prep_vals, prep_perm, x, *, n_rows, chunk_tile, interpret
):
    sums = sell_spmv_pallas(
        prep_cols, prep_vals, x, chunk_tile=chunk_tile, interpret=interpret
    )
    valid = prep_perm >= 0
    y = jnp.zeros((n_rows,), x.dtype)
    return y.at[jnp.where(valid, prep_perm, 0)].add(
        jnp.where(valid, sums, 0.0)
    )


def sell_spmv(
    prep: dict[str, Any], x: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """y = A @ x; un-permutes the kernel's sorted output."""
    if interpret is None:
        interpret = on_cpu()
    m, n = prep["shape"]
    return _sell_spmv_jit(
        prep["cols"], prep["vals"], prep["row_perm"], x,
        n_rows=m, chunk_tile=int(prep.get("chunk_tile", 8)),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Lane-row SELL: one segment per (row, 128-column lane-row of x)
# ---------------------------------------------------------------------------
def _lane_row_segments(a) -> tuple[np.ndarray | None, np.ndarray, np.ndarray, int]:
    """A's nonzeros grouped into (row, ``col >> 7``) segments.

    Returns ``(order, key, first, n_lr)``: ``order`` sorts A's nonzeros by
    (row, column) (None where A's columns already ascend within rows),
    ``key`` is each sorted nonzero's ``row * n_lr + (col >> 7)``, ``first``
    marks each segment's first nonzero, and ``n_lr`` is x's number of
    128-lane rows.
    """
    n = max(int(a.shape[1]), 1)
    n_lr = -(-n // LANES)
    rows = formats_nnz_row_ids(a.indptr, dtype=np.int64)
    cols = a.indices.astype(np.int64)
    pos = rows * n + cols
    order = None
    if pos.size and np.any(pos[1:] < pos[:-1]):
        order = np.argsort(pos, kind="stable")
        rows, cols = rows[order], cols[order]
    key = rows * n_lr + (cols >> 7)
    first = np.ones(key.size, dtype=bool)
    first[1:] = key[1:] != key[:-1]
    return order, key, first, n_lr


def lanerow_counts(a) -> np.ndarray:
    """Segments per row: how many 128-column lane-rows of x each row of A
    touches.  Their maximum is lane-row SELL's width W_seg."""
    _, key, first, n_lr = _lane_row_segments(a)
    return np.bincount(key[first] // n_lr, minlength=a.shape[0]).astype(np.int64)


@functools.partial(jax.jit, static_argnames=("n_slots",))
def _lane_rows_from_coo(flat, vals, *, n_slots):
    """(n_slots, 128) value rows from (flat position, value) pairs sorted
    by position, built on the device: the rows are 128 / nnz-per-segment
    times the values' bytes, so only the compact pairs cross the host
    link.  Sorted positions also keep the TPU compile of this scatter
    under a second at ldoor's size (about 24 s unsorted)."""
    out = jnp.zeros((n_slots * LANES,), vals.dtype).at[flat].add(
        vals, indices_are_sorted=True
    )
    return out.reshape(n_slots, LANES)


def sell_lr_prepare(a, C: int = 8, chunk_tile: int = 16) -> dict[str, Any]:
    """Pack A as lane-row SELL (``kernels.sell_spmv.sell_lr_spmv_pallas``).

    Each row's nonzeros are grouped by ``col >> 7`` into segments.  Rows
    stay in their order (no sorting window) and are packed into chunks of
    ``C``; every row gets one global width W_seg (the most segments any
    row has, rounded up to 8) and the chunk count is padded to a multiple
    of CHUNK_ALIGN.  ``seg_row`` (n_chunks * C * W_seg,), flat, holds each
    segment's lane-row (padding: lane-row 0); ``seg_vals`` (n_chunks * C *
    W_seg, 128) holds its values at lane ``col & 127`` and zeros elsewhere
    (padding: a zero row).  The host computes (position, value) pairs of
    nnz length; the device scatters them into the rows.
    """
    m, _ = a.shape
    order, key, first, n_lr = _lane_row_segments(a)
    vals = np.asarray(a.data)
    lanes = a.indices.astype(np.int64) & (LANES - 1)
    if order is not None:
        vals, lanes = vals[order], lanes[order]
    seg_key = key[first]
    seg_rows = seg_key // n_lr
    counts = np.bincount(seg_rows, minlength=m).astype(np.int64)
    W = max(int(counts.max(initial=0)), 1)
    W = -(-W // 8) * 8
    n_chunks = max(1, -(-m // C))
    align = max(CHUNK_ALIGN, chunk_tile)
    n_chunks = -(-n_chunks // align) * align
    n_slots = n_chunks * C * W
    assert n_slots * LANES < 2**31, ("lane-row SELL too large", n_slots)

    row_start = np.cumsum(counts) - counts  # first segment of each row
    seg_pos = seg_rows * W + np.arange(seg_key.size) - row_start[seg_rows]
    seg_row = np.zeros(n_slots, dtype=np.int32)
    seg_row[seg_pos] = seg_key - seg_rows * n_lr
    # Nonzeros sorted by (row, column) give ascending positions.
    flat = (seg_pos[np.cumsum(first) - 1] * LANES + lanes).astype(np.int32)
    return {
        "seg_row": jnp.asarray(seg_row),
        "seg_vals": _lane_rows_from_coo(
            jnp.asarray(flat), jnp.asarray(vals), n_slots=n_slots
        ),
        "shape": a.shape,
        "width": W,
        "C": int(C),
        "chunk_tile": int(chunk_tile),
    }


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "width", "C", "chunk_tile", "interpret"),
)
def _sell_lr_spmv_jit(seg_row, seg_vals, x, *, n_rows, width, C, chunk_tile,
                      interpret):
    sums = sell_lr_spmv_pallas(
        seg_row, seg_vals, x, width=width, C=C, chunk_tile=chunk_tile,
        interpret=interpret,
    )
    return sums[:n_rows]


def sell_lr_spmv(
    prep: dict[str, Any], x: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """y = A @ x through lane-row SELL: its rows are A's, in order."""
    if interpret is None:
        interpret = on_cpu()
    m, _ = prep["shape"]
    return _sell_lr_spmv_jit(
        prep["seg_row"], prep["seg_vals"], x,
        n_rows=m, width=int(prep["width"]), C=int(prep["C"]),
        chunk_tile=int(prep["chunk_tile"]), interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Cache-blocked SELL: column slabs for matrices whose x exceeds VMEM
# ---------------------------------------------------------------------------
def sell_prepare_blocked(a, n_slabs: int, chunk_tile: int = 8,
                         C: int = 8, sigma: int = 64) -> dict[str, Any]:
    """Split A into column slabs, one SELL per slab (paper refs' cache
    blocking, Nishtala et al.): the kernel then keeps only an x-slab
    resident in VMEM per pass instead of the whole vector.

    The split is fully vectorized: one searchsorted assigns every nonzero to
    its slab, and each slab's CSR falls out of a boolean mask + bincount
    (the mask preserves row-major nnz order, so per-row column order is
    unchanged from A).
    """
    from repro.core.formats import CSRMatrix, sell_from_csr

    m, n = a.shape
    bounds = np.linspace(0, n, n_slabs + 1).astype(np.int64)
    rows_of_nnz = formats_nnz_row_ids(a.indptr, dtype=np.int64)
    slab_of_nnz = np.searchsorted(bounds[1:], a.indices, side="right")
    slabs = []
    for s in range(n_slabs):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        sel = slab_of_nnz == s
        counts = np.bincount(rows_of_nnz[sel], minlength=m)
        indptr = np.zeros(m + 1, dtype=a.indptr.dtype)
        np.cumsum(counts, out=indptr[1:])
        sub = CSRMatrix(
            (m, hi - lo), indptr,
            (a.indices[sel] - lo).astype(a.indices.dtype),
            a.data[sel],
        )
        slabs.append(sell_prepare(sell_from_csr(sub, C=C, sigma=sigma,
                                                width_align=8), chunk_tile))
    return {"slabs": slabs, "bounds": bounds, "shape": a.shape}


def _sell_prepare_blocked_loop(a, n_slabs: int, chunk_tile: int = 8,
                               C: int = 8, sigma: int = 64) -> dict[str, Any]:
    """Original O(m * n_slabs) python-row-loop slab split.

    Kept only as the reference for the vectorized-equality regression test
    (tests/test_kernel_edges.py); not used on any hot path.
    """
    from repro.core.formats import CSRMatrix, sell_from_csr

    m, n = a.shape
    bounds = np.linspace(0, n, n_slabs + 1).astype(np.int64)
    slabs = []
    for s in range(n_slabs):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        indptr = np.zeros(m + 1, dtype=a.indptr.dtype)
        idx_parts, val_parts = [], []
        for r in range(m):
            st, en = a.indptr[r], a.indptr[r + 1]
            cols_r = a.indices[st:en]
            sel = (cols_r >= lo) & (cols_r < hi)
            idx_parts.append((cols_r[sel] - lo).astype(a.indices.dtype))
            val_parts.append(a.data[st:en][sel])
            indptr[r + 1] = indptr[r] + int(sel.sum())
        sub = CSRMatrix(
            (m, hi - lo), indptr,
            np.concatenate(idx_parts) if idx_parts else np.zeros(0, a.indices.dtype),
            np.concatenate(val_parts) if val_parts else np.zeros(0, a.data.dtype),
        )
        slabs.append(sell_prepare(sell_from_csr(sub, C=C, sigma=sigma,
                                                width_align=8), chunk_tile))
    return {"slabs": slabs, "bounds": bounds, "shape": a.shape}


def sell_spmv_blocked(prep: dict[str, Any], x: jax.Array,
                      *, interpret: bool | None = None) -> jax.Array:
    """y = A @ x with column-slab accumulation (each slab's x fits VMEM).

    One kernel launch per slab; kept as the reference for the fused
    single-launch :func:`sell_spmv_blocked_stacked` path below.
    """
    m, _ = prep["shape"]
    y = jnp.zeros((m,), x.dtype)
    for s, slab in enumerate(prep["slabs"]):
        lo, hi = int(prep["bounds"][s]), int(prep["bounds"][s + 1])
        y = y + sell_spmv(slab, x[lo:hi], interpret=interpret)
    return y


# ---------------------------------------------------------------------------
# Stacked column-slab SELL: one launch, x slabs streamed through the pipeline
# ---------------------------------------------------------------------------
def sell_prepare_blocked_stacked(a, n_slabs: int, C: int = 8,
                                 sigma: int = 64,
                                 chunk_tile: int = 8) -> dict[str, Any]:
    """Pack A into ``n_slabs`` column slabs sharing ONE row permutation.

    Unlike :func:`sell_prepare_blocked` (independent SELL per slab, python
    loop of kernel launches), every slab here is packed over the same
    window-of-``sigma`` row sort, so the per-slab partial sums align
    positionally: the kernel accumulates them in sorted order across slabs
    and the caller un-permutes once.  All slabs share one padded width W
    (max nonzeros of any (row, slab) cell, lane-aligned), making the device
    arrays rectangular: cols/vals (n_slabs, n_chunks, C, W).

    Slab widths are uniform (``slab_n = ceil(n / n_slabs)``; x is zero-padded
    to ``n_slabs * slab_n``) so the kernel's x-slab stream is a plain
    leading-dim slicing.  The chunk count is padded to a multiple of
    ``CHUNK_ALIGN``, which the kernel's SMEM tiles divide.
    """
    m, n = a.shape
    slab_n = max(1, -(-n // n_slabs))
    lengths = np.diff(a.indptr).astype(np.int64)
    # Shared row permutation: the same window-sigma descending-length sort as
    # formats.sell_from_csr, computed once on whole-row lengths.
    perm = np.arange(m)
    for s in range(0, m, sigma):
        e = min(s + sigma, m)
        window = perm[s:e]
        perm[s:e] = window[np.argsort(-lengths[window], kind="stable")]
    inv_perm = np.empty(m, dtype=np.int64)
    inv_perm[perm] = np.arange(m)
    n_chunks = max(1, -(-m // C))
    align = max(CHUNK_ALIGN, chunk_tile)
    n_chunks = -(-n_chunks // align) * align

    rows_of_nnz = formats_nnz_row_ids(a.indptr, dtype=np.int64)
    slab_of_nnz = a.indices.astype(np.int64) // slab_n
    # Within a row, columns ascend, so each (row, slab) group is a contiguous
    # run; the slot of a nonzero is its rank inside that run.
    key = rows_of_nnz * n_slabs + slab_of_nnz
    counts = np.bincount(key, minlength=m * n_slabs) if a.nnz else np.zeros(1)
    W = int(max(counts.max(initial=0), 1))
    W = -(-W // 8) * 8  # lane alignment, as in sell_from_csr(width_align=8)
    run_start = np.zeros(a.nnz, dtype=np.int64)
    if a.nnz:
        new_run = np.flatnonzero(np.diff(key) != 0) + 1
        starts = np.concatenate([[0], new_run])
        run_id = np.zeros(a.nnz, dtype=np.int64)
        run_id[new_run] = 1
        run_id = np.cumsum(run_id)
        run_start = starts[run_id]
    slot = np.arange(a.nnz, dtype=np.int64) - run_start

    sorted_row = inv_perm[rows_of_nnz]
    cols = np.zeros((n_slabs, n_chunks, C, W), dtype=np.int32)
    vals = np.zeros((n_slabs, n_chunks, C, W), dtype=a.data.dtype)
    cols[slab_of_nnz, sorted_row // C, sorted_row % C, slot] = (
        a.indices.astype(np.int64) - slab_of_nnz * slab_n
    )
    vals[slab_of_nnz, sorted_row // C, sorted_row % C, slot] = a.data
    row_perm = np.full(n_chunks * C, -1, dtype=np.int32)
    row_perm[:m] = perm
    return {
        "cols": jnp.asarray(cols),
        "vals": jnp.asarray(vals),
        "row_perm": jnp.asarray(row_perm),
        "slab_n": slab_n,
        "chunk_tile": int(chunk_tile),
        "shape": a.shape,
    }


@functools.partial(
    jax.jit, static_argnames=("n_rows", "slab_n", "chunk_tile", "interpret")
)
def _sell_blocked_stacked_jit(cols, vals, row_perm, x, *, n_rows, slab_n,
                              chunk_tile, interpret):
    n_slabs = cols.shape[0]
    x_pad = jnp.zeros((n_slabs * slab_n,), x.dtype).at[: x.shape[0]].set(x)
    sums = sell_spmv_blocked_pallas(
        cols, vals, x_pad, slab_n=slab_n, chunk_tile=chunk_tile,
        interpret=interpret,
    )
    valid = row_perm >= 0
    y = jnp.zeros((n_rows,), x.dtype)
    return y.at[jnp.where(valid, row_perm, 0)].add(
        jnp.where(valid, sums, 0.0)
    )


def sell_spmv_blocked_stacked(
    prep: dict[str, Any], x: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """y = A @ x through the single-launch stacked column-slab kernel."""
    if interpret is None:
        interpret = on_cpu()
    m, _ = prep["shape"]
    return _sell_blocked_stacked_jit(
        prep["cols"], prep["vals"], prep["row_perm"], x,
        n_rows=m, slab_n=int(prep["slab_n"]),
        chunk_tile=prep["chunk_tile"], interpret=interpret,
    )
