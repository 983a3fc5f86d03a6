"""Merge-style nnz-balanced SpMV/SpMM: the load-balance tier.

The paper's load-balancing lever is OpenMP ``dynamic,64`` row scheduling —
cheap on a cache-coherent Phi, unavailable to a statically-shaped XLA/TPU
program.  Every row-parallel tier here (CSR segment-sum, SELL's padded
chunks) therefore pays for row-length skew: SELL pads every chunk to its
longest row (power-law rows inflate stored slots by orders of magnitude) and
the CSR gather funnels all nonzeros through one serialized scatter-add.
Merge-based SpMV (Merrill & Garland's merge-path applied to CSR) fixes the
balance *in the decomposition*: split the nonzero stream — not the rows —
into equal work chunks, reduce each chunk independently, and fix up the rows
that straddle chunk boundaries with a carry pass.

This module is that algorithm as a segmented reduction, which XLA compiles
to dense, perfectly balanced vector code with NO data-dependent scatter
over the nonzeros:

* prepare (host, once): pad nnz to ``n_chunks * chunk`` and lay the
  streams out slot-major, (chunk, n_chunks), so one step of the scan
  touches the same slot of every chunk; mark each row's first nonzero
  with a head flag (the padding tail gets one too, so it never joins a
  row); hoist each row's last slot and, for the rows that straddle chunk
  boundaries, which chunk tails belong to them — the chunk table.
* phase 1 (chunk-local): products ``A.data * x[cols]``, then a segmented
  running sum down the ``chunk`` slots of all chunks at once, restarting
  at every head.
* phase 2 (carry/fixup): the running sum at each chunk's last slot is the
  partial of the row open there; a segment sum over those n_chunks tails
  merges each straddling row's partials into one carry.
* gather: row r's sum is the running sum at its last slot plus its carry
  — O(1) per row whatever its length, so a 4700-nonzero webbase row costs
  exactly what an empty row costs.  Empty rows are exact zeros.

Cost is O(nnz) adds in ``chunk`` sequential steps + O(m) gathers,
independent of the row distribution — the tier the tuner reaches for when
``nnz_row_cv`` says SELL padding and row-parallel CSR will burn (see
tune.candidates' imbalance cost term).

Precision: a row's sum adds that row's products and nothing else, so its
error is bounded by ~nnz_row * eps * (|A| |x|)_i like any float32 row
reduction.  Rows taken as differences of one global prefix-sum table would
carry the rounding of the whole prefix instead: at webbase-1M's full size a
short row late in the stream came back off by half of (|A| |x|)_i.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["merge_prepare", "merge_spmv", "merge_spmm", "DEFAULT_CHUNK"]

DEFAULT_CHUNK = 4096  # equal-nnz work chunk (the merge-path grain)


def merge_prepare(a, chunk: int = DEFAULT_CHUNK) -> dict[str, Any]:
    """Host-side chunk table: slot-major padded streams, heads, row ends.

    The returned dict is a jit-stable pytree.  ``indices``/``data``/
    ``heads`` are (chunk, n_chunks): nonzero ``s`` sits at
    ``[s % chunk, s // chunk]``, and padding gathers x[0] with value 0.0
    in a segment of its own.  ``last_slot``/``last_chunk`` locate each
    row's last nonzero (``nonempty`` masks empty rows).  ``tail_row`` names,
    for each chunk, the straddling row open at its end (``n_straddle`` for
    none), and ``carry_row`` each row's straddle id (``n_straddle`` for a
    row held in one chunk).  ``chunk``, ``n_chunks`` and ``n_straddle``
    ride along as static python ints.

    The offsets are int32: a matrix with nnz >= 2**31 cannot be
    represented by this tier and is rejected here — loudly, because the
    ``astype(np.int32)`` below would otherwise WRAP the large indptr tails
    to negative offsets and the kernel would return silently wrong values
    for every late row.
    """
    chunk = max(1, int(chunk))
    nnz = a.nnz
    n_chunks = max(1, -(-nnz // chunk))
    nnz_pad = n_chunks * chunk
    if int(a.indptr[-1]) >= 2**31 or nnz_pad >= 2**31:
        raise OverflowError(
            f"merge tier: nnz={int(a.indptr[-1])} (padded {nnz_pad}) "
            "overflows the int32 slot offsets; this matrix needs the "
            "CSR/SELL tiers (or row-partitioned shards each below 2**31 nnz)"
        )
    pad = nnz_pad - nnz
    indices = np.concatenate([a.indices, np.zeros(pad, a.indices.dtype)])
    data = np.concatenate([a.data, np.zeros(pad, a.data.dtype)])
    start = np.asarray(a.indptr[:-1], np.int64)
    end = np.asarray(a.indptr[1:], np.int64)
    nonempty = end > start
    heads = np.zeros(nnz_pad, np.bool_)
    heads[start[nonempty]] = True
    if pad:
        heads[nnz] = True
    last = np.where(nonempty, end - 1, 0)
    c0, c1 = start // chunk, last // chunk
    straddle = nonempty & (c1 > c0)
    n_straddle = int(straddle.sum())
    carry_row = np.full(a.shape[0], n_straddle, np.int64)
    carry_row[straddle] = np.arange(n_straddle)
    # Chunks c0 .. c1-1 of a straddling row end inside it: their tails are
    # its partial sums.
    span = (c1 - c0)[straddle]
    tails = np.repeat(c0[straddle], span) + (
        np.arange(int(span.sum())) - np.repeat(np.cumsum(span) - span, span)
    )
    tail_row = np.full(n_chunks, n_straddle, np.int64)
    tail_row[tails] = np.repeat(np.arange(n_straddle), span)

    def slot_major(v):
        return jnp.asarray(v.reshape(n_chunks, chunk).T)

    return {
        "indices": slot_major(indices),
        "data": slot_major(data),
        "heads": slot_major(heads),
        "last_slot": jnp.asarray((last % chunk).astype(np.int32)),
        "last_chunk": jnp.asarray(c1.astype(np.int32)),
        "nonempty": jnp.asarray(nonempty),
        "tail_row": jnp.asarray(tail_row.astype(np.int32)),
        "carry_row": jnp.asarray(carry_row.astype(np.int32)),
        "chunk": chunk,
        "n_chunks": n_chunks,
        "n_straddle": n_straddle,
        "shape": a.shape,
    }


@functools.partial(jax.jit, static_argnames=("n_straddle",))
def _merge_apply(data, indices, heads, last_slot, last_chunk, nonempty,
                 tail_row, carry_row, x2, *, n_straddle):
    """Y (m, k) = A @ X2 from the slot-major chunk table.

    Products are laid out (chunk, k, n_chunks), or (chunk, n_chunks) at
    k = 1, so each scan step is one lane-dense row of every chunk.
    """
    k = x2.shape[1]
    if k == 1:
        prod = data * x2[indices, 0]
        h = heads
    else:
        prod = data[:, None, :] * jnp.moveaxis(x2[indices, :], 2, 1)
        h = heads[:, None, :]

    def step(v, ph):
        p, hj = ph
        v = jnp.where(hj, p, v + p)
        return v, v

    # Phase 1: every chunk's segmented running sum, one slot per step.
    _, S = jax.lax.scan(step, jnp.zeros_like(prod[0]), (prod, h), unroll=8)
    # Phase 2: the partial of the row open at each chunk's end, summed per
    # straddling row (ids == n_straddle are dropped).
    tails = S[-1][:, None] if k == 1 else S[-1].T  # (n_chunks, k)
    carry = jax.ops.segment_sum(tails, tail_row, num_segments=n_straddle)
    carry = jnp.concatenate([carry, jnp.zeros((1, k), carry.dtype)])
    y = (S[last_slot, last_chunk][:, None] if k == 1
         else S[last_slot, :, last_chunk])
    return jnp.where(nonempty[:, None], y, 0) + carry[carry_row]


def merge_spmm(prep: dict[str, Any], x: jax.Array) -> jax.Array:
    """Y = A @ X, X (n, k): nnz-balanced segmented reduction."""
    return _merge_apply(
        prep["data"], prep["indices"], prep["heads"], prep["last_slot"],
        prep["last_chunk"], prep["nonempty"], prep["tail_row"],
        prep["carry_row"], x, n_straddle=prep["n_straddle"],
    )


def merge_spmv(prep: dict[str, Any], x: jax.Array) -> jax.Array:
    """y = A @ x: the k=1 column of :func:`merge_spmm`."""
    return merge_spmm(prep, x[:, None])[:, 0]
