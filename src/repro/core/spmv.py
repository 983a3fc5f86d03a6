"""SpMV / SpMM reference implementations and the format-dispatch layer.

Five algorithm tiers mirror the paper's compiler study (Fig 4) plus its two
decisive levers — latency hiding and load balance:

* ``spmv_csr_scalar``  — the "-O1" analogue: one nonzero at a time via a
  sequential row loop (lax.fori_loop); useful only as the unvectorized
  baseline in benchmarks.
* ``spmv_csr``/``spmm_csr`` — the "-O3" analogue: fully vectorized
  gather + segment-sum, XLA-compiled.  The per-nnz row map is hoisted to
  prepare time (:func:`csr_prepare`) so no dispatch pays a searchsorted
  over nnz; raw ``CSRMatrix.device()`` dicts still work via a derive-on-
  the-fly compat shim.
* kernels/merge_spmv — the nnz-balanced merge tier: equal-nnz work chunks
  with a carry/fixup scan, immune to power-law row skew (the paper's
  ``dynamic,64`` load balancing recast for statically-shaped XLA).
* Pallas kernels (kernels/sell_spmv, kernels/bcsr_spmm) — the hand-tiled
  vgatherd/register-blocking adaptations, their operand streams
  double-buffered through kernels/pipeline; this module only dispatches.
* kernels/spmspv — the sparse-RHS bucket tier (Azad–Buluc): when x itself
  is sparse, a CSC column gather expands only the touched columns into a
  work-bucketed scatter — O(columns x selects), never O(nnz(A)).  The
  tuner measures the density crossover against the densified tiers above.

All functions take the ``device()`` pytrees of core.formats containers plus
static shape info, so they jit cleanly.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "csr_prepare",
    "csr_bind",
    "spmv_csr",
    "spmm_csr",
    "spmv_csr_scalar",
    "spmv_sell",
    "spmm_sell",
    "spmm_bcsr_dense",
    "spmv",
    "spmm",
    "symmetrize",
    "spd_shift",
]


# ---------------------------------------------------------------------------
# CSR — vectorized gather + segment-sum ("-O3" tier)
# ---------------------------------------------------------------------------
def csr_prepare(a) -> dict[str, Any]:
    """Device CSR dict with the per-nnz row map hoisted to prepare time.

    ``rows[t]`` is the row of nonzero ``t`` — the quantity every dispatch
    used to re-derive with a searchsorted over nnz.  Computing it here (one
    O(nnz) numpy repeat per matrix) removes that work from the hot path;
    the dispatch functions below accept both this dict and a raw
    ``CSRMatrix.device()`` dict (compat shim derives rows on the fly).
    """
    from .formats import nnz_row_ids

    dev = a.device()
    dev["rows"] = jnp.asarray(nnz_row_ids(a.indptr))
    return dev


@functools.partial(jax.jit, static_argnames=("n_rows",))
def _csr_apply(data, indices, rows, x, *, n_rows: int) -> jax.Array:
    prod = (data * x[indices] if x.ndim == 1
            else data[:, None] * x[indices, :])
    return jax.ops.segment_sum(prod, rows, num_segments=n_rows)


def csr_bind(dev: dict[str, Any], *, n_rows: int):
    """Bind a prepared CSR dict into ``fn(x)``.

    The dict-argument entry points above flatten and hash a 4-leaf pytree on
    every call; binding resolves the three leaves a dispatch reads once, so
    ``x`` is the only per-call operand.  The leaves reach the compiled
    program as *arguments* — never as constants closed into it, which
    would copy the whole matrix into every program that calls ``fn``
    (see ``runtime.executable.hoisted_jit``, which the engine's and the
    solver's programs use to lift them).

    The operand's rank picks the form: ``(n,)`` is SpMV, ``(n, k)`` SpMM.
    """
    data, indices = dev["data"], dev["indices"]
    rows = dev["rows"] if "rows" in dev else _rows_from_indptr(
        dev["indptr"], indices.shape[0], n_rows
    )

    def fn(x):
        return _csr_apply(data, indices, rows, x, n_rows=n_rows)

    return fn


def _row_map(csr: dict[str, Any], n_rows: int) -> jax.Array:
    """Prepared row map if present, else the legacy per-dispatch derivation."""
    if "rows" in csr:
        return csr["rows"]
    return _rows_from_indptr(csr["indptr"], csr["indices"].shape[0], n_rows)


@functools.partial(jax.jit, static_argnames=("n_rows",))
def spmv_csr(csr: dict[str, Any], x: jax.Array, *, n_rows: int) -> jax.Array:
    """y = A @ x with A in CSR. 2 flops/nnz, gather on x (vgatherd analogue)."""
    prod = csr["data"] * x[csr["indices"]]
    return jax.ops.segment_sum(prod, _row_map(csr, n_rows), num_segments=n_rows)


@functools.partial(jax.jit, static_argnames=("n_rows",))
def spmm_csr(csr: dict[str, Any], x: jax.Array, *, n_rows: int) -> jax.Array:
    """Y = A @ X, X (n, k) — the paper's §5 SpMM with k simultaneous vectors."""
    prod = csr["data"][:, None] * x[csr["indices"], :]
    return jax.ops.segment_sum(prod, _row_map(csr, n_rows), num_segments=n_rows)


def _rows_from_indptr(indptr: jax.Array, nnz: int, n_rows: int) -> jax.Array:
    """Expand indptr -> per-nnz row ids without host round-trip.

    Compat shim for raw-dict callers only: prepared dicts carry ``rows``
    (see :func:`csr_prepare`) and never hit this searchsorted.
    """
    # row[t] = number of indptr entries (excluding leading 0) <= t
    ids = jnp.arange(nnz, dtype=indptr.dtype)
    return jnp.searchsorted(indptr[1:], ids, side="right").astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_rows",))
def spmv_csr_scalar(csr: dict[str, Any], x: jax.Array, *, n_rows: int) -> jax.Array:
    """One-nonzero-at-a-time accumulation — the paper's -O1 scalar tier.

    A sequential lax.fori_loop over nonzeros (3 memory indirections + 1 FMA
    per element, exactly the paper's description of the -O1 inner loop).
    Benchmarks contrast it with the gather/segment-sum tier the way the paper
    contrasts -O1 with -O3.
    """
    indices, data = csr["indices"], csr["data"]
    if indices.shape[0] == 0:  # empty matrix: nothing to accumulate
        return jnp.zeros(n_rows, x.dtype)
    rows = _row_map(csr, n_rows)

    def body(t, y):
        return y.at[rows[t]].add(data[t] * x[indices[t]])

    return jax.lax.fori_loop(
        0, indices.shape[0], body, jnp.zeros(n_rows, x.dtype)
    )


# ---------------------------------------------------------------------------
# SELL-C-sigma — vectorized reference (kernel lives in kernels/sell_spmv)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_rows",))
def spmv_sell(sell: dict[str, Any], x: jax.Array, *, n_rows: int) -> jax.Array:
    """y = A @ x with A in SELL-C-sigma (gathers are chunk-local and dense)."""
    cols, vals, row_perm = sell["cols"], sell["vals"], sell["row_perm"]
    partial = (vals * x[cols]).sum(axis=-1).reshape(-1)  # (n_chunks*C,)
    y = jnp.zeros(n_rows, x.dtype)
    valid = row_perm >= 0
    return y.at[jnp.where(valid, row_perm, 0)].add(
        jnp.where(valid, partial, 0.0)
    )


@functools.partial(jax.jit, static_argnames=("n_rows",))
def spmm_sell(sell: dict[str, Any], x: jax.Array, *, n_rows: int) -> jax.Array:
    """Y = A @ X with A in SELL-C-sigma and a stacked RHS X (n, k).

    The k-dimension generalization of :func:`spmv_sell`: the chunk-local
    dense gathers pull k columns at a time, amortizing the cols/vals streams
    over the whole RHS batch (the paper's Fig 9 move applied to SELL).
    """
    cols, vals, row_perm = sell["cols"], sell["vals"], sell["row_perm"]
    k = x.shape[-1]
    # (..., W) slots gather (..., W, k) rows of X; reduce the W axis.
    partial = (vals[..., None] * x[cols]).sum(axis=-2).reshape(-1, k)
    y = jnp.zeros((n_rows, k), x.dtype)
    valid = row_perm >= 0
    return y.at[jnp.where(valid, row_perm, 0)].add(
        jnp.where(valid[:, None], partial, 0.0)
    )


# ---------------------------------------------------------------------------
# BCSR — dense-block einsum reference (kernel lives in kernels/bcsr_spmm)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_block_rows",))
def spmm_bcsr_dense(
    bcsr: dict[str, Any], x_blocked: jax.Array, *, n_block_rows: int
) -> jax.Array:
    """Y = A @ X with A in BCSR and X pre-blocked to (n_col_blocks, bk, k).

    Returns (n_block_rows, bm, k).  One (bm,bk)x(bk,k) matmul per stored
    block — the MXU version of the paper's register-blocked FMA streams.
    """
    blocks, bcols, brows = bcsr["blocks"], bcsr["block_cols"], bcsr["block_rows"]
    gathered = x_blocked[bcols]  # (n_blocks, bk, k)
    # HIGHEST: on TPU a default-precision f32 einsum runs reduced-precision
    # MXU passes, and this tier is the float32 reference for the bcsr kernel.
    prods = jnp.einsum("bij,bjk->bik", blocks, gathered,
                       precision=jax.lax.Precision.HIGHEST)
    return jax.ops.segment_sum(prods, brows, num_segments=n_block_rows)


# ---------------------------------------------------------------------------
# Solver-workload constructors (runtime/solver.py consumes these)
#
# The iterative solvers the paper motivates SpMV with (CG, Lanczos, LOBPCG)
# assume symmetric / symmetric-positive-definite operators; the Table 1
# suite matrices are general.  These two host-side helpers build the solver
# workloads from any CSR so the example, the fig17 benchmark, and the
# correctness tests construct them one way.
# ---------------------------------------------------------------------------
def symmetrize(a):
    """(A + A^T) / 2 as a new CSRMatrix (host construction, duplicate-summed)."""
    from .formats import csr_from_coo

    rows = np.repeat(np.arange(a.shape[0]), np.diff(a.indptr))
    r = np.concatenate([rows, a.indices])
    c = np.concatenate([a.indices, rows])
    v = np.concatenate([a.data, a.data]) * 0.5
    return csr_from_coo(a.shape, r, c, v)


def spd_shift(a, margin: float = 1.0):
    """A symmetric positive-definite operator with ``a``'s pattern.

    Symmetrizes, then adds ``(max off-diagonal |row sum| + margin) * I`` —
    strict diagonal dominance with positive diagonal, hence SPD (Gershgorin).
    The CG correctness suite and fig17 solve against these systems; the
    conditioning is benign by construction so convergence behavior probes
    the *runtime*, not the matrix.
    """
    from .formats import csr_from_coo

    s = symmetrize(a)
    rows = np.repeat(np.arange(s.shape[0]), np.diff(s.indptr))
    off = rows != s.indices
    row_abs = np.zeros(s.shape[0], s.data.dtype)
    np.add.at(row_abs, rows[off], np.abs(s.data[off]))
    shift = np.float32(row_abs.max(initial=0.0) + margin)
    r = np.concatenate([rows, np.arange(s.shape[0])])
    c = np.concatenate([s.indices, np.arange(s.shape[0])])
    v = np.concatenate(
        [np.where(off, s.data, np.abs(s.data)), np.full(s.shape[0], shift, s.data.dtype)]
    )
    return csr_from_coo(s.shape, r, c, v)


# ---------------------------------------------------------------------------
# Dispatch layer — thin back-compat wrappers.
#
# New code should go through the repro.tune facade instead:
#     op = repro.tune.SparseOperator.build(csr);  y = op @ x
# which autotunes the (format, impl, params) choice per matrix and caches
# the plan.  These functions remain for callers that already hold prepared
# format dicts and want explicit dispatch.
# ---------------------------------------------------------------------------
def spmv(fmt: str, mat: dict[str, Any], x: jax.Array, *, n_rows: int, impl: str = "vector"):
    if fmt == "csr":
        fn = spmv_csr_scalar if impl == "scalar" else spmv_csr
        return fn(mat, x, n_rows=n_rows)
    if fmt == "sell":
        if impl == "pallas":
            from repro.kernels import ops as kops

            return kops.sell_spmv(mat, x)
        return spmv_sell(mat, x, n_rows=n_rows)
    if fmt == "merge":
        from repro.kernels.merge_spmv import merge_spmv

        return merge_spmv(mat, x)
    raise ValueError(f"unknown format for spmv: {fmt}")


def spmm(fmt: str, mat: dict[str, Any], x: jax.Array, *, n_rows: int, impl: str = "vector"):
    if fmt == "csr":
        return spmm_csr(mat, x, n_rows=n_rows)
    if fmt == "sell":
        return spmm_sell(mat, x, n_rows=n_rows)
    if fmt == "merge":
        from repro.kernels.merge_spmv import merge_spmm

        return merge_spmm(mat, x)
    if fmt == "bcsr":
        if impl == "pallas":
            from repro.kernels import ops as kops

            return kops.bcsr_spmm(mat, x)
        return spmm_bcsr_dense(mat, x, n_block_rows=n_rows)
    raise ValueError(f"unknown format for spmm: {fmt}")
