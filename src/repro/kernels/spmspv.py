"""SpMSpV — y = A @ x with a *sparse* x — the work-efficient bucket tier.

Adaptation of the Azad-Buluc SpMSpV-bucket algorithm (the ``pla-kernels``
exemplar in SNIPPETS.md) to statically-shaped XLA/Pallas:

* ``spmspv_prepare`` builds the CSC view of A once on host (column starts
  and lengths plus row/value streams), because a sparse x touches
  *columns*, not rows; a virtual length-0 sentinel column at index ``n``
  makes padded x-slots free.
* dispatch expands exactly the touched columns into a ``(rows, products)``
  stream: per-slot offsets come from a cumsum over the touched column
  lengths and a ``searchsorted`` maps every product lane back to its
  x-slot — O(T log B) for T gathered nonzeros, never O(nnz(A)).  The
  stream is padded to a static *work bucket* G drawn from a geometric
  ladder (``WORK_BUCKET_BASE * WORK_BUCKET_GROWTH**i``, capped at nnz),
  the per-request analogue of the engine's k-bucket round-up, so every
  (B, G) pair compiles exactly once.
* accumulation is the bucket scatter.  The ref impl is one segment
  scatter (``zeros(m).at[rows].add(products)``); the Pallas impl streams
  the (rows, products) buckets through ``kernels.pipeline.slab_pipeline``
  into a VMEM-resident accumulator — Azad & Buluc's destination buckets
  become slab-serialized DMA chunks (the sequential slab loop needs no
  atomics, and on hardware the next slab's DMA overlaps the current
  slab's scatter).

Padding conventions: x-slots pad with the sentinel column index ``n`` and
value 0; product lanes beyond the true total T carry (row 0, value 0).
An all-zero / empty x is therefore the smallest work bucket of pure
padding and returns exact zeros — degenerate inputs are the fast path,
not a crash.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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

__all__ = [
    "WORK_BUCKET_BASE",
    "WORK_BUCKET_GROWTH",
    "expand_products",
    "pad_sparse_rhs",
    "spmspv_bind",
    "spmspv_prepare",
    "spmspv_ref_fn",
    "spmspv_scatter_pallas",
    "validate_sparse_rhs",
    "work_bucket",
]

# Geometric work-bucket ladder: G = BASE * GROWTH**i, capped at nnz(A)
# rounded up to BASE.  The scatter's cost is O(G) whatever the real work,
# so BASE bounds the thin-x floor — 256 keeps a one-column request ~16x
# cheaper than the old 4096 floor while still amortizing dispatch.  Pallas
# slabs are clamped to gcd(slab, G) (both powers-of-two multiples of BASE)
# so the stream always tiles evenly.
WORK_BUCKET_BASE = 256
WORK_BUCKET_GROWTH = 4
SLAB_ALIGN = 1024  # the pallas scatter's slab (and bucket) granularity


def validate_sparse_rhs(indices, values, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Validate a sparse RHS given as (indices, values); return host copies.

    Loud rejection with remediation text (the merge-tier OverflowError
    style): the bucketed dispatch keys column segments by sorted
    coordinates, so out-of-range, unsorted, or duplicated indices would
    silently corrupt the gather instead of failing here.
    """
    idx = np.asarray(indices)
    val = np.asarray(values)
    if idx.ndim != 1 or val.ndim != 1 or idx.shape[0] != val.shape[0]:
        raise ValueError(
            f"sparse RHS: indices shape {idx.shape} and values shape {val.shape} "
            "must be 1-D and the same length; pass the nonzero coordinates of x "
            "as (indices, values)"
        )
    if not np.issubdtype(idx.dtype, np.integer):
        raise ValueError(
            f"sparse RHS: indices dtype {idx.dtype} is not an integer type; pass "
            "int32/int64 column coordinates (np.nonzero(x) produces them directly)"
        )
    if idx.size:
        lo, hi = int(idx.min()), int(idx.max())
        if lo < 0 or hi >= n:
            bad = lo if lo < 0 else hi
            raise ValueError(
                f"sparse RHS: index {bad} is outside [0, {n}) for this "
                f"{n}-column operand; sparse coordinates address columns of A — "
                "check the operand orientation or clip the coordinate list"
            )
        if np.any(np.diff(idx) <= 0):
            raise ValueError(
                "sparse RHS: indices must be strictly increasing (sorted, no "
                "duplicates) — the bucketed dispatch keys column segments by "
                "sorted coordinates; canonicalize with np.unique (summing the "
                "values of duplicate coordinates first)"
            )
    return idx.astype(np.int64, copy=False), val


def pad_sparse_rhs(idx: np.ndarray, val: np.ndarray, bucket: int, n: int):
    """Pad validated (idx, val) to the x-nnz ``bucket`` with sentinel slots."""
    size = int(idx.size)
    if size > bucket:
        raise ValueError(
            f"sparse RHS has nnz={size} but the x-nnz bucket is {bucket}; "
            f"build the operator with x_nnz >= {size} (the engine's "
            "submit_sparse picks the bucket automatically)"
        )
    xi = np.full(bucket, n, dtype=np.int32)  # sentinel = empty column n
    xv = np.zeros(bucket, dtype=np.float32)
    xi[:size] = idx
    xv[:size] = val
    return xi, xv


def spmspv_prepare(a) -> dict:
    """Host-side CSC view of a CSR matrix, with a sentinel empty column.

    Returns ``col_start``/``col_len`` of shape (n+1,) — entry ``n`` is the
    virtual length-0 padding column — plus the CSC-ordered ``rows``/``vals``
    streams (padded with one zero entry so gathers stay in-bounds when
    nnz == 0).  ``col_len_np`` keeps a host copy for the O(nnz(x))
    work-bucket selection at dispatch time.
    """
    m, n = a.shape
    nnz = int(a.indptr[-1])
    if nnz >= 2**31:
        raise OverflowError(
            f"spmspv tier: nnz={nnz} overflows the int32 CSC offsets; this "
            "matrix needs row-partitioned shards each below 2**31 nnz"
        )
    lengths = np.diff(np.asarray(a.indptr))
    rows_of = np.repeat(np.arange(m, dtype=np.int64), lengths)
    order = np.argsort(np.asarray(a.indices), kind="stable")
    csc_rows = rows_of[order].astype(np.int32)
    csc_vals = np.asarray(a.data)[order].astype(np.float32)
    if csc_rows.size == 0:
        csc_rows = np.zeros(1, np.int32)
        csc_vals = np.zeros(1, np.float32)
    col_len = np.zeros(n + 1, np.int32)
    if n:
        col_len[:n] = np.bincount(np.asarray(a.indices), minlength=n)
    col_start = np.zeros(n + 1, np.int32)
    col_start[1:] = np.cumsum(col_len[:n])  # col_start[n] = nnz: empty sentinel
    return {
        "col_start": jnp.asarray(col_start),
        "col_len": jnp.asarray(col_len),
        "rows": jnp.asarray(csc_rows),
        "vals": jnp.asarray(csc_vals),
        "col_len_np": col_len,
        "shape": (int(m), int(n)),
        "nnz": nnz,
    }


def work_bucket(total: int, nnz: int) -> int:
    """Smallest ladder bucket >= ``total`` gathered products, capped at nnz.

    The cap is nnz rounded up to WORK_BUCKET_BASE, so G is always a
    multiple of the base (and therefore of every pallas slab size) and the
    number of distinct compiled sizes stays logarithmic.
    """
    cap = -(-max(int(nnz), 1) // WORK_BUCKET_BASE) * WORK_BUCKET_BASE
    g = WORK_BUCKET_BASE
    while g < min(int(total), cap):
        g *= WORK_BUCKET_GROWTH
    return min(g, cap)


def expand_products(prep: dict, xi, xv, G: int):
    """Expand touched columns into (rows, products) streams of length G.

    ``searchsorted`` over the cumulative touched-column lengths maps each
    product lane t back to its x-slot; lanes past the true total carry
    (row 0, value 0) so the downstream scatter adds exact zeros.
    """
    B = xi.shape[0]
    lens = prep["col_len"][xi]  # (B,); the sentinel column n contributes 0
    offs = jnp.concatenate([jnp.zeros(1, lens.dtype), jnp.cumsum(lens)])
    total = offs[-1]
    t = jnp.arange(G, dtype=jnp.int32)
    slot = jnp.clip(jnp.searchsorted(offs, t, side="right").astype(jnp.int32) - 1, 0, B - 1)
    within = t - offs[slot]
    valid = t < total
    src = jnp.where(valid, prep["col_start"][xi[slot]] + within, 0)
    rows = jnp.where(valid, prep["rows"][src], 0)
    prods = jnp.where(valid, prep["vals"][src] * xv[slot], 0.0)
    return rows, prods


_CSC_KEYS = ("col_start", "col_len", "rows", "vals")


def _csc(prep: dict) -> dict:
    """The device leaves of a prepared CSC view (what a dispatch reads)."""
    return {k: prep[k] for k in _CSC_KEYS}


@functools.partial(jax.jit, static_argnames=("m", "G"))
def _spmspv_ref(csc, xi, xv, *, m, G):
    rows, prods = expand_products(csc, xi, xv, G)
    return jnp.zeros((m,), prods.dtype).at[rows].add(prods)


def spmspv_ref_fn(prep: dict, G: int):
    """Jitted reference impl: expansion + one XLA segment scatter."""
    m, _ = prep["shape"]
    csc = _csc(prep)
    return lambda xi, xv: _spmspv_ref(csc, xi, xv, m=m, G=G)


@functools.partial(jax.jit, static_argnames=("m", "slab", "interpret", "pipelined"))
def spmspv_scatter_pallas(rows, prods, *, m, slab, interpret=False, pipelined=None):
    """Bucketed scatter: stream (rows, products) slabs into a VMEM accumulator.

    The slab loop is sequential, so read-modify-write accumulation needs no
    atomics; with ``pipelined=True`` the DMA pipeline prefetches slab s+1
    while slab s scatters.  Mosaic lowers no vector scatter
    (``.at[rows].add`` is refused: "Unimplemented primitive ...
    scatter-add"), so the (rows, products) slabs stream into SMEM and the
    scalar core adds each product into the lane-dense accumulator
    (``pipeline.add_to_elem``: one dynamic row load, masked add, store).
    """
    (G,) = rows.shape
    if G % slab:
        raise ValueError(f"work bucket {G} must tile into slabs of {slab}")
    n_slabs = G // slab
    pipe = resolve_pipelined(pipelined, interpret)
    out_rows = lane_dense(jnp.zeros((m,), jnp.float32)).shape[0]

    def _kernel(rows_hbm, prods_hbm, o_ref):
        o_ref[...] = jnp.zeros_like(o_ref)
        lane = lane_iota()

        def bucket(s, rows_t, prods_t):
            def eight(ii, _):  # 8 products per trip, unrolled
                for u in range(8):
                    i = ii * 8 + u
                    add_to_elem(o_ref, rows_t[i], prods_t[i], lane)
                return 0

            jax.lax.fori_loop(0, slab // 8, eight, 0)

        slab_pipeline(
            bucket,
            [(rows_hbm, slab, SMEM), (prods_hbm, slab, SMEM)],
            n_slabs,
            pipelined=pipe,
        )

    out = pl.pallas_call(
        _kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((out_rows, LANES), jnp.float32),
        compiler_params=_CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(rows, prods.astype(jnp.float32))
    return out.reshape(-1)[:m].astype(prods.dtype)


def spmspv_pallas_fn(prep: dict, G: int, slab: int, interpret: bool, pipelined=None):
    """Jitted pallas impl: expansion + bucketed slab-pipeline scatter."""
    import math

    m, _ = prep["shape"]
    # Mosaic slices the 1-D (rows, products) streams only in whole 1024
    # tiles: the smallest ladder bucket is padded up to one (its extra
    # lanes are (row 0, value 0)), and gcd keeps slab | G for every ladder
    # point (both are power-of-two multiples of 1024 from here on).
    G = max(int(G), SLAB_ALIGN)
    slab = max(math.gcd(int(slab), G), SLAB_ALIGN)
    csc = _csc(prep)
    return lambda xi, xv: _spmspv_pallas(
        csc, xi, xv, m=m, G=G, slab=slab, interpret=interpret,
        pipelined=pipelined,
    )


@functools.partial(
    jax.jit, static_argnames=("m", "G", "slab", "interpret", "pipelined")
)
def _spmspv_pallas(csc, xi, xv, *, m, G, slab, interpret, pipelined):
    rows, prods = expand_products(csc, xi, xv, G)
    return spmspv_scatter_pallas(
        rows, prods, m=m, slab=slab, interpret=interpret, pipelined=pipelined
    )


def spmspv_bind(prep: dict, x_nnz: int, *, impl="ref", slab=4096, interpret=None):
    """Bind ``fn((xi, xv)) -> y`` over padded (x_nnz,) sparse operands.

    The host picks the work bucket G from the geometric ladder in
    O(nnz(x)) numpy (sum of touched column lengths) and dispatches the
    (x_nnz, G) executable, compiled once per bucket pair — the kernel-side
    mirror of how the engine rounds requests up to nnz buckets.

    Pass the padded operands as HOST numpy arrays (``pad_sparse_rhs``
    output): the bucket selection reads ``xi`` on host, so a device array
    here forces a device->host sync per call that costs more than the
    kernel at serving sizes.  Device arrays still work, just slower.
    """
    if interpret is None:
        from .ops import on_cpu

        interpret = on_cpu()
    col_len = prep["col_len_np"]
    nnz = prep["nnz"]
    fns: dict[int, object] = {}

    def fn(sx):
        xi, xv = sx
        xi_host = np.clip(np.asarray(xi).astype(np.int32, copy=False),
                          0, col_len.size - 1)
        total = int(col_len[xi_host].sum())
        G = work_bucket(total, nnz)
        run = fns.get(G)
        if run is None:
            if impl == "ref":
                run = spmspv_ref_fn(prep, G)
            else:
                run = spmspv_pallas_fn(prep, G, int(slab), bool(interpret))
            fns[G] = run
        # Hand the jitted executable the host arrays directly — an explicit
        # jnp.asarray here costs more dispatch than the kernel at thin x.
        return run(xi_host, np.asarray(xv, dtype=np.float32))

    return fn
