"""Candidate enumeration and the byte-model cost estimate that prunes it.

A *candidate* is one (format, impl, params) point from the cross-product the
paper sweeps by hand: CSR scalar/vector (Fig 4's -O1/-O3 tiers), SELL-C-sigma
with sigma in {1, 64, 256} and resident vs column-slabbed x (Fig 5 / cache
blocking), BCSR with the Table 2 block shapes, and the nnz-balanced merge
tier (kernels/merge_spmv) whose chunked-scan decomposition is immune to
row-length skew — the search-space answer to the paper's ``dynamic,64``
load balancing.

Pruning happens *before* any format is materialized or timed, from a cost
model in abstract byte units: the paper's §4.2 application-bytes model per
format (stored matrix bytes + vector traffic), scaled by an impl throughput
penalty (the scalar tier has no SIMD — paper Fig 4 shows ~an order of
magnitude; Pallas kernels on the CPU backend run in interpret mode and are
never competitive, which the model encodes so the measured search skips
them).  Candidates costlier than ``prune_factor`` x the cheapest estimate are
dropped without being timed.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Iterable

import numpy as np

from repro.core.distributed import SCHEDULES
from repro.core.formats import CSRMatrix
from repro.core.metrics import spmm_app_bytes, spmv_app_bytes

from .features import MatrixFeatures

__all__ = [
    "Candidate",
    "make",
    "split_reorder",
    "enumerate_candidates",
    "enumerate_mesh_candidates",
    "estimate_cost",
    "prune",
    "sell_padded_slots",
    "bcsr_block_count",
    "DEFAULT_PRUNE_FACTOR",
    "SELL_SIGMAS",
    "BCSR_BLOCKS",
    "MERGE_CHUNKS",
    "REORDER_METHODS",
    "ROW_IMBALANCE_WEIGHT",
    "SCHEDULES",
    "RING_STEP_OVERHEAD_BYTES",
    "SOLVER_STEP_AMORTIZE",
    "SOLVER_VEC_PASSES",
    "SMEM_BUDGET_BYTES",
    "tpu_pallas_fits",
]

SELL_SIGMAS = (1, 64, 256)
BCSR_BLOCKS = ((8, 8), (8, 16), (8, 128))  # Table 2's TPU-tile adaptation
MERGE_CHUNKS = (2048, 16384)  # equal-nnz grains for the merge tier
DEFAULT_PRUNE_FACTOR = 3.0
REORDER_METHODS = ("rcm",)  # paper §4.4; opt-in via enumerate(reorders=...)
# SCHEDULES (re-exported above) is owned by core.distributed: the module
# that implements a collective schedule is the one that names it.

# Impl throughput penalties (multiplies the byte estimate).  "scalar" is the
# paper's unvectorized -O1 tier; "pallas" on the CPU backend runs the kernels
# in interpret mode, which is orders of magnitude off and should never be
# picked (on TPU the penalty is 1.0 and the kernels compete on bytes).
SCALAR_SLOWDOWN = 32.0
INTERPRET_SLOWDOWN = 256.0

# Fixed dispatch/launch latency expressed in equivalent bytes (~100us at
# ~tens of GB/s).  Small problems are overhead-bound, where the byte streams
# cannot separate candidates — adding the constant makes their estimates
# near-tied so pruning keeps them all and the measured search decides.  At
# scale the streams dominate and pruning bites, exactly where the paper's
# bandwidth models are predictive.
OVERHEAD_BYTES = 4 * 1024 * 1024

# Per-rotation cost of the ring schedule in equivalent bytes: each of the P
# steps issues a ppermute + one slab SpMM, so the ring pays P small launches
# where allgather pays one collective.  The flip side (modelled below) is
# that the rotation bytes overlap the slab compute instead of serializing
# ahead of it.
RING_STEP_OVERHEAD_BYTES = 512 * 1024

# Row-imbalance penalty for tiers whose parallel decomposition follows rows.
# The paper's dynamic,64 scheduling absorbs skew on the Phi; a static
# row-parallel XLA program cannot, so its effective throughput degrades with
# the nnz/row dispersion (nnz_row_cv).  SELL pays its skew cost explicitly
# through padded slots (already in its byte count) and the merge tier's
# equal-nnz chunks pay nothing — only the CSR tiers carry this multiplier.
# The CV is capped so one pathological row cannot zero out a whole tier
# before measurement (pruning keeps near-ties; the measured search decides).
ROW_IMBALANCE_WEIGHT = 0.5
ROW_IMBALANCE_CV_CAP = 4.0

# Solver-step byte model (kind="solver_step"): inside a fused iterative
# solver the operand x is PRODUCED and CONSUMED on device between
# iterations — one lax.while_loop launch runs hundreds of steps — so the
# fixed dispatch constant that dominates small-matrix SpMV estimates is
# amortized over the whole solve.  That moves the crossover: candidates
# that were near-tied behind OVERHEAD_BYTES now separate on their stream
# bytes alone, which is why solver plans are tuned (and cached) as their
# own kind instead of reusing the spmv/spmm winner.  The step's non-SpMV
# traffic (axpys + dot reductions over the iteration vectors r/p/x/Ap)
# adds ~SOLVER_VEC_PASSES full passes over an m-vector per step —
# format-independent, but it keeps estimates honest against measurement.
SOLVER_STEP_AMORTIZE = 64.0  # iterations sharing one launch (order, not fit)
SOLVER_VEC_PASSES = 6


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the search space; params is a sorted tuple of pairs so
    the dataclass stays hashable (dict-valued params would not be)."""

    fmt: str  # csr | sell | sell_blocked | bcsr | dist (mesh schedules)
    impl: str  # scalar | vector | ref | pallas; for dist: allgather | ring
    params: tuple = ()

    @property
    def param_dict(self) -> dict[str, Any]:
        return dict(self.params)

    def key(self) -> str:
        if not self.params:
            return f"{self.fmt}/{self.impl}"
        inner = ",".join(f"{k}={v}" for k, v in self.params)
        return f"{self.fmt}/{self.impl}[{inner}]"


def make(fmt: str, impl: str, **params: Any) -> Candidate:
    norm = tuple(
        sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in params.items())
    )
    return Candidate(fmt, impl, norm)


def split_reorder(cand: Candidate) -> tuple[str | None, Candidate]:
    """(reorder method, candidate without the reorder param).

    Reordering (paper §4.4: RCM densification) is orthogonal to the
    format/impl choice, so it rides along as a ``reorder=<method>`` param;
    prepare/runner strip it here and wrap the base candidate in the
    permutation.
    """
    p = cand.param_dict
    method = p.pop("reorder", None)
    if method is None:
        return None, cand
    return str(method), make(cand.fmt, cand.impl, **p)


# The Pallas kernels' size rules on TPU (see kernels/ops.py).  A kernel is
# enumerated there only where what it keeps on chip fits: the lane-dense
# (rows, 128) vectors it indexes element-wise in VMEM, its x window, and
# the SELL kernel's double-buffered (cols, vals) tiles in SMEM (1 MiB).
SMEM_BUDGET_BYTES = 512 * 1024


def _lane_dense_bytes(n: int) -> int:
    return -(-max(int(n), 1) // 1024) * 1024 * 4


def tpu_pallas_fits(cand: Candidate, feats: MatrixFeatures) -> bool:
    """Whether a Pallas candidate's on-chip operands fit a v5e core.

    Besides sizes, one compiler refusal shapes the space: Mosaic slices a
    block stream out of HBM only in whole 128-lane tiles ("Slice shape
    along dimension 2 must be aligned to tiling (128), but is 8"), so the
    BCSR kernel runs only with lane-dense (bm, 128) blocks on TPU.
    """
    from repro.kernels.bcsr_spmm import TILE_ROWS
    from repro.kernels.ops import VMEM_BUDGET_BYTES
    from repro.kernels.sell_spmv import smem_tile_slots

    p = cand.param_dict
    if cand.fmt in ("sell", "sell_blocked"):
        smem = 2 * 2 * 4 * smem_tile_slots(
            int(p.get("chunk_tile", 8)), feats.nnz_row_max
        )
        n_slabs = int(p.get("n_slabs", 1))
        vmem = _lane_dense_bytes(feats.m) + _lane_dense_bytes(
            -(-feats.n // n_slabs)
        )
        return smem <= SMEM_BUDGET_BYTES and vmem <= VMEM_BUDGET_BYTES
    if cand.fmt == "bcsr":
        bm, bk = p["block"]
        window = TILE_ROWS + 2 * int(feats.bandwidth) + 2 * int(bk)
        return int(bk) == 128 and window * 128 * 4 <= VMEM_BUDGET_BYTES
    if cand.fmt == "spmspv":
        return _lane_dense_bytes(feats.m) <= VMEM_BUDGET_BYTES
    return True


def enumerate_candidates(
    feats: MatrixFeatures,
    kind: str = "spmv",
    *,
    k: int = 1,
    sigmas: Iterable[int] = SELL_SIGMAS,
    bcsr_blocks: Iterable[tuple[int, int]] = BCSR_BLOCKS,
    chunk_tiles: Iterable[int] = (8, 16),
    merge_chunks: Iterable[int] = MERGE_CHUNKS,
    include_scalar: bool = True,
    include_pallas: bool = True,
    reorders: Iterable[str] = (),
) -> list[Candidate]:
    """The format x impl x params cross-product for one matrix.

    SELL and the scalar tier only exist for SpMV (kind="spmv"); SpMM
    (kind="spmm") contrasts CSR gather/segment-sum with the Table 2 BCSR
    shapes.  Column-slabbed SELL variants are enumerated only when the x
    footprint exceeds the VMEM budget (features.x_fits_vmem).  The merge
    tier (nnz-balanced segmented scan, kernels/merge_spmv) enumerates for
    both kinds — it is the only tier whose work decomposition ignores the
    row distribution, so it is what the search falls back on when
    ``nnz_row_cv`` is high.

    ``kind="solver_step"`` (the fused iterative-solver runtime,
    runtime/solver.py) enumerates the SpMV space at ``k == 1`` and the
    SpMM space at block width ``k > 1`` — the candidate *kernels* are the
    same, but the byte model and the measured probe differ (see
    :func:`estimate_cost` ``fused=``), so solver plans are a separate
    cache kind.  The scalar tier is excluded: a solver multiplies every
    per-step cost by hundreds of iterations, and an unvectorized inner
    loop can never recover.

    ``reorders`` (e.g. ``("rcm",)``) doubles the space with row/column
    permuted variants of every non-scalar candidate — the paper's §4.4
    densification folded into the search.  Square matrices only (RCM is
    defined on the symmetrized pattern); the scalar tier is skipped since
    reordering cannot rescue an unvectorized inner loop.

    On TPU the Pallas candidates are kept only where
    :func:`tpu_pallas_fits` admits them (see :func:`_fit_backend`).
    """
    if kind == "solver_step":
        kind = "spmv" if int(k) == 1 else "spmm"
        include_scalar = False
    if kind == "spmspv":
        # Sparse-RHS search space: every dense-RHS SpMV tier competes through
        # a densify wrapper (tune.operator.sparse_rhs_runner), so the
        # dense-vs-spmspv crossover is a *measured* decision on one operand,
        # not an API fork; the spmspv bucket kernels join the same space.
        # The scalar tier is excluded (a sequential row loop cannot exploit
        # x sparsity) and reorders don't ride (the permutation would have to
        # re-sort the sparse coordinates on every call).
        cands = enumerate_candidates(
            feats,
            "spmv",
            k=1,
            sigmas=sigmas,
            bcsr_blocks=bcsr_blocks,
            chunk_tiles=chunk_tiles,
            merge_chunks=merge_chunks,
            include_scalar=False,
            include_pallas=include_pallas,
            reorders=(),
        )
        cands.append(make("spmspv", "ref"))
        if include_pallas:
            cands.append(make("spmspv", "pallas", slab=4096))
        return _fit_backend(cands, feats)
    cands: list[Candidate] = [make("csr", "vector")]
    cands.extend(make("merge", "scan", chunk=int(c)) for c in merge_chunks)
    if kind == "spmv":
        if include_scalar:
            cands.append(make("csr", "scalar"))
        for sigma in sigmas:
            cands.append(make("sell", "ref", C=8, sigma=sigma))
            if include_pallas:
                for ct in chunk_tiles:
                    cands.append(
                        make("sell", "pallas", C=8, sigma=sigma, chunk_tile=ct)
                    )
        if not feats.x_fits_vmem:
            # Column-slab SELL is an SpMV tier: its slabs split x, which at
            # k > 1 would be an (n, k) slab it has no kernel for.
            from repro.kernels.ops import VMEM_BUDGET_BYTES

            n_slabs = max(2, -(-feats.x_bytes // VMEM_BUDGET_BYTES))
            for sigma in sigmas:
                cands.append(
                    make("sell_blocked", "ref", C=8, sigma=sigma, n_slabs=n_slabs)
                )
                if include_pallas:
                    cands.append(
                        make(
                            "sell_blocked",
                            "pallas",
                            C=8,
                            sigma=sigma,
                            n_slabs=n_slabs,
                            chunk_tile=8,
                        )
                    )
    else:
        # SpMM grew a SELL tier (spmm_sell stacks the RHS through the
        # chunk-local gathers); the pallas SELL kernel remains k=1-only.
        for sigma in sigmas:
            cands.append(make("sell", "ref", C=8, sigma=sigma))
    for block in bcsr_blocks:
        cands.append(make("bcsr", "ref", block=tuple(block)))
        if include_pallas:
            cands.append(make("bcsr", "pallas", block=tuple(block)))
    if reorders and feats.m == feats.n:
        base = [c for c in cands if c.impl != "scalar"]
        for method in reorders:
            cands.extend(
                make(c.fmt, c.impl, reorder=method, **c.param_dict) for c in base
            )
    return _fit_backend(cands, feats)


def _fit_backend(cands: list[Candidate], feats: MatrixFeatures) -> list[Candidate]:
    """On TPU, drop the Pallas candidates :func:`tpu_pallas_fits` refuses,
    so no kernel reaches the measured search to be refused there.
    Elsewhere the kernels run in interpret mode and stay enumerated (the
    cost model prices them out)."""
    import jax

    if jax.default_backend() != "tpu":
        return cands
    return [
        c for c in cands
        if split_reorder(c)[1].impl != "pallas"
        or tpu_pallas_fits(split_reorder(c)[1], feats)
    ]


def enumerate_mesh_candidates(
    feats: MatrixFeatures,
    n_shards: int,
    *,
    schedules: Iterable[str] = SCHEDULES,
) -> list[Candidate]:
    """The collective-schedule dimension of the search space.

    On a device mesh the format question collapses to local CSR (shards jit
    under shard_map with static shapes) and the open dimension is *how x
    reaches every shard* — the paper's "input vector distribution" future-work
    note.  Each schedule is one candidate (``fmt="dist"``, impl names the
    schedule); :func:`estimate_cost` separates them by collective bytes and
    the measured search settles ties, exactly like the single-device tiers.
    """
    return [make("dist", s, n_shards=int(n_shards)) for s in schedules]


# ---------------------------------------------------------------------------
# Byte-model cost estimate (paper §4.2, generalized per format)
# ---------------------------------------------------------------------------
def sell_padded_slots(
    lengths: np.ndarray, C: int, sigma: int, width_align: int = 8
) -> int:
    """Stored slots (incl. padding) of sell_from_csr for these row lengths.

    Mirrors formats.sell_from_csr exactly: rows sorted by descending length
    within sigma-windows, chunks of C rows, all chunks padded to the global
    max width rounded up to width_align.
    """
    m = lengths.size
    if m == 0:
        return 0
    window = np.arange(m) // sigma
    # lexsort: primary key window, secondary descending length — the same
    # multiset per window as the per-window argsort in sell_from_csr.
    sorted_len = lengths[np.lexsort((-lengths, window))]
    n_chunks = -(-m // C)
    padded = np.zeros(n_chunks * C, dtype=np.int64)
    padded[:m] = sorted_len
    W = int(max(padded.reshape(n_chunks, C).max(axis=1).max(initial=1), 1))
    if width_align > 1:
        W = -(-W // width_align) * width_align
    return n_chunks * C * W


def bcsr_block_count(a: CSRMatrix, block: tuple[int, int]) -> int:
    """Number of occupied (bm, bk) blocks — no block materialization."""
    if a.nnz == 0:
        return 0
    bm, bk = block
    rows = np.repeat(np.arange(a.shape[0], dtype=np.int64), np.diff(a.indptr))
    gn = -(-a.shape[1] // bk)
    key = (rows // bm) * gn + a.indices.astype(np.int64) // bk
    return int(np.unique(key).size)


def estimate_cost(
    a: CSRMatrix,
    cand: Candidate,
    feats: MatrixFeatures,
    *,
    k: int = 1,
    val_bytes: int = 4,
    idx_bytes: int = 4,
    on_cpu: bool | None = None,
    fused: bool = False,
    sparse_rhs: bool = False,
) -> float:
    """Abstract cost (bytes x impl slowdown) of running this candidate.

    Only relative magnitudes matter: prune() compares candidates against the
    cheapest estimate for the same matrix.

    ``fused=True`` estimates one *solver step* instead of one standalone
    dispatch (kind="solver_step"): the operand is produced and consumed on
    device inside a single ``lax.while_loop`` launch, so the fixed dispatch
    constant is divided by :data:`SOLVER_STEP_AMORTIZE` and the step's
    axpy/dot vector traffic (:data:`SOLVER_VEC_PASSES` m-vector passes) is
    added.  Small matrices stop being overhead-bound under fusion, which
    is exactly the crossover shift that makes solver plans their own kind.

    ``sparse_rhs=True`` estimates serving a *sparse* x (kind="spmspv"):
    the ``fmt="spmspv"`` branch charges only the touched columns (scaled
    by ``feats.x_density``), while dense-RHS tiers pay one extra densify
    pass over the operand vector — which is how the tuner crosses over
    from the dense tiers to spmspv as x thins.
    """
    if on_cpu is None:
        from repro.kernels.ops import on_cpu as _on_cpu

        on_cpu = _on_cpu()
    m, n = a.shape
    method, base = split_reorder(cand)
    if method is not None:
        # Estimated on the *original* structure (permuting just to estimate
        # would cost more than the estimate saves); RCM typically reduces
        # SELL padding, so this is conservative.  The extra term is the
        # x-gather / y-scatter permutation traffic at the boundary.
        perm_bytes = (m + n) * (k * val_bytes + idx_bytes)
        return (
            estimate_cost(
                a, base, feats, k=k, val_bytes=val_bytes,
                idx_bytes=idx_bytes, on_cpu=on_cpu, fused=fused,
                sparse_rhs=sparse_rhs,
            )
            + perm_bytes
        )
    p = cand.param_dict
    if cand.fmt == "spmspv":
        # Work-efficient SpMSpV (Azad-Buluc bucket scheme): traffic scales
        # with the TOUCHED columns only — expected gathered products are
        # x_density * nnz — never with nnz(A).  Streams: the CSC gather of
        # touched (row, val) pairs, the expanded product stream's write +
        # scatter read-back, the x coordinates with their column-table
        # lookups, and the accumulator output.
        density = min(max(float(feats.x_density), 0.0), 1.0)
        touched = density * float(a.nnz)
        bytes_ = (
            3.0 * touched * (val_bytes + idx_bytes)
            + density * n * (2 * idx_bytes + val_bytes)
            + m * val_bytes
        )
    elif cand.fmt == "csr":
        bytes_ = (
            spmv_app_bytes(m, n, a.nnz, val_bytes, idx_bytes)
            if k == 1
            else spmm_app_bytes(m, n, a.nnz, k, val_bytes, idx_bytes)
        )
        # Row-parallel decomposition: effective bytes degrade with nnz/row
        # dispersion (see ROW_IMBALANCE_WEIGHT above).  SELL pays this
        # through padded slots; merge is immune by construction.
        cv = min(float(feats.nnz_row_cv), ROW_IMBALANCE_CV_CAP)
        bytes_ = bytes_ * (1.0 + ROW_IMBALANCE_WEIGHT * cv)
    elif cand.fmt == "merge":
        # Equal-nnz chunks: padded product stream in, segmented scan
        # (read + write ~ one extra pass over the products), row-end
        # gathers.  No term depends on the row distribution — that is the
        # tier's reason to exist.
        chunk = max(1, int(p["chunk"]))
        nnz_pad = max(1, -(-a.nnz // chunk)) * chunk
        bytes_ = (
            nnz_pad * (val_bytes + idx_bytes)  # data + indices streams
            + n * k * val_bytes  # x gather
            + 2 * nnz_pad * k * val_bytes  # scan write + gather-back
            + m * (2 * idx_bytes + k * val_bytes)  # start/end + y out
        )
    elif cand.fmt in ("sell", "sell_blocked"):
        lengths = np.diff(a.indptr).astype(np.int64)
        slots = sell_padded_slots(lengths, int(p["C"]), int(p["sigma"]))
        bytes_ = (
            slots * (val_bytes + idx_bytes)  # padded cols+vals streams
            + (m + n) * k * val_bytes  # x in, y out
            + m * idx_bytes  # row_perm
        )
        if cand.fmt == "sell_blocked":
            # Slab splitting re-pads each slab to its own width; small
            # overhead on top of the whole-matrix estimate.
            bytes_ = int(bytes_ * 1.15)
    elif cand.fmt == "bcsr":
        bm, bk = p["block"]
        n_blocks = bcsr_block_count(a, (int(bm), int(bk)))
        bytes_ = (
            n_blocks * (bm * bk * val_bytes + 2 * idx_bytes)  # fill-in stored
            + (m + n) * k * val_bytes
        )
        if cand.impl == "ref" and not on_cpu:
            # On TPU the XLA tier's (n_blocks, bm, bk) blocks, gathered
            # (bk, k) x tiles and (bm, k) products are each laid out in
            # (8, 128) tiles: a narrow minor dimension is padded to 128
            # lanes (webbase-1M's (8, 8) blocks at k = 16 asked for 33 GB
            # of a v5e's 16 GB HBM).
            def tiled(rows, cols):
                return -(-rows // 8) * 8 * -(-cols // 128) * 128 * val_bytes

            bytes_ += n_blocks * (
                tiled(bm, bk) + tiled(bk, k) + tiled(bm, k)
                - bm * bk * val_bytes
            )
    elif cand.fmt == "dist":
        # Collective schedules (core.distributed): per-shard stream bytes
        # plus the traffic needed to make x visible to every shard — the
        # multi-chip form of the paper's "same x re-fetched into 61 private
        # L2s" observation.  Both schedules move (P-1)/P * |x| per shard;
        # allgather pays it up-front (serialized with compute), the ring
        # overlaps rotation with the matching col-slab SpMM at the price of
        # P per-step launches.
        P = max(1, int(p["n_shards"]))
        local = (
            spmv_app_bytes(m, n, a.nnz, val_bytes, idx_bytes)
            if k == 1
            else spmm_app_bytes(m, n, a.nnz, k, val_bytes, idx_bytes)
        ) / P
        collective = (P - 1) / P * n * k * val_bytes
        if cand.impl == "allgather":
            bytes_ = local + collective
        elif cand.impl == "ring":
            bytes_ = max(local, collective) + P * RING_STEP_OVERHEAD_BYTES
        else:  # pragma: no cover - enumeration and cost stay in sync
            raise ValueError(f"unknown schedule impl: {cand.impl}")
    else:  # pragma: no cover - enumeration and cost stay in sync
        raise ValueError(f"unknown candidate format: {cand.fmt}")

    if sparse_rhs and cand.fmt != "spmspv":
        # A dense-RHS tier serving a sparse request densifies first: one
        # zeros-init + scatter pass over the operand vector.
        bytes_ = float(bytes_) + n * val_bytes

    slowdown = 1.0
    if cand.impl == "scalar":
        slowdown = SCALAR_SLOWDOWN
    elif cand.impl == "pallas" and on_cpu:
        slowdown = INTERPRET_SLOWDOWN
    overhead = OVERHEAD_BYTES
    if fused:
        # One launch runs the whole solve: the dispatch constant amortizes
        # over the iterations, and every step pays the axpy/dot reduction
        # traffic on top of the kernel's streams.
        overhead = OVERHEAD_BYTES / SOLVER_STEP_AMORTIZE
        bytes_ = float(bytes_) + SOLVER_VEC_PASSES * m * k * val_bytes
    cost = (float(bytes_) + overhead) * slowdown
    if not math.isfinite(cost):
        # Degenerate inputs (nnz = 0, poisoned features) must never hand a
        # NaN to prune(): NaN loses every comparison silently, so the whole
        # ranking would be garbage.  An infinite estimate simply loses, and
        # prune()'s fallback still keeps a deterministic default.
        return math.inf
    return cost


def prune(
    costs: dict[Candidate, float], factor: float = DEFAULT_PRUNE_FACTOR
) -> list[Candidate]:
    """Keep candidates within ``factor`` of the cheapest estimate.

    The cheapest candidate always survives, so the measured search is never
    left with an empty slate.  Non-finite estimates never rank: when every
    estimate is inf/NaN (a degenerate matrix poisoned the byte model) the
    tuner falls back to ONE deterministic default — the baseline csr/vector
    tier when enumerated — instead of silently comparing NaNs.
    """
    if not costs:
        return []
    finite = {c: est for c, est in costs.items() if math.isfinite(est)}
    if not finite:
        for c in costs:
            if c.fmt == "csr" and c.impl == "vector":
                return [c]
        return [next(iter(costs))]
    best = min(finite.values())
    return [c for c, est in finite.items() if est <= factor * best]
