"""The SparseOperator facade: one object wrapping prepare + dispatch.

    from repro.tune import SparseOperator
    op = SparseOperator.build(csr)      # autotuned (plan-cached) SpMV
    y = op @ x

``build`` runs the paper's whole selection pipeline: extract structural
features, enumerate the format x impl x params cross-product, prune it with
the byte-model cost estimate, time the survivors with the benchmark timer,
persist the winning :class:`~repro.tune.plan.Plan` in the JSON plan cache
(keyed by structure fingerprint, so a rebuild skips the search), and return
an operator holding the prepared device arrays for the winning candidate.

``core.spmv.spmv``/``spmm`` remain as the thin low-level dispatch for code
that already holds prepared format dicts; everything user-facing goes
through this facade.
"""
from __future__ import annotations

import collections
import hashlib
import math
import os
import threading
import warnings
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import CSRMatrix, bcsr_from_csr, sell_from_csr
from repro.core.spmv import (
    csr_bind,
    csr_prepare,
    spmm_bcsr_dense,
    spmm_csr,
    spmm_sell,
    spmv_csr,
    spmv_csr_scalar,
    spmv_sell,
)

from .candidates import Candidate, enumerate_candidates, estimate_cost, prune
from .candidates import (
    DEFAULT_PRUNE_FACTOR,
    REORDER_METHODS,
    enumerate_mesh_candidates,
    fits_prep_budget,
    split_reorder,
)
from repro.runtime.executable import hoisted_jit

from .features import MatrixFeatures, extract
from .plan import Plan, PlanCache, default_cache, fingerprint
from .timing import RACE_FACTOR, time_fn

__all__ = [
    "SparseOperator",
    "PrepCache",
    "prep_nbytes",
    "prepare",
    "prepare_cached",
    "evict_prepared",
    "prep_memo_stats",
    "runner",
    "solver_step_probe",
    "sparse_rhs_runner",
]


# ---------------------------------------------------------------------------
# Prepare + dispatch per candidate
# ---------------------------------------------------------------------------
def prepare(
    a: CSRMatrix,
    cand: Candidate,
    *,
    mesh=None,
    axis: str | None = None,
    prep_cache: dict | None = None,
) -> dict[str, Any]:
    """Host-side format construction for one candidate.

    ``fmt="dist"`` candidates (collective schedules) additionally need the
    target ``mesh``/``axis`` so the stacked shard arrays land row-sharded on
    the device mesh.  ``prep_cache`` (keyed by schedule) shares the placed
    operand across calls for the same matrix: the engine's k-buckets differ
    only in RHS width, so one partition+placement per schedule serves every
    bucket instead of holding per-bucket copies on the devices.
    """
    from repro.kernels import ops as kops

    method, base = split_reorder(cand)
    if method is not None:
        from repro.core import reorder as ro

        perm = {"rcm": ro.rcm, "degree": ro.degree_order}[method](a)
        ar = a.permuted(perm)
        return {"perm": perm, "matrix": ar, "inner": prepare(ar, base)}

    p = cand.param_dict
    if cand.fmt == "dist":
        from repro.core.distributed import build_mesh_operand, place_mesh_operand

        if mesh is None or axis is None:
            raise ValueError("dist candidates need mesh= and axis=")
        key = (cand.impl, int(p["n_shards"]))
        if prep_cache is not None and key in prep_cache:
            return prep_cache[key]
        prep = place_mesh_operand(
            build_mesh_operand(a, int(p["n_shards"]), cand.impl), mesh, axis
        )
        if prep_cache is not None:
            prep_cache[key] = prep
        return prep
    if cand.fmt == "csr":
        return {"dev": csr_prepare(a)}  # row map hoisted out of dispatch
    if cand.fmt == "merge":
        from repro.kernels.merge_spmv import merge_prepare

        return merge_prepare(a, int(p.get("chunk", 4096)))
    if cand.fmt == "sell":
        return kops.sell_prepare(
            sell_from_csr(a, C=int(p["C"]), sigma=int(p["sigma"]), width_align=8),
            int(p.get("chunk_tile", 8)),
        )
    if cand.fmt == "sell_lr":
        return kops.sell_lr_prepare(
            a, C=int(p["C"]), chunk_tile=int(p["chunk_tile"])
        )
    if cand.fmt == "sell_blocked":
        if cand.impl == "pallas":
            # Stacked single-launch variant: slabs share one row permutation
            # and the kernel streams (A-slab, x-slab) pairs through the
            # double-buffered pipeline.
            return kops.sell_prepare_blocked_stacked(
                a, int(p["n_slabs"]), C=int(p["C"]), sigma=int(p["sigma"])
            )
        return kops.sell_prepare_blocked(
            a,
            int(p["n_slabs"]),
            chunk_tile=int(p.get("chunk_tile", 8)),
            C=int(p["C"]),
            sigma=int(p["sigma"]),
        )
    if cand.fmt == "bcsr":
        return kops.bcsr_prepare(bcsr_from_csr(a, tuple(p["block"])))
    if cand.fmt == "spmspv":
        from repro.kernels.spmspv import spmspv_prepare

        return spmspv_prepare(a)
    raise ValueError(f"unknown candidate format: {cand.fmt}")


# ---------------------------------------------------------------------------
# Preparation memo: one prepared-dict instance per (structure, values, cand)
# ---------------------------------------------------------------------------
def prep_nbytes(obj: Any) -> int:
    """Device/host bytes pinned by a prepared format dict (recursive).

    Counts every array leaf (jax and numpy both expose ``.nbytes``) through
    nested dicts/lists, including the reordered-candidate case where the
    prep holds a whole permuted :class:`CSRMatrix`.  This is the weight the
    residency budgets below (and the fleet's tenant accounting) charge.
    """
    if isinstance(obj, CSRMatrix):
        return prep_nbytes([obj.indptr, obj.indices, obj.data])
    if isinstance(obj, dict):
        return sum(prep_nbytes(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(prep_nbytes(v) for v in obj)
    nbytes = getattr(obj, "nbytes", None)
    return int(nbytes) if nbytes is not None else 0


_ENV_PREP_BUDGET = "REPRO_PREP_BUDGET_BYTES"
_DEFAULT_PREP_BUDGET = 256 * 1024 * 1024  # prepared dicts are O(matrix) each


class PrepCache:
    """Byte-budgeted, thread-safe memo of prepared format dicts.

    The engine's k-buckets and the benchmarks' pinned candidates used to
    re-prepare (and re-hold on device) one format dict per k — but
    preparation depends only on the matrix, never on k.  Keyed by the
    structure fingerprint plus a value digest (two matrices sharing a
    pattern share plans but NOT prepared values), every caller holding the
    same matrix shares one instance.

    Pre-PR-7 this memo was an unbounded-bytes LRU capped at 64 *entries*;
    across a multi-tenant fleet that is hundreds of matrices' prepared
    arrays pinned forever.  Now eviction is by BYTES (LRU order, never the
    entry just inserted — the caller holds it), with hit/miss/evict
    counters surfaced through :func:`prep_memo_stats` into ``FleetStats``.
    A single prep larger than the whole budget is still served (the caller
    needs it) and becomes the next insert's first eviction.
    """

    def __init__(self, budget_bytes: int | None = None):
        if budget_bytes is None:
            budget_bytes = int(
                os.environ.get(_ENV_PREP_BUDGET, _DEFAULT_PREP_BUDGET)
            )
        self.budget_bytes = int(budget_bytes)
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._bytes: dict = {}  # key -> cached prep_nbytes (walk once)
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def resident_bytes(self) -> int:
        return sum(self._bytes.values())

    def get_or_build(self, key: tuple, build: Callable[[], dict]) -> dict:
        with self._lock:
            prep = self._entries.get(key)
            if prep is not None:
                self.hits += 1
                self._entries.move_to_end(key)
                return prep
            self.misses += 1
        # Build OUTSIDE the lock: preparation is O(nnz) host work and two
        # threads preparing different matrices must not serialize.  A racing
        # duplicate build of the same key is wasted work, not corruption —
        # last insert wins and both callers hold a correct prep.
        prep = build()
        nbytes = prep_nbytes(prep)
        with self._lock:
            self._entries[key] = prep
            self._entries.move_to_end(key)
            self._bytes[key] = nbytes
            while (
                len(self._entries) > 1
                and self.resident_bytes > self.budget_bytes
            ):
                old_key, _ = self._entries.popitem(last=False)
                self._bytes.pop(old_key, None)
                self.evictions += 1
        return prep

    def evict_fp(self, fp: str) -> int:
        """Drop every entry of one fingerprint (fleet tenant eviction must
        actually release the prepared arrays, not just the engine).  Returns
        bytes released."""
        with self._lock:
            keys = [k for k in self._entries if k[0] == fp]
            released = 0
            for k in keys:
                del self._entries[k]
                released += self._bytes.pop(k, 0)
                self.evictions += 1
            return released

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes.clear()

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "resident_bytes": self.resident_bytes,
                "budget_bytes": self.budget_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
            }


_PREP_MEMO = PrepCache()


def _value_digest(a: CSRMatrix) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(a.data).tobytes()
    ).hexdigest()[:16]


def prepare_cached(
    a: CSRMatrix,
    cand: Candidate,
    *,
    fp: str | None = None,
    mesh=None,
    axis: str | None = None,
    prep_cache: dict | None = None,
) -> dict[str, Any]:
    """:func:`prepare`, memoized on (fingerprint, value digest, candidate)
    in the process-wide byte-budgeted :class:`PrepCache`.

    ``fmt="dist"`` candidates bypass the memo — their placement is mesh-bound
    and already shared through the caller-scoped ``prep_cache``.
    """
    from repro.runtime.faults import active_plan

    faults = active_plan()
    if faults is not None:
        # The OOM injection site: format preparation is where the biggest
        # allocations happen (padded slabs, permutations), so this is where
        # a memory-pressure fault would surface in production.
        faults.fire("prepare.oom", exc=MemoryError, candidate=cand.key())
    if cand.fmt == "dist":
        return prepare(a, cand, mesh=mesh, axis=axis, prep_cache=prep_cache)
    key = (fp or fingerprint(a), _value_digest(a), cand.key())
    return _PREP_MEMO.get_or_build(key, lambda: prepare(a, cand))


def evict_prepared(fp: str) -> int:
    """Release every memoized prepared dict of one fingerprint; returns
    bytes released.  The fleet's residency manager calls this when it
    evicts a tenant."""
    return _PREP_MEMO.evict_fp(fp)


def prep_memo_stats() -> dict[str, int]:
    """Hit/miss/evict + residency counters of the process-wide prep memo
    (wired into ``FleetStats``)."""
    return _PREP_MEMO.stats()


def solver_step_probe(run, k: int):
    """Wrap a bound runner into the composite a solver step actually runs.

    kind="solver_step" plans are timed on this probe instead of the bare
    kernel: one y = A @ x plus the axpy updates and dot-product reductions
    a CG / power step fuses around it, all in ONE jitted program — the same
    shape of program ``runtime.solver`` lowers its ``lax.while_loop`` body
    to.  The non-SpMV ops are format-independent, but timing them *with*
    the kernel is the point: fusion changes which kernel wins (XLA can
    overlap or fold the vector traffic differently per kernel), and the
    dispatch overhead a standalone SpMV measurement is dominated by at
    small sizes is exactly what the fused solver does not pay.

    The orthogonalization a block step adds (QR at k > 1) is excluded: its
    cost is identical across candidates and would only dilute separation.
    """
    if k == 1:

        @hoisted_jit
        def step(x):
            y = run(x)
            # CG-shaped traffic: two reductions + two axpys over m-vectors.
            hi = jax.lax.Precision.HIGHEST
            curve = jnp.vdot(x, y, precision=hi)
            alpha = jnp.vdot(x, x, precision=hi) / jnp.where(
                curve == 0, 1.0, curve
            )
            r = x - alpha * y
            return r + alpha * x

    else:

        @hoisted_jit
        def step(v):
            w = run(v)
            # Block-power-shaped traffic: per-column Rayleigh quotients
            # (diag(V^T A V)) + the normalized update.
            theta = jnp.sum(v * w, axis=0)
            scale = jnp.linalg.norm(w, axis=0)
            return w / jnp.where(scale == 0, 1.0, scale) + 0.0 * theta

    return step


def runner(
    a: CSRMatrix,
    cand: Candidate,
    prep: dict[str, Any],
    *,
    k: int = 1,
    mesh=None,
    axis: str | None = None,
    donate_rhs: bool = False,
) -> Callable[[jax.Array], jax.Array]:
    """Bind a candidate + prepared arrays into ``fn(x) -> y``.

    k == 1 binds the SpMV path (x is (n,)); k > 1 binds SpMM (x is (n, k)).
    ``fmt="dist"`` candidates dispatch through the mesh's shard_map schedule
    and accept either shape (the engine's k-buckets share one runner);
    ``donate_rhs`` (dist only) donates the RHS buffer to the shard_map
    program — for callers like the serving engine that own their assembled
    batch outright and never reuse it after dispatch.
    """
    from repro.kernels import ops as kops

    m, n = a.shape
    if cand.fmt == "spmspv":
        raise ValueError(
            "spmspv candidates take a sparse operand — bind them through "
            "sparse_rhs_runner(a, cand, prep, x_nnz=...) instead of runner()"
        )
    if cand.fmt == "dist":
        from repro.core.distributed import mesh_spmm_runner

        if mesh is None or axis is None:
            raise ValueError("dist candidates need mesh= and axis=")
        return mesh_spmm_runner(mesh, axis, prep, donate_rhs=donate_rhs)
    method, base = split_reorder(cand)
    if method is not None:
        # y = A x == P^T (PAP^T) (P x): gather x by the permutation, run the
        # base candidate on the reordered matrix, scatter y back (square
        # matrices only — enumeration enforces this).
        inner = runner(prep["matrix"], base, prep["inner"], k=k)
        perm = jnp.asarray(prep["perm"], jnp.int32)

        def fn(x):
            yp = inner(x[perm])
            return jnp.zeros(yp.shape, yp.dtype).at[perm].set(yp)

        return hoisted_jit(fn)
    if cand.fmt == "csr":
        dev = prep["dev"]
        if cand.impl == "scalar":
            if k > 1:
                raise ValueError("csr/scalar has no SpMM tier (k > 1)")
            return lambda x: spmv_csr_scalar(dev, x, n_rows=m)
        # Vector tiers bind the prepared leaves once: x is the only
        # per-call operand, so serving-rate dispatch never re-flattens the
        # 4-leaf dict (see core.spmv.csr_bind).
        return csr_bind(dev, n_rows=m)

    if cand.fmt == "merge":
        from repro.kernels.merge_spmv import merge_spmm, merge_spmv

        if k == 1:
            return lambda x: merge_spmv(prep, x)
        return lambda x: merge_spmm(prep, x)

    if cand.fmt == "sell":
        if cand.impl == "pallas":
            if k > 1:
                raise ValueError("sell/pallas has no SpMM tier (k > 1)")
            return lambda x: kops.sell_spmv(prep, x)
        dev = {key: prep[key] for key in ("cols", "vals", "row_perm")}
        if k > 1:
            return lambda x: spmm_sell(dev, x, n_rows=m)
        return lambda x: spmv_sell(dev, x, n_rows=m)

    if cand.fmt == "sell_lr":
        if k > 1:
            raise ValueError("sell_lr/pallas has no SpMM tier (k > 1)")
        return lambda x: kops.sell_lr_spmv(prep, x)

    if cand.fmt == "sell_blocked":
        if cand.impl == "pallas":
            return lambda x: kops.sell_spmv_blocked_stacked(prep, x)
        slabs = [
            {key: slab[key] for key in ("cols", "vals", "row_perm")}
            for slab in prep["slabs"]
        ]
        bounds = [int(b) for b in prep["bounds"]]

        def fn(x):
            y = jnp.zeros((m,), x.dtype)
            for s, dev in enumerate(slabs):
                y = y + spmv_sell(dev, x[bounds[s] : bounds[s + 1]], n_rows=m)
            return y

        return hoisted_jit(fn)

    if cand.fmt == "bcsr":
        gm, gn = prep["grid_shape"]
        bm, bk = prep["block_shape"]
        if cand.impl == "pallas":
            if k == 1:
                return lambda x: kops.bcsr_spmm(prep, x[:, None])[:, 0]
            return lambda x: kops.bcsr_spmm(prep, x)
        dev = {key: prep[key] for key in ("blocks", "block_cols", "block_rows")}

        def fn(x):
            x2 = x[:, None] if x.ndim == 1 else x
            kk = x2.shape[-1]
            xp = jnp.zeros((gn * bk, kk), x2.dtype).at[:n].set(x2)
            out = spmm_bcsr_dense(dev, xp.reshape(gn, bk, kk), n_block_rows=gm)
            out = out.reshape(gm * bm, kk)[:m]
            return out[:, 0] if x.ndim == 1 else out

        return hoisted_jit(fn)

    raise ValueError(f"unknown candidate format: {cand.fmt}")


def sparse_rhs_runner(
    a: CSRMatrix,
    cand: Candidate,
    prep: dict[str, Any],
    *,
    x_nnz: int,
) -> Callable[[tuple], jax.Array]:
    """Bind ANY candidate into ``fn((xi, xv)) -> y`` over a sparse RHS.

    ``xi``/``xv`` are (x_nnz,) padded coordinate/value arrays (sentinel
    index n, value 0 — see kernels.spmspv.pad_sparse_rhs).  ``fmt="spmspv"``
    candidates dispatch the bucket kernel directly; every dense-RHS tier is
    wrapped in an in-jit densify (``zeros(n).at[xi].add(xv)``, the sentinel
    dropped by OOB-scatter semantics) ahead of its normal k=1 runner.  One
    signature for the whole space is what lets the measured search time
    dense and spmspv candidates on the SAME sparse operand — the crossover
    is a measurement, not an API fork.
    """
    bucket = max(int(x_nnz), 1)
    n = a.shape[1]
    if cand.fmt == "spmspv":
        from repro.kernels.spmspv import spmspv_bind

        return spmspv_bind(prep, bucket, impl=cand.impl, **cand.param_dict)
    base = runner(a, cand, prep, k=1)

    def densify(xi, xv):
        x = jnp.zeros((n,), xv.dtype).at[xi].add(xv, mode="drop")
        return base(x)

    densified = hoisted_jit(densify, name="sparse_rhs_densify")

    def fn(sx):
        xi, xv = sx
        return densified(xi, xv)

    return fn


# ---------------------------------------------------------------------------
# The facade
# ---------------------------------------------------------------------------
def _plan_fits(a: CSRMatrix, plan: Plan, k: int,
               prep_budget: int | None) -> bool:
    """Whether a cached plan's prepared operand fits ``prep_budget``."""
    if prep_budget is None or plan.fmt != "sell_lr":
        return True
    return fits_prep_budget(plan.candidate, extract(a, k=k), prep_budget)


class SparseOperator:
    """An autotuned sparse linear operator: ``y = op @ x``."""

    def __init__(
        self,
        a: CSRMatrix,
        plan: Plan,
        prep: dict[str, Any],
        *,
        from_cache: bool,
        features: MatrixFeatures | None = None,
        measurements: dict[str, float] | None = None,
        mesh=None,
        axis: str | None = None,
    ):
        self.a = a
        self.plan = plan
        self.shape = a.shape
        self.from_cache = from_cache  # True -> the measured search was skipped
        self.features = features
        self.measurements = dict(measurements or {})  # candidate key -> seconds
        self.mesh = mesh
        self.axis = axis
        self._prep = prep
        if plan.kind == "spmspv":
            # plan.k stores the x-nnz bucket; the runner takes (xi, xv).
            self._run = sparse_rhs_runner(a, plan.candidate, prep, x_nnz=plan.k)
        else:
            self._run = runner(
                a, plan.candidate, prep, k=plan.k, mesh=mesh, axis=axis
            )
        self._csr_dev: dict | None = prep.get("dev")  # fallback path, lazy
        self._aot: dict = {}  # donate_rhs -> persistent compiled executable
        # Set by build_predicted: the tune.predict.Prediction that chose
        # this plan (None for measured / cache-loaded operators).
        self.predicted = None

    # -- construction -------------------------------------------------------
    @classmethod
    def build(
        cls,
        a: CSRMatrix,
        *,
        k: int | None = None,
        cache: PlanCache | None = None,
        candidates: Iterable[Candidate] | None = None,
        prune_factor: float = DEFAULT_PRUNE_FACTOR,
        warmup: int = 1,
        timed: int = 3,
        force_search: bool = False,
        include_reorder: bool = False,
        mesh=None,
        axis: str | None = None,
        prep_cache: dict | None = None,
        seed: int = 0,
        race: bool = True,
        solver_step: bool = False,
        x_nnz: int | None = None,
        prep_budget: int | None = None,
    ) -> "SparseOperator":
        """Autotune (or fetch the cached plan for) this matrix.

        k=None tunes SpMV; k=<width> tunes SpMM with a (n, k) operand.

        ``x_nnz=<bucket>`` tunes for a *sparse* RHS instead
        (kind="spmspv"): the space is the dense SpMV tiers (each timed
        through a densify wrapper) plus the spmspv bucket kernels, all
        measured on one random sparse operand with ``x_nnz`` nonzeros —
        ``plan.k`` stores the bucket, so the cache keys sparse plans per
        nnz(x) bucket exactly as it keys SpMM plans per k.  Serve with
        ``op.apply_sparse(indices, values)`` (or ``op @ (indices,
        values)``).  Mutually exclusive with ``k``/``solver_step``; device
        meshes are not supported yet (distributed SpMSpV under the mesh
        schedules is the ROADMAP follow-on).

        ``solver_step=True`` tunes at the *solver-step* level instead
        (kind="solver_step", the fused iterative-solver runtime's plans):
        the same kernel candidates, but estimated with the fused byte model
        (``estimate_cost(fused=True)`` — the dispatch constant amortizes
        over a while_loop's iterations) and *measured on the solver-step
        probe* (:func:`solver_step_probe`: SpMV + axpys + dot reductions in
        one program) rather than the bare kernel.  The best format for one
        standalone y = A @ x is not necessarily best when x is produced and
        consumed on device between iterations; these plans are cached as
        their own kind so neither table shadows the other.
        ``candidates`` overrides enumeration (pruning still applies);
        ``force_search`` ignores a cached plan and re-times;
        ``include_reorder`` adds RCM-permuted variants to the search space
        (paper §4.4).  Cached plans are point measurements: a plan recorded
        on another backend or at another (m, n, nnz) is invalidated and the
        search re-runs.

        ``race`` (default on) enables early-exit candidate racing: survivors
        are timed cheapest-estimate-first, and one whose first steady-state
        rep exceeds ``RACE_FACTOR`` x the current best median — confirmed
        by one more rep, so a lone scheduler blip cannot discard the true
        best — is abandoned without burning its remaining reps (its
        measurement is recorded as ``inf`` and counted in
        ``plan.n_raced``).  Cold-start search latency drops; the winner
        cannot change unless two candidates are within the factor, which
        racing by construction never separates.

        ``mesh=``/``axis=`` switch the search space to the collective
        schedules (allgather vs ring over ``axis``): the plan records the
        mesh topology and is cached per (fingerprint, kind, k, mesh_shape),
        so a topology change re-searches instead of silently reusing a
        schedule tuned for a different shard count.
        """
        kind = "spmv" if k is None else "spmm"
        if solver_step:
            kind = "solver_step"
        kk = 1 if k is None else int(k)
        if x_nnz is not None:
            if k is not None or solver_step:
                raise ValueError(
                    "x_nnz= (sparse RHS) is mutually exclusive with "
                    "k=/solver_step="
                )
            if mesh is not None:
                raise NotImplementedError(
                    "sparse RHS over a device mesh is not implemented yet: "
                    "distributed SpMSpV under the mesh schedules is the "
                    "ROADMAP follow-on of this tier"
                )
            kind = "spmspv"
            kk = max(int(x_nnz), 1)  # plan.k carries the x-nnz bucket
        fp = fingerprint(a)
        backend = jax.default_backend()
        scale = [int(a.shape[0]), int(a.shape[1]), int(a.nnz)]
        if mesh is not None:
            axis = axis or mesh.axis_names[0]
            mesh_shape = [int(s) for s in mesh.devices.shape]
        else:
            mesh_shape = []
        cache = default_cache() if cache is None else cache
        if not force_search:
            plan = cache.get(fp, kind, kk, backend=backend, scale=scale,
                             mesh_shape=mesh_shape or None)
            if plan is not None and _plan_fits(a, plan, kk, prep_budget):
                return cls(
                    a,
                    plan,
                    prepare_cached(a, plan.candidate, fp=fp, mesh=mesh,
                                   axis=axis, prep_cache=prep_cache),
                    from_cache=True,
                    mesh=mesh,
                    axis=axis,
                )

        sparse_kind = kind == "spmspv"
        feats = extract(
            a,
            k=1 if sparse_kind else kk,
            x_nnz=kk if sparse_kind else None,
        )
        if candidates is not None:
            cands = list(candidates)
        elif mesh is not None:
            cands = enumerate_mesh_candidates(feats, mesh.shape[axis])
        else:
            cands = enumerate_candidates(
                feats, kind, k=kk,
                reorders=REORDER_METHODS if include_reorder else (),
                prep_budget=prep_budget,
            )
        costs = {
            c: estimate_cost(
                a, c, feats, k=1 if sparse_kind else kk,
                fused=solver_step, sparse_rhs=sparse_kind,
            )
            for c in cands
        }
        survivors = prune(costs, factor=prune_factor)

        rng = np.random.default_rng(seed)
        if sparse_kind:
            # One random sparse operand probes every survivor — dense tiers
            # time their densify wrapper on it, so the dense-vs-spmspv
            # crossover is decided by measurement on equal terms.
            from repro.kernels.spmspv import pad_sparse_rhs

            n = a.shape[1]
            nx = min(kk, n)
            idx = np.sort(rng.choice(n, size=nx, replace=False)).astype(np.int64)
            val = rng.standard_normal(nx).astype(np.float32)
            # Host tuple: the spmspv runners pick the work bucket on
            # host, so device operands would sync every timed rep.
            x = pad_sparse_rhs(idx, val, kk, n)
        else:
            shape = (a.shape[1],) if kk == 1 else (a.shape[1], kk)
            x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))

        # Cheapest-estimate-first so racing establishes a credible best
        # early: every later candidate's first rep races against it.
        survivors = sorted(survivors, key=costs.get)
        measurements: dict[str, float] = {}
        best: tuple[float, Candidate, dict] | None = None
        n_raced = 0
        # Racing forces a warmup on every candidate whose first rep might
        # abort; the FIRST candidate (no best yet, abort=None) must get the
        # same discipline, or with warmup=0 its lone timed rep would eat
        # the compile and bias the search against the cheapest estimate.
        warmup_eff = max(warmup, 1) if race else warmup
        failed: dict[str, str] = {}
        for c in survivors:
            try:
                prep = prepare_cached(a, c, fp=fp, mesh=mesh, axis=axis,
                                      prep_cache=prep_cache)
                if sparse_kind:
                    fn = sparse_rhs_runner(a, c, prep, x_nnz=kk)
                else:
                    fn = runner(a, c, prep, k=kk, mesh=mesh, axis=axis)
                if solver_step:  # time the fused composite, not the kernel
                    fn = solver_step_probe(fn, kk)
                abort = (RACE_FACTOR * best[0]
                         if (race and best is not None) else None)
                t = time_fn(fn, x, warmup=warmup_eff, timed=timed,
                            abort_above=abort)
            except Exception as exc:
                # One candidate failing to prepare or run (OOM under memory
                # pressure, a compile refusal, a broken kernel path) must not
                # kill the whole search — the others still compete — but it
                # must not vanish either: it is recorded on the plan
                # (``Plan.failed``) and warned about.
                measurements[c.key()] = float("inf")
                failed[c.key()] = f"{type(exc).__name__}: {exc}"[:1000]
                warnings.warn(
                    f"tuner: candidate {c.key()} failed for kind={kind} "
                    f"k={kk}: {failed[c.key()][:300]}",
                    RuntimeWarning,
                    stacklevel=2,
                )
                continue
            measurements[c.key()] = t
            if math.isinf(t):
                n_raced += 1  # abandoned after one rep — pruned by racing
                continue
            if best is None or t < best[0]:
                best = (t, c, prep)
        if best is None:
            last = next(reversed(failed.items()), None)
            raise RuntimeError(
                f"measured search found no usable candidate for kind="
                f"{kind!r} k={kk} ({len(survivors)} survivors, "
                f"{len(failed)} failed"
                + (f"; last error {last[0]}: {last[1]}" if last else "")
                + ")"
            )
        t_best, c_best, prep_best = best
        plan = Plan(
            fingerprint=fp,
            kind=kind,
            fmt=c_best.fmt,
            impl=c_best.impl,
            params={kp: list(v) if isinstance(v, tuple) else v
                    for kp, v in c_best.params},
            est_cost=costs[c_best],
            measured_s=t_best,
            n_candidates=len(cands),
            n_measured=len(survivors),
            k=kk,
            backend=backend,
            scale=scale,
            mesh_shape=mesh_shape,
            n_raced=n_raced,
            # The searched features ride along in the persisted plan: the
            # cache doubles as the transfer-tuning training set
            # (tune.predict nearest-neighbors over them for new
            # fingerprints).
            features=feats.to_dict(),
            failed=failed,
        )
        cache.put(plan)
        return cls(
            a,
            plan,
            prep_best,
            from_cache=False,
            features=feats,
            measurements=measurements,
            mesh=mesh,
            axis=axis,
        )

    # -- transfer tuning ----------------------------------------------------
    @classmethod
    def build_predicted(
        cls,
        a: CSRMatrix,
        *,
        k: int | None = None,
        cache: PlanCache | None = None,
        radius: float | None = None,
        exclude: Iterable[str] = (),
        prep_budget: int | None = None,
    ) -> "SparseOperator":
        """A serve-NOW operator: no measured search, ever.

        Resolution order (single-device only — mesh plans are topology-bound
        point measurements and are not predicted):

        1. exact plan-cache hit for this fingerprint/backend/scale — the
           normal warm path, identical to ``build`` without ``force_search``;
        2. nearest-neighbor transfer (:func:`repro.tune.predict.
           predict_candidate`): the cached plan whose persisted features are
           closest to this matrix's, if within the confidence radius;
        3. byte-model argmin over the enumerated candidate space.

        The returned plan has ``measured_s == 0`` and ``predicted_from``
        set (neighbor fingerprint or ``"byte_model"``) unless it came from
        the cache; predicted plans are NEVER persisted — the fleet's
        background retune runs the real search and its measured plan both
        enters the cache and hot-swaps the serving executables.  ``exclude``
        drops training fingerprints (leave-one-out evaluation);
        ``prep_budget`` is :meth:`build`'s, at each of the three steps.
        """
        from .predict import PREDICT_RADIUS, predict_candidate

        kind = "spmv" if k is None else "spmm"
        kk = 1 if k is None else int(k)
        fp = fingerprint(a)
        backend = jax.default_backend()
        scale = [int(a.shape[0]), int(a.shape[1]), int(a.nnz)]
        cache = default_cache() if cache is None else cache
        plan = cache.get(fp, kind, kk, backend=backend, scale=scale)
        if plan is not None and _plan_fits(a, plan, kk, prep_budget):
            return cls(
                a,
                plan,
                prepare_cached(a, plan.candidate, fp=fp),
                from_cache=True,
            )
        feats = extract(a, k=kk)
        pred = predict_candidate(
            a, kind, kk, cache,
            feats=feats, backend=backend, exclude=set(exclude) | {fp},
            radius=PREDICT_RADIUS if radius is None else radius,
            prep_budget=prep_budget,
        )
        cand = pred.candidate
        plan = Plan(
            fingerprint=fp,
            kind=kind,
            fmt=cand.fmt,
            impl=cand.impl,
            params={kp: list(v) if isinstance(v, tuple) else v
                    for kp, v in cand.params},
            est_cost=estimate_cost(a, cand, feats, k=kk),
            measured_s=0.0,
            n_candidates=pred.n_neighbors,
            n_measured=0,
            k=kk,
            backend=backend,
            scale=scale,
            features=feats.to_dict(),
            predicted_from=pred.source,
        )
        op = cls(
            a, plan, prepare_cached(a, cand, fp=fp),
            from_cache=False, features=feats,
        )
        op.predicted = pred
        return op

    # -- persistent executables ---------------------------------------------
    def aot(self, *, donate_rhs: bool = False):
        """AOT-compile this operator's dispatch into a persistent executable.

        Returns a compiled callable over exactly the plan's operand shape
        ((n,) for a k=1 plan, (n, k) otherwise) with the prepared-dict
        leaves closed over as compile-time constants — per-call cost is one
        executable invocation, no tracing, no pytree flattening of index
        arrays, no shape dispatch.  The serving engine lowers its per-bucket
        executables this way; benchmarks use it to time exactly the
        steady-state hot path.

        ``donate_rhs=True`` donates the operand buffer to the executable —
        the caller hands over ownership per call (a fresh batch each time,
        as the engine's assembled slabs are), letting XLA reuse it for
        scratch/output.  A candidate kernel opts in simply by consuming x
        linearly; nothing format-specific is required.  Do NOT donate when
        the same x is applied repeatedly (e.g. ``time_fn`` loops).

        Mesh-planned operators place and jit internally (the shard_map
        program is already persistent); for those the bound runner is
        returned as-is.
        """
        if self.plan.kind == "spmspv":
            # The sparse-RHS runner is already a persistent per-work-bucket
            # dispatch (kernels.spmspv.spmspv_bind caches its jitted
            # executables); donation does not apply to the coordinate pair.
            return self._run
        if self.mesh is not None:
            if not donate_rhs:
                return self._run  # already a persistent bound runner
            key = ("mesh", True)
            fn = self._aot.get(key)
            if fn is None:
                fn = self._aot[key] = runner(
                    self.a, self.plan.candidate, self._prep, k=self.plan.k,
                    mesh=self.mesh, axis=self.axis, donate_rhs=True,
                )
            return fn
        key = bool(donate_rhs)
        fn = self._aot.get(key)
        if fn is None:
            from repro.runtime.executable import aot_compile

            n = self.shape[1]
            shape = (n,) if self.plan.k == 1 else (n, self.plan.k)
            run = self._run
            fn = self._aot[key] = aot_compile(
                lambda x: run(x),
                jax.ShapeDtypeStruct(shape, jnp.float32),
                donate_argnums=(0,) if donate_rhs else (),
            )
        return fn

    @classmethod
    def from_candidate(
        cls, a: CSRMatrix, cand: Candidate, *, k: int | None = None,
        donate_rhs: bool = False, x_nnz: int | None = None,
    ) -> "SparseOperator":
        """Build with a forced candidate — no search, no cache.

        Benchmarks use this to pin each fixed configuration (e.g. Fig 4's
        scalar tier, Table 2's block shapes) while still going through the
        facade's prepare + dispatch path.  k picks the SpMM path as in
        ``build``.  ``donate_rhs=True`` pre-lowers the pinned candidate into
        a donation-enabled persistent executable (``op.aot`` with the same
        flag) so a pin is serving-ready without a second lowering step.

        ``x_nnz=<bucket>`` pins for a sparse RHS (kind="spmspv", serve via
        ``apply_sparse``); required for ``fmt="spmspv"`` candidates, and a
        dense candidate pinned this way serves through its densify wrapper
        — how fig16 pins the dense baseline on sparse operands.
        """
        if x_nnz is not None and k is not None:
            raise ValueError("x_nnz= is mutually exclusive with k=")
        if cand.fmt == "spmspv" and x_nnz is None:
            raise ValueError(
                "spmspv candidates need x_nnz= (the sparse-RHS nnz bucket)"
            )
        if x_nnz is not None:
            kind = "spmspv"
            kk = max(int(x_nnz), 1)
        else:
            kk = 1 if k is None else int(k)
            kind = "spmv" if kk == 1 else "spmm"
        plan = Plan(
            fingerprint=fingerprint(a),
            kind=kind,
            fmt=cand.fmt,
            impl=cand.impl,
            params={kp: list(v) if isinstance(v, tuple) else v
                    for kp, v in cand.params},
            est_cost=0.0,
            measured_s=0.0,
            n_candidates=1,
            n_measured=0,
            k=kk,
            backend=jax.default_backend(),
            scale=[int(a.shape[0]), int(a.shape[1]), int(a.nnz)],
        )
        op = cls(a, plan, prepare_cached(a, cand), from_cache=False)
        if donate_rhs:
            op.aot(donate_rhs=True)  # pre-lower the donation-enabled exec
        return op

    @classmethod
    def build_multi(
        cls,
        a: CSRMatrix,
        *,
        ks: Iterable[int] = (1, 4, 16, 64),
        cache: PlanCache | None = None,
        **build_kwargs: Any,
    ) -> dict[int, "SparseOperator"]:
        """Tune one plan per k-bucket; returns ``{k: SparseOperator}``.

        The serving engine's plan table: k=1 tunes the SpMV kind, k>1 tunes
        SpMM with a (n, k) operand — so at runtime, batch occupancy decides
        whether the CSR-vector SpMV plan or a wide SpMM plan runs (the
        serving analogue of the paper's Fig 9 crossover).  All buckets share
        one plan cache: each (fingerprint, kind, k) is a separate entry, so
        a restarted engine reloads the whole table without re-searching.
        Mesh builds also share one placed operand per collective schedule
        across the buckets (they differ only in RHS width), instead of
        holding a per-bucket copy of the partitioned matrix on the devices.
        """
        cache = default_cache() if cache is None else cache
        if build_kwargs.get("mesh") is not None:
            build_kwargs.setdefault("prep_cache", {})
        table: dict[int, SparseOperator] = {}
        for k in sorted({int(k) for k in ks}):
            if k < 1:
                raise ValueError(f"k-bucket must be >= 1, got {k}")
            table[k] = cls.build(
                a, k=None if k == 1 else k, cache=cache, **build_kwargs
            )
        return table

    # -- application --------------------------------------------------------
    def apply_sparse(self, indices, values) -> jax.Array:
        """y = A x for a sparse x given as sorted ``(indices, values)``.

        Only spmspv-kind operators (built with ``x_nnz=``) accept sparse
        operands; coordinates are validated loudly (bounds, strictly
        increasing — see kernels.spmspv.validate_sparse_rhs) and padded to
        the plan's nnz bucket.  More nonzeros than the bucket is an error —
        build a wider bucket, or let the engine's ``submit_sparse`` pick it.
        """
        if self.plan.kind != "spmspv":
            raise ValueError(
                "apply_sparse needs an operator built for sparse RHS "
                "(SparseOperator.build(a, x_nnz=...)); this plan is kind="
                f"{self.plan.kind!r}.  For a dense x use op @ x."
            )
        from repro.kernels.spmspv import pad_sparse_rhs, validate_sparse_rhs

        n = self.shape[1]
        idx, val = validate_sparse_rhs(indices, values, n)
        # Host tuple: the spmspv runner reads xi on host for the work
        # bucket; device operands here would sync per call.
        return self._run(pad_sparse_rhs(idx, val, self.plan.k, n))

    def __matmul__(self, x) -> jax.Array:
        if isinstance(x, tuple):  # sparse RHS as (indices, values)
            return self.apply_sparse(*x)
        x = jnp.asarray(x)
        if self.plan.kind == "spmspv":
            # Dense operand on a sparse-RHS plan: plan.k is an nnz bucket,
            # not an SpMM width — serve through the CSR fallback
            # (documented), same as a k-mismatched dense plan.
            fn = spmv_csr if x.ndim == 1 else spmm_csr
            return fn(self._csr_fallback(), x, n_rows=self.shape[0])
        if x.ndim == 1:
            if self.plan.k == 1:
                return self._run(x)
            return spmv_csr(self._csr_fallback(), x, n_rows=self.shape[0])
        if self.plan.k > 1:
            return self._run(x)
        # spmv-tuned operator applied to a matrix: CSR fallback (documented).
        return spmm_csr(self._csr_fallback(), x, n_rows=self.shape[0])

    def matvec(self, x: jax.Array) -> jax.Array:
        return self @ x

    @property
    def x_loads_per_nnz(self) -> float | None:
        """x row loads per nonzero of a SELL plan (stored slots / nnz) or a
        lane-row SELL plan (segment slots / nnz); None for other tiers
        (and for a reordered plan, whose prep nests its tier's)."""
        key = {"sell": "cols", "sell_lr": "seg_row"}.get(self.plan.fmt)
        slots = self._prep.get(key) if isinstance(self._prep, dict) else None
        if slots is None:
            return None
        return slots.size / max(int(self.a.nnz), 1)

    def _csr_fallback(self) -> dict:
        if self._csr_dev is None:
            self._csr_dev = csr_prepare(self.a)
        return self._csr_dev

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        src = "cache" if self.from_cache else "search"
        return (
            f"SparseOperator({self.shape[0]}x{self.shape[1]}, "
            f"nnz={self.a.nnz}, plan={self.plan.candidate.key()}, from {src})"
        )
