"""Fused iterative-solver runtime: CG / Lanczos / block power, one launch.

The paper motivates SpMV throughput through linear solvers and eigensolvers
— workloads that run the kernel hundreds of times with the operand produced
and consumed between iterations.  A dispatch-per-iteration loop pays the
full host round-trip PR 5 eliminated for serving (jit-cache lookup, pytree
flatten, a device->host transfer for the convergence check, a mandatory
block) multiplied by the iteration count.  This module removes it the same
way the serving engine did:

* One *solver step* — SpMV/SpMM through the bucket's tuned kernel plus the
  surrounding axpys and dot-product reductions — lowers ONCE per plan into
  a single on-device program (the prepared-dict leaves enter it as
  device-resident arguments through ``runtime.executable.hoisted_jit``,
  exactly like the engine's bucket programs).
* Iterations chain with ``lax.while_loop`` and convergence is checked ON
  DEVICE, so the host sees only the final state: solution, residual norm,
  iteration count, converged flag.  No per-iteration transfer exists to
  serialize the loop.
* Plans are tuned at the *solver-step* level (``kind="solver_step"``): the
  measured search times ``tune.operator.solver_step_probe`` — kernel +
  axpys + dots in one program — under a byte model whose dispatch constant
  amortizes over the loop (``estimate_cost(fused=True)``).  The best format
  for one standalone y = A @ x is not necessarily best inside a fused
  step, and the plan cache keeps the two kinds separate.
* Block solvers (``block_power``) ride the SpMM k-bucket machinery: the
  step's A @ V runs the plan tuned at width k, the Rayleigh quotients
  ``diag(V^T A V)`` reduce all k vectors at once.
* Mesh solves (``mesh=``/``axis=``) reuse the tuned collective schedules:
  A @ x dispatches through the plan's shard_map program
  (``core.distributed.mesh_spmm_runner``) and every reduction lowers to a
  ``lax.psum`` shard_map program on the same axis
  (``core.distributed.psum_dot_runner``), so a sharded solve equals the
  single-device one to float32 tolerance with no host hop per iteration.

``cg_host_loop`` / ``block_power_host_loop`` keep the dispatch-per-
iteration discipline as measured baselines: ``benchmarks/fig17_solver.py``
gates the fused runtime's iterations/second against them, and the
correctness suite checks that iteration counts and convergence flags agree
(both run the same step arithmetic; only the loop's location differs).

    from repro.runtime.solver import SparseSolver
    s = SparseSolver(spd_csr)            # tunes (or cache-loads) solver plans
    res = s.cg(b, tol=1e-5)              # ONE launch; host sees final state
    res.x, res.residual, res.iterations, res.converged

Everything runs in float32 (the repo-wide serving dtype); float64 inputs
are cast on entry.  CG assumes SPD, Lanczos assumes symmetric —
``core.spmv.spd_shift`` / ``symmetrize`` build such operators from any CSR.
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.formats import CSRMatrix
from repro.runtime.executable import hoisted_jit
from repro.runtime.faults import FaultPlan, active_plan
from repro.runtime.supervisor import (
    FALLBACK_TIERS,
    NonFiniteOutput,
    Supervisor,
    fallback_op,
)
from repro.tune import PlanCache, SparseOperator

__all__ = [
    "SolverResult",
    "SparseSolver",
    "cg_host_loop",
    "block_power_host_loop",
    "tridiag_eigvalsh",
]

_TINY = jnp.float32(1e-30)


@dataclasses.dataclass
class SolverResult:
    """Final state of one solve — the only thing the host ever sees.

    ``residual`` is the solver's own stopping quantity: ||b - Ax|| for CG,
    the last off-diagonal beta for Lanczos, the relative Ritz-value change
    for block power.  ``plan`` records which tuned candidate the step ran.
    """

    solver: str
    iterations: int
    residual: float
    converged: bool
    plan: str = ""
    x: jax.Array | None = None  # CG solution
    eigenvalues: np.ndarray | None = None
    eigenvectors: jax.Array | None = None  # block power's final V
    alphas: np.ndarray | None = None  # Lanczos tridiagonal diagonal
    betas: np.ndarray | None = None  # Lanczos off-diagonals (last = residual)


def tridiag_eigvalsh(alphas: np.ndarray, betas: np.ndarray) -> np.ndarray:
    """Eigenvalues of the symmetric tridiagonal (alphas; betas off-diag).

    scipy's specialized solver when available; otherwise the dense
    ``eigvalsh`` of the explicitly-built tridiagonal (the Lanczos step
    counts are small, so O(s^3) on the host is immaterial).
    """
    try:
        from scipy.linalg import eigh_tridiagonal

        return eigh_tridiagonal(alphas, betas, eigvals_only=True)
    except ImportError:  # pragma: no cover - scipy is in the container
        t = np.diag(alphas) + np.diag(betas, 1) + np.diag(betas, -1)
        return np.linalg.eigvalsh(t)


def _plain_dot(u: jax.Array, v: jax.Array) -> jax.Array:
    """(n,) x (n,) -> scalar; (n, k) x (n, k) -> (k,) per-column dots.

    HIGHEST: on TPU a default-precision f32 dot runs reduced-precision
    passes, which CG's residual test at tol 1e-5 cannot afford.
    """
    if u.ndim == 1:
        return jnp.vdot(u, v, precision=jax.lax.Precision.HIGHEST)
    return jnp.sum(u * v, axis=0)


class SparseSolver:
    """Autotuned fused iterative solvers over one sparse operator.

    Holds a lazy table of solver-step plans (one per block width, like the
    engine's k-buckets) and one compiled program per (solver, static
    config).  ``mesh=``/``axis=`` shards A with the tuned collective
    schedule and lowers reductions to ``psum`` programs on the same axis;
    remaining keyword arguments pass through to
    :meth:`SparseOperator.build` (warmup/timed/force_search/...).
    """

    def __init__(
        self,
        a: CSRMatrix,
        *,
        cache: PlanCache | None = None,
        mesh: Any = None,
        axis: str | None = None,
        name: str | None = None,
        supervisor: Supervisor | None = None,
        faults: FaultPlan | None = None,
        nan_guard: bool = False,
        **build_kwargs: Any,
    ):
        m, n = a.shape
        if m != n:
            raise ValueError(f"iterative solvers need a square operator, got {a.shape}")
        self.a = a
        self.shape = a.shape
        self.cache = cache
        self.mesh = mesh
        self.axis = axis if axis is not None else (
            mesh.axis_names[0] if mesh is not None else None
        )
        self.name = name
        self.supervisor = supervisor if supervisor is not None else Supervisor()
        self.faults = faults if faults is not None else active_plan()
        self.nan_guard = bool(nan_guard)
        self._build_kwargs = build_kwargs
        self._ops: dict[int, SparseOperator] = {}
        self._progs: dict[tuple, Callable] = {}
        self._demoted: dict[int, int] = {}  # k -> fallback-chain level
        if mesh is not None:
            from repro.core.distributed import psum_dot_runner

            self._dot = psum_dot_runner(mesh, self.axis, n)
        else:
            self._dot = _plain_dot

    # -- plan table ----------------------------------------------------------
    def op(self, k: int = 1) -> SparseOperator:
        """The solver-step plan at block width k (tuned or cache-loaded)."""
        k = int(k)
        op = self._ops.get(k)
        if op is None:
            op = self._ops[k] = SparseOperator.build(
                self.a,
                k=None if k == 1 else k,
                solver_step=True,
                cache=self.cache,
                mesh=self.mesh,
                axis=self.axis,
                **self._build_kwargs,
            )
        return op

    @property
    def from_cache(self) -> bool:
        """True when every built width's plan came from the cache."""
        return all(op.from_cache for op in self._ops.values())

    # -- supervised dispatch -------------------------------------------------
    def _prog(self, key: tuple, k: int, builder: Callable) -> Callable:
        """The compiled program for (solver, static-config), built lazily
        against the CURRENT plan at width k (so a demotion's ``_progs``
        clear rebinds every program to the fallback operator)."""
        prog = self._progs.get(key)
        if prog is None:
            prog = self._progs[key] = hoisted_jit(
                builder(self.op(k)._run), name=f"solver_{key[0]}"
            )
        return prog

    def _call(self, key: tuple, k: int, builder: Callable, *args):
        """Run one solve under supervision: retry with capped backoff, then
        demote the width's plan down the fallback chain, then re-raise.

        Mirrors the engine's batch policy (see ``SparseEngine._recover``):
        ``max_retries`` attempts per tier, a demotion refills the budget,
        and an exhausted chain propagates the last failure to the caller —
        a solve either returns a finished result or raises, never wedges.
        With ``nan_guard=True`` non-finite floating outputs are treated as
        faults (a converged-looking state full of NaN is worse than an
        exception).
        """
        with TraceAnnotation("solver.call", solver=key[0], k=k):
            sup = self.supervisor
            budget = sup.max_retries
            attempt = 0
            last: BaseException | None = None
            while True:
                try:
                    if self.faults is not None:
                        self.faults.fire(
                            "solver.dispatch", solver=key[0], k=k, name=self.name
                        )
                    with TraceAnnotation("solver.launch"):
                        out = self._prog(key, k, builder)(*args)
                    with TraceAnnotation("solver.device_wait"):
                        jax.block_until_ready(out)
                    if self.nan_guard:
                        for leaf in jax.tree_util.tree_leaves(out):
                            if jnp.issubdtype(
                                leaf.dtype, jnp.floating
                            ) and not bool(jnp.isfinite(leaf).all()):
                                raise NonFiniteOutput(
                                    f"solver {key[0]!r} (k={k}) produced "
                                    "non-finite outputs"
                                )
                    if attempt:
                        sup.record(
                            "solver_recovered", solver=key[0], k=k, attempts=attempt
                        )
                    return out
                except Exception as exc:
                    last = exc
                    sup.record(
                        "solver_attempt_failed",
                        solver=key[0],
                        k=k,
                        error=repr(exc),
                    )
                    if budget > 0:
                        budget -= 1
                        sup.retries += 1
                        sup.sleep(sup.backoff(attempt))
                        attempt += 1
                        continue
                    if self._demote(key[0], k, exc):
                        budget = sup.max_retries
                        attempt += 1
                        continue
                    sup.failures += 1
                    sup.record("solver_failed", solver=key[0], k=k, error=repr(exc))
                    raise last

    def _demote(self, solver: str, k: int, exc: BaseException) -> bool:
        """Walk width k's plan one tier down the fallback chain.

        Mesh solvers never demote: the chain's tiers are single-device
        operators and silently unsharding a solve the caller laid out over
        a mesh would change its memory story — the failure propagates
        instead.  A tier whose own build fails is skipped.  Clearing
        ``_progs`` drops every compiled program (they close over the old
        plan's prepared arrays); untouched widths just recompile.
        """
        if self.mesh is not None:
            return False
        level = self._demoted.get(k, 0) + 1
        while level <= len(FALLBACK_TIERS):
            try:
                tier, op = fallback_op(self.a, int(k), level)
            except Exception:
                level += 1
                continue
            self._ops[k] = op
            self._demoted[k] = level
            self._progs.clear()
            self.supervisor.demotions += 1
            self.supervisor.record(
                "demote",
                solver=solver,
                k=k,
                tier=tier,
                level=level,
                error=repr(exc),
            )
            return True
        return False

    def _x0(self, x0, shape) -> jax.Array:
        if x0 is None:
            return jnp.zeros(shape, jnp.float32)
        x0 = jnp.asarray(x0, jnp.float32)
        if x0.shape != shape:
            raise ValueError(f"expected x0 of shape {shape}, got {x0.shape}")
        return x0

    # -- CG ------------------------------------------------------------------
    def cg(
        self,
        b: jax.Array,
        *,
        x0: jax.Array | None = None,
        tol: float = 1e-5,
        maxiter: int = 500,
    ) -> SolverResult:
        """Solve A x = b (A SPD) by conjugate gradients, fused.

        Stops when ||r|| <= tol * ||b|| or at ``maxiter``.  The whole loop
        is one program: ``maxiter`` is compile-static (programs are cached
        per value), ``tol`` is an operand, convergence is a device-side
        predicate.  The host receives exactly (x, ||r||, iterations,
        converged).  ``tol < 0`` disables the convergence test — exactly
        ``maxiter`` iterations run and ``converged`` reports False
        (fig17's fixed-budget per-iteration-rate mode).
        """
        b = jnp.asarray(b, jnp.float32)
        x, res, it, conv = self._call(
            ("cg", int(maxiter)),
            1,
            lambda run: _make_cg_prog(run, self._dot, int(maxiter)),
            b,
            self._x0(x0, b.shape),
            jnp.float32(tol),
        )
        with TraceAnnotation("solver.fetch"):
            it, res, conv = int(it), float(res), bool(conv)
        return SolverResult(
            solver="cg",
            iterations=it,
            residual=res,
            converged=conv,
            plan=self.op(1).plan.candidate.key(),
            x=x,
        )

    # -- Lanczos -------------------------------------------------------------
    def lanczos(
        self,
        *,
        num_steps: int = 32,
        v0: jax.Array | None = None,
        seed: int = 0,
    ) -> SolverResult:
        """Lanczos tridiagonalization of symmetric A, fused (``lax.scan``).

        Runs exactly ``num_steps`` three-term recurrences in one launch and
        returns the tridiagonal coefficients; ``eigenvalues`` are the Ritz
        values of the resulting tridiagonal (host-side, O(steps) data).
        The final beta is reported as the residual — it bounds how well the
        Krylov space has closed.
        """
        n = self.shape[1]
        if v0 is None:
            rng = np.random.default_rng(seed)
            v0 = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        else:
            v0 = jnp.asarray(v0, jnp.float32)
        out = self._call(
            ("lanczos", int(num_steps)),
            1,
            lambda run: _make_lanczos_prog(run, self._dot, int(num_steps)),
            v0,
        )
        with TraceAnnotation("solver.fetch"):
            alphas, betas = (np.asarray(v) for v in out)
        ritz = tridiag_eigvalsh(alphas, betas[:-1]) if num_steps > 1 else alphas
        return SolverResult(
            solver="lanczos",
            iterations=int(num_steps),
            residual=float(betas[-1]),
            converged=True,
            plan=self.op(1).plan.candidate.key(),
            eigenvalues=ritz,
            alphas=alphas,
            betas=betas,
        )

    # -- block power ---------------------------------------------------------
    def block_power(
        self,
        k: int = 8,
        *,
        tol: float = 1e-4,
        maxiter: int = 200,
        v0: jax.Array | None = None,
        seed: int = 0,
    ) -> SolverResult:
        """Top-k eigenpairs of symmetric A by block power iteration, fused.

        The step is W = A V (the plan tuned at SpMM width k), Rayleigh
        quotients ``diag(V^T A V)`` — the mid-iteration eigenvalue
        estimates; the R diagonal of the QR is sign-indefinite and is NOT
        one — then QR re-orthonormalization.  Converges when the largest
        relative Ritz-value change drops below ``tol``, checked on device;
        ``tol < 0`` runs exactly ``maxiter`` iterations (the change is
        never negative — fig17's fixed-budget mode).
        """
        n = self.shape[1]
        k = int(k)
        if v0 is None:
            rng = np.random.default_rng(seed)
            v0 = jnp.asarray(rng.standard_normal((n, k)).astype(np.float32))
        else:
            v0 = jnp.asarray(v0, jnp.float32)
            if v0.shape != (n, k):
                raise ValueError(f"expected v0 of shape {(n, k)}, got {v0.shape}")
        V, theta, diff, it, conv = self._call(
            ("block_power", k, int(maxiter)),
            k,
            lambda run: _make_block_power_prog(run, self._dot, int(maxiter)),
            v0,
            jnp.float32(tol),
        )
        with TraceAnnotation("solver.fetch"):
            it, diff, conv = int(it), float(diff), bool(conv)
            theta = np.asarray(theta)
        return SolverResult(
            solver="block_power",
            iterations=it,
            residual=diff,
            converged=conv,
            plan=self.op(k).plan.candidate.key(),
            eigenvalues=theta,
            eigenvectors=V,
        )

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        plans = {k: op.plan.candidate.key() for k, op in self._ops.items()}
        return (
            f"SparseSolver({self.shape[0]}x{self.shape[1]}, nnz={self.a.nnz}, "
            f"plans={plans})"
        )


# ---------------------------------------------------------------------------
# Program builders — shared verbatim by the fused runtime and (for the step
# bodies) the host-loop baselines, so "agree with a host-loop baseline" is a
# statement about where the loop runs, not about two implementations.
# ---------------------------------------------------------------------------
def _cg_setup(b, x0, tol, run, dot):
    # tol < 0 is the fixed-budget mode: thresh2 = -inf keeps the loop
    # running for exactly maxiter iterations (rs >= 0 always exceeds it,
    # even when the f32 residual underflows to exact zero) and reports
    # converged=False.  Used by fig17 to measure per-iteration rate.
    thresh2 = jnp.where(
        tol < 0, -jnp.inf, (tol * tol) * jnp.maximum(dot(b, b), _TINY)
    )
    r0 = b - run(x0)
    return thresh2, r0, dot(r0, r0)


def _cg_body(run, dot):
    def body(state):
        x, r, p, rs, it = state
        Ap = run(p)
        pAp = dot(p, Ap)
        alpha = rs / jnp.where(pAp == 0, 1.0, pAp)
        x = x + alpha * p
        r = r - alpha * Ap
        rs_new = dot(r, r)
        beta = rs_new / jnp.where(rs == 0, 1.0, rs)
        return (x, r, r + beta * p, rs_new, it + 1)

    return body


def _make_cg_prog(run, dot, maxiter: int):
    body = _cg_body(run, dot)

    def prog(b, x0, tol):
        thresh2, r0, rs0 = _cg_setup(b, x0, tol, run, dot)

        def cond(state):
            _, _, _, rs, it = state
            return (it < maxiter) & (rs > thresh2)

        x, _, _, rs, it = jax.lax.while_loop(
            cond, body, (x0, r0, r0, rs0, jnp.int32(0))
        )
        return x, jnp.sqrt(rs), it, rs <= thresh2

    return prog


def _make_lanczos_prog(run, dot, num_steps: int):
    def prog(v0):
        v = v0 / jnp.sqrt(jnp.maximum(dot(v0, v0), _TINY))

        def step(carry, _):
            v_prev, v, beta = carry
            w = run(v) - beta * v_prev
            alpha = dot(w, v)
            w = w - alpha * v
            beta_new = jnp.sqrt(jnp.maximum(dot(w, w), 0.0))
            v_next = w / jnp.where(beta_new == 0, 1.0, beta_new)
            return (v, v_next, beta_new), (alpha, beta_new)

        init = (jnp.zeros_like(v), v, jnp.float32(0.0))
        _, (alphas, betas) = jax.lax.scan(step, init, None, length=num_steps)
        return alphas, betas

    return prog


def _block_power_body(run, dot):
    def body(state):
        V, theta, _, it = state
        W = run(V)
        # Rayleigh quotients diag(V^T A V): V's columns are orthonormal, so
        # these ARE the mid-iteration eigenvalue estimates.
        theta_new = dot(V, W)
        V_new, _ = jnp.linalg.qr(W)
        denom = jnp.maximum(jnp.max(jnp.abs(theta_new)), _TINY)
        diff = jnp.max(jnp.abs(theta_new - theta)) / denom
        return (V_new, theta_new, diff, it + 1)

    return body


def _make_block_power_prog(run, dot, maxiter: int):
    body = _block_power_body(run, dot)

    def prog(v0, tol):
        V, _ = jnp.linalg.qr(v0)
        k = v0.shape[1]

        def cond(state):
            _, _, diff, it = state
            return (it < maxiter) & (diff > tol)

        init = (V, jnp.zeros(k, jnp.float32), jnp.float32(np.inf), jnp.int32(0))
        V, theta, diff, it = jax.lax.while_loop(cond, body, init)
        return V, theta, diff, it, diff <= tol

    return prog


# ---------------------------------------------------------------------------
# Dispatch-per-iteration baselines (fig17's measured counterpart; also the
# reference the correctness suite checks iteration counts against).
# ---------------------------------------------------------------------------
# One jitted program set per matvec: without this, every *_host_loop call
# would wrap a fresh closure in jax.jit and re-trace per solve — the
# baseline would then measure compilation, not the per-iteration dispatch
# + transfer cost it exists to measure.  Keyed weakly so dropping the
# operator drops its programs.
_HOST_PROGS: "weakref.WeakKeyDictionary" = None  # initialized below


def _host_progs(matvec) -> dict[str, Callable]:
    global _HOST_PROGS
    if _HOST_PROGS is None:
        _HOST_PROGS = weakref.WeakKeyDictionary()
    try:
        progs = _HOST_PROGS.get(matvec)
    except TypeError:  # non-weakrefable callable: build unmemoized
        progs = None
    if progs is None:
        progs = {
            "cg_setup": hoisted_jit(
                lambda b, x, t: _cg_setup(b, x, t, matvec, _plain_dot)
            ),
            "cg_step": hoisted_jit(_cg_body(matvec, _plain_dot)),
            "power_step": hoisted_jit(_block_power_body(matvec, _plain_dot)),
        }
        try:
            _HOST_PROGS[matvec] = progs
        except TypeError:
            pass
    return progs


def cg_host_loop(
    matvec: Callable[[jax.Array], jax.Array],
    b: jax.Array,
    *,
    x0: jax.Array | None = None,
    tol: float = 1e-5,
    maxiter: int = 500,
) -> SolverResult:
    """CG with the loop on the HOST: one dispatch + one device->host
    convergence transfer per iteration (the ``float(rs)`` below blocks).

    Runs the same step arithmetic as the fused program — the body is one
    jitted call of the identical closure — so counts and flags agree with
    :meth:`SparseSolver.cg`; only the per-iteration host round-trip
    differs, which is exactly what fig17 measures.
    """
    b = jnp.asarray(b, jnp.float32)
    x = jnp.zeros_like(b) if x0 is None else jnp.asarray(x0, jnp.float32)
    progs = _host_progs(matvec)
    setup, step = progs["cg_setup"], progs["cg_step"]
    thresh2, r, rs = setup(b, x, jnp.float32(tol))
    thresh2 = float(thresh2)
    state = (x, r, r, rs, jnp.int32(0))
    it = 0
    rs_h = float(rs)  # per-iteration device->host transfer: the baseline's tax
    while it < maxiter and rs_h > thresh2:
        state = step(state)
        rs_h = float(state[3])
        it += 1
    x, _, _, rs, _ = state
    return SolverResult(
        solver="cg",
        iterations=it,
        residual=float(jnp.sqrt(rs)),
        converged=rs_h <= thresh2,
        x=x,
    )


def block_power_host_loop(
    matvec: Callable[[jax.Array], jax.Array],
    v0: jax.Array,
    *,
    tol: float = 1e-4,
    maxiter: int = 200,
) -> SolverResult:
    """Block power iteration with the loop on the host (see cg_host_loop)."""
    v0 = jnp.asarray(v0, jnp.float32)
    V, _ = jnp.linalg.qr(v0)
    k = v0.shape[1]
    step = _host_progs(matvec)["power_step"]
    state = (V, jnp.zeros(k, jnp.float32), jnp.float32(np.inf), jnp.int32(0))
    it = 0
    diff_h = float("inf")
    while it < maxiter and diff_h > tol:
        state = step(state)
        diff_h = float(state[2])  # per-iteration transfer, as above
        it += 1
    V, theta, diff, _ = state
    return SolverResult(
        solver="block_power",
        iterations=it,
        residual=float(diff),
        converged=diff_h <= tol,
        eigenvalues=np.asarray(theta),
        eigenvectors=V,
    )
