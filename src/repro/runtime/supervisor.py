"""Supervision policy for the serving runtime: retry, demote, repair.

The paper's central finding — the best kernel is per-matrix, and the gap
to a safe baseline is performance, not correctness — is exactly what makes
degraded-mode serving possible: when a tuned executable starts failing,
there is always a slower tier that computes the same y = A @ x.  This
module holds the pieces the engine, fleet and solver share:

* :class:`Supervisor` — the retry/backoff policy plus an event log and
  counters.  A failed batch is retried up to ``max_retries`` times with
  capped exponential backoff; persistent failure walks the bucket down the
  **fallback chain**; an exhausted chain fails the batch's futures via
  ``set_exception`` (the no-hung-futures guarantee — a request always
  resolves with a result or an exception, never blocks forever).
* :data:`FALLBACK_TIERS` / :func:`fallback_op` — the degraded-mode chain:
  tuned plan → ``csr/vector`` (the segment-sum XLA path every matrix
  supports at any k) → ``sell/ref`` (an independently written gather-based
  reference tier, so a bug in the CSR path cannot take both tiers down).
  Each tier builds through :meth:`SparseOperator.from_candidate` — the
  same facade the benchmarks pin configurations with — so a fallback is a
  full prepared operator, not a special case.
* :class:`CircuitOpenError` / :class:`NonFiniteOutput` — the exceptions
  the fleet's per-tenant circuit breaker and the opt-in on-device finite
  guard surface.

Re-promotion is the engine's job (``SparseEngine._repair_worker``): a
background thread probes the saved tuned executable and stages it back via
the PR-7 ``hot_swap`` machinery once a probe batch succeeds, so a
transient fault costs degraded throughput, never a permanent downgrade.

The event log is shared infrastructure: besides the fault-path kinds
(``batch_failed``/``demote``/``promote``/``batch_abandoned``/
``quarantine``), the PR-10 overload layer records ``brownout`` (every
HEALTHY/BROWNOUT/SHED transition of a :class:`runtime.overload.
BrownoutController`, with the pressure that caused it) and
``engine_aborted`` (futures failed by ``close(drain=False)``), so one
``events_of`` query reconstructs an incident timeline across fault AND
load protection.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any

from repro.tune import SparseOperator
from repro.tune.candidates import make

__all__ = [
    "Supervisor",
    "SupervisorEvent",
    "CircuitOpenError",
    "NonFiniteOutput",
    "FALLBACK_TIERS",
    "FAULT_EVENTS",
    "fallback_op",
]


class NonFiniteOutput(RuntimeError):
    """A batch produced NaN/Inf outputs (detected by the opt-in on-device
    guard, ``nan_guard=True``); treated exactly like a dispatch fault."""


class CircuitOpenError(RuntimeError):
    """The fleet's per-tenant circuit breaker is open: the tenant's batches
    kept failing, so its requests fail fast instead of stalling the
    cross-tenant scheduler.  Resubmit after the cooldown."""


# The degraded-mode chain, most-capable first.  csr/vector is the XLA
# segment-sum path (works at every k and every structure); sell/ref is a
# second, independently implemented reference tier (padded-slot gathers)
# so the chain never depends on a single kernel family.  sigma=1 disables
# the row-sorting window: a fallback must not pay a reorder.
FALLBACK_TIERS: tuple[tuple[str, Any], ...] = (
    ("csr/vector", make("csr", "vector")),
    ("sell/ref", make("sell", "ref", C=8, sigma=1)),
)


def fallback_op(a, bucket, level: int) -> tuple[str, SparseOperator]:
    """Build tier ``level`` (1-based) of the chain for one bucket.

    ``bucket`` is an engine k-bucket (int), or ``("spmspv", B)`` for the
    sparse-RHS buckets — those build with ``x_nnz=`` so the dense fallback
    serves through its densify wrapper.  Raises ``IndexError`` past the
    end of the chain (the caller's exhausted signal).
    """
    name, cand = FALLBACK_TIERS[level - 1]
    if isinstance(bucket, tuple):
        op = SparseOperator.from_candidate(a, cand, x_nnz=int(bucket[1]))
    else:
        b = int(bucket)
        op = SparseOperator.from_candidate(a, cand, k=None if b == 1 else b)
    return name, op


# Event kinds that mean a batch or a solve did not run on its tuned plan.
FAULT_EVENTS = (
    "batch_failed",
    "batch_abandoned",
    "demote",
    "solver_attempt_failed",
    "solver_failed",
)


@dataclasses.dataclass(frozen=True)
class SupervisorEvent:
    """One supervision decision (failure, retry, demote, promote, ...)."""

    kind: str
    t: float
    info: dict[str, Any]


class Supervisor:
    """Retry/backoff/repair policy plus counters and an event log.

    One instance per engine or solver (the fleet builds one per tenant so
    event attribution stays per-tenant).  ``max_retries`` is the per-tier
    retry budget; backoff is ``base * 2**attempt`` capped at ``cap``;
    ``repair_interval_s`` paces the engine's background probe of a demoted
    bucket's saved tuned executable.
    """

    def __init__(
        self,
        *,
        max_retries: int = 2,
        backoff_base_s: float = 0.005,
        backoff_cap_s: float = 0.25,
        repair_interval_s: float = 0.05,
    ):
        self.max_retries = max(0, int(max_retries))
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.repair_interval_s = float(repair_interval_s)
        self.retries = 0
        self.failures = 0
        self.demotions = 0
        self.promotions = 0
        self.events: list[SupervisorEvent] = []
        self._lock = threading.Lock()

    def backoff(self, attempt: int) -> float:
        """Capped exponential backoff for the attempt-th retry (0-based)."""
        return min(self.backoff_cap_s, self.backoff_base_s * (2.0 ** attempt))

    def sleep(self, seconds: float) -> None:
        if seconds > 0.0:
            time.sleep(seconds)

    def record(self, kind: str, **info: Any) -> None:
        """Append one event (thread-safe: serving, retune and repair
        threads all report here)."""
        with self._lock:
            self.events.append(
                SupervisorEvent(kind=kind, t=time.perf_counter(), info=info)
            )

    def events_of(self, kind: str) -> list[SupervisorEvent]:
        with self._lock:
            return [e for e in self.events if e.kind == kind]

    def faults(self) -> dict[str, int]:
        """Counts of the events that mean a run was degraded — a failed or
        abandoned batch, a demotion, a failed solver attempt — by kind.
        Empty for a clean run; entry points that must not report success on
        a degraded run (``chip_smoke.py``, ``launch.serve``) check it."""
        with self._lock:
            kinds: dict[str, int] = {}
            for e in self.events:
                if e.kind in FAULT_EVENTS:
                    kinds[e.kind] = kinds.get(e.kind, 0) + 1
        return kinds

    def summary(self) -> dict[str, Any]:
        with self._lock:
            kinds: dict[str, int] = {}
            for e in self.events:
                kinds[e.kind] = kinds.get(e.kind, 0) + 1
        return {
            "retries": self.retries,
            "failures": self.failures,
            "demotions": self.demotions,
            "promotions": self.promotions,
            "events": kinds,
        }

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (
            f"Supervisor(max_retries={self.max_retries}, "
            f"retries={self.retries}, failures={self.failures}, "
            f"demotions={self.demotions}, promotions={self.promotions})"
        )
