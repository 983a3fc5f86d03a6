"""SparseFleet: multi-tenant sparse serving with ~zero cold start.

One process, one accelerator, MANY matrices.  ``SparseEngine`` (PR 5) made
steady-state serving of a single fingerprint zero-overhead; the remaining
cost was everything *around* it: a new tenant pays the measured search
before its first result, every tenant's prepared dicts live forever, and
nothing arbitrates device time between tenants.  ``SparseFleet`` closes
those three gaps, and they are one mechanism, not three:

**Transfer-tuned admission (~zero cold start).**  ``add_tenant`` builds the
per-bucket plan table with :meth:`repro.tune.SparseOperator.
build_predicted` — exact plan-cache hit, else nearest-neighbor transfer
over the cache's persisted features, else the byte-model argmin — so the
first request is served after format preparation only, never after a
measured search.  Every bucket that was *predicted* (not cache-exact) is
queued for **background retune**: a worker thread runs the real measured
search off the hot path, persists the winning plans (they enter the shared
cache — the training set grows), prewarms the new per-bucket executables
with :meth:`SparseEngine._make_exec`, and stages them with
:meth:`SparseEngine.hot_swap`.  The serving thread adopts the table at its
next dispatch boundary; in-flight batches retire on their old-plan results
bitwise-unchanged.

**Residency management.**  Prepared dicts are the fleet's device-memory
spend; tenants come and go.  The fleet holds a byte budget
(``budget_bytes``, default ``$REPRO_FLEET_BUDGET_BYTES`` or 512 MiB): when
admitting a tenant would exceed it, idle tenants are evicted
lowest-traffic-weight first (an exponentially decayed request counter —
LRU weighted by how much the tenant actually serves), their engines
dropped and their fingerprints purged from the global prepared-dict memo
(:func:`repro.tune.evict_prepared`).  An evicted tenant is re-admitted on
its next ``submit`` — by then retune has usually persisted its measured
plans, so reactivation is an exact cache hit: eviction costs re-prepare,
never re-search.  Admission and retune plan each tenant within
``prep_budget``, a quarter of the byte budget: a tier whose prepared
operand outgrows the matrix (lane-row SELL, 512 bytes a segment slot) is
chosen only where it leaves room for other tenants.

**Cross-tenant scheduling.**  ``step()`` serves every tenant with work,
deadline-first: tenants are ordered by their oldest pending request's SLO
deadline (``t_submit + max_wait_s``), with a rotating round-robin start so
equal-deadline tenants share the device fairly.  Each tenant's engine
keeps its own ``max_wait_s`` admission gate, so a burst tenant fills wide
buckets while a latency-sensitive one still dispatches partial buckets on
time.

**Overload protection** (PR 10, see ``runtime.overload``).  Per-tenant
queue caps (``max_queue`` + ``overload_policy`` forwarded to every tenant
engine), per-tenant token-bucket rate limits (``tenant_rate``/
``tenant_burst``) so one tenant's burst fails fast with
:class:`OverloadError` instead of consuming the shared queue budget, a
*bounded* retune queue that coalesces duplicate requests per tenant, and
an optional fleet-owned :class:`BrownoutController`: the fleet drives it
from fleet-wide pressure in ``step()``, every tenant engine consults it
(widest-bucket dispatch, SHED refusals) without updating it, the retune
worker defers measured searches while browned out (re-queued on
recovery), and residency eviction tightens to ``brownout_budget_frac`` of
the byte budget.

    fleet = SparseFleet(budget_bytes=1 << 29)
    fleet.add_tenant("fem", a_fem, max_wait_s=5e-3)
    req = fleet.submit("fem", x)         # served on the predicted plan
    fleet.step(); req.result()
    fleet.wait_retunes()                 # measured plans land + hot-swap
    fleet.stats().summary()              # per-tenant + fleet-wide counters
"""
from __future__ import annotations

import dataclasses
import os
import queue
import threading
import time
from typing import Any, Iterable, Sequence

import jax

from repro.core.formats import CSRMatrix
from repro.runtime.engine import K_BUCKETS, EngineRequest, SparseEngine
from repro.runtime.faults import FaultPlan, active_plan
from repro.runtime.overload import (
    HEALTHY,
    BrownoutController,
    BrownoutTransition,
    OverloadError,
    TokenBucket,
)
from repro.runtime.supervisor import CircuitOpenError, Supervisor
from repro.tune import (
    PlanCache,
    SparseOperator,
    default_cache,
    evict_prepared,
    fingerprint,
    prep_memo_stats,
    prep_nbytes,
)

__all__ = [
    "SparseFleet",
    "FleetStats",
    "Tenant",
    "TRAFFIC_HALFLIFE_S",
    "CircuitOpenError",
    "OverloadError",
    "TokenBucket",
    "BrownoutController",
]

_ENV_BUDGET = "REPRO_FLEET_BUDGET_BYTES"
_DEFAULT_BUDGET = 512 * 1024 * 1024

# Traffic-weight half-life: a tenant's eviction weight is a request counter
# decayed by 2^(-dt / half_life), so "recent traffic" dominates and a
# tenant idle for a few half-lives decays toward zero — zero-traffic
# tenants are always the first evicted.
TRAFFIC_HALFLIFE_S = 30.0


def _table_bytes(ops: dict[int, SparseOperator]) -> int:
    """Prepared-dict bytes of a plan table, deduplicating shared preps
    (buckets whose plans picked the same candidate share one prepared dict
    through the global memo)."""
    seen: set[int] = set()
    total = 0
    for op in ops.values():
        if id(op._prep) not in seen:
            seen.add(id(op._prep))
            total += prep_nbytes(op._prep)
    return total


@dataclasses.dataclass
class Tenant:
    """One fingerprint's residency record inside the fleet.

    ``engine is None`` means evicted: the host CSR and the plan-cache
    entries survive, the prepared dicts and executables do not.  ``weight``
    is the decayed traffic counter (see ``TRAFFIC_HALFLIFE_S``); ``nbytes``
    the prepared-dict bytes the tenant holds while resident.
    """

    name: str
    a: CSRMatrix
    fp: str
    max_wait_s: float | None = None
    engine: SparseEngine | None = None
    nbytes: int = 0
    weight: float = 0.0
    t_weight: float = 0.0  # perf_counter of the last decay
    admitted_from: dict[int, str] = dataclasses.field(default_factory=dict)
    n_admissions: int = 0
    n_evictions: int = 0
    retuned: bool = False
    # Circuit breaker: perf_counter time the quarantine lifts (0 = closed).
    # A quarantined tenant's submits fail fast with CircuitOpenError and
    # step() skips it, so a poisoning tenant never stalls the scheduler.
    quarantined_until: float = 0.0
    n_quarantines: int = 0
    # Fair-share admission: a token bucket (None = unlimited) consulted at
    # submit — a greedy burst drains its OWN bucket and fails fast with
    # OverloadError, never the shared queue budget.  The bucket survives
    # eviction: rate limits are a tenant property, not a residency one.
    bucket: TokenBucket | None = None

    @property
    def quarantined(self) -> bool:
        return time.perf_counter() < self.quarantined_until

    def touch(self, now: float, add: float = 1.0) -> None:
        self.decay(now)
        self.weight += add

    def decay(self, now: float) -> float:
        dt = max(0.0, now - self.t_weight)
        if dt > 0.0 and self.weight > 0.0:
            self.weight *= 2.0 ** (-dt / TRAFFIC_HALFLIFE_S)
        self.t_weight = now
        return self.weight

    @property
    def resident(self) -> bool:
        return self.engine is not None

    @property
    def busy(self) -> bool:
        """Work the fleet must not discard: queued or in-flight requests."""
        return self.engine is not None and (
            self.engine.pending > 0 or self.engine.in_flight > 0
        )


@dataclasses.dataclass
class FleetStats:
    """Fleet-wide counters; per-tenant engine stats join in ``summary``."""

    admissions: int = 0
    cache_admissions: int = 0  # every bucket an exact plan-cache hit
    predicted_admissions: int = 0  # >=1 bucket transferred or byte-model
    transferred_buckets: int = 0  # confident nearest-neighbor buckets
    byte_model_buckets: int = 0  # fallback-prior buckets
    evictions: int = 0
    bytes_evicted: int = 0
    reactivations: int = 0
    over_budget_admissions: int = 0  # admitted with nothing left to evict
    retunes_queued: int = 0
    retunes_done: int = 0
    retunes_failed: int = 0  # exhausted every retry; predicted plan serves on
    retune_errors: int = 0  # every retune attempt that raised (incl. retried)
    last_retune_error: str | None = None
    quarantines: int = 0  # circuit-breaker openings across all tenants
    # Overload counters (runtime.overload):
    rate_limited: int = 0  # token-bucket refusals at submit (fair share)
    retunes_coalesced: int = 0  # duplicate requests folded into one queued
    retunes_dropped: int = 0  # bounded retune queue was full; request lost
    retunes_deferred: int = 0  # browned out: parked, re-queued on recovery
    _fleet: Any = dataclasses.field(default=None, repr=False, compare=False)

    def summary(self) -> dict[str, Any]:
        out = {
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(self)
            if not f.name.startswith("_")
        }
        fleet = self._fleet
        if fleet is not None:
            out["resident_bytes"] = fleet.resident_bytes
            out["budget_bytes"] = fleet.budget_bytes
            engines = [
                t.engine
                for t in fleet._tenants.values()
                if t.engine is not None
            ]
            out["rejected"] = sum(e.stats.rejected for e in engines)
            out["shed_oldest"] = sum(e.stats.shed_oldest for e in engines)
            out["shed_deadline"] = sum(
                e.stats.shed_deadline for e in engines
            )
            if fleet._brownout is not None:
                out["brownout"] = fleet._brownout.summary()
            out["swaps_applied"] = sum(
                t.engine.swaps_applied
                for t in fleet._tenants.values()
                if t.engine is not None
            )
            out["tenants"] = {
                t.name: {
                    "resident": t.resident,
                    "weight": round(t.decay(time.perf_counter()), 4),
                    "nbytes": t.nbytes if t.resident else 0,
                    "quarantined": t.quarantined,
                    "quarantines": t.n_quarantines,
                    "admitted_from": {
                        k: v for k, v in sorted(t.admitted_from.items())
                    },
                    "retuned": t.retuned,
                    "evictions": t.n_evictions,
                    **(
                        {"engine": t.engine.stats.summary()}
                        if t.engine is not None
                        else {}
                    ),
                }
                for t in fleet._tenants.values()
            }
        out["prep_memo"] = prep_memo_stats()
        return out


class SparseFleet:
    """Multi-tenant serving: many fingerprints over one shared device.

    ``ks`` is the shared k-bucket ladder (every tenant's engine uses it, so
    plan-cache entries and prepared dicts transfer across tenants of the
    same structure).  ``cache`` is the shared plan cache — the transfer
    predictor's training set as well as the warm-restart store.
    ``budget_bytes`` bounds resident prepared-dict bytes across tenants;
    ``retune=False`` disables the background measured search (predicted
    plans then serve indefinitely — useful for tests and benchmarks that
    need the predicted table pinned).  ``max_wait_s`` is the default
    per-tenant SLO; ``add_tenant`` can override it per tenant.
    """

    def __init__(
        self,
        *,
        ks: Sequence[int] = K_BUCKETS,
        cache: PlanCache | None = None,
        budget_bytes: int | None = None,
        max_wait_s: float | None = None,
        async_depth: int = 2,
        retune: bool = True,
        retune_kwargs: dict[str, Any] | None = None,
        retune_max_retries: int = 2,
        retune_backoff_s: float = 0.05,
        breaker_threshold: int = 3,
        breaker_reset_s: float = 5.0,
        supervisor_kwargs: dict[str, Any] | None = None,
        nan_guard: bool = False,
        faults: FaultPlan | None = None,
        max_queue: int | None = None,
        overload_policy: str = "reject",
        block_timeout_s: float = 1.0,
        shed_after_s: float | None = None,
        tenant_rate: float | None = None,
        tenant_burst: float | None = None,
        brownout: BrownoutController | None = None,
        brownout_budget_frac: float = 0.5,
        retune_queue_max: int = 32,
    ):
        self.ks = tuple(sorted({int(k) for k in ks}))
        self.cache = default_cache() if cache is None else cache
        if budget_bytes is None:
            budget_bytes = int(os.environ.get(_ENV_BUDGET, _DEFAULT_BUDGET))
        self.budget_bytes = int(budget_bytes)
        self.default_max_wait_s = max_wait_s
        self.async_depth = int(async_depth)
        self.retune_default = bool(retune)
        self.retune_kwargs = dict(retune_kwargs or {})
        self.retune_max_retries = max(0, int(retune_max_retries))
        self.retune_backoff_s = float(retune_backoff_s)
        # Per-tenant circuit breaker: after `breaker_threshold` consecutive
        # fully-failed batches the tenant is quarantined for
        # `breaker_reset_s` (queued requests fail fast with
        # CircuitOpenError) instead of stalling cross-tenant scheduling.
        self.breaker_threshold = max(1, int(breaker_threshold))
        self.breaker_reset_s = float(breaker_reset_s)
        self.supervisor_kwargs = dict(supervisor_kwargs or {})
        self.nan_guard = bool(nan_guard)
        self.faults = faults if faults is not None else active_plan()
        # Overload protection (runtime.overload): per-tenant queue caps,
        # token-bucket fair share, and the fleet-owned brownout controller
        # every tenant engine consults (but only the fleet updates — an
        # idle tenant's empty queue must not vote the fleet healthy).
        self.max_queue = max_queue
        self.overload_policy = overload_policy
        self.block_timeout_s = float(block_timeout_s)
        self.shed_after_s = shed_after_s
        self.tenant_rate = tenant_rate
        self.tenant_burst = tenant_burst
        self._brownout = brownout
        self.brownout_budget_frac = float(brownout_budget_frac)
        self.supervisor = Supervisor(**self.supervisor_kwargs)
        if self._brownout is not None:
            self._brownout.add_listener(self._on_brownout)
        self._tenants: dict[str, Tenant] = {}
        self._rr = 0  # rotating round-robin start for equal-deadline ties
        self.stats_fleet = FleetStats(_fleet=self)
        # Bounded retune queue: a flapping tenant coalesces into ONE queued
        # request (the pending set); overflow drops the request (counted) —
        # a lost retune only pins the predicted plan, never correctness.
        self._retune_q: queue.Queue = queue.Queue(
            maxsize=max(1, int(retune_queue_max))
        )
        self._retune_pending: set[str] = set()
        self._deferred_retunes: list[str] = []
        self._retune_thread: threading.Thread | None = None
        self._retune_lock = threading.Lock()  # guards thread start + counters
        self._closed = False

    # -- residency ----------------------------------------------------------
    @property
    def resident_bytes(self) -> int:
        return sum(t.nbytes for t in self._tenants.values() if t.resident)

    @property
    def prep_budget(self) -> int:
        """The most bytes one tenant's prepared operand may take, a quarter
        of the budget (``SparseOperator.build(prep_budget=)``)."""
        return self.budget_bytes // 4

    @property
    def tenants(self) -> dict[str, Tenant]:
        return dict(self._tenants)

    def _make_room(self, incoming: int) -> None:
        """Evict idle tenants (lowest decayed traffic first) until
        ``incoming`` bytes fit the budget.  Tenants with queued or in-flight
        work are never evicted; if nothing evictable remains the admission
        proceeds over budget (and is counted) — serving beats refusing.
        """
        now = time.perf_counter()
        budget = self.budget_bytes
        if self._brownout is not None and self._brownout.state != HEALTHY:
            # Browned out: tighten residency — prepared-dict bytes are the
            # pressure we can actually shed without failing requests.
            budget = int(budget * self.brownout_budget_frac)
        while self.resident_bytes + incoming > budget:
            victims = [
                t for t in self._tenants.values() if t.resident and not t.busy
            ]
            if not victims:
                self.stats_fleet.over_budget_admissions += 1
                return
            victim = min(victims, key=lambda t: t.decay(now))
            self._evict(victim)

    def _evict(self, tenant: Tenant) -> int:
        """Drop a tenant's engine, executables and prepared dicts.

        The host CSR and the plan cache survive — so does any measured plan
        the background retune persisted — which is why reactivation costs a
        re-prepare, never a re-search.
        """
        assert tenant.engine is not None and not tenant.busy
        freed = tenant.nbytes
        tenant.engine = None
        tenant.n_evictions += 1
        evict_prepared(tenant.fp)  # release the global memo's share
        self.stats_fleet.evictions += 1
        self.stats_fleet.bytes_evicted += freed
        return freed

    # -- admission ----------------------------------------------------------
    def add_tenant(
        self,
        name: str,
        a: CSRMatrix,
        *,
        max_wait_s: float | None = None,
        retune: bool | None = None,
        rate: float | None = None,
        burst: float | None = None,
    ) -> Tenant:
        """Admit a matrix under ``name``; serving-ready on return.

        The plan table comes from ``build_predicted`` (cache hit ->
        transfer -> byte model), so no measured search runs on this path;
        predicted buckets are queued for the background retune (unless
        ``retune=False`` here or fleet-wide).

        ``rate``/``burst`` (requests/s, token cap; default the fleet's
        ``tenant_rate``/``tenant_burst``) arm this tenant's fair-share
        token bucket — its submits fail fast with :class:`OverloadError`
        once the bucket runs dry.
        """
        if name in self._tenants:
            raise ValueError(f"tenant {name!r} already exists")
        rate = self.tenant_rate if rate is None else rate
        bucket = None
        if rate is not None:
            if burst is None:
                burst = (
                    self.tenant_burst
                    if self.tenant_burst is not None
                    else 2.0 * rate
                )
            bucket = TokenBucket(rate, burst)
        tenant = Tenant(
            name=name,
            a=a,
            fp=fingerprint(a),
            max_wait_s=(
                self.default_max_wait_s if max_wait_s is None else max_wait_s
            ),
            bucket=bucket,
        )
        self._tenants[name] = tenant
        self._admit(tenant, retune=retune)
        return tenant

    def _admit(self, tenant: Tenant, *, retune: bool | None = None) -> None:
        ops: dict[int, SparseOperator] = {}
        for k in self.ks:
            op = SparseOperator.build_predicted(
                tenant.a, k=None if k == 1 else k, cache=self.cache,
                prep_budget=self.prep_budget,
            )
            ops[k] = op
            if op.from_cache:
                tenant.admitted_from[k] = "cache"
            else:
                pred = op.predicted
                tenant.admitted_from[k] = (
                    pred.source if pred is not None else "byte_model"
                )
                if pred is not None and pred.confident:
                    self.stats_fleet.transferred_buckets += 1
                else:
                    self.stats_fleet.byte_model_buckets += 1
        nbytes = _table_bytes(ops)
        self._make_room(nbytes)
        tenant.engine = SparseEngine(
            tenant.a,
            ks=self.ks,
            ops=ops,
            max_wait_s=tenant.max_wait_s,
            async_depth=self.async_depth,
            name=tenant.name,
            # One supervisor per tenant so failure/demotion attribution and
            # event logs stay per-tenant.
            supervisor=Supervisor(**self.supervisor_kwargs),
            faults=self.faults,
            nan_guard=self.nan_guard,
            max_queue=self.max_queue,
            overload_policy=self.overload_policy,
            block_timeout_s=self.block_timeout_s,
            shed_after_s=self.shed_after_s,
            # The engine CONSULTS the fleet controller (SHED refusals,
            # widest-bucket dispatch, paused repair) but never updates it:
            # only fleet-wide pressure — computed in fleet.step() — may
            # move the state, or one idle tenant would vote for recovery.
            brownout=self._brownout,
            brownout_update=False,
        )
        tenant.nbytes = nbytes
        tenant.n_admissions += 1
        self.stats_fleet.admissions += 1
        if all(op.from_cache for op in ops.values()):
            self.stats_fleet.cache_admissions += 1
        else:
            self.stats_fleet.predicted_admissions += 1
            if self.retune_default if retune is None else retune:
                self._queue_retune(tenant.name)

    # -- background retune --------------------------------------------------
    def _queue_retune(self, name: str) -> None:
        """Enqueue a measured search for ``name`` — bounded and coalesced.

        A tenant already queued coalesces (a flapping tenant enqueues ONE
        search, not an unbounded backlog of redundant ones); a full queue
        drops the request (counted — a lost retune pins the predicted
        plan, never correctness).  While browned out the request is parked
        in ``_deferred_retunes`` instead: the measured search is device
        time the brownout exists to protect, and recovery re-queues it.
        """
        if self._brownout is not None and self._brownout.state != HEALTHY:
            with self._retune_lock:
                if (
                    name not in self._deferred_retunes
                    and name not in self._retune_pending
                ):
                    self._deferred_retunes.append(name)
                    self.stats_fleet.retunes_deferred += 1
            return
        with self._retune_lock:
            if name in self._retune_pending:
                self.stats_fleet.retunes_coalesced += 1
                return
            try:
                self._retune_q.put_nowait(name)
            except queue.Full:
                self.stats_fleet.retunes_dropped += 1
                return
            self._retune_pending.add(name)
            self.stats_fleet.retunes_queued += 1
            if self._retune_thread is None:
                self._retune_thread = threading.Thread(
                    target=self._retune_worker,
                    name="fleet-retune",
                    daemon=True,
                )
                self._retune_thread.start()

    def _on_brownout(self, tr: BrownoutTransition) -> None:
        """Fleet-level brownout bookkeeping: publish the transition as a
        supervisor event and, on recovery to HEALTHY, re-queue every
        retune the brownout deferred."""
        self.supervisor.record(
            "brownout", frm=tr.frm, to=tr.to,
            pressure=round(tr.pressure, 4),
        )
        if tr.to == HEALTHY:
            with self._retune_lock:
                deferred = self._deferred_retunes
                self._deferred_retunes = []
            for name in deferred:
                self._queue_retune(name)

    def _retune_worker(self) -> None:
        while True:
            name = self._retune_q.get()
            if name is None:  # close() sentinel
                self._retune_q.task_done()
                return
            with self._retune_lock:
                # Unpend BEFORE running: a retune requested mid-search is
                # new information (the cache just grew) and re-queues.
                self._retune_pending.discard(name)
            if (
                self._brownout is not None
                and self._brownout.state != HEALTHY
            ):
                # Browned out after queueing: park it; _on_brownout
                # re-queues on recovery.
                with self._retune_lock:
                    if name not in self._deferred_retunes:
                        self._deferred_retunes.append(name)
                        self.stats_fleet.retunes_deferred += 1
                self._retune_q.task_done()
                continue
            try:
                # Capped-backoff retry: a transient failure (device hiccup,
                # injected fault) must not silently pin the predicted plan
                # forever.  Every raising attempt is counted and surfaced in
                # FleetStats; only exhaustion marks the retune failed (the
                # predicted plan keeps serving either way).
                for attempt in range(self.retune_max_retries + 1):
                    try:
                        self._retune_one(name)
                        self.stats_fleet.retunes_done += 1
                        break
                    except Exception as exc:
                        self.stats_fleet.retune_errors += 1
                        self.stats_fleet.last_retune_error = f"{name}: {exc!r}"
                        if attempt >= self.retune_max_retries:
                            self.stats_fleet.retunes_failed += 1
                        else:
                            time.sleep(
                                min(1.0, self.retune_backoff_s * 2.0 ** attempt)
                            )
            finally:
                self._retune_q.task_done()

    def _retune_one(self, name: str) -> None:
        """The measured search for one tenant, entirely off the hot path.

        Runs ``SparseOperator.build`` per bucket (persisting each winning
        plan into the shared cache — the predictor's training set grows
        with every retune), prewarms the new executables by invoking them
        once with zero columns, then stages the table with ``hot_swap``.
        The serving thread adopts it at its next dispatch boundary; if the
        tenant was evicted meanwhile, the cache entries still make its
        reactivation an exact hit.
        """
        tenant = self._tenants.get(name)
        if tenant is None:
            return
        if self.faults is not None:
            self.faults.fire("fleet.retune", tenant=name)
        ops = SparseOperator.build_multi(
            tenant.a, ks=self.ks, cache=self.cache,
            **{"prep_budget": self.prep_budget, **self.retune_kwargs},
        )
        eng = tenant.engine
        if eng is None:
            return  # evicted mid-retune: plans are cached, nothing to swap
        execs: dict[int, Any] = {}
        zero = jax.numpy.zeros((tenant.a.shape[1],), jax.numpy.float32)
        for k in self.ks:
            fn = eng._make_exec(k, ops[k])
            # compile + warm here (guarded executables return a tuple)
            jax.block_until_ready(fn(*([zero] * k)))
            execs[k] = fn
        eng.hot_swap(ops, execs=execs)
        tenant.nbytes = _table_bytes(ops)
        tenant.retuned = True

    def retune(self, name: str) -> None:
        """Queue a background measured search + hot swap for ``name``.

        Admission queues this automatically for predicted tenants; calling
        it again re-searches (useful after the cache gained better training
        data, or to force a measured table for benchmarks).
        """
        if name not in self._tenants:
            raise KeyError(name)
        self._queue_retune(name)

    def wait_retunes(self, timeout: float | None = None) -> bool:
        """Block until every queued retune finished; False on timeout."""
        deadline = (
            None if timeout is None else time.perf_counter() + float(timeout)
        )
        while self._retune_q.unfinished_tasks:
            if deadline is not None and time.perf_counter() >= deadline:
                return False
            time.sleep(0.002)
        return True

    def close(self) -> None:
        """Stop the retune worker (after finishing queued work) and every
        resident tenant's background repair thread."""
        if self._closed:
            return
        self._closed = True
        if self._retune_thread is not None:
            self._retune_q.put(None)
            self._retune_thread.join()
            self._retune_thread = None
        for t in self._tenants.values():
            if t.engine is not None:
                t.engine._repair_stop.set()

    # -- serving ------------------------------------------------------------
    def submit(self, name: str, x: jax.Array) -> EngineRequest:
        """Enqueue y = A_name @ x; reactivates an evicted tenant first.

        A quarantined tenant (its circuit breaker opened after
        ``breaker_threshold`` consecutive fully-failed batches) fails fast
        with :class:`CircuitOpenError` until its cooldown lapses — failing
        in microseconds beats queueing work a poisoned engine will fail in
        milliseconds anyway.
        """
        tenant = self._tenants[name]
        if tenant.quarantined:
            remaining = tenant.quarantined_until - time.perf_counter()
            raise CircuitOpenError(
                f"tenant {name!r} is quarantined for another "
                f"{remaining:.3f}s ({tenant.n_quarantines} quarantines so "
                "far); resubmit after the cooldown"
            )
        bucket = tenant.bucket
        if bucket is not None and not bucket.try_take():
            self.stats_fleet.rate_limited += 1
            raise OverloadError(
                f"tenant {name!r} rate-limited: token bucket dry "
                f"(rate={bucket.rate:g}/s, burst={bucket.burst:g}) — the "
                "burst fails fast instead of consuming the shared queue "
                "budget"
            )
        tenant.touch(time.perf_counter())
        if tenant.engine is None:
            self._admit(tenant)
            self.stats_fleet.reactivations += 1
        return tenant.engine.submit(x)

    def step(self) -> int:
        """One fleet scheduling pass; returns #requests dispatched.

        Deadline-first: tenants with pending work are served in order of
        their oldest request's SLO deadline (``t_submit + max_wait_s``; no
        SLO sorts last among pending).  The scan start rotates round-robin
        so equal-deadline tenants share the device fairly.  Each engine
        still applies its own ``max_wait_s`` admission gate, so visiting a
        tenant early never force-flushes a partial bucket ahead of its SLO.
        """
        if self._brownout is not None:
            # The fleet is the ONE writer of the shared controller; engines
            # only read it.  Update before the ready check so an idle fleet
            # still recovers (pressure decays to zero with empty queues).
            self._brownout.update(self._overload_pressure())
        ready = [
            t
            for t in self._tenants.values()
            if t.engine is not None
            and not t.quarantined
            and (t.engine.pending > 0 or t.engine.in_flight > 0)
        ]
        if not ready:
            return 0
        self._rr = (self._rr + 1) % len(ready)
        ready = ready[self._rr :] + ready[: self._rr]  # RR tie-break

        def deadline(t: Tenant) -> float:
            if t.engine.pending == 0:
                return float("inf")  # retire-only visit: after dispatches
            head = t.engine._queue[0].t_submit
            return head + (
                t.max_wait_s if t.max_wait_s is not None else float("inf")
            )

        served = 0
        for tenant in sorted(ready, key=deadline):  # stable: keeps RR ties
            served += tenant.engine.step()
            self._check_breaker(tenant)
        return served

    def _overload_pressure(self) -> float:
        """Fleet-wide overload pressure: the max of every resident
        engine's pressure (queue fill, oldest age, prep-dict bytes) — the
        most-stressed tenant defines the fleet's state, because the device
        and the prep memo are shared."""
        return max(
            (
                t.engine._overload_pressure()
                for t in self._tenants.values()
                if t.engine is not None
            ),
            default=0.0,
        )

    def _check_breaker(self, tenant: Tenant) -> None:
        """Open the tenant's circuit after ``breaker_threshold`` consecutive
        fully-failed batches: quarantine it for ``breaker_reset_s``, retire
        its in-flight work, and fail its queued requests fast with
        :class:`CircuitOpenError` (never leave them hanging).  The engine's
        demote/repair machinery keeps healing underneath; the breaker only
        protects *other* tenants' latency from a poisoning one.
        """
        eng = tenant.engine
        if eng is None or eng.consecutive_failures < self.breaker_threshold:
            return
        now = time.perf_counter()
        tenant.quarantined_until = now + self.breaker_reset_s
        tenant.n_quarantines += 1
        self.stats_fleet.quarantines += 1
        eng.flush()  # retire (or fail) whatever is still in flight
        while eng._queue:
            req = eng._queue.popleft()
            req.set_exception(
                CircuitOpenError(
                    f"tenant {tenant.name!r} quarantined after "
                    f"{eng.consecutive_failures} consecutive batch failures"
                )
            )
            eng.stats.failed_requests += 1
        eng.consecutive_failures = 0
        eng.supervisor.record(
            "quarantine",
            tenant=tenant.name,
            until=tenant.quarantined_until,
            reset_s=self.breaker_reset_s,
        )

    def drain(self) -> int:
        """Serve every pending request of every tenant; returns #served."""
        served = 0
        while True:
            pass_served = 0
            for tenant in list(self._tenants.values()):
                if tenant.engine is not None:
                    pass_served += tenant.engine.drain()
            served += pass_served
            if pass_served == 0:
                return served

    def flush(self) -> int:
        """Retire every in-flight batch fleet-wide (no new dispatches)."""
        return sum(
            t.engine.flush() for t in self._tenants.values() if t.engine
        )

    def stats(self) -> FleetStats:
        return self.stats_fleet

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        res = sum(1 for t in self._tenants.values() if t.resident)
        return (
            f"SparseFleet({len(self._tenants)} tenants, {res} resident, "
            f"{self.resident_bytes}/{self.budget_bytes} bytes, "
            f"ks={self.ks})"
        )
