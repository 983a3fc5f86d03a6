"""SparseEngine: a batch-aggregating, k-aware SpMV serving runtime.

The paper's decisive throughput lever on a memory-bound machine is turning
SpMV (k=1) into SpMM (k>1): Fig 9 shows matrix traffic amortized over many
right-hand sides beats any single-kernel tweak.  This module is that finding
as a serving runtime: the engine owns a request queue, aggregates pending
SpMV requests into stacked right-hand-side batches (columns of X), and
dispatches each batch through the ``repro.tune`` plan tuned for that width.

Plans are held per *k-bucket* (default k in {1, 4, 16, 64}); a batch of b
pending requests is rounded up to the smallest bucket >= b.  Occupancy
therefore decides at runtime whether the k=1 SpMV plan (CSR-vector / SELL)
or a wide SpMM plan (CSR gather / BCSR) runs — the serving analogue of the
paper's Fig 9 crossover.  The bucket plan table comes from
:meth:`repro.tune.SparseOperator.build_multi` and lives in the shared JSON
plan cache, so a restarted engine reloads every bucket's plan without
re-searching.

**The zero-overhead hot path** (``runtime.executable``): steady-state
serving does no avoidable host work per batch.

* Each k-bucket lowers ONCE to a persistent compiled executable with the
  plan's prepared-dict leaves closed over as compile-time constants — a
  dispatch is one warmed-fastpath invocation, with no per-call pytree
  flattening of index arrays and no re-trace.
* Batches assemble ON DEVICE, inside that same single program: the
  (already device-resident) request vectors stack straight into the RHS
  slab — never a host ``np.stack``.  Burst tails reuse the bucket's one
  program by padding the argument list with a shared device-resident zero
  column (bit-identical to the synchronous padding), so a novel occupancy
  never recompiles mid-serving.  (See ``runtime.executable`` for why the
  dispatch path does not *donate* the slab on this backend, and where
  donation is kept instead.)
* The loop is asynchronous and double-buffered: ``step()`` dispatches
  without blocking and keeps up to ``async_depth`` (<= 2) batches in
  flight, so the host aggregates and assembles batch t+1 while the device
  computes batch t.  ``submit()`` returns immediately with a future-like
  ticket — ``req.result()`` blocks for exactly that request;
  ``drain()``/``flush()`` retire everything.  Results are
  bitwise-identical to a synchronous engine (``async_depth=0``) because
  both run the same executables.

``legacy_dispatch=True`` keeps the pre-hot-path behavior — eager per-batch
``jnp.stack`` into a per-bucket jitted function, fully synchronous — as the
measured baseline for ``benchmarks/fig15_dispatch.py``.

Row-partitioned mode (``n_shards > 1``) routes batches through
``core.distributed.stacked_spmm``: the same ring assembly feeds one vmapped
shard dispatch compiled into the bucket executable.  Mesh mode
(``mesh=``/``axis=``) partitions A across a real device mesh: ring assembly
compiles to a slab executable whose output feeds the bucket's shard_map
schedule through a donation-enabled runner (the engine owns its slabs).

``max_wait_s`` adds admission control: ``step()`` holds a partial bucket
back while more requests may still arrive, but dispatches it as soon as the
oldest pending request has waited that long — a single request under SLO
never waits for a wide bucket to fill.

**Overload protection** (``runtime.overload``).  The paper's saturation
finding — past the memory-latency knee, extra concurrent work buys no
throughput and only adds latency — is enforced as serving discipline:

* ``max_queue`` bounds the pending queue; ``overload_policy`` picks what a
  full queue does to ``submit()``: ``"reject"`` fails fast with a typed
  :class:`OverloadError`, ``"shed-oldest"`` evicts the oldest queued
  request (failing ITS future) to admit the new one, ``"block"`` waits up
  to ``block_timeout_s`` for space (driving the serving loop if no other
  thread is) and then rejects.
* ``shed_after_s`` is deadline-aware load shedding: a request still queued
  when its wait exceeds this lapses at dispatch time — failed fast via
  ``set_exception`` with :class:`DeadlineExceededError` instead of
  occupying a bucket slot computing an answer nobody is waiting for.
  Counted in ``EngineStats.shed_deadline``.
* ``brownout=`` attaches a :class:`repro.runtime.overload.
  BrownoutController`; the engine feeds it queue-depth / oldest-age /
  prep-byte pressure each ``step()`` (unless ``brownout_update=False`` —
  the fleet drives a shared controller itself) and degrades by state:
  BROWNOUT pins dispatch to the widest k-bucket and pauses the background
  repair prober; SHED additionally rejects NEW submissions fast while the
  queue keeps draining.  Transitions are published as supervisor events.

    eng = SparseEngine(a)            # tunes (or cache-loads) all buckets
    reqs = [eng.submit(x) for x in xs]
    eng.drain()                      # dispatches k-bucketed batches
    reqs[0].y, reqs[0].latency_s     # per-request result + latency
    eng.stats.summary()              # occupancy / padding / bucket counts
"""
from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from collections import deque
from typing import Any, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.distributed import assemble_rows, stacked_spmm
from repro.core.formats import CSRMatrix
from repro.core.partition import rows_balanced, stack_csr_shards
from repro.runtime.executable import finite_guard, fused_batch_executable
from repro.runtime.faults import FaultPlan, InjectedFault, active_plan
from repro.runtime.overload import (
    HEALTHY,
    SHED,
    BrownoutController,
    DeadlineExceededError,
    EngineClosedError,
    OverloadError,
)
from repro.runtime.supervisor import (
    FALLBACK_TIERS,
    NonFiniteOutput,
    Supervisor,
    fallback_op,
)
from repro.tune import PlanCache, SparseOperator
from repro.tune.operator import prep_memo_stats
from repro.tune.operator import runner as _bind_runner

__all__ = [
    "SparseEngine",
    "EngineRequest",
    "EngineStats",
    "K_BUCKETS",
    "OVERLOAD_POLICIES",
    "OverloadError",
    "DeadlineExceededError",
    "EngineClosedError",
]

K_BUCKETS = (1, 4, 16, 64)

OVERLOAD_POLICIES = ("reject", "shed-oldest", "block")

# Condition-wait granularity for blocked callers (result(timeout=), block-
# policy submits): bounded so a deadline stays honored even when nothing
# ever notifies (a wedged device), but callers wake EARLY on every
# retirement/failure notification instead of polling.
_WAIT_QUANTUM_S = 0.005


@dataclasses.dataclass(slots=True)
class EngineRequest:
    """One queued y = A @ x request — a future filled in at retirement.

    ``submit()`` returns immediately; the batch the request rides in may
    still be in flight on the device.  ``result()`` blocks until exactly
    this request is served (dispatching/retiring as needed) and returns y.
    """

    rid: int
    x: Any  # (n,) dense operand, or (indices, values) for submit_sparse
    t_submit: float
    t_done: float | None = None
    # k-bucket the request was dispatched in; sparse-RHS requests carry
    # ("spmspv", <x-nnz bucket>) so the two bucket spaces never collide.
    bucket: Any = None
    _ys: jax.Array | None = None  # the whole batch result (m, bucket)
    _col: int = 0  # this request's column of _ys
    _exc: BaseException | None = None  # set when the batch failed for good
    _engine: Any = dataclasses.field(default=None, repr=False, compare=False)

    @property
    def done(self) -> bool:
        """Resolved — with a result OR an exception.  A request never stays
        un-done forever: a batch the supervisor cannot serve fails every
        future in it via :meth:`set_exception`."""
        return self._ys is not None or self._exc is not None

    @property
    def failed(self) -> bool:
        return self._exc is not None

    @property
    def y(self) -> jax.Array | None:
        """(m,) result; sliced lazily so serving never pays per-column
        dispatch overhead inside the batch hot path."""
        if self._ys is None:
            return None
        return self._ys[:, self._col] if self._ys.ndim == 2 else self._ys

    def set_exception(self, exc: BaseException) -> None:
        """Fail this future: ``result()`` raises ``exc`` instead of
        blocking forever on a batch that will never retire."""
        self._exc = exc
        self.t_done = time.perf_counter()
        if self._engine is not None:
            self._engine._notify()  # wake callers blocked in result()

    def result(self, timeout: float | None = None) -> jax.Array:
        """Block until this request resolves; returns y (the future API).

        Raises the batch's failure if the supervisor gave up on it, or
        ``TimeoutError`` (with this request's bucket/engine context) after
        ``timeout`` seconds — so a caller can bound its wait even when the
        serving loop itself is wedged.
        """
        with TraceAnnotation("engine.result", rid=self.rid):
            if not self.done:
                if self._engine is None:
                    raise RuntimeError("request is not attached to an engine")
                deadline = (
                    None if timeout is None
                    else time.perf_counter() + float(timeout)
                )
                self._engine._fulfill(self, deadline=deadline)
            if self._exc is not None:
                raise self._exc
            return self.y

    @property
    def latency_s(self) -> float:
        assert self.t_done is not None, "request not served yet"
        return self.t_done - self.t_submit


@dataclasses.dataclass
class EngineStats:
    n_requests: int = 0
    n_dispatches: int = 0
    dispatched: dict = dataclasses.field(default_factory=dict)  # bucket -> #
    occupied_cols: int = 0  # real request columns dispatched (served work)
    padded_cols: int = 0  # zero columns added by bucket round-up (NOT work)
    latencies_s: list = dataclasses.field(default_factory=list)
    # Sparse-RHS dispatches, counted per x-nnz bucket ("spmspv<B>" keys).
    # They never enter the k-bucket occupancy math: a sparse dispatch serves
    # exactly one request, so column padding does not apply to it.
    sparse_dispatched: dict = dataclasses.field(default_factory=dict)
    # Supervision counters (see runtime.supervisor): a retried batch counts
    # one retry per re-dispatch; a batch the fallback chain could not serve
    # counts its requests under failed_requests (their futures carry the
    # exception — they are resolved, not served, so they never enter the
    # latency or occupancy figures).
    failed_requests: int = 0
    failed_batches: int = 0
    retries: int = 0
    demotions: int = 0
    promotions: int = 0
    # Overload counters (runtime.overload): rejected never entered the
    # queue (reject policy / block timeout / SHED state — the exception
    # surfaced at submit); shed_oldest were queued but evicted to admit
    # newer work; shed_deadline lapsed past shed_after_s before dispatch.
    # Shed/rejected requests never enter the latency or occupancy figures.
    rejected: int = 0
    shed_oldest: int = 0
    shed_deadline: int = 0
    _engine: Any = dataclasses.field(default=None, repr=False, compare=False)

    def record(self, bucket, n_real: int, lats: Iterable[float]) -> None:
        self.n_dispatches += 1
        if isinstance(bucket, tuple):  # ("spmspv", B): sparse-RHS dispatch
            key = f"spmspv{bucket[1]}"
            self.sparse_dispatched[key] = self.sparse_dispatched.get(key, 0) + 1
            self.latencies_s.extend(lats)
            return
        self.dispatched[bucket] = self.dispatched.get(bucket, 0) + 1
        self.occupied_cols += n_real
        self.padded_cols += bucket - n_real
        self.latencies_s.extend(lats)

    @property
    def occupancy(self) -> float:
        """TRUE occupancy: real requests / dispatched bucket capacity.

        Padded zero-columns are device work but not served work — they
        never enter the numerator here (and must not enter any
        requests-per-second figure derived from these stats).
        """
        total = self.occupied_cols + self.padded_cols
        return self.occupied_cols / total if total else 0.0

    @property
    def padded_occupancy(self) -> float:
        """Fraction of dispatched bucket capacity that was zero padding —
        the device-time waste of bucket round-up, reported separately so
        padding can never masquerade as throughput."""
        total = self.occupied_cols + self.padded_cols
        return self.padded_cols / total if total else 0.0

    def summary(self) -> dict[str, Any]:
        """The counters; with the engine, also each dense bucket's x row
        loads per nonzero (``SparseOperator.x_loads_per_nnz``), where its
        plan has one."""
        lats = np.asarray(self.latencies_s) if self.latencies_s else np.zeros(1)
        out = {
            "requests": self.n_requests,
            "dispatches": self.n_dispatches,
            "by_bucket": dict(sorted(self.dispatched.items())),
            "sparse_by_bucket": dict(sorted(self.sparse_dispatched.items())),
            "occupancy": round(self.occupancy, 4),
            "padded_occupancy": round(self.padded_occupancy, 4),
            "served_cols": self.occupied_cols,
            "padded_cols": self.padded_cols,
            "latency_mean_ms": round(float(lats.mean()) * 1e3, 3),
            "latency_p99_ms": round(float(np.quantile(lats, 0.99)) * 1e3, 3),
            "failed_requests": self.failed_requests,
            "failed_batches": self.failed_batches,
            "retries": self.retries,
            "demotions": self.demotions,
            "promotions": self.promotions,
            "rejected": self.rejected,
            "shed_oldest": self.shed_oldest,
            "shed_deadline": self.shed_deadline,
        }
        if self._engine is not None:
            loads = {b: op.x_loads_per_nnz for b, op in self._engine.ops.items()}
            out["x_loads_per_nnz"] = {
                b: round(v, 4) for b, v in sorted(loads.items()) if v is not None
            }
        return out


class SparseEngine:
    """Batch-aggregating serving runtime over a k-indexed plan table.

    ``ks`` are the tuned batch widths (ascending); ``cache`` is the shared
    plan cache (defaults to the on-disk one, so engine restarts skip the
    measured search).  ``mesh=``/``axis=`` runs every bucket on a device
    mesh: A is partitioned over ``axis`` and each bucket's plan picks a
    collective schedule (allgather vs ring) through the measured search,
    dispatching under shard_map.  ``n_shards > 1`` (single-device) switches
    every dispatch to the row-partitioned ``stacked_spmm`` path (CSR shards
    under one vmap); the tuned plan table is skipped entirely in that mode.
    ``max_wait_s`` caps how long a request may wait for its bucket to fill
    (None keeps the dispatch-immediately behavior).

    ``async_depth`` (0..2, default 2) is the in-flight window: how many
    dispatched batches may be outstanding before ``step()`` blocks to
    retire the oldest.  0 is fully synchronous (every step blocks); 2 is
    the double-buffered loop — batch t+1 assembles while batch t computes.
    ``legacy_dispatch=True`` restores the pre-hot-path eager-stack dispatch
    (benchmark baseline).  Remaining keyword arguments
    (warmup/timed/force_search/include_reorder/...) pass through to
    :meth:`SparseOperator.build`.

    **Dtype policy.** The engine serves float32 end to end (ring slots, pad
    columns and every tuned kernel are f32).  A non-f32 ``submit()`` input
    is cast to float32 — visibly: the first such cast warns (once per
    engine), because a float64 operand silently losing half its mantissa
    looks like a kernel accuracy bug from the caller's side.
    ``strict_dtype=True`` turns the cast into a ``TypeError`` for callers
    that would rather fail than lose precision.

    **Failure policy** (``runtime.supervisor``).  A batch that fails — the
    dispatch raises, the device block raises, or (with ``nan_guard=True``)
    the on-device finite guard flags NaN/Inf output — is retried up to
    ``supervisor.max_retries`` times with capped exponential backoff, then
    the bucket is *demoted* down the fallback chain (tuned plan →
    ``csr/vector`` → ``sell/ref``); if even the chain's last tier cannot
    serve it, every future in the batch fails via ``set_exception`` — a
    submitted request ALWAYS resolves, with a result or an exception.
    FIFO retirement and bitwise results for unaffected batches are
    preserved: recovery happens strictly after older in-flight batches
    retire, on freshly re-assembled operands.  A background repair thread
    probes a demoted bucket's saved tuned executable every
    ``supervisor.repair_interval_s`` and re-promotes it through
    ``hot_swap`` once a probe succeeds (dispatch-boundary semantics, like
    a retune swap; mesh buckets demote to a single-device fallback and
    repair the same way).  ``faults=`` arms a
    :class:`repro.runtime.faults.FaultPlan` (defaults to the
    ``$REPRO_FAULTS`` plan); ``name=`` labels this engine in fault
    contexts and error messages (the fleet passes the tenant name).
    """

    def __init__(
        self,
        a: CSRMatrix,
        *,
        ks: Sequence[int] = K_BUCKETS,
        cache: PlanCache | None = None,
        n_shards: int = 1,
        mesh: Any = None,
        axis: str | None = None,
        max_wait_s: float | None = None,
        max_queue: int | None = None,
        overload_policy: str = "reject",
        block_timeout_s: float = 1.0,
        shed_after_s: float | None = None,
        brownout: BrownoutController | None = None,
        brownout_update: bool = True,
        async_depth: int = 2,
        legacy_dispatch: bool = False,
        strict_dtype: bool = False,
        ops: dict[int, SparseOperator] | None = None,
        x_nnz_buckets: Sequence[int] | None = None,
        name: str | None = None,
        supervisor: Supervisor | None = None,
        faults: FaultPlan | None = None,
        nan_guard: bool = False,
        **build_kwargs: Any,
    ):
        if not ks:
            raise ValueError("need at least one k-bucket")
        if ops is not None and (mesh is not None or n_shards > 1):
            raise ValueError(
                "ops= injects a prebuilt single-device plan table; it cannot "
                "be combined with mesh= or n_shards>1"
            )
        self.a = a
        self.shape = a.shape
        self.name = name
        self.supervisor = supervisor if supervisor is not None else Supervisor()
        self.faults = faults if faults is not None else active_plan()
        self.nan_guard = bool(nan_guard)
        self.ks = tuple(sorted({int(k) for k in ks}))
        self.mesh = mesh
        self.axis = axis if axis is not None else (
            mesh.axis_names[0] if mesh is not None else None
        )
        self.max_wait_s = max_wait_s
        if overload_policy not in OVERLOAD_POLICIES:
            raise ValueError(
                f"overload_policy {overload_policy!r} is not one of "
                f"{OVERLOAD_POLICIES}"
            )
        if max_queue is not None and int(max_queue) < 1:
            raise ValueError("max_queue must be >= 1 (None = unbounded)")
        self.max_queue = None if max_queue is None else int(max_queue)
        self.overload_policy = overload_policy
        self.block_timeout_s = float(block_timeout_s)
        self.shed_after_s = (
            None if shed_after_s is None else float(shed_after_s)
        )
        # Brownout: the engine owns and updates its controller unless the
        # fleet injected a shared one (brownout_update=False) that it
        # drives with fleet-wide pressure itself.
        self._brownout = brownout
        self._brownout_update = bool(brownout_update)
        self.n_shards = int(n_shards)
        # The ring double-buffers across consecutive batches, so at most two
        # dispatches can be in flight before a buffer must be reused.
        self.async_depth = max(0, min(int(async_depth), 2))
        self.legacy_dispatch = bool(legacy_dispatch)
        self.strict_dtype = bool(strict_dtype)
        self._dtype_warned = False  # the cast warning fires once per engine
        if mesh is not None:
            if n_shards > 1:
                raise ValueError("mesh= and n_shards= are mutually exclusive")
            self.n_shards = int(mesh.shape[self.axis])
            self.ops = SparseOperator.build_multi(
                a, ks=self.ks, cache=cache, mesh=mesh, axis=self.axis,
                **build_kwargs,
            )
        elif self.n_shards > 1:
            # Row-partitioned mode dispatches through stacked_spmm for every
            # bucket; don't pay the per-bucket measured search for plans that
            # would never run.
            self.ops = {}
            part = rows_balanced(a, self.n_shards)
            self._stacked = {
                key: jnp.asarray(v)
                for key, v in stack_csr_shards(part.shards).items()
            }
            self._shard_rows = np.diff(part.bounds)
        elif ops is not None:
            # Injected plan table (SparseFleet's predicted-plan admission):
            # skip build_multi entirely — the caller already chose a plan per
            # bucket (measured, cached, or transfer-predicted).
            missing = [k for k in self.ks if int(k) not in ops]
            if missing:
                raise ValueError(f"ops= is missing buckets {missing}")
            self.ops = {int(k): ops[int(k)] for k in self.ks}
        else:
            self.ops = SparseOperator.build_multi(
                a, ks=self.ks, cache=cache, **build_kwargs
            )
        # Sparse-RHS serving state (submit_sparse): requests bucket by
        # nnz(x) the way dense requests bucket by k.  Plans build lazily on
        # first use of each bucket (plan-cached, so restarts are warm).
        self._cache = cache
        self._build_kwargs = dict(build_kwargs)
        if x_nnz_buckets is None:
            n = a.shape[1]
            x_nnz_buckets = (
                max(1, n // 256), max(1, n // 64), max(1, n // 16),
                max(1, n // 4),
            )
        self.x_nnz_buckets = tuple(sorted({max(1, int(b)) for b in x_nnz_buckets}))
        self._sparse_ops: dict[int, SparseOperator] = {}
        self._sparse_execs: dict[int, Any] = {}
        self._queue: deque[EngineRequest] = deque()
        # (ys, ok, reqs, bucket, take, batch)
        self._inflight: deque[tuple] = deque()
        self._rid = 0
        self._batch = 0  # sequence number of the next launched batch
        # Blocked callers (result(timeout=), block-policy submits) sleep on
        # this condition and are notified at every retirement/failure
        # instead of burning a poll loop; _serve_lock elects ONE of them to
        # drive the engine while the rest wait.
        self._cond = threading.Condition()
        self._serve_lock = threading.Lock()
        if self._brownout is not None and self._brownout_update:
            # Publish this engine's brownout transitions as supervisor
            # events (a fleet-shared controller is published by the fleet).
            sup, nm = self.supervisor, name
            self._brownout.add_listener(
                lambda tr: sup.record(
                    "brownout", engine=nm, frm=tr.frm, to=tr.to,
                    pressure=round(tr.pressure, 4),
                )
            )
        self._execs: dict[int, Any] = {}  # bucket -> persistent executable
        self._batch_fns: dict[int, Any] = {}  # legacy: bucket -> jitted stack
        # Hot-swap staging: a background tuner builds a better plan table and
        # stages it here (under the lock); the serving thread applies it at
        # the next step() dispatch boundary.  See hot_swap().
        self._swap_lock = threading.Lock()
        self._pending_swap: tuple[dict, dict] | None = None
        self.swaps_applied = 0
        # Shared device-resident zero column: burst tails pad their argument
        # list with it so ONE executable per bucket serves every occupancy
        # (also the legacy path's pad column).
        self._zero = jnp.zeros((self.shape[1],), jnp.float32)
        self._nan_col = None  # lazy poisoned column for the engine.nan site
        self.stats = EngineStats(_engine=self)
        # Degraded-mode state: bucket -> fallback-chain level (1-based), and
        # the saved tuned (op, exec) the repair thread probes/re-promotes.
        self._closed = False
        self.consecutive_failures = 0  # fully-failed batches since a success
        self._demoted: dict[Any, int] = {}
        self._demote_saved: dict[Any, tuple] = {}
        self._repair_lock = threading.Lock()
        self._repair_thread: threading.Thread | None = None
        self._repair_stop = threading.Event()

    # -- queueing -----------------------------------------------------------
    @property
    def from_cache(self) -> bool:
        """True when every bucket's plan came from the cache (no search)."""
        return all(op.from_cache for op in self.ops.values())

    @property
    def pending(self) -> int:
        return len(self._queue)

    @property
    def in_flight(self) -> int:
        """Dispatched-but-unretired batches (0..async_depth)."""
        return len(self._inflight)

    def submit(self, x: jax.Array) -> EngineRequest:
        """Enqueue y = A @ x; returns a future filled in by a later step().

        Non-float32 inputs are cast to f32 (ring slots and pads are f32) —
        warning once per engine, or raising ``TypeError`` under
        ``strict_dtype=True``.  See the class docstring's dtype policy.
        """
        with TraceAnnotation("engine.submit", rid=self._rid):
            self._check_open()
            if not isinstance(x, jax.Array):  # asarray on a device array costs
                # Through numpy, NOT jnp: with x64 disabled jnp.asarray folds
                # float64 to f32 before the dtype is ever observable, which is
                # exactly the silent downcast this policy exists to surface.
                x = np.asarray(x)
            if x.shape != (self.shape[1],):
                raise ValueError(
                    f"expected x of shape ({self.shape[1]},), got {x.shape}"
                )
            if x.dtype != jnp.float32:
                if self.strict_dtype:
                    raise TypeError(
                        f"submit() got dtype {x.dtype}; this engine serves "
                        "float32 and strict_dtype=True forbids the implicit cast"
                    )
                if not self._dtype_warned:
                    self._dtype_warned = True
                    warnings.warn(
                        f"SparseEngine.submit: casting {x.dtype} input to "
                        "float32 (the engine's serving dtype) — submit float32 "
                        "to avoid the cast, or build the engine with "
                        "strict_dtype=True to make this an error; warning once "
                        "per engine",
                        stacklevel=2,
                    )
                x = jnp.asarray(x, jnp.float32)
            elif not isinstance(x, jax.Array):
                x = jnp.asarray(x)
            self._admit_one()
            req = EngineRequest(rid=self._rid, x=x, t_submit=time.perf_counter(),
                                _engine=self)
            self._rid += 1
            self._queue.append(req)
            self.stats.n_requests += 1
            return req

    # -- bounded admission (runtime.overload) -------------------------------
    def _admit_one(self) -> None:
        """Gate one submission against the queue bound and brownout state.

        SHED state rejects outright (typed, microseconds — the brownout
        ladder's last rung protects the work already queued).  A full queue
        applies ``overload_policy``: ``reject`` raises
        :class:`OverloadError`; ``shed-oldest`` evicts the head request —
        the one closest to lapsing anyway — failing its future so FIFO
        order among survivors is untouched; ``block`` waits up to
        ``block_timeout_s`` for space, driving the serving loop itself when
        no other thread holds it, then rejects.
        """
        b = self._brownout
        if b is not None and b.state == SHED:
            self.stats.rejected += 1
            raise OverloadError(
                f"engine {self.name or 'unnamed'} is shedding load "
                f"(brownout state={b.state}, pressure="
                f"{b.pressure_last:.2f}); resubmit after recovery"
            )
        if self.max_queue is None or len(self._queue) < self.max_queue:
            return
        if self.overload_policy == "reject":
            self.stats.rejected += 1
            raise OverloadError(
                f"engine {self.name or 'unnamed'} queue is full "
                f"({len(self._queue)}/{self.max_queue} pending, "
                f"policy=reject); back off and resubmit"
            )
        if self.overload_policy == "shed-oldest":
            victim = self._queue.popleft()
            victim.set_exception(
                OverloadError(
                    f"request {victim.rid} shed: engine "
                    f"{self.name or 'unnamed'} queue hit max_queue="
                    f"{self.max_queue} (policy=shed-oldest) and a newer "
                    "request displaced it"
                )
            )
            self.stats.shed_oldest += 1
            return
        # block: wait for space, bounded.  One thread at a time may drive
        # the engine to make that space; the rest sleep on the condition
        # and are woken by each retirement.
        deadline = time.perf_counter() + self.block_timeout_s
        while len(self._queue) >= self.max_queue:
            now = time.perf_counter()
            if now >= deadline:
                self.stats.rejected += 1
                raise OverloadError(
                    f"engine {self.name or 'unnamed'} queue still full "
                    f"({len(self._queue)}/{self.max_queue}) after blocking "
                    f"{self.block_timeout_s:.3f}s (policy=block)"
                )
            if self._serve_lock.acquire(blocking=False):
                try:
                    if self.step() > 0:
                        continue
                    self._retire_ready()
                    if len(self._queue) < self.max_queue:
                        return
                finally:
                    self._serve_lock.release()
            with self._cond:
                if len(self._queue) >= self.max_queue:
                    self._cond.wait(
                        timeout=min(_WAIT_QUANTUM_S, deadline - now)
                    )

    # -- sparse RHS ---------------------------------------------------------
    def submit_sparse(self, indices, values) -> EngineRequest:
        """Serve y = A @ x for a SPARSE x given as sorted (indices, values).

        The sparse-RHS analogue of :meth:`submit`: the request is routed to
        the smallest ``x_nnz_buckets`` entry >= nnz(x) and dispatched
        through the ``kind="spmspv"`` plan tuned for that bucket
        (:meth:`SparseOperator.build` with ``x_nnz=``), mirroring how dense
        requests round up to k-buckets.  Coordinates are validated loudly —
        out-of-range, unsorted, or duplicated indices raise ``ValueError``
        with remediation text (kernels.spmspv.validate_sparse_rhs) — and
        values follow the engine's f32 dtype policy.  A request thicker
        than the largest bucket densifies onto the dense k=1 path: past the
        measured crossover the dense tiers win anyway.

        Sparse requests dispatch immediately (they never aggregate into
        SpMM slabs — each is its own single-column program), but they share
        the async in-flight window and retire through the same machinery;
        the returned future behaves exactly like a dense one.
        """
        with TraceAnnotation("engine.submit", rid=self._rid):
            self._check_open()
            b = self._brownout
            if b is not None and b.state == SHED:
                # Sparse requests dispatch immediately (no queue to bound), but
                # SHED refuses them the same way: new work is new load.
                self.stats.rejected += 1
                raise OverloadError(
                    f"engine {self.name or 'unnamed'} is shedding load "
                    f"(brownout state={b.state}); resubmit after recovery"
                )
            if self.mesh is not None or self.n_shards > 1:
                raise NotImplementedError(
                    "submit_sparse is single-device for now: distributed SpMSpV "
                    "under the mesh schedules is the ROADMAP follow-on of this "
                    "tier"
                )
            from repro.kernels.spmspv import validate_sparse_rhs

            n = self.shape[1]
            idx, val = validate_sparse_rhs(indices, values, n)
            val = np.asarray(val)
            if val.dtype != np.float32:
                if self.strict_dtype:
                    raise TypeError(
                        f"submit_sparse() got values dtype {val.dtype}; this "
                        "engine serves float32 and strict_dtype=True forbids "
                        "the implicit cast"
                    )
                if not self._dtype_warned:
                    self._dtype_warned = True
                    warnings.warn(
                        f"SparseEngine.submit_sparse: casting {val.dtype} values "
                        "to float32 (the engine's serving dtype) — submit "
                        "float32 to avoid the cast, or build the engine with "
                        "strict_dtype=True to make this an error; warning once "
                        "per engine",
                        stacklevel=2,
                    )
                val = val.astype(np.float32)
            bucket = next((b for b in self.x_nnz_buckets if b >= idx.size), None)
            if bucket is None:
                x = np.zeros((n,), np.float32)
                x[idx] = val
                return self.submit(x)
            req = EngineRequest(
                rid=self._rid, x=(idx, val), t_submit=time.perf_counter(),
                _engine=self,
            )
            self._rid += 1
            self.stats.n_requests += 1
            window = max(1, self.async_depth)
            while len(self._inflight) >= window:
                self._retire_one()
            key = ("spmspv", bucket)
            batch = self._next_batch()
            try:
                with TraceAnnotation("engine.launch", batch=batch, bucket=key,
                                     take=1):
                    ys, ok = self._launch(key, [req])
            except Exception as exc:
                self.flush()  # older batches retire first: FIFO holds under faults
                self._recover([req], key, 1, exc, batch)
                return req
            self._inflight.append((ys, ok, [req], key, 1, batch))
            if self.async_depth == 0:
                self._retire_one()
            return req

    def _sparse_op(self, bucket: int) -> SparseOperator:
        op = self._sparse_ops.get(bucket)
        if op is None:
            op = self._sparse_ops[bucket] = SparseOperator.build(
                self.a, x_nnz=bucket, cache=self._cache, **self._build_kwargs
            )
        return op

    def _sparse_exec(self, bucket: int):
        fn = self._sparse_execs.get(bucket)
        if fn is None:
            # The sparse runner is already a persistent per-work-bucket
            # dispatch (spmspv_bind caches jitted executables per gathered
            # work size); no fused batch assembly applies to one request.
            fn = self._sparse_op(bucket)._run
            if self.nan_guard:
                fn = finite_guard(fn)
            self._sparse_execs[bucket] = fn
        return fn

    # -- hot swap -----------------------------------------------------------
    def hot_swap(
        self,
        ops: dict[int, SparseOperator],
        execs: dict[int, Any] | None = None,
    ) -> None:
        """Stage a replacement plan table; applied at a dispatch boundary.

        Thread-safe: a background tuner calls this from its own thread with
        a freshly built (and, via ``_make_exec``, ideally prewarmed) table;
        the serving thread picks it up at the top of the NEXT ``step()``.
        No lock is ever held on the hot path beyond the staging pointer
        exchange.  Batches already in flight keep their old-plan device
        results — their futures retire bitwise-unchanged — and every batch
        dispatched after the swap runs the new table.  ``execs`` optionally
        carries prewarmed per-bucket executables (missing buckets re-lower
        lazily on first use).
        """
        missing = [k for k in self.ks if int(k) not in ops]
        if missing:
            raise ValueError(f"hot_swap ops is missing buckets {missing}")
        staged_ops = {int(k): ops[int(k)] for k in self.ks}
        staged_execs = {
            int(k): v for k, v in (execs or {}).items() if int(k) in staged_ops
        }
        with self._swap_lock:
            self._pending_swap = (staged_ops, staged_execs)

    def _apply_pending_swap(self) -> None:
        """Adopt a staged table (serving thread only, between dispatches)."""
        with self._swap_lock:
            staged = self._pending_swap
            self._pending_swap = None
        if staged is None:
            return
        ops, execs = staged
        self.ops = ops
        self._execs = dict(execs)  # unprewarmed buckets re-lower lazily
        self._batch_fns.clear()  # legacy closures captured the old plans
        self.swaps_applied += 1

    # -- dispatch -----------------------------------------------------------
    def _bucket_for(self, n_pending: int) -> tuple[int, int]:
        take = min(n_pending, self.ks[-1])
        if self._brownout is not None and self._brownout.state != HEALTHY:
            # Browned out: pin dispatch to the widest k-bucket — under a
            # backlog batches are full anyway, and one executable with
            # maximal SpMM amortization is the highest-goodput way through.
            return self.ks[-1], take
        bucket = next(k for k in self.ks if k >= take)
        return bucket, take

    def _overload_pressure(self) -> float:
        """Scalar overload pressure in [0, 1+] for the brownout controller:
        max of queue fill (vs ``max_queue``), oldest-request age (vs the
        shed deadline, or 4x the SLO when only ``max_wait_s`` is set — at
        healthy load the head request never waits past one SLO), and the
        process-wide prepared-dict byte pressure."""
        q = (len(self._queue) / self.max_queue) if self.max_queue else None
        ref = self.shed_after_s
        if ref is None and self.max_wait_s:
            ref = 4.0 * self.max_wait_s
        age = None
        if ref and self._queue:
            age = (time.perf_counter() - self._queue[0].t_submit) / ref
        st = prep_memo_stats()
        prep = (
            st["resident_bytes"] / st["budget_bytes"]
            if st["budget_bytes"] > 0
            else None
        )
        return BrownoutController.pressure(queue=q, age=age, prep=prep)

    def _shed_lapsed(self) -> None:
        """Deadline-aware load shedding: fail queued requests whose wait
        already exceeds ``shed_after_s`` at dispatch time — fast, typed,
        via the ``set_exception`` path — instead of spending a bucket slot
        on an answer nobody is waiting for.  FIFO makes the head the oldest
        request, so the scan stops at the first survivor."""
        if self.shed_after_s is None or not self._queue:
            return
        now = time.perf_counter()
        while (
            self._queue
            and now - self._queue[0].t_submit > self.shed_after_s
        ):
            req = self._queue.popleft()
            req.set_exception(
                DeadlineExceededError(
                    f"request {req.rid} lapsed: waited "
                    f"{now - req.t_submit:.4f}s > shed_after_s="
                    f"{self.shed_after_s:.4f}s before dispatch on engine "
                    f"{self.name or 'unnamed'}"
                )
            )
            self.stats.shed_deadline += 1

    def step(self, *, force: bool = False) -> int:
        """Dispatch one aggregated batch; returns #requests dispatched.

        Takes up to max(ks) pending requests, rounds the count up to the
        smallest k-bucket, assembles the batch into the device ring, and
        launches the bucket's persistent executable WITHOUT blocking on the
        result: the batch joins the in-flight window and is retired (result
        readiness awaited, futures filled, stats recorded) either when the
        window is full, by ``flush()``/``drain()``, or by a request's
        ``result()``.  With ``async_depth=0`` the dispatch is retired
        before step() returns (synchronous mode).

        Admission control: with ``max_wait_s`` set, a partial bucket (fewer
        pending than max(ks)) is held back — step() returns 0 — until the
        oldest pending request has waited ``max_wait_s``, then dispatched
        as-is (rounded up to its bucket).  ``force=True`` (used by drain)
        bypasses the wait and flushes immediately.
        """
        with TraceAnnotation("engine.step"):
            self._apply_pending_swap()  # dispatch boundary: adopt a staged table
            if self._brownout is not None and self._brownout_update:
                self._brownout.update(self._overload_pressure())
            self._shed_lapsed()  # deadline shedding happens AT dispatch time
            if not self._queue:
                self._retire_ready()  # idle: resolve futures promptly
                return 0
            if (
                not force
                and self.max_wait_s is not None
                and len(self._queue) < self.ks[-1]
                and time.perf_counter() - self._queue[0].t_submit < self.max_wait_s
            ):
                # Held by the admission gate: use the wait to retire in-flight
                # batches whose results are already on device, so their
                # latency stats record availability, not bookkeeping lag.
                self._retire_ready()
                return 0
            bucket, take = self._bucket_for(len(self._queue))
            pop = self._queue.popleft
            reqs = [pop() for _ in range(take)]
            self._notify()  # queue space freed: wake submitters blocked on it

            if self.legacy_dispatch:
                return self._step_legacy(reqs, bucket, take)

            # In-flight window: bound how far dispatch runs ahead of retirement
            # (two-deep by default — batch t+1 assembles and launches while
            # batch t computes; retirement stays FIFO).
            window = max(1, self.async_depth)
            while len(self._inflight) >= window:
                self._retire_one()

            batch = self._next_batch()
            try:
                with TraceAnnotation("engine.launch", batch=batch,
                                     bucket=bucket, take=take):
                    ys, ok = self._launch(bucket, reqs)
            except Exception as exc:
                # A dispatch-time failure must not reorder retirement: retire
                # every older in-flight batch first, then recover this one
                # synchronously (retry -> demote -> fail its futures).
                self.flush()
                self._recover(reqs, bucket, take, exc, batch)
                return take
            self._inflight.append((ys, ok, reqs, bucket, take, batch))
            if self.async_depth == 0:
                self._retire_one()
            return take

    def _check_open(self) -> None:
        if self._closed:
            raise EngineClosedError(
                f"SparseEngine {self.name or 'unnamed'} is closed: submit "
                "after close() would enqueue into a dead serving loop — "
                "build a new engine (plans are cached, so it is cheap)"
            )

    def close(self, drain: bool = True) -> None:
        """Refuse new submissions and stop the background repair thread.
        Idempotent.

        ``drain=True`` (the default) serves every outstanding request
        first — close is graceful.  ``drain=False`` aborts: every future
        still queued or in flight fails immediately with a typed
        :class:`EngineClosedError`, so a caller blocked in ``result()``
        raises instead of hanging on an engine nobody will ever drive
        again.
        """
        if self._closed:
            return
        if drain:
            self.drain()
        self._closed = True
        if not drain:
            exc = EngineClosedError(
                f"SparseEngine {self.name or 'unnamed'} closed with "
                "drain=False: this request was abandoned, not served"
            )
            aborted = 0
            while self._queue:
                self._queue.popleft().set_exception(exc)
                aborted += 1
            while self._inflight:
                _ys, _ok, reqs, _bucket, take, _batch = self._inflight.popleft()
                for req in reqs:
                    req.set_exception(exc)
                aborted += take
            self.stats.failed_requests += aborted
            if aborted:
                self.supervisor.record(
                    "engine_aborted", engine=self.name, n_requests=aborted
                )
        self._repair_stop.set()
        self._notify()  # closed is a terminal resolution for any waiter
        t = self._repair_thread
        if t is not None and t.is_alive():
            t.join(timeout=5.0)

    def _nan_column(self) -> jax.Array:
        if self._nan_col is None:
            self._nan_col = jnp.full((self.shape[1],), jnp.nan, jnp.float32)
        return self._nan_col

    def _assemble(self, reqs: list, bucket) -> tuple:
        """(Re)build a batch's operand list from its requests — recovery
        re-assembles from ``req.x`` so a retry never reuses an operand a
        fault may have poisoned."""
        if isinstance(bucket, tuple):  # sparse-RHS: one request per batch
            from repro.kernels.spmspv import pad_sparse_rhs

            idx, val = reqs[0].x
            return (pad_sparse_rhs(idx, val, bucket[1], self.shape[1]),)
        xs = [r.x for r in reqs]
        if len(xs) < bucket:  # burst tail: same program, zero pad columns
            xs.extend([self._zero] * (bucket - len(xs)))
        return tuple(xs)

    def _next_batch(self) -> int:
        batch = self._batch
        self._batch += 1
        return batch

    def _launch(self, bucket, reqs: list):
        """Assemble + dispatch one batch through the bucket's executable,
        firing any armed injection sites on the way; returns ``(ys, ok)``
        where ``ok`` is the on-device all-finite flag (None when the guard
        is off).  Callers wrap it in the ``engine.launch`` span, whose
        ``batch`` the batch's ``engine.retire`` span shares."""
        faults = self.faults
        if faults is not None:
            stall = faults.delay(
                "engine.overload", engine=self.name, bucket=bucket
            )
            if stall > 0.0:
                # Synthetic overload: a slow dispatch with a KNOWN service
                # cost, so load tests measure capacity deterministically.
                time.sleep(stall)
            faults.fire("engine.dispatch", engine=self.name, bucket=bucket)
        xs = self._assemble(reqs, bucket)
        if (
            faults is not None
            and not isinstance(bucket, tuple)
            and faults.should_fire("engine.nan", engine=self.name,
                                   bucket=bucket)
        ):
            # "Slab DMA returned garbage": poison one column so the kernel
            # output goes NaN — detected by the nan_guard at retirement.
            xs = (self._nan_column(),) + xs[1:]
        if isinstance(bucket, tuple):
            ys = self._sparse_exec(bucket[1])(*xs)  # host (xi, xv) tuple:
            # the spmspv runner picks the work bucket from xi on host
        else:
            ys = self._exec(bucket)(*xs)
        if isinstance(ys, tuple):
            return ys  # guarded executable: (ys, all_finite)
        return ys, None

    def _exec(self, bucket: int):
        """The bucket's persistent executable: ``(x_0..x_{bucket-1}) -> ys``
        — on-device assembly and kernel in ONE launch.

        Lowered once per bucket on first use and reused for every occupancy
        (tails pad their argument list with the shared zero column, so a
        novel tail size never recompiles mid-serving); prepared arrays are
        closed over as compile-time constants, so a dispatch is one
        executable invocation with no pytree flattening.
        """
        fn = self._execs.get(bucket)
        if fn is not None:
            return fn
        if self.mesh is None and self.n_shards > 1:
            stacked = self._stacked
            counts = [int(r) for r in self._shard_rows]

            def body(xb):
                return assemble_rows(stacked_spmm(stacked, xb), counts)

            fn = fused_batch_executable(
                (lambda x: body(x[:, None])) if bucket == 1 else body,
                bucket=bucket,
                guard=self.nan_guard,
                name=f"engine_k{bucket}",
            )
        else:
            fn = self._make_exec(bucket, self.ops[bucket])
        self._execs[bucket] = fn
        return fn

    def _make_exec(self, bucket: int, op: SparseOperator):
        """Lower ONE bucket's executable for ``op`` without touching engine
        state — besides backing ``_exec``'s lazy path, this is how a retune
        thread prewarms a staged table (build the fn, call it once with
        zeros, then ``hot_swap(ops, execs=...)`` so the serving thread never
        pays the lowering).
        """
        if self.mesh is not None:
            # The mesh runner places its RHS across devices before its own
            # jitted shard_map program runs, so only the slab assembly
            # lowers here; the expensive collective program is compiled
            # once per bucket and donates the engine-owned slab.
            run = _bind_runner(
                self.a, op.plan.candidate, op._prep, k=op.plan.k,
                mesh=self.mesh, axis=self.axis, donate_rhs=True,
            )
            asm = fused_batch_executable(
                None, bucket=bucket, name=f"engine_mesh_slab_k{bucket}"
            )

            def fn(*xs, _asm=asm, _run=run):
                return _run(_asm(*xs))

            return finite_guard(fn) if self.nan_guard else fn
        return fused_batch_executable(
            op._run, bucket=bucket, guard=self.nan_guard,
            name=f"engine_k{bucket}",
        )

    # -- retirement ---------------------------------------------------------
    def _retire_one(self) -> int:
        """Await the oldest in-flight batch; fill its futures + stats.
        A batch that failed on device (or flagged non-finite output) goes
        through :meth:`_recover` instead of filling futures."""
        ys, ok, reqs, bucket, take, batch = self._inflight.popleft()
        with TraceAnnotation("engine.retire", batch=batch):
            exc: Exception | None = None
            try:
                with TraceAnnotation("engine.device_wait"):
                    ys.block_until_ready()
                if ok is not None and not bool(ok):
                    exc = self._nonfinite(bucket)
            except Exception as e:  # device-side failure surfaces at the block
                exc = e
            if exc is not None:
                return self._recover(reqs, bucket, take, exc, batch)
            t_done = time.perf_counter()
            lats = []
            for i, req in enumerate(reqs):
                req._ys = ys
                req._col = i
                req.t_done = t_done
                req.bucket = bucket
                lats.append(t_done - req.t_submit)
            self.stats.record(bucket, take, lats)
            self.consecutive_failures = 0
            self._notify()  # futures resolved: wake callers blocked in result()
            return take

    def _nonfinite(self, bucket) -> NonFiniteOutput:
        return NonFiniteOutput(
            f"bucket {bucket} batch produced non-finite outputs "
            f"(engine {self.name or 'unnamed'}; nan_guard flagged it on "
            "device)"
        )

    # -- supervision: retry -> demote -> fail-the-futures -------------------
    def _recover(self, reqs: list, bucket, take: int, exc: Exception,
                 batch: int) -> int:
        """Serve a failed batch through the supervision policy.

        Retries the current tier up to ``max_retries`` times with capped
        backoff (operands re-assembled from the requests each attempt, so a
        poisoned slab is never reused), then demotes the bucket down the
        fallback chain and retries there; when the chain is exhausted every
        future fails via ``set_exception`` — the no-hung-futures guarantee.
        Runs synchronously on the serving thread AFTER older batches
        retired, so FIFO retirement order and bitwise results of unaffected
        batches are untouched.
        """
        with TraceAnnotation("engine.recover", batch=batch):
            sup = self.supervisor
            sup.record(
                "batch_failed", engine=self.name, bucket=bucket, error=repr(exc)
            )
            last: Exception = exc
            attempt = 0
            budget = sup.max_retries  # retries left on the current tier
            while True:
                if budget <= 0:
                    if not self._demote(bucket, last):
                        break  # chain exhausted
                    budget = 1 + sup.max_retries  # fresh budget for the new tier
                budget -= 1
                sup.sleep(sup.backoff(attempt))
                attempt += 1
                self.stats.retries += 1
                sup.retries += 1
                try:
                    with TraceAnnotation("engine.launch", batch=batch,
                                         bucket=bucket, take=take):
                        ys, ok = self._launch(bucket, reqs)
                    with TraceAnnotation("engine.device_wait"):
                        ys.block_until_ready()
                    if ok is not None and not bool(ok):
                        raise self._nonfinite(bucket)
                except Exception as e:
                    last = e
                    continue
                t_done = time.perf_counter()
                lats = []
                for i, req in enumerate(reqs):
                    req._ys = ys
                    req._col = i
                    req.t_done = t_done
                    req.bucket = bucket
                    lats.append(t_done - req.t_submit)
                self.stats.record(bucket, take, lats)
                self.consecutive_failures = 0
                self._notify()
                return take
            for req in reqs:
                req.bucket = bucket
                req.set_exception(last)
            self.stats.failed_batches += 1
            self.stats.failed_requests += take
            self.consecutive_failures += 1
            sup.failures += 1
            sup.record(
                "batch_abandoned", engine=self.name, bucket=bucket,
                n_requests=take, error=repr(last),
            )
            return take

    def _demote(self, bucket, exc: Exception) -> bool:
        """Install the next fallback tier for one bucket; False when the
        chain is exhausted.  The tuned (op, exec) is saved the first time
        so the repair thread can probe and re-promote it."""
        if self.legacy_dispatch:
            return False  # the baseline path has no executable table to swap
        level = self._demoted.get(bucket, 0)
        while level < len(FALLBACK_TIERS):
            level += 1
            try:
                tier, op = fallback_op(self.a, bucket, level)
            except Exception:
                continue  # this tier can't build here (e.g. its prepare
                # failed too); try the next one down
            if bucket not in self._demote_saved:
                if isinstance(bucket, tuple):
                    saved = (
                        self._sparse_ops.get(bucket[1]),
                        self._sparse_execs.get(bucket[1]),
                    )
                else:
                    saved = (self.ops.get(bucket), self._execs.get(bucket))
                self._demote_saved[bucket] = saved
            if isinstance(bucket, tuple):
                fn = op._run
                if self.nan_guard:
                    fn = finite_guard(fn)
                self._sparse_ops[bucket[1]] = op
                self._sparse_execs[bucket[1]] = fn
            else:
                # Always a single-device fused executable: a mesh bucket
                # degrades to unsharded serving (correct, slower) because
                # from_candidate tiers are single-device by construction.
                fn = fused_batch_executable(
                    op._run, bucket=bucket, guard=self.nan_guard,
                    name=f"engine_k{bucket}_fallback{level}",
                )
                self.ops[bucket] = op
                self._execs[bucket] = fn
            self._demoted[bucket] = level
            self.stats.demotions += 1
            self.supervisor.demotions += 1
            self.supervisor.record(
                "demote", engine=self.name, bucket=bucket, tier=tier,
                level=level, error=repr(exc),
            )
            self._start_repair()
            return True
        return False

    # -- background repair: probe the tuned exec, re-promote via hot_swap ---
    def _start_repair(self) -> None:
        with self._repair_lock:
            t = self._repair_thread
            if t is not None and t.is_alive():
                return
            self._repair_stop.clear()
            t = threading.Thread(
                target=self._repair_worker, name="engine-repair", daemon=True
            )
            self._repair_thread = t
            t.start()

    def _repair_worker(self) -> None:
        """Probe each demoted bucket's saved tuned executable off the hot
        path; on a clean probe, stage the tuned plan back in through
        ``hot_swap`` (the serving thread adopts it at its next dispatch
        boundary — the same semantics as a retune swap).  Exits when no
        demotions remain; a later demotion starts a fresh thread."""
        interval = self.supervisor.repair_interval_s
        while not self._repair_stop.wait(interval):
            if not self._demoted:
                return
            if (
                self._brownout is not None
                and self._brownout.state != HEALTHY
            ):
                # Browned out: repair probes are device work stolen from
                # serving — stay demoted (correct, slower) until recovery.
                continue
            for bucket in [b for b in list(self._demoted)
                           if not isinstance(b, tuple)]:
                saved = self._demote_saved.get(bucket)
                if saved is None or saved[0] is None:
                    continue  # injected/shard tables: nothing to restore
                op, fn = saved
                try:
                    if fn is None:
                        fn = self._make_exec(bucket, op)
                        self._demote_saved[bucket] = (op, fn)
                    faults = self.faults
                    if faults is not None:
                        faults.fire("engine.dispatch", engine=self.name,
                                    bucket=bucket, probe=True)
                        if faults.should_fire("engine.nan", engine=self.name,
                                              bucket=bucket, probe=True):
                            raise InjectedFault(
                                "injected nan at repair probe"
                            )
                    out = fn(*([self._zero] * bucket))
                    ys = out[0] if isinstance(out, tuple) else out
                    jax.block_until_ready(ys)
                    if not bool(jnp.isfinite(ys).all()):
                        raise self._nonfinite(bucket)
                except Exception:
                    continue  # still sick; probe again next interval
                self._promote(bucket, op, fn)

    def _promote(self, bucket: int, op: SparseOperator, fn) -> None:
        """Stage the healed tuned plan back via ``hot_swap``.  Note the
        swap replaces the whole table from a snapshot: a bucket demoted
        between staging and adoption briefly reverts to its tuned exec and
        simply re-recovers on its next failure."""
        if not all(int(k) in self.ops for k in self.ks):
            return  # shard-mode table: nothing to swap through
        ops = {int(k): self.ops[int(k)] for k in self.ks}
        ops[bucket] = op
        execs = dict(self._execs)
        execs[bucket] = fn
        try:
            self.hot_swap(ops, execs=execs)
        except Exception:
            return
        self._demoted.pop(bucket, None)
        self._demote_saved.pop(bucket, None)
        self.stats.promotions += 1
        self.supervisor.promotions += 1
        self.supervisor.record("promote", engine=self.name, bucket=bucket)

    def _retire_ready(self) -> None:
        """Retire in-flight batches whose results are already materialized.

        Called at idle points (empty queue, admission-gate holds) so a
        future resolves — and its latency is stamped — as soon as the
        caller could actually consume the result, instead of waiting for
        the window to fill or an explicit flush.  Never blocks: FIFO order
        stops at the first batch still computing.
        """
        while self._inflight and self._inflight[0][0].is_ready():
            self._retire_one()

    def flush(self) -> int:
        """Retire every in-flight batch; returns #requests completed."""
        served = 0
        while self._inflight:
            served += self._retire_one()
        return served

    def _notify(self) -> None:
        """Wake every thread blocked in ``result()`` or a ``block``-policy
        ``submit()`` — called whenever a future resolves or queue space
        frees, so waiters sleep on a :class:`threading.Condition` instead
        of burning CPU in a poll loop."""
        with self._cond:
            self._cond.notify_all()

    def _fulfill(self, req: EngineRequest, deadline: float | None = None) -> None:
        """Serve until ``req`` is done (the blocking half of its future).

        One caller at a time elects itself the *driver* (non-blocking
        ``_serve_lock``) and serves the engine; every other blocked caller
        sleeps on the engine condition and is woken by :meth:`_notify`
        when futures resolve — no thread sleep-polls.

        The driver retires the in-flight window FIRST: a request whose
        batch is already on device resolves without force-dispatching
        unrelated queued requests past the ``max_wait_s`` admission gate.
        Only when ``req`` is still queued does the loop force dispatch —
        the caller blocking on it overrides the gate for the queue ahead
        of it.

        ``deadline`` (perf_counter time) bounds the wait: past it, a still
        unresolved request raises ``TimeoutError`` with its bucket/engine
        context instead of blocking forever on a wedged batch.
        """
        while not req.done:
            now = time.perf_counter()
            if deadline is not None and now >= deadline:
                raise TimeoutError(
                    f"request {req.rid} (bucket={req.bucket}, engine="
                    f"{self.name or 'unnamed'}) unresolved at timeout: "
                    f"{self.pending} queued, {self.in_flight} in flight "
                    "— the supervisor fails dead batches via "
                    "set_exception, so a persistent timeout usually "
                    "means nothing is driving step()"
                )
            if not self._serve_lock.acquire(blocking=False):
                # Another thread is already driving the engine: wait for
                # its progress notification (bounded, so a deadline stays
                # honored even if the driver wedges), then re-check.
                with self._cond:
                    if not req.done:
                        t = _WAIT_QUANTUM_S
                        if deadline is not None:
                            t = min(t, max(0.0, deadline - now))
                        self._cond.wait(timeout=t)
                continue
            try:
                if req.done:
                    break
                if (
                    deadline is not None
                    and self._inflight
                    and not self._inflight[0][0].is_ready()
                ):
                    # Head batch still computing under a bounded wait: a
                    # condition wait (woken early by any retire) replaces
                    # the old 1 ms sleep-poll, honoring the deadline even
                    # when the batch never becomes ready.
                    with self._cond:
                        self._cond.wait(
                            timeout=min(
                                _WAIT_QUANTUM_S,
                                max(0.0, deadline - now),
                            )
                        )
                    continue
                if self._inflight:
                    self._retire_one()
                    continue
                if self.step(force=True) == 0:
                    if req.done:  # step's idle-path retire served it
                        break
                    raise RuntimeError(
                        "request is not pending on this engine"
                    )
            finally:
                self._serve_lock.release()

    # -- legacy (pre-hot-path) dispatch: fig15's measured baseline ----------
    def _step_legacy(self, reqs, bucket: int, take: int) -> int:
        if bucket == 1:
            ys = self._dispatch_one(reqs[0].x)  # (m,)
        else:
            cols = [r.x for r in reqs] + [self._zero] * (bucket - take)
            ys = self._batched_fn(bucket)(cols)
        ys = jax.block_until_ready(ys)

        t_done = time.perf_counter()
        for i, req in enumerate(reqs):
            req._ys = ys
            req._col = i
            req.t_done = t_done
            req.bucket = bucket
        self.stats.record(bucket, take, (r.latency_s for r in reqs))
        self._notify()
        return take

    def _dispatch_one(self, x: jax.Array) -> jax.Array:
        if self.mesh is None and self.n_shards > 1:
            ys = stacked_spmm(self._stacked, x[:, None])
            return assemble_rows(ys, self._shard_rows)[:, 0]
        return self.ops[1] @ x

    def _batched_fn(self, bucket: int):
        """Legacy per-bucket dispatch: eager list -> jitted stack + kernel.

        The pre-hot-path fused program: the column stack, zero-padding and
        the plan's kernel compile into one XLA program, but every call
        re-flattens the Python list of columns and the prepared dict, and
        the caller blocks per batch.  Kept as the measured baseline for
        ``benchmarks/fig15_dispatch.py``.
        """
        fn = self._batch_fns.get(bucket)
        if fn is None:
            if self.mesh is None and self.n_shards > 1:
                stacked, rows = self._stacked, self._shard_rows

                def raw(cols):
                    ys = stacked_spmm(stacked, jnp.stack(cols, axis=1))
                    return assemble_rows(ys, rows)
            else:
                run = self.ops[bucket]._run  # plan kernel / shard_map runner

                def raw(cols):
                    return run(jnp.stack(cols, axis=1))

            # Mesh runners place + jit internally (the stack stays eager);
            # the single-device paths fuse stack+pad+kernel into one jit.
            fn = self._batch_fns[bucket] = (
                raw if self.mesh is not None else jax.jit(raw)
            )
        return fn

    # -- bulk serving -------------------------------------------------------
    def drain(self) -> int:
        """Dispatch until the queue is empty, then retire every in-flight
        batch; returns #requests served.

        Draining is an explicit flush: it bypasses the ``max_wait_s``
        admission gate (the caller has decided no more requests are coming).
        The count covers every request retired during the call — including
        batches that were already in flight when drain() was entered.
        """
        before = self.stats.occupied_cols  # incremented per retired request
        while self.step(force=True):
            pass
        self.flush()
        return self.stats.occupied_cols - before

    def run(self, xs: Iterable[jax.Array]) -> list[jax.Array]:
        """Convenience: submit all, drain, return results in submit order.

        A bounded engine (``max_queue`` + ``reject``, or a brownout in
        SHED) refuses admission with :class:`OverloadError`; since run()
        owns the serving loop anyway, it absorbs the backpressure itself —
        drain a batch (or wait out a shedding brownout) and resubmit —
        instead of surfacing the refusal to a caller with no queue to
        manage.
        """
        reqs = []
        for x in xs:
            while True:
                try:
                    reqs.append(self.submit(x))
                    break
                except OverloadError:
                    if self.step(force=True) == 0:
                        self.flush()
                        time.sleep(1e-3)  # shedding brownout: wait it out
        self.drain()
        return [r.y for r in reqs]

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        plans = {k: op.plan.candidate.key() for k, op in self.ops.items()}
        return (
            f"SparseEngine({self.shape[0]}x{self.shape[1]}, nnz={self.a.nnz}, "
            f"buckets={plans}, shards={self.n_shards}, "
            f"async_depth={self.async_depth})"
        )
