"""Plans and the JSON plan cache.

A :class:`Plan` is the durable result of one measured search: which
(format, impl, params) won for one matrix structure, with enough bookkeeping
to audit the decision (estimated cost, measured time, how many candidates
were enumerated vs actually timed).

The cache key is a *structure fingerprint* — sha256 over shape, dtype and
the indptr/indices byte streams.  Values are deliberately excluded: the
paper's phenomena (UCLD, fill ratio, row-length dispersion) depend only on
the pattern, so two matrices with the same pattern share the optimal plan
and a value update (e.g. a new timestep of the same mesh) hits the cache.

Plans additionally record *where* they were measured: the jax backend
("cpu"/"tpu"/...) and the problem scale (m, n, nnz).  A plan is a point
measurement — the candidate that wins on one backend or at one size loses
at another (interpret-mode Pallas on CPU vs MXU tiles on TPU is the extreme
case) — so ``PlanCache.get`` treats a backend or scale mismatch as a cache
miss and the caller re-searches.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import tempfile
import threading
import time
import warnings
from pathlib import Path
from typing import Any, Iterable

try:  # POSIX advisory locks for the shared on-disk cache (see PlanCache.put)
    import fcntl
except ImportError:  # pragma: no cover - non-POSIX: fall back to merge-only
    fcntl = None

import numpy as np

from repro.core.formats import CSRMatrix

from .candidates import Candidate, make

__all__ = ["PLAN_VERSION", "Plan", "PlanCache", "fingerprint", "default_cache"]

# v4: the merge tier joined the candidate space and CSR prepared dicts carry
# the hoisted row map — v3 plans were picked from a smaller space against a
# slower baseline, so they are dropped and re-searched rather than served.
# v5: the solver-step kind joined the space with a fused byte model (the
# dispatch constant amortizes over a while_loop's iterations and axpy/dot
# traffic enters the estimate), which moves the crossover pruning sees for
# every kind sharing the model's constants — pre-v5 plans are re-searched.
# v6: the spmspv tier (sparse RHS) joined the space and features grew the
# x-density axis that its byte branch ranks on — the dense tiers now pay a
# densify term under sparse-RHS kinds, so what an old plan would have
# picked changes; pre-v6 plans are dropped at load and re-searched.
# v7: lane-row SELL (sell_lr/pallas) joined the k = 1 space, with its own
# branch of the byte model; a v6 plan never measured it, so it is dropped
# at load and re-searched.
PLAN_VERSION = 7

_ENV_CACHE = "REPRO_TUNE_CACHE"
_DEFAULT_CACHE = "~/.cache/repro_tune/plans.json"

# Paths that already emitted a corrupt-cache warning this process — the
# condition is sticky on disk (the torn file was moved aside), so repeating
# the warning per PlanCache instance is noise.
_QUARANTINE_WARNED: set[str] = set()
_QUARANTINE_LOCK = threading.Lock()


def fingerprint(a: CSRMatrix) -> str:
    """Structure-only fingerprint: shape + dtype + indptr/indices bytes."""
    h = hashlib.sha256()
    h.update(repr((tuple(a.shape), a.nnz, str(a.data.dtype))).encode())
    h.update(np.ascontiguousarray(a.indptr).tobytes())
    h.update(np.ascontiguousarray(a.indices).tobytes())
    return h.hexdigest()[:16]


@dataclasses.dataclass
class Plan:
    fingerprint: str
    kind: str  # "spmv" | "spmm"
    fmt: str
    impl: str
    params: dict[str, Any]
    est_cost: float
    measured_s: float
    n_candidates: int  # enumerated
    n_measured: int  # survived pruning and were timed
    k: int = 1  # dense-operand width (1 for spmv)
    backend: str = ""  # jax backend the timings were taken on ("" = unknown)
    scale: list = dataclasses.field(default_factory=list)  # [m, n, nnz]
    # Search-cost bookkeeping: survivors abandoned by candidate racing (their
    # first timed rep already exceeded RACE_FACTOR x the best median), i.e.
    # timed once instead of the full rep count.  Audit-only — it never enters
    # cache matching, so the field is schema-additive (no version bump).
    n_raced: int = 0
    # Device-mesh topology the plan was measured on ([] = single device).
    # A collective-schedule plan tuned at one shard count is meaningless at
    # another — the allgather/ring crossover moves with P — so a topology
    # change is a miss, same as backend/scale.
    mesh_shape: list = dataclasses.field(default_factory=list)
    # Structural features of the fingerprinted matrix at search time
    # (MatrixFeatures.to_dict()).  Persisting them turns the plan cache into
    # a labelled (features -> winning plan) dataset that tune.predict
    # nearest-neighbors over for transfer tuning.  Schema-additive: absent
    # in pre-PR-7 entries (loads as None, the entry is simply not usable as
    # a training point) and never consulted by cache matching, so no
    # PLAN_VERSION bump — it changes no picks.
    features: dict | None = None
    # "" for measured plans.  A *predicted* plan (SparseOperator.
    # build_predicted) records where its candidate came from: the neighbor
    # fingerprint it transferred from, or "byte_model" for the argmin
    # fallback.  Predicted plans are never persisted — only measured search
    # results enter the cache — so on cached entries this is always "".
    predicted_from: str = ""
    # Candidates the measured search lost to an exception (compile refusal,
    # OOM, a broken kernel path): candidate key -> error text.  A search
    # that silently dropped a tier would report a winner that only won by
    # default; recording the loss lets callers see it and refuse it.
    # Schema-additive (empty in older entries), never used for matching.
    failed: dict = dataclasses.field(default_factory=dict)
    version: int = PLAN_VERSION

    def matches(
        self,
        backend: str | None,
        scale: Iterable[int] | None,
        mesh_shape: Iterable[int] | None = None,
    ) -> bool:
        """True when this plan's measurement context covers the request.

        An empty recorded backend/scale (legacy or hand-written plans) never
        matches a concrete request: point measurements must not be trusted
        outside the context they were taken in.  ``mesh_shape`` is always
        checked: None/() means the single-device context, so a mesh plan
        never leaks into single-device serving or vice versa.
        """
        if backend is not None and self.backend != backend:
            return False
        if scale is not None and list(self.scale) != [int(s) for s in scale]:
            return False
        if [int(s) for s in self.mesh_shape] != [
            int(s) for s in (mesh_shape or ())
        ]:
            return False
        return True

    @property
    def candidate(self) -> Candidate:
        return make(self.fmt, self.impl, **self.params)

    def to_json(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict[str, Any]) -> "Plan":
        return cls(**d)


class PlanCache:
    """In-memory plan store with optional JSON persistence.

    ``PlanCache()`` is memory-only (one process); ``PlanCache(path)`` loads
    the JSON file if present and rewrites it atomically on every put.
    """

    def __init__(
        self,
        path: str | os.PathLike | None = None,
        *,
        faults: Any = None,
    ):
        self.path = Path(path).expanduser() if path else None
        self._faults = faults
        self._plans: dict[str, dict] = self._load_resident()

    def _read_text(self) -> str:
        """The cache file's text, through the fault plan's torn-read site
        (``plan_cache.read`` truncates at a seeded offset — the
        kill-mid-write failure mode)."""
        text = self.path.read_text()
        faults = self._faults
        if faults is None:
            from repro.runtime.faults import active_plan

            faults = active_plan()
        if faults is not None:
            text = faults.corrupt_text(
                "plan_cache.read", text, path=str(self.path)
            )
        return text

    def _load_resident(self) -> dict[str, dict]:
        """Load the on-disk table; a torn/corrupt file is QUARANTINED.

        A cache that fails to parse (kill mid-write on a filesystem without
        atomic replace, disk corruption, a hand edit gone wrong) must not
        crash serving — but silently reusing its path would also let the
        next atomic ``put`` overwrite the evidence.  The broken file is
        moved aside to ``<path>.corrupt-<millis>`` (preserved for
        inspection), one warning names it, and the table starts empty —
        every plan is then re-searched, which is slow and correct.
        """
        if self.path is None or not self.path.exists():
            return {}
        try:
            return self._current(json.loads(self._read_text()))
        except (json.JSONDecodeError, OSError) as exc:
            self._quarantine(exc)
            return {}

    def _quarantine(self, exc: Exception) -> None:
        try:
            dest = f"{self.path}.corrupt-{int(time.time() * 1000)}"
            os.replace(self.path, dest)
        except OSError:  # racing process already moved it, or FS refused
            dest = None
        with _QUARANTINE_LOCK:
            first = str(self.path) not in _QUARANTINE_WARNED
            _QUARANTINE_WARNED.add(str(self.path))
        if first:
            warnings.warn(
                f"plan cache {self.path} is corrupt ({exc!r}); "
                + (
                    f"quarantined to {dest}"
                    if dest
                    else "quarantine rename failed"
                )
                + " — starting with an empty table (plans will re-search)",
                RuntimeWarning,
                stacklevel=3,
            )

    @staticmethod
    def _current(plans: Any) -> dict[str, dict]:
        """Drop entries from other PLAN_VERSIONs (and malformed ones).

        A version bump means the schema or its semantics changed; old
        entries are dead weight that must neither be served nor crash the
        load (v2 files predate ``mesh_shape``, for example).
        """
        if not isinstance(plans, dict):
            return {}
        return {
            key: d
            for key, d in plans.items()
            if isinstance(d, dict) and d.get("version") == PLAN_VERSION
        }

    @staticmethod
    def _key(fp: str, kind: str, k: int = 1,
             mesh_shape: Iterable[int] = ()) -> str:
        base = f"{fp}:{kind}:k{k}"
        mesh = "x".join(str(int(s)) for s in mesh_shape or ())
        return f"{base}:mesh{mesh}" if mesh else base

    def __len__(self) -> int:
        return len(self._plans)

    def get(
        self,
        fp: str,
        kind: str,
        k: int = 1,
        *,
        backend: str | None = None,
        scale: Iterable[int] | None = None,
        mesh_shape: Iterable[int] | None = None,
    ) -> Plan | None:
        """Fetch a plan; backend/scale/topology mismatches invalidate.

        Passing ``backend``/``scale`` asserts the caller's measurement
        context; a cached plan taken on a different backend or at a
        different (m, n, nnz) is a stale point-measurement and is treated
        as a miss so the caller re-searches.  ``mesh_shape`` keys mesh
        plans separately per topology: the same fingerprint at a different
        shard count is a miss (and never shadows the single-device plan).
        """
        # _current() filtered stale versions at load/merge time, so any
        # entry present here is already PLAN_VERSION.
        d = self._plans.get(self._key(fp, kind, k, mesh_shape or ()))
        if d is None:
            return None
        try:
            plan = Plan.from_json(d)
        except TypeError:
            # Entry shape drifted (hand edit, or a field change without a
            # version bump): treat as a miss, never crash.
            return None
        if not plan.matches(backend, scale, mesh_shape):
            return None
        return plan

    def plans(self) -> list[Plan]:
        """Every well-formed resident plan — the predictor's training set.

        Entries whose shape drifted (hand edits, foreign schemas) are
        skipped, mirroring ``get``'s treat-as-miss discipline.
        """
        out = []
        for d in self._plans.values():
            try:
                out.append(Plan.from_json(d))
            except TypeError:
                continue
        return out

    @contextlib.contextmanager
    def _write_lock(self):
        """Exclusive advisory lock over the cache file's sidecar ``.lock``.

        Merge-then-replace alone leaves a read→replace window in which a
        second engine tuning the same (or another) matrix can persist a plan
        that our replace then clobbers.  Holding the lock across the whole
        read-merge-write-replace closes that window for every cooperating
        process; on platforms without fcntl the merge-only behavior remains
        (last replace wins ties, nothing corrupts).
        """
        if fcntl is None or self.path is None:
            yield
            return
        lock_path = self.path.with_name(self.path.name + ".lock")
        with open(lock_path, "w") as lock_f:
            fcntl.flock(lock_f, fcntl.LOCK_EX)
            try:
                yield
            finally:
                fcntl.flock(lock_f, fcntl.LOCK_UN)

    def put(self, plan: Plan) -> None:
        key = self._key(plan.fingerprint, plan.kind, plan.k, plan.mesh_shape)
        self._plans[key] = plan.to_json()
        if self.path is not None:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            # Merge-then-replace, under an advisory lock, so concurrent
            # processes sharing the file don't clobber plans persisted since
            # our load (ours win ties).  The write itself is an atomic
            # tmp-file + os.replace — a reader never observes a torn file.
            # Stale-version entries on disk are dropped, not carried along.
            with self._write_lock():
                try:
                    on_disk = self._current(json.loads(self._read_text()))
                    self._plans = {**on_disk, **self._plans}
                except FileNotFoundError:
                    pass  # nothing persisted yet: first writer
                except (json.JSONDecodeError, OSError) as exc:
                    # A torn on-disk file must not merge (it would parse to
                    # nothing and our replace would destroy the evidence):
                    # quarantine it exactly like the load path, then write
                    # the resident table fresh.
                    self._quarantine(exc)
                fd, tmp = tempfile.mkstemp(
                    dir=self.path.parent, prefix=self.path.name, suffix=".tmp"
                )
                try:
                    with os.fdopen(fd, "w") as f:
                        json.dump(self._plans, f, indent=1, sort_keys=True)
                    os.replace(tmp, self.path)
                except BaseException:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
                    raise


_default: PlanCache | None = None


def default_cache() -> PlanCache:
    """Process-wide cache at $REPRO_TUNE_CACHE or ~/.cache/repro_tune/."""
    global _default
    if _default is None:
        _default = PlanCache(os.environ.get(_ENV_CACHE, _DEFAULT_CACHE))
    return _default
