"""Chaos-hardening: fault injection, failure-propagating futures, degraded
dispatch, plan-cache quarantine, breaker/retune surfacing, solver supervision.

Every fault here is INJECTED through runtime.faults (deterministic, logged);
the assertions are about policy: futures always resolve (result or
exception), degradation preserves correctness, repair re-promotes, and one
tenant's storm never hangs another's requests."""
import glob
import json
import threading
import time
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import csr_from_dense
from repro.runtime.engine import SparseEngine
from repro.runtime.faults import FaultPlan, InjectedFault, set_active
from repro.runtime.fleet import CircuitOpenError, SparseFleet
from repro.runtime.solver import SparseSolver
from repro.runtime.supervisor import Supervisor
from repro.tune import PlanCache, SparseOperator
from repro.tune.plan import Plan

# Zero backoff + fast repair: the tests exercise policy, not pacing.
SUP_KW = dict(backoff_base_s=0.0, backoff_cap_s=0.0, repair_interval_s=0.005)


def small(seed=0, m=128, density=0.06):
    rng = np.random.default_rng(seed)
    d = ((rng.random((m, m)) < density) * rng.standard_normal((m, m))).astype(
        np.float32
    )
    return d, csr_from_dense(d)


def engine(a, ks=(1, 4, 16), cache=None, **kw):
    cache = cache if cache is not None else PlanCache()
    return SparseEngine(a, ks=ks, cache=cache, warmup=0, timed=1, **kw)


def xs_for(a, count, seed=1):
    rng = np.random.default_rng(seed)
    return [
        rng.standard_normal(a.shape[1]).astype(np.float32)
        for _ in range(count)
    ]


# -- FaultPlan ---------------------------------------------------------------
def test_fault_plan_spec_parse_match_and_log():
    plan = FaultPlan("engine.dispatch:n=2:engine=bad;plan_cache.read:p=0.5;seed=9")
    assert plan.seed == 9
    # Context mismatch never fires and never consumes the armed count.
    assert not plan.should_fire("engine.dispatch", engine="good")
    assert plan.should_fire("engine.dispatch", engine="bad")
    with pytest.raises(InjectedFault, match="engine.dispatch"):
        plan.fire("engine.dispatch", engine="bad")
    assert not plan.should_fire("engine.dispatch", engine="bad")  # n spent
    assert plan.fired("engine.dispatch") == 2 and plan.fired() == 2
    assert [e.seq for e in plan.log] == [0, 1]
    # Unarmed sites are free; fire() with a custom type raises that type.
    assert not plan.should_fire("engine.nan")
    one_shot = FaultPlan({"prepare.oom": {"n": 1}})
    with pytest.raises(MemoryError):
        one_shot.fire("prepare.oom", exc=MemoryError)
    # corrupt_text tears strictly inside the text, deterministically per seed.
    torn = FaultPlan({"plan_cache.read": {"n": 1}}, seed=3)
    text = "x" * 100
    out = torn.corrupt_text("plan_cache.read", text)
    assert 1 <= len(out) < len(text) and text.startswith(out)
    with pytest.raises(ValueError, match="plan option"):
        FaultPlan("bogus=1")
    with pytest.raises(ValueError, match="malformed"):
        FaultPlan("engine.dispatch:n")


# -- PlanCache quarantine ----------------------------------------------------
def test_torn_plan_cache_quarantined_at_many_offsets(tmp_path):
    d, a = small(seed=1, m=64)
    src = tmp_path / "seed" / "plans.json"
    SparseOperator.build(a, cache=PlanCache(src), warmup=0, timed=1)
    text = src.read_text()
    for i, frac in enumerate((0.01, 0.3, 0.6, 0.99)):
        path = tmp_path / f"tear{i}" / "plans.json"
        path.parent.mkdir()
        path.write_text(text[: max(1, int(frac * len(text)))])
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            cache = PlanCache(path)
        assert len(cache) == 0  # empty table, never a crash
        assert not path.exists()  # moved aside, not overwritten in place
        corrupt = glob.glob(f"{path}.corrupt-*")
        assert len(corrupt) == 1
        assert any("quarantined" in str(w.message) for w in caught)
        # The quarantined bytes are the torn file, preserved for inspection.
        assert open(corrupt[0]).read() == text[: max(1, int(frac * len(text)))]
        # put() works on the quarantined path: a fresh file appears.
        SparseOperator.build(a, cache=cache, warmup=0, timed=1)
        assert len(PlanCache(path)) >= 1
        json.loads(path.read_text())  # and it is valid JSON again


def test_torn_read_on_put_merge_path_quarantines(tmp_path):
    d, a = small(seed=2, m=64)
    path = tmp_path / "plans.json"
    cache = PlanCache(path, faults=FaultPlan({"plan_cache.read": {"n": 1}}))
    # Init saw no file (no fire consumed: the site only tears reads of an
    # existing file), so the first build's put() merge read is the torn one.
    assert cache._faults.fired("plan_cache.read") == 0
    path.write_text(json.dumps({"not": "valid plan schema"}) + "{{{")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        SparseOperator.build(a, cache=cache, warmup=0, timed=1)
    assert any("quarantined" in str(w.message) for w in caught)
    assert glob.glob(f"{path}.corrupt-*")
    assert len(PlanCache(path)) >= 1  # resident table written fresh


def test_plan_cache_concurrent_writer_fuzz(tmp_path):
    path = tmp_path / "plans.json"
    n_threads, per_thread = 6, 5
    errors = []

    def plan_for(t, j):
        return Plan(
            fingerprint=f"fp{t}_{j}", kind="spmv", fmt="csr", impl="vector",
            params={}, est_cost=1.0, measured_s=1.0, n_candidates=1,
            n_measured=1, backend="cpu", scale=[8, 8, 8],
        )

    def writer(t):
        try:
            cache = PlanCache(path)
            for j in range(per_thread):
                cache.put(plan_for(t, j))
        except Exception as exc:  # pragma: no cover - the failure signal
            errors.append(exc)

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    final = PlanCache(path)  # parses: no torn interleaving survived
    assert len(final) == n_threads * per_thread  # every writer's plans merged
    for t in range(n_threads):
        for j in range(per_thread):
            assert final.get(f"fp{t}_{j}", "spmv", backend="cpu",
                             scale=[8, 8, 8]) is not None


# -- engine supervision ------------------------------------------------------
def test_injected_dispatch_failure_fails_futures_fifo_for_survivors():
    d, a = small(seed=3)
    plan = FaultPlan({"engine.dispatch": {"n": 3}})
    # No repair probe during the storm: a probe fires the same site, and
    # one that ran between two demotions would take one of the 3 fires.
    sup = Supervisor(max_retries=0, **{**SUP_KW, "repair_interval_s": 60.0})
    eng = engine(a, ks=(4,), faults=plan, supervisor=sup)
    xs = xs_for(a, 8)
    reqs = [eng.submit(x) for x in xs]
    eng.drain()
    # Batch 1 ate the whole chain (tuned, csr/vector, sell/ref: 3 fires).
    for r in reqs[:4]:
        assert r.done and r.failed
        with pytest.raises(InjectedFault):
            r.result()
    # Batch 2 after the storm serves correctly — FIFO held for survivors.
    for r, x in zip(reqs[4:], xs[4:]):
        assert r.done and not r.failed
        np.testing.assert_allclose(np.asarray(r.result()), d @ x, atol=2e-3)
    assert eng.stats.failed_requests == 4 and eng.stats.failed_batches == 1
    assert eng.stats.demotions == 2
    assert plan.fired("engine.dispatch") == 3
    eng.close()


def test_retry_budget_recovers_without_demotion():
    d, a = small(seed=4)
    plan = FaultPlan({"engine.dispatch": {"n": 2}})
    eng = engine(a, ks=(4,), faults=plan,
                 supervisor=Supervisor(max_retries=2, **SUP_KW))
    xs = xs_for(a, 4)
    reqs = [eng.submit(x) for x in xs]
    eng.drain()
    for r, x in zip(reqs, xs):
        np.testing.assert_allclose(np.asarray(r.result()), d @ x, atol=2e-3)
    assert eng.stats.retries == 2 and eng.stats.demotions == 0
    assert eng.stats.failed_requests == 0
    eng.close()


def test_nan_guard_demotes_recovers_and_repromotes():
    d, a = small(seed=5)
    plan = FaultPlan({"engine.nan": {"n": 2}})
    eng = engine(a, ks=(1, 4), faults=plan, nan_guard=True,
                 supervisor=Supervisor(max_retries=0, **SUP_KW))
    xs = xs_for(a, 4)
    reqs = [eng.submit(x) for x in xs]
    eng.drain()
    # Poisoned slab caught on device twice -> recovered on sell/ref.
    for r, x in zip(reqs, xs):
        assert not r.failed
        np.testing.assert_allclose(np.asarray(r.result()), d @ x, atol=2e-3)
    assert eng.stats.demotions == 2
    # Background repair probes the saved tuned executable and re-promotes.
    deadline = time.perf_counter() + 30.0
    while eng.supervisor.promotions < 1 and time.perf_counter() < deadline:
        time.sleep(0.005)
    assert eng.supervisor.promotions >= 1, "repair never re-promoted"
    # The staged table is adopted at the next dispatch boundary.
    reqs2 = [eng.submit(x) for x in xs]
    eng.drain()
    for r, x in zip(reqs2, xs):
        np.testing.assert_allclose(np.asarray(r.result()), d @ x, atol=2e-3)
    assert eng.swaps_applied >= 1
    eng.close()


class _NeverReady:
    def is_ready(self):
        return False


def test_result_timeout_raises_with_context():
    d, a = small(seed=6)
    eng = engine(a, ks=(4,), name="stuck")
    req = eng.submit(xs_for(a, 1)[0])
    # Wedge the engine: the head in-flight batch never becomes ready.
    eng._queue.clear()
    eng._inflight.append((_NeverReady(), None, [req], 4, 1))
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError, match="stuck"):
        req.result(timeout=0.05)
    assert time.perf_counter() - t0 < 5.0  # bounded, not a hang
    eng._inflight.clear()
    assert not req.done  # timeout resolves the CALL, not the future


def test_submit_on_closed_engine_raises():
    d, a = small(seed=7)
    eng = engine(a, ks=(1, 4))
    r = eng.submit(xs_for(a, 1)[0])
    eng.close()  # drains first: pending work is served, not dropped
    assert r.done and not r.failed
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(xs_for(a, 1)[0])
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit_sparse(np.array([0, 3], np.int64),
                          np.array([1.0, 2.0], np.float32))


# -- fleet: breaker + retune surfacing ---------------------------------------
def test_circuit_breaker_quarantines_poisoning_tenant():
    d_good, a_good = small(seed=8, m=96)
    d_bad, a_bad = small(seed=9, m=96)
    storm = FaultPlan({"engine.dispatch": {"n": 500, "engine": "bad"}})
    fleet = SparseFleet(
        ks=(1, 4), cache=PlanCache(), retune=False, faults=storm,
        breaker_threshold=2, breaker_reset_s=0.2,
        supervisor_kwargs=dict(max_retries=0, **SUP_KW),
    )
    fleet.add_tenant("good", a_good)
    fleet.add_tenant("bad", a_bad)
    good_reqs, bad_reqs = [], []
    for x in xs_for(a_good, 8, seed=10):
        good_reqs.append(fleet.submit("good", x))
    for x in xs_for(a_bad, 8, seed=11):
        bad_reqs.append(fleet.submit("bad", x))
    for _ in range(40):
        fleet.step()
    fleet.drain()
    tenant = fleet.tenants["bad"]
    assert tenant.n_quarantines >= 1
    assert fleet.stats().quarantines >= 1
    # Every faulty-tenant future RESOLVED (injected or breaker exception).
    for r in bad_reqs:
        assert r.done and r.failed
        with pytest.raises((InjectedFault, CircuitOpenError)):
            r.result()
    # The healthy tenant never noticed.
    for r, x in zip(good_reqs, xs_for(a_good, 8, seed=10)):
        assert not r.failed
        np.testing.assert_allclose(np.asarray(r.result()), d_good @ x,
                                   atol=2e-3)
    # While open, submits fail fast; after the cooldown they are accepted.
    if tenant.quarantined:
        with pytest.raises(CircuitOpenError, match="quarantined"):
            fleet.submit("bad", xs_for(a_bad, 1)[0])
    time.sleep(0.25)
    assert not tenant.quarantined
    fleet.submit("bad", xs_for(a_bad, 1)[0])  # accepted again
    summary = fleet.stats().summary()
    assert summary["tenants"]["bad"]["quarantines"] >= 1
    fleet.close()


def test_retune_failure_retried_and_surfaced():
    d, a = small(seed=12, m=96)
    plan = FaultPlan({"fleet.retune": {"n": 2}})
    fleet = SparseFleet(
        ks=(1, 4), cache=PlanCache(), faults=plan,
        retune_max_retries=2, retune_backoff_s=0.001,
        retune_kwargs=dict(warmup=0, timed=1),
    )
    fleet.add_tenant("t", a)
    assert fleet.wait_retunes(timeout=300)
    s = fleet.stats().summary()
    assert s["retune_errors"] == 2  # both injected raises counted
    assert s["retunes_done"] == 1 and s["retunes_failed"] == 0
    assert "InjectedFault" in s["last_retune_error"]
    fleet.close()


def test_retune_exhaustion_marks_failed_and_keeps_serving():
    d, a = small(seed=13, m=96)
    plan = FaultPlan({"fleet.retune": {"n": 10}})
    fleet = SparseFleet(
        ks=(1, 4), cache=PlanCache(), faults=plan,
        retune_max_retries=1, retune_backoff_s=0.001,
        retune_kwargs=dict(warmup=0, timed=1),
    )
    fleet.add_tenant("t", a)
    assert fleet.wait_retunes(timeout=300)
    s = fleet.stats().summary()
    assert s["retunes_failed"] == 1 and s["retune_errors"] == 2
    # The predicted plan still serves.
    x = xs_for(a, 1, seed=14)[0]
    r = fleet.submit("t", x)
    fleet.drain()
    np.testing.assert_allclose(np.asarray(r.result()), d @ x, atol=2e-3)
    fleet.close()


# -- measured search under prepare failure -----------------------------------
def test_build_skips_candidate_whose_prepare_raises():
    d, a = small(seed=15, m=96)
    prev = set_active(FaultPlan({"prepare.oom": {"n": 1}}))
    try:
        from repro.tune import evict_prepared, fingerprint

        evict_prepared(fingerprint(a))
        op = SparseOperator.build(a, cache=PlanCache(), warmup=0, timed=1,
                                  force_search=True)
    finally:
        set_active(prev)
    # The OOMed candidate is marked lost, the search still picks a winner.
    assert sum(1 for v in op.measurements.values() if v == float("inf")) >= 1
    x = xs_for(a, 1, seed=16)[0]
    np.testing.assert_allclose(np.asarray(op @ x), d @ x, atol=2e-3)


# -- solver supervision ------------------------------------------------------
def test_solver_dispatch_fault_retried_then_demoted():
    rng = np.random.default_rng(17)
    m = 96
    d = ((rng.random((m, m)) < 0.08) * rng.standard_normal((m, m))).astype(
        np.float32
    )
    from repro.core.spmv import spd_shift

    a = spd_shift(csr_from_dense(d))
    b = jnp.asarray(rng.standard_normal(m), jnp.float32)

    # Two injected faults inside the default retry budget: recovered on the
    # tuned plan, no demotion.
    s = SparseSolver(a, cache=PlanCache(), warmup=0, timed=1,
                     faults=FaultPlan({"solver.dispatch": {"n": 2}}))
    s.supervisor.backoff_base_s = 0.0
    res = s.cg(b, tol=1e-6)
    assert res.converged
    assert s.supervisor.retries == 2 and s.supervisor.demotions == 0

    # Faults outlasting the budget walk the fallback chain; the degraded
    # solve still converges and its solution satisfies A x = b.
    s2 = SparseSolver(a, cache=PlanCache(), warmup=0, timed=1,
                      faults=FaultPlan({"solver.dispatch": {"n": 2}}),
                      supervisor=Supervisor(max_retries=0, **SUP_KW))
    res2 = s2.cg(b, tol=1e-6)
    assert res2.converged and s2.supervisor.demotions == 2
    assert res2.plan == "sell/ref[C=8,sigma=1]"  # the last-tier plan served
    import scipy.sparse as sp

    al = sp.csr_matrix(
        (np.asarray(a.data), np.asarray(a.indices), np.asarray(a.indptr)),
        shape=a.shape,
    )
    np.testing.assert_allclose(al @ np.asarray(res2.x), np.asarray(b),
                               atol=1e-3)

    # A persistent, name-scoped storm exhausts the chain and PROPAGATES.
    s3 = SparseSolver(
        a, cache=PlanCache(), warmup=0, timed=1, name="victim",
        faults=FaultPlan({"solver.dispatch": {"n": 100, "name": "victim"}}),
        supervisor=Supervisor(max_retries=0, **SUP_KW),
    )
    with pytest.raises(InjectedFault):
        s3.cg(b, tol=1e-6)
    assert s3.supervisor.demotions == 2 and s3.supervisor.failures == 1


def test_supervisor_faults_names_degraded_runs():
    """Supervisor.faults() is what entry points check before reporting
    success: empty on a clean run, non-empty once a batch failed."""
    d, a = small(seed=7)
    eng = engine(a, ks=(1,))
    eng.run(xs_for(a, 2))
    assert eng.supervisor.faults() == {}
    eng.close()
    plan = FaultPlan({"engine.dispatch": {"n": 1}})
    faulty = engine(a, ks=(1,), faults=plan,
                    supervisor=Supervisor(max_retries=1, **SUP_KW))
    faulty.run(xs_for(a, 2))
    assert faulty.supervisor.faults() == {"batch_failed": 1}
    faulty.close()


def test_serve_cli_exits_nonzero_on_a_degraded_run(monkeypatch, tmp_path):
    """`launch.serve --sparse` must not report success when a batch failed
    (here: one injected dispatch fault, recovered by a retry)."""
    import sys

    from repro.core.compile_cache import CACHE_ENV
    from repro.launch import serve
    from repro.tune import plan as plan_mod

    monkeypatch.setenv(CACHE_ENV, str(tmp_path))  # leave jax's cache alone
    monkeypatch.setattr(plan_mod, "_default", PlanCache())
    monkeypatch.setattr(sys, "argv", [
        "serve", "--sparse", "cant", "--scale", "0.005", "--requests", "8",
        "--k-buckets", "1,4",
    ])
    prev = set_active(FaultPlan({"engine.dispatch": {"n": 1}}))
    try:
        with pytest.raises(SystemExit, match="degraded run"):
            serve.main()
    finally:
        set_active(prev)
