"""SparseFleet: transfer-tuned admission, hot-swap atomicity, residency
budget eviction/reactivation, and cross-tenant scheduling fairness."""
import numpy as np
import jax.numpy as jnp

from repro.core.formats import csr_from_dense
from repro.runtime.engine import SparseEngine
from repro.runtime.fleet import SparseFleet, _table_bytes
from repro.tune import (
    PlanCache,
    SparseOperator,
    enumerate_candidates,
    extract,
    make,
    prep_memo_stats,
)


def small(seed=0, m=128, density=0.06):
    rng = np.random.default_rng(seed)
    d = ((rng.random((m, m)) < density) * rng.standard_normal((m, m))).astype(
        np.float32
    )
    return d, csr_from_dense(d)


def fleet(cache=None, **kw):
    cache = cache if cache is not None else PlanCache()
    kw.setdefault("ks", (1, 4))
    kw.setdefault("retune", False)  # tests opt in to the background thread
    kw.setdefault("retune_kwargs", dict(warmup=0, timed=1))
    return SparseFleet(cache=cache, **kw)


def xs_for(a, n, seed=1):
    rng = np.random.default_rng(seed)
    return [
        jnp.asarray(rng.standard_normal(a.shape[1]).astype(np.float32))
        for _ in range(n)
    ]


# -- engine hot swap --------------------------------------------------------
def test_hot_swap_in_flight_futures_resolve_on_old_plan_bitwise():
    """The atomicity contract: a swap staged while async_depth=2 batches are
    in flight never touches those batches — their futures resolve bitwise-
    equal to an unswapped engine — and the next dispatch uses the new
    table."""
    d, a = small(seed=4)
    ks = (1, 4)
    old = {k: SparseOperator.from_candidate(a, make("csr", "vector" if k == 1
                                                    else "gather"), k=k)
           for k in ks}
    new = {k: SparseOperator.from_candidate(a, make("sell", "ref", C=8,
                                                    sigma=64), k=k)
           for k in ks}
    xs = xs_for(a, 16)

    eng = SparseEngine(a, ks=ks, ops=old, async_depth=2)
    reference = SparseEngine(a, ks=ks, ops=dict(old), async_depth=2)
    ref_ys = [np.asarray(y) for y in reference.run(xs[:8])]

    reqs = [eng.submit(x) for x in xs[:8]]
    assert eng.step() == 4 and eng.step() == 4
    assert eng.in_flight == 2
    # Stage the swap mid-flight, prewarmed off the serving thread's path.
    execs = {k: eng._make_exec(k, new[k]) for k in ks}
    for k in ks:
        execs[k](*([jnp.zeros((a.shape[1],), jnp.float32)] * k))
    eng.hot_swap(new, execs=execs)
    assert eng.swaps_applied == 0  # staged, not applied: no dispatch yet

    late = [eng.submit(x) for x in xs[8:]]
    eng.drain()
    assert eng.swaps_applied == 1
    assert eng.ops[1] is new[1]
    # In-flight batches retired on the OLD plan, bitwise.
    for r, y_ref in zip(reqs, ref_ys):
        assert np.array_equal(np.asarray(r.y), y_ref)
    # Post-swap batches are correct on the new plan.
    for r, x in zip(late, xs[8:]):
        np.testing.assert_allclose(np.asarray(r.y), d @ np.asarray(x),
                                   atol=2e-3)


def test_hot_swap_rejects_missing_buckets_and_ops_injection_validates():
    _, a = small(seed=5)
    op1 = SparseOperator.from_candidate(a, make("csr", "vector"))
    eng = SparseEngine(a, ks=(1,), ops={1: op1})
    try:
        eng.hot_swap({})
        assert False, "expected ValueError for missing buckets"
    except ValueError:
        pass
    try:
        SparseEngine(a, ks=(1, 4), ops={1: op1})
        assert False, "expected ValueError for incomplete ops="
    except ValueError:
        pass
    try:
        SparseEngine(a, ks=(1,), ops={1: op1}, n_shards=2)
        assert False, "expected ValueError for ops= with n_shards"
    except ValueError:
        pass


# -- admission + background retune ------------------------------------------
def test_admission_is_predicted_and_retune_hot_swaps(tmp_path):
    """A cold fleet admits via the byte model (no measured search), serves
    correctly, and the background retune lands a measured table through
    hot_swap while futures stay correct."""
    d, a = small(seed=6)
    fl = fleet(retune=True)
    t = fl.add_tenant("t", a, max_wait_s=0.0)
    assert all(src == "byte_model" for src in t.admitted_from.values())
    assert t.engine is not None and fl.stats_fleet.predicted_admissions == 1
    for k, op in t.engine.ops.items():
        assert op.plan.measured_s == 0.0  # predicted, never measured
        assert op.plan.predicted_from == "byte_model"

    xs = xs_for(a, 6)
    reqs = [fl.submit("t", x) for x in xs]
    while any(r._ys is None for r in reqs):
        if fl.step() == 0:
            fl.flush()
    for r, x in zip(reqs, xs):
        np.testing.assert_allclose(np.asarray(r.y), d @ np.asarray(x),
                                   atol=2e-3)

    assert fl.wait_retunes(timeout=300), "background retune did not finish"
    assert fl.stats_fleet.retunes_done == 1
    # The measured plans entered the shared cache (the training set grew).
    assert len(fl.cache) == len(fl.ks)
    # The swap applies at the next dispatch boundary and stays correct.
    r = fl.submit("t", xs[0])
    while r._ys is None:
        if fl.step() == 0:
            fl.flush()
    assert t.engine.swaps_applied == 1 and t.retuned
    np.testing.assert_allclose(np.asarray(r.y), d @ np.asarray(xs[0]),
                               atol=2e-3)
    fl.close()


def test_second_tenant_transfers_from_first_after_retune():
    """Once one family member's measured plans are cached with features, a
    structurally similar matrix admits by nearest-neighbor transfer — its
    admitted_from records the neighbor's fingerprint, not 'byte_model'."""
    _, a1 = small(seed=7)
    _, a2 = small(seed=8)  # same generator family, different pattern
    fl = fleet(retune=True)
    t1 = fl.add_tenant("t1", a1)
    assert fl.wait_retunes(timeout=300)
    t2 = fl.add_tenant("t2", a2, retune=False)
    assert any(src == t1.fp for src in t2.admitted_from.values()), (
        t2.admitted_from)
    assert fl.stats_fleet.transferred_buckets >= 1
    fl.close()


# -- residency budget -------------------------------------------------------
def test_tenant_sized_exactly_at_budget_is_admitted_without_eviction():
    _, a1 = small(seed=9)
    fl = fleet()
    t1 = fl.add_tenant("t1", a1)
    # Shrink the budget to EXACTLY the resident bytes: nothing must be
    # evicted (<= budget is in budget), and the next admission must evict.
    fl.budget_bytes = fl.resident_bytes
    assert t1.resident and fl.stats_fleet.evictions == 0
    _, a2 = small(seed=10)
    t2 = fl.add_tenant("t2", a2)
    assert t2.resident
    assert not t1.resident  # t1 was idle and zero-traffic: evicted
    assert fl.stats_fleet.evictions == 1
    assert fl.stats_fleet.bytes_evicted > 0


def test_zero_traffic_tenant_evicted_before_active_one():
    d1, a1 = small(seed=11)
    _, a2 = small(seed=12)
    # t3 is deliberately sparser (smaller prepared dicts) so ONE eviction
    # makes room — the test then observes WHICH tenant was chosen.
    _, a3 = small(seed=13, density=0.02)
    fl = fleet()
    t1 = fl.add_tenant("t1", a1)
    t2 = fl.add_tenant("t2", a2)
    # Traffic on t1 only; t2 stays zero-traffic.
    xs = xs_for(a1, 4)
    reqs = [fl.submit("t1", x) for x in xs]
    while any(r._ys is None for r in reqs):
        if fl.step() == 0:
            fl.flush()
    fl.budget_bytes = fl.resident_bytes  # full: the next admission evicts
    t3 = fl.add_tenant("t3", a3)
    assert t3.resident
    assert not t2.resident, "zero-traffic tenant should be the victim"
    assert t1.resident, "the tenant with recent traffic must survive"
    # Eviction released the evicted fingerprint's share of the prep memo.
    assert fl.stats_fleet.evictions >= 1


def test_evicted_tenant_reactivates_from_cache_on_submit():
    d1, a1 = small(seed=14)
    _, a2 = small(seed=15)
    fl = fleet(retune=True)
    t1 = fl.add_tenant("t1", a1)
    assert fl.wait_retunes(timeout=300)  # measured plans now cached
    fl.budget_bytes = fl.resident_bytes
    fl.add_tenant("t2", a2, retune=False)
    assert not t1.resident
    # submit() to the evicted tenant re-admits it — from the cache, exactly
    # (no prediction, no search), because retune persisted measured plans.
    r = fl.submit("t1", xs_for(a1, 1)[0])
    assert t1.resident
    assert all(src == "cache" for src in t1.admitted_from.values())
    assert fl.stats_fleet.reactivations == 1
    while r._ys is None:
        if fl.step() == 0:
            fl.flush()
    np.testing.assert_allclose(np.asarray(r.y), d1 @ np.asarray(r.x),
                               atol=2e-3)
    fl.close()


def test_busy_tenants_are_never_evicted_over_budget_admission_counted():
    _, a1 = small(seed=16)
    _, a2 = small(seed=17)
    fl = fleet()
    fl.add_tenant("t1", a1)
    fl.submit("t1", xs_for(a1, 1)[0])  # pending work: t1 is busy
    fl.budget_bytes = 1  # nothing fits; t1 cannot be evicted
    t2 = fl.add_tenant("t2", a2)
    assert t2.resident and fl.tenants["t1"].resident
    assert fl.stats_fleet.evictions == 0
    assert fl.stats_fleet.over_budget_admissions >= 1
    fl.drain()


def _lane_row_plan(a, cache):
    """Measure lane-row SELL alone for ``a``'s k = 1 bucket, so the cache
    holds it as that bucket's plan; returns its value rows' bytes."""
    lr = make("sell_lr", "pallas", C=8, chunk_tile=16)
    op = SparseOperator.build(a, cache=cache, candidates=[lr], warmup=0,
                              timed=1)
    assert op.plan.candidate == lr
    return op._prep["seg_vals"].nbytes


def test_lane_row_tenant_admitted_beside_others_within_prep_budget():
    """A tenant whose k = 1 plan is lane-row SELL is admitted from the
    cache beside two others: its value rows (4 MiB at 128 rows, the chunk
    count padded to 1,024 rows) fit a quarter of the budget, nobody is
    evicted, and its bytes are charged to it."""
    d1, a1 = small(seed=81)
    _, a2 = small(seed=82)
    _, a3 = small(seed=83)
    cache = PlanCache()
    seg_bytes = _lane_row_plan(a1, cache)
    fl = fleet(cache=cache, budget_bytes=8 * seg_bytes)
    assert fl.prep_budget == 2 * seg_bytes
    t2 = fl.add_tenant("t2", a2)
    t1 = fl.add_tenant("t1", a1)
    t3 = fl.add_tenant("t3", a3)
    assert t1.admitted_from[1] == "cache"
    assert t1.engine.ops[1].plan.fmt == "sell_lr"
    assert t1.nbytes >= seg_bytes
    assert all(t.resident for t in (t1, t2, t3))
    assert fl.stats_fleet.evictions == 0
    assert fl.stats_fleet.over_budget_admissions == 0
    x = xs_for(a1, 1)[0]
    r = fl.submit("t1", x)
    while r._ys is None:
        if fl.step() == 0:
            fl.flush()
    np.testing.assert_allclose(np.asarray(r.y), d1 @ np.asarray(x), atol=2e-3)
    fl.close()


def test_lane_row_plan_over_prep_budget_is_neither_served_nor_searched():
    """Where the value rows exceed a quarter of the budget, the cached
    lane-row plan is passed over at admission (the tenant is predicted
    onto another tier and nobody is evicted), and the retune's measured
    search leaves the tier out."""
    _, a1 = small(seed=84)
    _, a2 = small(seed=85)
    cache = PlanCache()
    seg_bytes = _lane_row_plan(a1, cache)
    fl = fleet(cache=cache, budget_bytes=4 * seg_bytes - 4)
    assert fl.prep_budget < seg_bytes
    t2 = fl.add_tenant("t2", a2)
    t1 = fl.add_tenant("t1", a1)
    assert t1.admitted_from[1] != "cache"
    assert t1.engine.ops[1].plan.fmt != "sell_lr"
    assert t1.nbytes < seg_bytes
    assert t1.resident and t2.resident and fl.stats_fleet.evictions == 0
    op = SparseOperator.build(a1, cache=cache, prep_budget=fl.prep_budget,
                              warmup=0, timed=1)
    assert not op.from_cache and op.plan.fmt != "sell_lr"
    feats = extract(a1)
    assert any(c.fmt == "sell_lr" for c in enumerate_candidates(feats))
    assert not any(c.fmt == "sell_lr" for c in enumerate_candidates(
        feats, prep_budget=fl.prep_budget))
    fl.close()


# -- scheduling -------------------------------------------------------------
def test_round_robin_serves_all_tenants_and_slo_orders_first():
    """Every tenant with work is visited each step() pass; a tenant with an
    SLO'd oldest request is dispatched even while a burst tenant holds a
    deep backlog."""
    mats = [small(seed=s) for s in (18, 19, 20)]
    fl = fleet(ks=(1, 4))
    for i, (_, a) in enumerate(mats):
        fl.add_tenant(f"t{i}", a, max_wait_s=0.0)  # dispatch immediately
    all_reqs = {}
    for i, (_, a) in enumerate(mats):
        all_reqs[f"t{i}"] = [fl.submit(f"t{i}", x) for x in xs_for(a, 4)]
    # One fleet pass dispatches for EVERY tenant with pending work.
    assert fl.step() == 12
    fl.flush()
    for i, (d, _) in enumerate(mats):
        for r in all_reqs[f"t{i}"]:
            assert r.done
            np.testing.assert_allclose(
                np.asarray(r.y), d @ np.asarray(r.x), atol=2e-3)
    assert fl.drain() == 0  # everything already served


def test_fleet_drain_and_stats_summary_shapes():
    _, a = small(seed=21)
    fl = fleet()
    fl.add_tenant("t", a)
    reqs = [fl.submit("t", x) for x in xs_for(a, 5)]
    assert fl.drain() == 5
    assert all(r.done for r in reqs)
    s = fl.stats().summary()
    assert s["admissions"] == 1 and "t" in s["tenants"]
    assert s["tenants"]["t"]["engine"]["requests"] == 5
    assert set(s["prep_memo"]) >= {"entries", "resident_bytes", "hits",
                                   "misses", "evictions"}
    assert s["resident_bytes"] == _table_bytes(fl.tenants["t"].engine.ops)
