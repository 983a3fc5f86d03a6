"""Overload protection: bounded admission, deadline shedding, brownout,
fair share, and the bounded/coalesced retune queue.

Every refusal here must be TYPED (OverloadError / DeadlineExceededError /
EngineClosedError) and fast; every admitted request must resolve (served
or failed, never hung); and the brownout state machine must hold its
hysteresis — a boundary load cannot flap it."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import csr_from_dense
from repro.runtime.engine import SparseEngine
from repro.runtime.faults import FaultPlan
from repro.runtime.fleet import SparseFleet
from repro.runtime.overload import (
    BROWNOUT,
    HEALTHY,
    SHED,
    BrownoutController,
    DeadlineExceededError,
    EngineClosedError,
    OverloadError,
    TokenBucket,
)
from repro.tune import PlanCache


def small(seed=0, m=128, density=0.06):
    rng = np.random.default_rng(seed)
    d = ((rng.random((m, m)) < density) * rng.standard_normal((m, m))).astype(
        np.float32
    )
    return d, csr_from_dense(d)


def engine(a, ks=(1, 4), **kw):
    kw.setdefault("cache", PlanCache())
    return SparseEngine(a, ks=ks, warmup=0, timed=1, **kw)


def xs_for(a, count, seed=1):
    rng = np.random.default_rng(seed)
    return [
        jnp.asarray(rng.standard_normal(a.shape[1]).astype(np.float32))
        for _ in range(count)
    ]


# -- token bucket -------------------------------------------------------------
def test_token_bucket_burst_then_refill():
    b = TokenBucket(rate=8.0, burst=3.0)
    t = 100.0  # dyadic times: the dt * rate arithmetic stays exact
    assert all(b.try_take(now=t) for _ in range(3))  # the burst
    assert not b.try_take(now=t)  # dry: refuses, and no debt accrues
    assert b.try_take(now=t + 0.125)  # 0.125s * 8/s = 1 token back
    assert not b.try_take(now=t + 0.125)
    # refill caps at burst, never beyond
    assert sum(b.try_take(now=t + 100.0) for _ in range(10)) == 3


def test_token_bucket_validates():
    with pytest.raises(ValueError):
        TokenBucket(rate=0.0, burst=1.0)
    with pytest.raises(ValueError):
        TokenBucket(rate=1.0, burst=-1.0)


# -- brownout controller ------------------------------------------------------
def test_brownout_hysteresis_no_flap_on_boundary_load():
    # A load oscillating tightly around the enter watermark must produce
    # EXACTLY ONE transition: enter at 0.71, then hold (0.69 is far above
    # the 0.35 exit watermark — that gap is the hysteresis).
    c = BrownoutController(min_dwell_s=0.0)
    t = 0.0
    for i in range(50):
        t += 1.0
        c.update(0.71 if i % 2 == 0 else 0.69, now=t)
    assert c.state == BROWNOUT
    assert len(c.transitions) == 1
    c.update(0.34, now=t + 1.0)  # below exit: recovers
    assert c.state == HEALTHY and len(c.transitions) == 2


def test_brownout_min_dwell_pins_state():
    c = BrownoutController(min_dwell_s=1.0)
    c.update(1.0, now=10.0)  # still inside the initial dwell: no move
    assert c.state == HEALTHY or c.state == SHED  # dwell counts from init
    c2 = BrownoutController(min_dwell_s=1.0)
    c2._t_entered = 0.0
    c2.update(1.0, now=2.0)
    assert c2.state == SHED
    c2.update(0.0, now=2.5)  # dwell: pinned despite zero pressure
    assert c2.state == SHED
    c2.update(0.0, now=3.5)
    assert c2.state == BROWNOUT  # de-escalation is one level at a time
    c2.update(0.0, now=5.0)
    assert c2.state == HEALTHY


def test_brownout_shed_never_jumps_to_healthy():
    c = BrownoutController(min_dwell_s=0.0)
    c.update(1.0, now=1.0)
    assert c.state == SHED
    c.update(0.0, now=2.0)
    assert c.state == BROWNOUT  # never SHED -> HEALTHY directly
    assert [tr.to for tr in c.transitions] == [SHED, BROWNOUT]


def test_brownout_validates_watermarks():
    with pytest.raises(ValueError):
        BrownoutController(enter_brownout=0.5, exit_brownout=0.5)
    with pytest.raises(ValueError):
        BrownoutController(enter_brownout=0.96, enter_shed=0.95)


def test_brownout_pressure_folds_max_of_non_none():
    p = BrownoutController.pressure(queue=0.4, age=None, prep=0.9)
    assert p == 0.9
    assert BrownoutController.pressure(queue=None, age=None) == 0.0


# -- bounded admission edges --------------------------------------------------
def test_submit_at_exactly_max_queue_boundary():
    d, a = small()
    eng = engine(a, max_queue=3, overload_policy="reject", max_wait_s=10.0)
    xs = xs_for(a, 4)
    for x in xs[:3]:
        eng.submit(x)  # fills to exactly max_queue: all admitted
    assert eng.pending == 3
    with pytest.raises(OverloadError):
        eng.submit(xs[3])  # one past the cap: typed refusal
    assert eng.stats.rejected == 1
    assert eng.pending == 3  # the refusal never entered the queue
    eng.drain()
    eng.close()


def test_shed_oldest_preserves_fifo_for_survivors():
    d, a = small(seed=1)
    eng = engine(a, ks=(4,), max_queue=4, overload_policy="shed-oldest",
                 max_wait_s=10.0)
    xs = xs_for(a, 6)
    reqs = [eng.submit(x) for x in xs]
    # Two evictions: the two OLDEST queued requests, in order.
    assert reqs[0].failed and isinstance(reqs[0]._exc, OverloadError)
    assert reqs[1].failed and isinstance(reqs[1]._exc, OverloadError)
    assert eng.stats.shed_oldest == 2
    eng.drain()
    survivors = reqs[2:]
    assert all(r.done and not r.failed for r in survivors)
    # FIFO among survivors: resolved in submit order (non-decreasing rid
    # by t_done, all in the same batch or ordered batches).
    dones = [r.t_done for r in survivors]
    assert dones == sorted(dones)
    for r in survivors:  # correctness untouched by the shedding
        np.testing.assert_allclose(
            np.asarray(r.result()),
            d @ np.asarray(r.x),
            rtol=1e-4, atol=1e-4,
        )
    eng.close()


def test_block_policy_waits_then_admits():
    d, a = small(seed=2)
    eng = engine(a, ks=(1,), max_queue=1, overload_policy="block",
                 block_timeout_s=5.0, max_wait_s=0.0)
    xs = xs_for(a, 3)
    r0 = eng.submit(xs[0])
    r1 = eng.submit(xs[1])  # full queue: block self-drives a dispatch
    assert eng.stats.rejected == 0
    eng.drain()
    assert r0.done and r1.done
    eng.close()


def test_block_policy_times_out_typed():
    d, a = small(seed=3)
    eng = engine(a, ks=(4,), max_queue=1, overload_policy="block",
                 block_timeout_s=0.05, max_wait_s=30.0)
    # max_wait_s is huge and the bucket is partial, so the self-driven
    # step() can never dispatch: block must give up after its timeout.
    eng.submit(xs_for(a, 1)[0])
    t0 = time.perf_counter()
    with pytest.raises(OverloadError):
        eng.submit(xs_for(a, 1, seed=9)[0])
    waited = time.perf_counter() - t0
    assert 0.04 <= waited < 2.0  # bounded: roughly block_timeout_s
    assert eng.stats.rejected == 1
    eng.drain()
    eng.close()


def test_deadline_shed_is_typed_and_counted():
    d, a = small(seed=4)
    eng = engine(a, max_queue=16, max_wait_s=0.0, shed_after_s=0.002)
    r = eng.submit(xs_for(a, 1)[0])
    time.sleep(0.01)  # lapse the deadline before any dispatch runs
    served = eng.step()
    assert served == 0
    assert r.failed and isinstance(r._exc, DeadlineExceededError)
    assert isinstance(r._exc, OverloadError)  # the taxonomy nests
    assert eng.stats.shed_deadline == 1
    with pytest.raises(DeadlineExceededError):
        r.result()
    eng.close()


def test_overload_delay_site_stalls_dispatch():
    d, a = small(seed=5)
    plan = FaultPlan({"engine.overload": {"delay_s": 0.03, "n": 1}})
    eng = engine(a, ks=(1,), faults=plan)
    eng.run(xs_for(a, 1))  # fires the one armed delay
    assert plan.fired("engine.overload") == 1
    assert plan.delay("engine.overload") == 0.0  # n exhausted: no stall
    # and the slowed dispatch still served correctly
    eng.close()


# -- closed-engine regression (satellite S2) ----------------------------------
def test_close_without_drain_fails_futures_immediately():
    d, a = small(seed=6)
    eng = engine(a, max_wait_s=10.0)
    reqs = [eng.submit(x) for x in xs_for(a, 3)]
    eng.close(drain=False)
    t0 = time.perf_counter()
    for r in reqs:
        with pytest.raises(EngineClosedError):
            r.result(timeout=5.0)
    assert time.perf_counter() - t0 < 1.0  # immediate, not a timeout wait
    assert eng.stats.failed_requests == 3
    with pytest.raises(EngineClosedError, match="closed"):
        eng.submit(xs_for(a, 1)[0])
    # a second close is a no-op
    eng.close()


def test_close_drain_default_still_serves():
    d, a = small(seed=7)
    eng = engine(a)
    r = eng.submit(xs_for(a, 1)[0])
    eng.close()  # graceful: drains first
    assert r.done and not r.failed


# -- brownout wired through the engine ----------------------------------------
def test_engine_brownout_degrades_and_recovers():
    d, a = small(seed=8)
    ctrl = BrownoutController(min_dwell_s=0.0)
    eng = engine(a, ks=(1, 4), max_queue=8, shed_after_s=1.0,
                 max_wait_s=0.0, brownout=ctrl)
    events = eng.supervisor.events_of("brownout")
    assert events == []
    # saturate the queue, then step: pressure 8/8 = 1.0 -> SHED
    xs = xs_for(a, 8)
    for x in xs:
        eng.submit(x)
    eng.step()
    assert ctrl.entries(SHED) >= 1 or ctrl.entries(BROWNOUT) >= 1
    # under brownout, dispatch pins to the widest bucket: the next step
    # takes a full k=4 batch even though the controller is degraded
    while eng.pending:
        eng.step()
    eng.drain()
    # drained: pressure 0 -> the controller walks back to HEALTHY
    for _ in range(4):
        eng.step()
    assert ctrl.state == HEALTHY
    assert any(tr.to == HEALTHY for tr in ctrl.transitions)
    # transitions were published as supervisor events
    assert len(eng.supervisor.events_of("brownout")) == len(ctrl.transitions)
    assert all(r.done and not r.failed for r in [])  # no stragglers
    eng.close()


def test_brownout_pins_widest_bucket():
    d, a = small(seed=9)
    ctrl = BrownoutController(min_dwell_s=0.0)
    eng = engine(a, ks=(1, 4), brownout=ctrl, brownout_update=False)
    ctrl.update(0.8)  # BROWNOUT: engine consults but never updates
    assert ctrl.state == BROWNOUT
    eng.submit(xs_for(a, 1)[0])
    eng.step(force=True)
    eng.flush()
    assert eng.stats.dispatched.get(4, 0) == 1  # widest, not the k=1 bucket
    assert eng.stats.dispatched.get(1, 0) == 0
    ctrl.update(0.0)
    ctrl.update(0.0)
    assert ctrl.state == HEALTHY
    eng.submit(xs_for(a, 1)[0])
    eng.step(force=True)
    eng.flush()
    assert eng.stats.dispatched.get(1, 0) == 1  # healthy: right-sized again
    eng.close()


# -- fleet: fair share, bounded retunes, shared brownout ----------------------
def test_fair_share_greedy_cannot_starve_polite():
    d_g, a_greedy = small(seed=10)
    d_p, a_polite = small(seed=11)
    slo = 0.05
    fleet = SparseFleet(
        ks=(1, 4), cache=PlanCache(), retune=False, max_wait_s=0.0,
    )
    # Greedy gets a tiny bucket; polite is unlimited (rate=None default).
    fleet.add_tenant("greedy", a_greedy, rate=20.0, burst=2.0)
    fleet.add_tenant("polite", a_polite, max_wait_s=slo)
    xg = xs_for(a_greedy, 8, seed=12)
    xp = xs_for(a_polite, 8, seed=13)
    # compile both tenants outside the measured loop
    fleet.submit("polite", xp[0]); fleet.submit("greedy", xg[0])
    fleet.drain()
    engines = [fleet.tenants[name].engine for name in ("greedy", "polite")]

    def dispatches():
        return sum(e.stats.n_dispatches for e in engines)

    quanta, limited = [], 0
    for j in range(24):
        for b in range(8):  # greedy offers an 8x burst every round...
            try:
                fleet.submit("greedy", xg[(8 * j + b) % 8])
            except OverloadError:
                limited += 1  # ...and its excess fails fast, typed
        r = fleet.submit("polite", xp[j % 8])
        before = dispatches()
        while r._ys is None:
            if fleet.step() == 0:
                fleet.flush()
        # The polite request's wait in service quanta: the batches the
        # fleet's engines dispatched between its submit and its retirement
        # (its own included) -- counted by the engines, so the bound holds
        # however busy the CPU running the test is.
        quanta.append(dispatches() - before)
    fleet.drain()
    assert limited > 0  # the bucket actually bit
    assert fleet.stats_fleet.rate_limited == limited
    p99 = float(np.quantile(np.asarray(quanta), 0.99))
    # fig18/fig19's SLO budget shape: bounded service quanta.  The greedy
    # tenant's admitted trickle may interleave, but its REFUSED burst must
    # never show up in the polite tenant's tail.
    assert p99 <= 16, (
        f"polite p99 wait of {p99:.1f} dispatches blew the 16-quantum "
        f"budget ({limited} greedy refusals)")
    fleet.close()


def test_retune_queue_coalesces_and_bounds():
    d, a = small(seed=14)
    fleet = SparseFleet(ks=(1,), cache=PlanCache(), retune=False,
                        retune_queue_max=2)
    fleet.add_tenant("t1", a)
    # Hold the lock so the worker cannot drain while we pile on requests.
    with fleet._retune_lock:
        fleet._retune_q.put_nowait("t1")
        fleet._retune_pending.add("t1")
        fleet.stats_fleet.retunes_queued += 1
    for _ in range(4):
        fleet._queue_retune("t1")  # same tenant: all coalesce
    assert fleet.stats_fleet.retunes_coalesced == 4
    assert fleet.stats_fleet.retunes_queued == 1
    # Distinct names overflow the bounded queue and are dropped, counted.
    for name in ("t2", "t3", "t4", "t5"):
        fleet._queue_retune(name)
    assert fleet.stats_fleet.retunes_dropped >= 1
    assert fleet._retune_q.qsize() <= 2
    fleet.wait_retunes(timeout=60.0)
    fleet.close()


def test_fleet_brownout_defers_retunes_and_requeues_on_recovery():
    d, a = small(seed=15)
    ctrl = BrownoutController(min_dwell_s=0.0)
    fleet = SparseFleet(ks=(1,), cache=PlanCache(), retune=False,
                        brownout=ctrl, max_queue=8)
    fleet.add_tenant("t", a)
    ctrl.update(0.8)
    assert ctrl.state == BROWNOUT
    fleet._queue_retune("t")
    assert fleet.stats_fleet.retunes_deferred == 1
    assert fleet.stats_fleet.retunes_queued == 0  # parked, not queued
    ctrl.update(0.0)  # recovery listener re-queues the deferred search
    assert ctrl.state == HEALTHY
    assert fleet.stats_fleet.retunes_queued == 1
    # transitions surfaced on the FLEET supervisor (engines are read-only)
    assert len(fleet.supervisor.events_of("brownout")) == 2
    fleet.wait_retunes(timeout=60.0)
    fleet.close()


def test_fleet_rate_limit_is_typed_and_survives_eviction():
    d, a = small(seed=16)
    fleet = SparseFleet(ks=(1,), cache=PlanCache(), retune=False,
                        tenant_rate=5.0, tenant_burst=1.0)
    fleet.add_tenant("t", a)
    fleet.submit("t", xs_for(a, 1)[0])
    with pytest.raises(OverloadError):
        fleet.submit("t", xs_for(a, 1, seed=2)[0])
    assert fleet.stats_fleet.rate_limited == 1
    assert fleet.tenants["t"].bucket is not None
    fleet.drain()
    fleet.close()


def test_fleet_summary_aggregates_overload_counters():
    d, a = small(seed=17)
    ctrl = BrownoutController(min_dwell_s=0.0)
    fleet = SparseFleet(ks=(1,), cache=PlanCache(), retune=False,
                        max_queue=1, overload_policy="reject",
                        max_wait_s=10.0, brownout=ctrl)
    fleet.add_tenant("t", a)
    fleet.submit("t", xs_for(a, 1)[0])
    with pytest.raises(OverloadError):
        fleet.submit("t", xs_for(a, 1, seed=2)[0])  # per-tenant queue cap
    out = fleet.stats().summary()
    assert out["rejected"] == 1
    assert out["shed_oldest"] == 0 and out["shed_deadline"] == 0
    assert out["brownout"]["state"] == HEALTHY
    fleet.drain()
    fleet.close()


# -- result() wait path: condition, not sleep-poll (satellite S3) -------------
def test_result_wakes_via_condition_across_threads():
    d, a = small(seed=18)
    eng = engine(a, ks=(1,), max_wait_s=None)
    r = eng.submit(xs_for(a, 1)[0])
    got: list = []

    def waiter():
        got.append(np.asarray(r.result(timeout=10.0)))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.02)  # let the waiter elect itself driver / block
    # Either the waiter drove the engine itself (serve-lock election) or
    # this drain resolves it and the condition wakes the waiter.
    eng.drain()
    t.join(timeout=10.0)
    assert not t.is_alive() and len(got) == 1
    np.testing.assert_allclose(got[0], d @ np.asarray(r.x),
                               rtol=1e-4, atol=1e-4)
    eng.close()


def test_result_timeout_still_honored_with_condition_wait():
    d, a = small(seed=19)
    eng = engine(a, ks=(4,), max_wait_s=None)
    # a request on an engine nobody drives, with the serve lock held so
    # the caller cannot elect itself driver: the deadline must still fire
    r = eng.submit(xs_for(a, 1)[0])
    eng._serve_lock.acquire()
    try:
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError):
            r.result(timeout=0.05)
        assert time.perf_counter() - t0 < 2.0
    finally:
        eng._serve_lock.release()
    eng.drain()
    eng.close()
