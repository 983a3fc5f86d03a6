"""SparseEngine: batch aggregation vs the per-request SpMV oracle, k-bucket
padding, plan-table cache round-trip, shard dispatch, and queue edge cases."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributed import assemble_rows, stacked_spmm
from repro.core.formats import csr_from_dense
from repro.core.partition import rows_balanced, stack_csr_shards
from repro.runtime.engine import SparseEngine
from repro.tune import PlanCache, SparseOperator


def small(seed=0, m=128, density=0.06):
    rng = np.random.default_rng(seed)
    d = ((rng.random((m, m)) < density) * rng.standard_normal((m, m))).astype(
        np.float32
    )
    return d, csr_from_dense(d)


def engine(a, ks=(1, 4, 16), cache=None, **kw):
    # NOT `cache or PlanCache()`: an empty PlanCache is falsy (__len__ == 0),
    # which would silently discard a shared cache and let each engine
    # re-search with timing noise.
    cache = cache if cache is not None else PlanCache()
    return SparseEngine(a, ks=ks, cache=cache, warmup=0, timed=1, **kw)


def test_batch_aggregation_matches_per_request_oracle():
    d, a = small()
    eng = engine(a)
    rng = np.random.default_rng(1)
    xs = [rng.standard_normal(a.shape[1]).astype(np.float32) for _ in range(21)]
    reqs = [eng.submit(x) for x in xs]
    eng.drain()
    for r, x in zip(reqs, xs):
        assert r.done and r.t_done is not None and r.latency_s >= 0
        np.testing.assert_allclose(np.asarray(r.y), d @ x, atol=2e-3)
    assert eng.stats.n_requests == 21
    assert eng.pending == 0


def test_k_bucket_round_up_and_padding():
    d, a = small(seed=2)
    eng = engine(a)
    rng = np.random.default_rng(3)
    xs = [rng.standard_normal(a.shape[1]).astype(np.float32) for _ in range(5)]
    reqs = [eng.submit(x) for x in xs]
    assert eng.step() == 5  # one dispatch serves all five
    eng.flush()  # step() dispatched asynchronously; retire the batch
    # 5 pending rounds UP to the 16-bucket: 11 zero pad columns.
    assert eng.stats.dispatched == {16: 1}
    assert eng.stats.occupied_cols == 5 and eng.stats.padded_cols == 11
    assert abs(eng.stats.occupancy - 5 / 16) < 1e-9
    for r, x in zip(reqs, xs):
        assert r.bucket == 16
        np.testing.assert_allclose(np.asarray(r.y), d @ x, atol=2e-3)


def test_empty_queue_and_single_request():
    d, a = small(seed=4)
    eng = engine(a)
    assert eng.step() == 0  # empty queue is a no-op
    assert eng.drain() == 0
    x = np.random.default_rng(5).standard_normal(a.shape[1]).astype(np.float32)
    req = eng.submit(x)
    assert eng.step() == 1
    eng.flush()
    assert req.bucket == 1  # single request runs the k=1 SpMV plan
    np.testing.assert_allclose(np.asarray(req.y), d @ x, atol=2e-3)
    assert eng.stats.dispatched == {1: 1} and eng.stats.padded_cols == 0


def test_plan_table_cache_roundtrip(tmp_path):
    d, a = small(seed=6)
    path = tmp_path / "plans.json"
    eng = SparseEngine(a, ks=(1, 4), cache=PlanCache(path), warmup=0, timed=1)
    assert not eng.from_cache  # first build searches
    assert eng.ops[1].plan.kind == "spmv" and eng.ops[4].plan.kind == "spmm"
    # Restart: a fresh engine over the same file reloads every bucket's plan.
    eng2 = SparseEngine(a, ks=(1, 4), cache=PlanCache(path))
    assert eng2.from_cache
    assert all(eng2.ops[k].plan.candidate == eng.ops[k].plan.candidate
               for k in (1, 4))
    x = np.random.default_rng(7).standard_normal(a.shape[1]).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(eng2.run([x, x, x])[0]), d @ x, atol=2e-3
    )


def test_build_multi_is_the_engines_plan_table(tmp_path):
    _, a = small(seed=8)
    cache = PlanCache(tmp_path / "plans.json")
    table = SparseOperator.build_multi(a, ks=(1, 16), cache=cache,
                                       warmup=0, timed=1)
    assert set(table) == {1, 16}
    assert table[1].plan.k == 1 and table[16].plan.k == 16
    eng = SparseEngine(a, ks=(1, 16), cache=PlanCache(tmp_path / "plans.json"))
    assert eng.from_cache  # the engine rides the same k-indexed entries


def test_sharded_engine_matches_oracle_and_stacked_entry_point():
    d, a = small(seed=9, m=96)
    eng = engine(a, ks=(1, 4), n_shards=3)
    rng = np.random.default_rng(10)
    xs = [rng.standard_normal(a.shape[1]).astype(np.float32) for _ in range(6)]
    ys = eng.run(xs)
    for y, x in zip(ys, xs):
        np.testing.assert_allclose(np.asarray(y), d @ x, atol=2e-3)
    # The raw stacked-RHS entry point agrees too (one vmapped dispatch).
    part = rows_balanced(a, 3)
    stacked = {k: jnp.asarray(v) for k, v in
               stack_csr_shards(part.shards).items()}
    X = jnp.asarray(np.stack(xs[:4], axis=1))
    y_parts = stacked_spmm(stacked, X)
    got = assemble_rows(y_parts, np.diff(part.bounds))
    np.testing.assert_allclose(np.asarray(got), d @ np.asarray(X), atol=2e-3)


def test_admission_control_lone_request_never_waits_for_wide_bucket():
    """ROADMAP follow-up: with max_wait_s set, a lone request is held only
    until its deadline, then dispatched as a partial (k=1) bucket — it never
    waits for the 4-bucket to fill."""
    import time

    d, a = small(seed=20)
    eng = engine(a, ks=(1, 4), max_wait_s=0.05)
    x = np.random.default_rng(21).standard_normal(a.shape[1]).astype(np.float32)
    req = eng.submit(x)
    assert eng.step() == 0  # under SLO with a partial bucket: held back
    assert eng.pending == 1
    deadline = time.perf_counter() + 5.0
    while eng.step() == 0:
        assert time.perf_counter() < deadline, "SLO expiry never dispatched"
        time.sleep(0.005)
    eng.flush()
    assert req.done and req.bucket == 1  # partial bucket, not a padded 4
    assert req.latency_s < 1.0
    np.testing.assert_allclose(np.asarray(req.y), d @ x, atol=2e-3)


def test_admission_control_full_bucket_dispatches_immediately():
    d, a = small(seed=22)
    eng = engine(a, ks=(1, 4), max_wait_s=10.0)
    rng = np.random.default_rng(23)
    xs = [rng.standard_normal(a.shape[1]).astype(np.float32) for _ in range(4)]
    for x in xs:
        eng.submit(x)
    assert eng.step() == 4  # max(ks) pending: no reason to wait
    # drain() is an explicit flush: it bypasses the admission gate and
    # retires everything outstanding.  (The gate-held step() may already
    # have retired the ready full bucket via the idle-path _retire_ready,
    # so drain()'s own count is timing-dependent — assert on totals.)
    req = eng.submit(xs[0])
    assert eng.step() == 0
    eng.drain()
    assert req.done and req.bucket == 1
    assert eng.stats.occupied_cols == 5  # every request retired exactly once


# -- PR 5: async double-buffered loop + persistent executables --------------
def test_async_results_bitwise_match_synchronous_engine():
    """The async loop runs the SAME per-bucket persistent executables as the
    synchronous engine, so results must be bitwise identical — not merely
    close.  All engines share one plan cache: the first build's measured
    search decides the plans, the others reload them (otherwise timing
    noise could legitimately pick different kernels per engine)."""
    _, a = small(seed=11)
    cache = PlanCache()
    rng = np.random.default_rng(12)
    xs = [rng.standard_normal(a.shape[1]).astype(np.float32) for _ in range(23)]
    ys_sync = engine(a, cache=cache, async_depth=0).run(xs)
    ys_async = engine(a, cache=cache, async_depth=2).run(xs)
    for ys, ya in zip(ys_sync, ys_async):
        assert np.array_equal(np.asarray(ys), np.asarray(ya))
    # The legacy eager-stack baseline computes the same padded batch through
    # a different XLA program; agreement there is numeric, not bitwise.
    ys_legacy = engine(a, cache=cache, legacy_dispatch=True).run(xs)
    for yl, ys in zip(ys_legacy, ys_sync):
        np.testing.assert_allclose(np.asarray(yl), np.asarray(ys), atol=1e-5)


def test_async_two_batches_in_flight_and_drain_flushes():
    d, a = small(seed=13)
    eng = engine(a, ks=(1, 4), async_depth=2)
    rng = np.random.default_rng(14)
    xs = [rng.standard_normal(a.shape[1]).astype(np.float32) for _ in range(8)]
    reqs = [eng.submit(x) for x in xs]
    assert eng.step() == 4 and eng.step() == 4  # two dispatches, no retire
    assert eng.in_flight == 2  # the double-buffered window is full
    assert not any(r.done for r in reqs)  # futures unresolved while in flight
    assert eng.drain() == 8 and eng.in_flight == 0  # drain flushes the window
    assert all(r.done for r in reqs)
    for r, x in zip(reqs, xs):
        np.testing.assert_allclose(np.asarray(r.y), d @ x, atol=2e-3)
    assert eng.stats.n_dispatches == 2  # stats recorded at retirement


def test_futures_resolve_in_submission_order():
    """result() on a late request must first retire every earlier batch, so
    requests complete in submission order per request."""
    d, a = small(seed=15)
    eng = engine(a, ks=(1, 4), async_depth=2)
    rng = np.random.default_rng(16)
    xs = [rng.standard_normal(a.shape[1]).astype(np.float32) for _ in range(11)]
    reqs = [eng.submit(x) for x in xs]
    y_last = reqs[-1].result()  # drives dispatch + retirement of everything
    assert all(r.done for r in reqs)
    done_times = [r.t_done for r in reqs]
    assert done_times == sorted(done_times)  # FIFO retirement
    np.testing.assert_allclose(np.asarray(y_last), d @ xs[-1], atol=2e-3)
    # A foreign request is rejected rather than looping forever.
    other = engine(a, ks=(1,)).submit(xs[0])
    other._engine = eng
    import pytest

    with pytest.raises(RuntimeError):
        other.result()


def test_admission_slo_honored_with_two_batches_in_flight():
    """max_wait_s applies to the QUEUE, not the in-flight window: with two
    batches already dispatched, a lone queued request still dispatches once
    its deadline expires."""
    import time

    d, a = small(seed=17)
    eng = engine(a, ks=(1, 4), async_depth=2, max_wait_s=0.05)
    rng = np.random.default_rng(18)
    xs = [rng.standard_normal(a.shape[1]).astype(np.float32) for _ in range(9)]
    for x in xs[:8]:
        eng.submit(x)
    assert eng.step() == 4 and eng.step() == 4  # full buckets dispatch at once
    assert eng.in_flight == 2
    req = eng.submit(xs[8])
    t0 = time.perf_counter()
    assert eng.step() == 0  # partial bucket under SLO: held back
    deadline = time.perf_counter() + 5.0
    while eng.step() == 0:
        assert time.perf_counter() < deadline, "SLO expiry never dispatched"
        time.sleep(0.005)
    waited = time.perf_counter() - t0
    assert waited >= 0.05  # gate held at least the SLO window
    eng.flush()
    assert req.done and req.bucket == 1
    np.testing.assert_allclose(np.asarray(req.y), d @ xs[8], atol=2e-3)


def test_stats_padded_columns_are_not_served_work():
    """True occupancy (requests / bucket capacity) and padded occupancy are
    reported separately; padding never counts toward served columns."""
    _, a = small(seed=19)
    eng = engine(a, ks=(1, 4, 16))
    rng = np.random.default_rng(20)
    xs = [rng.standard_normal(a.shape[1]).astype(np.float32) for _ in range(5)]
    for x in xs:
        eng.submit(x)
    eng.step()
    eng.flush()
    s = eng.stats.summary()
    assert s["served_cols"] == 5 and s["padded_cols"] == 11
    assert abs(s["occupancy"] - 5 / 16) < 1e-9
    assert abs(s["padded_occupancy"] - 11 / 16) < 1e-9
    assert abs(s["occupancy"] + s["padded_occupancy"] - 1.0) < 1e-9
    assert eng.stats.n_requests == 5  # padded columns never become requests


def test_batched_server_prefill_assignment():
    """_assign must prefill (one pass per prompt), not replay decode steps,
    and a B=2 server must produce the same tokens as two B=1 servers."""
    import jax.numpy as jnp

    from repro.models.lm import ModelConfig, init_model
    from repro.runtime.server import BatchedServer, Request

    cfg = ModelConfig(arch_id="tiny", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=64,
                      dtype=jnp.float32, remat="none", attn_chunk=16)
    params, _ = init_model(cfg, 0)

    def serve(slots, prompts):
        srv = BatchedServer(cfg, params, batch_slots=slots, max_seq=32)
        reqs = [Request(rid=i, prompt=p, max_new=6)
                for i, p in enumerate(prompts)]
        for r in reqs:
            srv.submit(r)
        srv.run_until_drained(max_steps=200)
        return reqs, srv

    prompts = [np.arange(4, dtype=np.int32), np.arange(4, dtype=np.int32) + 7]
    batched, srv = serve(2, prompts)
    assert srv.prefills == 2  # one prefill pass per request, no replay
    assert srv.steps <= 6 + 1  # no decode steps burned on prompt tokens
    assert 0.9 <= srv.occupancy <= 1.0
    for p in prompts:
        solo, _ = serve(1, [p])
        match = [r for r in batched if np.array_equal(r.prompt, p)]
        assert match[0].out == solo[0].out  # slot isolation: same greedy path


# ---------------------------------------------------------------------------
# PR 6: the submit() dtype policy (no more silent downcasts)
# ---------------------------------------------------------------------------
def test_submit_non_f32_warns_once_per_engine_and_casts():
    import warnings

    d, a = small(seed=30)
    eng = engine(a)
    x64 = np.linspace(-1.0, 1.0, a.shape[1])  # float64
    assert x64.dtype == np.float64
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        r1 = eng.submit(x64)
        r2 = eng.submit(x64)  # second cast: silent (once per engine)
        r3 = eng.submit(x64.astype(np.float32))  # f32: never warns
    msgs = [w for w in caught if "float32" in str(w.message)]
    assert len(msgs) == 1, [str(w.message) for w in caught]
    eng.drain()
    ref = d @ x64.astype(np.float32)
    for r in (r1, r2, r3):
        assert r.y.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(r.y), ref, atol=2e-3)
    # A second engine gets its own one warning.
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        engine(a).submit(x64)
    assert any("float32" in str(w.message) for w in caught)


def test_submit_strict_dtype_raises_instead_of_casting():
    import pytest

    _, a = small(seed=31)
    eng = engine(a, strict_dtype=True)
    with pytest.raises(TypeError, match="float64"):
        eng.submit(np.zeros(a.shape[1], np.float64))
    with pytest.raises(TypeError, match="int32"):
        eng.submit(np.zeros(a.shape[1], np.int32))
    # Exact-dtype traffic is unaffected.
    r = eng.submit(np.zeros(a.shape[1], np.float32))
    eng.drain()
    assert r.done and r.y.dtype == jnp.float32


# ---------------------------------------------------------------------------
# PR 8: sparse-RHS serving (submit_sparse)
# ---------------------------------------------------------------------------
def test_submit_sparse_matches_dense_oracle_and_buckets_by_nnz():
    d, a = small(seed=70)
    eng = engine(a, ks=(1,), x_nnz_buckets=(4, 16))
    rng = np.random.default_rng(71)
    idx = np.sort(rng.choice(128, size=3, replace=False)).astype(np.int64)
    val = rng.standard_normal(3).astype(np.float32)
    x_dense = np.zeros(128, np.float32)
    x_dense[idx] = val
    fut = eng.submit_sparse(idx, val)
    eng.drain()
    np.testing.assert_allclose(
        np.asarray(fut.result()), d @ x_dense, atol=1e-4
    )
    # nnz=3 rounds up to the 4-bucket; stats record the sparse lane apart
    # from the dense k-buckets.
    s = eng.stats.summary()
    assert s["sparse_by_bucket"] == {"spmspv4": 1}


def test_submit_sparse_oversize_falls_back_to_densify():
    d, a = small(seed=72)
    eng = engine(a, ks=(1,), x_nnz_buckets=(4,))
    rng = np.random.default_rng(73)
    idx = np.sort(rng.choice(128, size=9, replace=False)).astype(np.int64)
    val = rng.standard_normal(9).astype(np.float32)
    x_dense = np.zeros(128, np.float32)
    x_dense[idx] = val
    fut = eng.submit_sparse(idx, val)  # nnz=9 > largest bucket 4
    eng.drain()
    np.testing.assert_allclose(
        np.asarray(fut.result()), d @ x_dense, atol=1e-4
    )
    s = eng.stats.summary()
    assert s["sparse_by_bucket"] == {}  # served by the dense k=1 lane
    assert s["by_bucket"] == {1: 1}


def test_submit_sparse_rejects_bad_indices_loudly():
    _, a = small(seed=74)
    eng = engine(a, ks=(1,), x_nnz_buckets=(8,))
    val2 = np.ones(2, np.float32)
    with pytest.raises(ValueError, match="outside"):
        eng.submit_sparse(np.array([0, 128], np.int64), val2)
    with pytest.raises(ValueError, match="strictly increasing"):
        eng.submit_sparse(np.array([5, 2], np.int64), val2)
    with pytest.raises(ValueError, match="strictly increasing"):  # duplicates
        eng.submit_sparse(np.array([3, 3], np.int64), val2)
    with pytest.raises(ValueError, match="integer"):
        eng.submit_sparse(np.array([0.0, 1.0]), val2)
    with pytest.raises(ValueError, match="1-D"):
        eng.submit_sparse(np.array([[0, 1]], np.int64), val2)
    with pytest.raises(ValueError, match="same length"):
        eng.submit_sparse(np.array([0, 1], np.int64), np.ones(3, np.float32))


def test_submit_sparse_strict_dtype_raises_instead_of_casting():
    _, a = small(seed=75)
    eng = engine(a, ks=(1,), x_nnz_buckets=(8,), strict_dtype=True)
    with pytest.raises(TypeError, match="float32"):
        eng.submit_sparse(
            np.array([1, 2], np.int64), np.ones(2, np.float64)
        )


def test_result_on_aborted_engine_raises_immediately():
    # PR 10 regression guard: close(drain=False) must fail every pending
    # future with the typed EngineClosedError, so a caller already parked
    # in result(timeout=...) returns in milliseconds -- not after the
    # full timeout, and never by hanging.
    import time

    from repro.runtime.overload import EngineClosedError

    _, a = small(seed=76)
    eng = engine(a, ks=(1, 4), max_wait_s=10.0)  # batch never fills: queued
    rng = np.random.default_rng(76)
    x = jnp.asarray(rng.standard_normal(a.shape[1]).astype(np.float32))
    req = eng.submit(x)
    eng.close(drain=False)
    t0 = time.perf_counter()
    with pytest.raises(EngineClosedError):
        req.result(timeout=30.0)
    assert time.perf_counter() - t0 < 1.0  # immediate, not timeout-bound
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(x)


def test_bucket_program_takes_prepared_arrays_as_arguments():
    """The fused bucket program must not carry the matrix as HLO constants:
    closed over by a plain jit, every prepared array would be copied into
    the program text (and into every compile) — at Table-1 sizes hundreds
    of MB per bucket.  The arrays enter as arguments instead."""
    d, a = small(m=512, density=0.2)
    eng = engine(a, ks=(4,))
    prog = eng._exec(4)
    zero = jnp.zeros((a.shape[1],), jnp.float32)
    text = prog.lower(*[zero] * 4).as_text()
    assert a.nnz * 4 > 150_000  # the data stream alone would show up
    assert len(text) < a.nnz * 4 // 2
    ys = np.asarray(prog(*[zero] * 4))
    assert ys.shape == (a.shape[0], 4) and not ys.any()


def test_summary_reports_x_loads_per_nnz_of_the_serving_plans():
    """Each dense bucket's summary line carries its plan's x row loads per
    nonzero: segment slots / nnz for lane-row SELL, stored slots / nnz for
    SELL; a tier without slots (CSR here) is left out."""
    from repro.tune import make

    d, a = small(seed=71)
    csr = SparseOperator.from_candidate(a, make("csr", "vector"), k=16)
    rng = np.random.default_rng(72)
    xs = [rng.standard_normal(a.shape[1]).astype(np.float32) for _ in range(3)]
    loads = {}
    for fmt, extra, slots in (("sell_lr", {}, "seg_row"),
                              ("sell", {"sigma": 1}, "cols")):
        op = SparseOperator.from_candidate(
            a, make(fmt, "pallas", C=8, chunk_tile=16, **extra))
        eng = SparseEngine(a, ks=(1, 16), ops={1: op, 16: csr})
        ys = eng.run(xs[:1]) + eng.run(xs[1:])  # a k = 1 and a k = 16 batch
        for x, y in zip(xs, ys):
            np.testing.assert_allclose(np.asarray(y), d @ x, atol=2e-3)
        summary = eng.stats.summary()
        assert summary["by_bucket"] == {1: 1, 16: 1}
        assert set(summary["x_loads_per_nnz"]) == {1}
        loads[fmt] = summary["x_loads_per_nnz"][1]
        assert loads[fmt] == pytest.approx(op._prep[slots].size / a.nnz,
                                           abs=1e-4)
    assert loads["sell_lr"] < loads["sell"]
