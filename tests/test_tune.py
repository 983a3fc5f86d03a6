"""repro.tune: plan-cache round-trip, cost-model pruning safety, and
SparseOperator correctness for every candidate plan."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import csr_from_dense
from repro.data.suite import generate
from repro.tune import (
    PlanCache,
    SparseOperator,
    enumerate_candidates,
    estimate_cost,
    extract,
    fingerprint,
    prepare,
    prune,
    runner,
    time_fn,
)


def small_csr(seed=0, m=96, n=96, density=0.08):
    rng = np.random.default_rng(seed)
    d = ((rng.random((m, n)) < density) * rng.standard_normal((m, n))).astype(
        np.float32
    )
    return d, csr_from_dense(d)


# ---------------------------------------------------------------------------
# Fingerprint + plan cache
# ---------------------------------------------------------------------------
def test_fingerprint_stable_and_structure_only():
    d, a = small_csr()
    assert fingerprint(a) == fingerprint(a)
    # Same pattern, different values -> same fingerprint (plans transfer).
    b = csr_from_dense(d)
    b.data = b.data * 3.0
    assert fingerprint(b) == fingerprint(a)
    # Different pattern -> different fingerprint.
    d2 = d.copy()
    d2[0, :5] = 1.0
    assert fingerprint(csr_from_dense(d2)) != fingerprint(a)


def test_plan_cache_roundtrip_and_hit_skips_timing(tmp_path):
    path = tmp_path / "plans.json"
    d, a = small_csr(seed=1)
    op = SparseOperator.build(a, cache=PlanCache(path), warmup=0, timed=1)
    assert not op.from_cache
    assert op.plan.n_measured >= 1
    assert op.measurements  # the search actually timed candidates

    # Fresh cache object re-reads the JSON file: round-trip through disk.
    op2 = SparseOperator.build(a, cache=PlanCache(path), warmup=0, timed=1)
    assert op2.from_cache
    assert op2.measurements == {}  # cache hit ran no timing at all
    assert op2.plan.candidate == op.plan.candidate
    assert op2.plan.fingerprint == fingerprint(a)

    x = np.random.default_rng(2).standard_normal(a.shape[1]).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(op2 @ jnp.asarray(x)), d @ x, atol=1e-3
    )


def test_force_search_ignores_cache(tmp_path):
    _, a = small_csr(seed=2)
    cache = PlanCache(tmp_path / "plans.json")
    SparseOperator.build(a, cache=cache, warmup=0, timed=1)
    op = SparseOperator.build(
        a, cache=cache, warmup=0, timed=1, force_search=True
    )
    assert not op.from_cache


# ---------------------------------------------------------------------------
# Cost-model pruning never drops the measured-best candidate
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["cant", "scircuit", "shallow_water1"])
def test_pruning_keeps_measured_best(name):
    """Pruning must never cost real performance: the best surviving candidate
    has to be within noise of the best *viable* measured candidate.

    Two deliberate exclusions, both scale artifacts of the 1/256 toy size:
    the scalar (-O1) tier and interpret-mode pallas are suppressed by the
    cost model BY DESIGN (SCALAR_SLOWDOWN / INTERPRET_SLOWDOWN — they lose
    catastrophically at serving scale), yet at a few hundred rows a
    sequential loop can beat XLA scatter overhead.  And near-tied survivors
    flap with scheduler jitter, so the assertion carries a noise factor —
    the same near-tie noise REPRO_TUNE_REPS exists for."""
    import repro.kernels.ops as kops

    a = generate(name, scale=1 / 256)
    feats = extract(a)
    cands = enumerate_candidates(feats)
    costs = {c: estimate_cost(a, c, feats) for c in cands}
    survivors = set(prune(costs))

    x = jnp.asarray(
        np.random.default_rng(0).standard_normal(a.shape[1]).astype(np.float32)
    )
    measured = {}
    for c in cands:
        if c.impl == "scalar" or (c.impl == "pallas" and kops.on_cpu()):
            continue  # suppressed by the model by design (see docstring)
        fn = runner(a, c, prepare(a, c))
        measured[c] = time_fn(fn, x, warmup=1, timed=3)
    best = min(measured, key=measured.get)
    viable_survivors = [measured[c] for c in survivors if c in measured]
    assert viable_survivors, (
        f"every pruning survivor is a suppressed impl: "
        f"{sorted(c.key() for c in survivors)}"
    )
    best_surviving = min(viable_survivors)
    assert best_surviving <= 1.5 * measured[best], (
        f"pruning dropped {best.key()} ({measured[best]*1e6:.0f}us) and the "
        f"best survivor is {best_surviving*1e6:.0f}us "
        f"(survivors: {sorted(c.key() for c in survivors)})"
    )


# ---------------------------------------------------------------------------
# SparseOperator matches the CSR oracle for every candidate plan
# ---------------------------------------------------------------------------
def test_operator_matches_oracle_for_every_spmv_candidate():
    d, a = small_csr(seed=3, m=100, n=80, density=0.1)  # non-square
    rng = np.random.default_rng(4)
    x = rng.standard_normal(80).astype(np.float32)
    ref = d @ x
    for cand in enumerate_candidates(extract(a)):
        op = SparseOperator.from_candidate(a, cand)
        got = np.asarray(op @ jnp.asarray(x))
        np.testing.assert_allclose(got, ref, atol=2e-3, err_msg=cand.key())


def test_operator_matches_oracle_for_every_spmm_candidate():
    k = 16
    d, a = small_csr(seed=5, m=64, n=96, density=0.15)
    rng = np.random.default_rng(6)
    X = rng.standard_normal((96, k)).astype(np.float32)
    ref = d @ X
    for cand in enumerate_candidates(extract(a, k=k), kind="spmm"):
        op = SparseOperator.from_candidate(a, cand, k=k)
        got = np.asarray(op @ jnp.asarray(X))
        np.testing.assert_allclose(got, ref, atol=5e-3, err_msg=cand.key())


def test_operator_matches_oracle_for_every_spmspv_candidate():
    """Every candidate in the sparse-RHS space — the spmspv tier AND the
    densify-wrapped dense tiers it competes with — matches the dense
    oracle on the same sparse operand."""
    d, a = small_csr(seed=51, m=100, n=80, density=0.1)  # non-square
    rng = np.random.default_rng(52)
    nx = 6
    idx = np.sort(rng.choice(80, size=nx, replace=False)).astype(np.int64)
    val = rng.standard_normal(nx).astype(np.float32)
    x_dense = np.zeros(80, np.float32)
    x_dense[idx] = val
    ref = d @ x_dense
    cands = enumerate_candidates(extract(a, x_nnz=nx), kind="spmspv")
    assert any(c.fmt == "spmspv" for c in cands)
    assert any(c.fmt != "spmspv" for c in cands)  # dense tiers compete too
    for cand in cands:
        op = SparseOperator.from_candidate(a, cand, x_nnz=nx)
        got = np.asarray(op.apply_sparse(idx, val))
        np.testing.assert_allclose(got, ref, atol=2e-3, err_msg=cand.key())
        # tuple dispatch through @ is the same path
        got2 = np.asarray(op @ (idx, val))
        np.testing.assert_allclose(got2, ref, atol=2e-3, err_msg=cand.key())


def test_spmspv_cost_model_crosses_over_with_density():
    """The byte model must prefer spmspv as x thins and the dense-RHS tiers
    as x fills — the measured search then only confirms the ranking."""
    from repro.tune.candidates import make
    from repro.tune.features import MatrixFeatures

    _, a = small_csr(seed=53, m=1024, n=1024, density=0.05)
    base = extract(a)
    spmspv = make("spmspv", "ref")
    csr = make("csr", "vector")

    import dataclasses

    thin = dataclasses.replace(base, x_density=0.001)
    full = dataclasses.replace(base, x_density=1.0)
    assert estimate_cost(a, spmspv, thin, sparse_rhs=True) < estimate_cost(
        a, csr, thin, sparse_rhs=True
    )
    assert estimate_cost(a, spmspv, full, sparse_rhs=True) > estimate_cost(
        a, csr, full, sparse_rhs=True
    )
    assert isinstance(base, MatrixFeatures)  # x_density rides the features


def test_spmspv_build_persists_plan_and_reloads(tmp_path):
    """build(x_nnz=B) is a measured search over the mixed space; the winning
    plan persists under kind="spmspv" keyed by the nnz bucket and a second
    build serves it from cache."""
    _, a = small_csr(seed=54)
    cache = PlanCache(tmp_path / "plans.json")
    op = SparseOperator.build(a, x_nnz=8, cache=cache, warmup=0, timed=1)
    assert op.plan.kind == "spmspv" and op.plan.k == 8
    assert not op.from_cache
    again = SparseOperator.build(
        a, x_nnz=8, cache=PlanCache(tmp_path / "plans.json")
    )
    assert again.from_cache and again.plan.candidate.key() == (
        op.plan.candidate.key()
    )


def test_feature_vector_has_x_density_axis_with_default():
    """PLAN_VERSION-6 feature schema: x_density is the trailing axis and
    dicts persisted before the axis existed default to dense (1.0)."""
    from repro.tune.features import FEATURE_NAMES, feature_vector

    assert FEATURE_NAMES[-1] == "x_density"
    _, a = small_csr(seed=55)
    feats = extract(a, x_nnz=12)
    d = feats.to_dict()
    assert d["x_density"] == pytest.approx(12 / 96)
    v = feature_vector(d)
    assert len(v) == len(FEATURE_NAMES)
    legacy = {k: val for k, val in d.items() if k != "x_density"}
    assert feature_vector(legacy)[-1] == 1.0


def test_rcm_candidates_enumerated_and_oracle_correct():
    """reorders=("rcm",) doubles the non-scalar space with permuted variants
    (square matrices only), and every reordered candidate matches the dense
    oracle through the facade's gather/scatter wrapping."""
    d, a = small_csr(seed=9)  # square
    feats = extract(a)
    base = enumerate_candidates(feats)
    cands = enumerate_candidates(feats, reorders=("rcm",))
    rcm_cands = [c for c in cands if c.param_dict.get("reorder") == "rcm"]
    assert len(rcm_cands) == sum(1 for c in base if c.impl != "scalar")
    assert len(cands) == len(base) + len(rcm_cands)
    # Off by default, and never enumerated for non-square shapes.
    assert all("reorder" not in c.param_dict for c in base)
    feats_rect = extract(csr_from_dense(np.asarray(d)[:64]))
    assert all(
        "reorder" not in c.param_dict
        for c in enumerate_candidates(feats_rect, reorders=("rcm",))
    )

    x = np.random.default_rng(10).standard_normal(a.shape[1]).astype(np.float32)
    ref = d @ x
    for cand in rcm_cands:
        op = SparseOperator.from_candidate(a, cand)
        got = np.asarray(op @ jnp.asarray(x))
        np.testing.assert_allclose(got, ref, atol=2e-3, err_msg=cand.key())


def test_plan_invalidates_on_backend_or_scale_mismatch(tmp_path):
    """Satellite: a plan is a point measurement at one (backend, scale);
    serving it elsewhere must be a cache miss, not a silent reuse."""
    _, a = small_csr(seed=11)
    cache = PlanCache(tmp_path / "plans.json")
    op = SparseOperator.build(a, cache=cache, warmup=0, timed=1)
    fp = fingerprint(a)
    m, n, nnz = a.shape[0], a.shape[1], a.nnz
    assert op.plan.backend != "" and op.plan.scale == [m, n, nnz]
    fresh = PlanCache(tmp_path / "plans.json")
    assert fresh.get(fp, "spmv", 1) is not None  # context-free fetch works
    hit = fresh.get(fp, "spmv", 1, backend=op.plan.backend, scale=[m, n, nnz])
    assert hit is not None
    assert fresh.get(fp, "spmv", 1, backend="not-a-backend") is None
    assert fresh.get(fp, "spmv", 1, scale=[m, n, nnz + 1]) is None
    # build() asserts its own context, so a poisoned entry re-searches.
    bad = hit
    bad.backend = "tpu"
    fresh.put(bad)
    op2 = SparseOperator.build(a, cache=PlanCache(tmp_path / "plans.json"),
                               warmup=0, timed=1)
    assert not op2.from_cache


def test_plan_topology_invalidation(tmp_path):
    """Satellite: same fingerprint, different mesh_shape -> miss (and mesh
    plans never shadow the single-device entry for the same (kind, k))."""
    from repro.tune import Plan

    _, a = small_csr(seed=13)
    fp = fingerprint(a)
    cache = PlanCache(tmp_path / "plans.json")
    plan = Plan(fingerprint=fp, kind="spmm", fmt="dist", impl="ring",
                params={"n_shards": 4}, est_cost=1.0, measured_s=1e-4,
                n_candidates=2, n_measured=2, k=4, backend="cpu",
                scale=[a.shape[0], a.shape[1], a.nnz], mesh_shape=[4])
    cache.put(plan)
    fresh = PlanCache(tmp_path / "plans.json")
    hit = fresh.get(fp, "spmm", 4, mesh_shape=[4])
    assert hit is not None and hit.candidate == plan.candidate
    assert fresh.get(fp, "spmm", 4, mesh_shape=[8]) is None  # topology change
    assert fresh.get(fp, "spmm", 4, mesh_shape=[2, 2]) is None
    assert fresh.get(fp, "spmm", 4) is None  # single-device lookup: no leak
    # The mesh build on a changed topology re-searches instead of reusing.
    import jax
    from jax.sharding import Mesh

    mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("shard",))
    op = SparseOperator.build(a, k=4, mesh=mesh1, cache=fresh,
                              warmup=0, timed=1)
    assert not op.from_cache and op.plan.mesh_shape == [1]
    op2 = SparseOperator.build(a, k=4, mesh=mesh1,
                               cache=PlanCache(tmp_path / "plans.json"))
    assert op2.from_cache  # same topology: table reload


def test_plan_version_bump_drops_old_entries_cleanly(tmp_path):
    """Satellite: a v2-era cache file (no mesh_shape field) must neither be
    served nor crash load/get/put — entries are dropped, then rewritten."""
    import json

    from repro.tune import PLAN_VERSION, Plan

    _, a = small_csr(seed=14)
    fp = fingerprint(a)
    path = tmp_path / "plans.json"
    v2_entry = {  # the PR-2 schema: no mesh_shape key at all
        "fingerprint": fp, "kind": "spmv", "fmt": "csr", "impl": "vector",
        "params": {}, "est_cost": 1.0, "measured_s": 1e-4,
        "n_candidates": 5, "n_measured": 3, "k": 1, "backend": "cpu",
        "scale": [a.shape[0], a.shape[1], a.nnz], "version": 2,
    }
    path.write_text(json.dumps({f"{fp}:spmv:k1": v2_entry,
                                "not-even-a-dict": 3}))
    cache = PlanCache(path)
    assert len(cache) == 0  # stale versions dropped at load
    assert cache.get(fp, "spmv", 1) is None
    plan = Plan(fingerprint=fp, kind="spmv", fmt="csr", impl="vector",
                params={}, est_cost=1.0, measured_s=1e-4, n_candidates=5,
                n_measured=3, k=1, backend="cpu",
                scale=[a.shape[0], a.shape[1], a.nnz])
    cache.put(plan)  # no KeyError/TypeError merging over the old file
    on_disk = json.loads(path.read_text())
    assert all(d.get("version") == PLAN_VERSION for d in on_disk.values())
    assert PlanCache(path).get(fp, "spmv", 1) is not None


def test_mesh_candidates_enumeration_and_collective_cost():
    """The schedule dimension: both schedules enumerate, their costs carry
    the collective term, and overlap makes the ring win at wide k / many
    shards while small meshes prefer the single-collective allgather."""
    from repro.tune import enumerate_mesh_candidates
    from repro.tune.candidates import make

    _, a = small_csr(seed=15)
    feats = extract(a)
    cands = enumerate_mesh_candidates(feats, 4)
    assert {c.impl for c in cands} == {"allgather", "ring"}
    assert all(c.fmt == "dist" and c.param_dict["n_shards"] == 4
               for c in cands)
    # Both survive pruning at this scale: the measured search decides.
    costs = {c: estimate_cost(a, c, feats, k=8) for c in cands}
    assert set(prune(costs)) == set(cands)
    # The cost model's structure, not its absolute numbers: allgather
    # serializes the collective with compute, the ring overlaps it — so the
    # ring wins once both streams dwarf its per-step launch overhead (large
    # problems), and loses on small ones where the P launches dominate.
    # The dist branch reads only (shape, nnz), so a shape stub suffices.
    import types

    big = types.SimpleNamespace(shape=(500_000, 500_000), nnz=50_000_000)
    ag_big = estimate_cost(big, make("dist", "allgather", n_shards=8),
                           feats, k=64)
    ring_big = estimate_cost(big, make("dist", "ring", n_shards=8),
                             feats, k=64)
    assert ring_big < ag_big
    small = types.SimpleNamespace(shape=(512, 512), nnz=4_000)
    ag_small = estimate_cost(small, make("dist", "allgather", n_shards=8),
                             feats, k=1)
    ring_small = estimate_cost(small, make("dist", "ring", n_shards=8),
                               feats, k=1)
    assert ag_small < ring_small


def test_spmm_search_space_has_sell_tier():
    """The k dimension grew into SELL: spmm enumeration carries sell/ref
    candidates (covered against the oracle by the sweep test above)."""
    _, a = small_csr(seed=12)
    cands = enumerate_candidates(extract(a, k=8), kind="spmm")
    assert any(c.fmt == "sell" and c.impl == "ref" for c in cands)
    assert not any(c.fmt == "sell" and c.impl == "pallas" for c in cands)


def test_built_operator_matches_oracle_spmv_and_spmm_fallback():
    d, a = small_csr(seed=7)
    op = SparseOperator.build(a, cache=PlanCache(), warmup=0, timed=1)
    rng = np.random.default_rng(8)
    x = rng.standard_normal(a.shape[1]).astype(np.float32)
    X = rng.standard_normal((a.shape[1], 4)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(op @ jnp.asarray(x)), d @ x, atol=1e-3)
    # spmv-tuned operator applied to a matrix: documented CSR fallback.
    np.testing.assert_allclose(np.asarray(op @ jnp.asarray(X)), d @ X, atol=1e-3)


def test_prepared_dicts_memoized_across_k_buckets_and_benchmarks():
    """Satellite: preparation depends on the matrix, never on k — one
    prepared-dict instance per (structure, values, candidate) serves every
    k-bucket and every from_candidate pin.  Same pattern with different
    values must NOT share (plans transfer across values; prepared data
    does not)."""
    from repro.core.formats import CSRMatrix
    from repro.tune import make
    from repro.tune.operator import _PREP_MEMO

    d, a = small_csr(seed=21)
    cand = make("merge", "scan", chunk=2048)
    op1 = SparseOperator.from_candidate(a, cand)  # k=1 (spmv)
    op16 = SparseOperator.from_candidate(a, cand, k=16)  # k=16 (spmm)
    assert op1._prep is op16._prep

    ops = SparseOperator.build_multi(
        a, ks=(1, 4), cache=PlanCache(), candidates=[cand],
        warmup=0, timed=1,
    )
    assert ops[1]._prep is op1._prep and ops[4]._prep is op1._prep

    b = CSRMatrix(a.shape, a.indptr, a.indices, a.data * 3.0)
    assert fingerprint(b) == fingerprint(a)  # same structure...
    opb = SparseOperator.from_candidate(b, cand)
    assert opb._prep is not op1._prep  # ...but values differ: no sharing
    x = np.random.default_rng(22).standard_normal(a.shape[1]).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(opb @ jnp.asarray(x)), 3.0 * (d @ x), atol=1e-2
    )
    assert _PREP_MEMO  # the memo is actually holding the shared instances


def test_time_fn_env_rep_floor(monkeypatch):
    """Satellite: REPRO_TUNE_REPS floors the rep count of every call (and
    forces at least one discarded warmup so the median never sees a
    compile); unset, explicit counts are untouched."""
    calls = []

    def fn():
        calls.append(1)

    monkeypatch.delenv("REPRO_TUNE_REPS", raising=False)
    time_fn(fn, warmup=0, timed=2)
    assert len(calls) == 2
    calls.clear()
    monkeypatch.setenv("REPRO_TUNE_REPS", "7")
    time_fn(fn, warmup=0, timed=2)
    assert len(calls) == 8  # 7 timed + 1 forced warmup
    calls.clear()
    monkeypatch.setenv("REPRO_TUNE_REPS", "not-a-number")
    time_fn(fn, warmup=1, timed=3)
    assert len(calls) == 4  # bad value ignored


def test_plan_version_6_drops_v5_entries_and_rebuilds(tmp_path):
    """Acceptance: the v6 bump (spmspv tier + x-density feature axis +
    densify term under sparse-RHS kinds) must drop v5-era entries at load —
    they were picked from a smaller space under the old model — and a fresh
    build repopulates the file at the current version (7 since lane-row
    SELL joined the space)."""
    import json

    from repro.tune import PLAN_VERSION

    assert PLAN_VERSION == 7
    _, a = small_csr(seed=23)
    fp = fingerprint(a)
    path = tmp_path / "plans.json"
    v5_entry = {  # PR-6/7 schema: solver_step present, predates spmspv
        "fingerprint": fp, "kind": "spmv", "fmt": "csr", "impl": "vector",
        "params": {}, "est_cost": 1.0, "measured_s": 1e-4,
        "n_candidates": 5, "n_measured": 3, "k": 1, "backend": "cpu",
        "scale": [a.shape[0], a.shape[1], a.nnz], "mesh_shape": [],
        "n_raced": 0, "version": 5,
    }
    path.write_text(json.dumps({f"{fp}:spmv:k1": v5_entry}))
    cache = PlanCache(path)
    assert len(cache) == 0 and cache.get(fp, "spmv", 1) is None
    op = SparseOperator.build(a, cache=cache, warmup=0, timed=1)
    assert not op.from_cache  # stale plan re-searched, not served
    on_disk = json.loads(path.read_text())
    assert all(e.get("version") == PLAN_VERSION for e in on_disk.values())
    # Restarted process reloads the rebuilt table without searching.
    assert SparseOperator.build(a, cache=PlanCache(path)).from_cache


# ---------------------------------------------------------------------------
# PR 5: candidate racing, plan-cache write safety, persistent executables
# ---------------------------------------------------------------------------
def test_time_fn_abort_above_races_out_slow_candidates():
    """Satellite: a candidate whose FIRST timed rep exceeds the bound is
    abandoned after one confirmation rep (inf, no further reps); a blip on
    the first rep alone does NOT abandon; a surviving candidate completes
    its full rep count, and racing forces a warmup so compile time can
    never trigger the abort."""
    import math
    import time as _time

    calls = []

    def slow():
        calls.append(1)
        _time.sleep(0.01)

    t = time_fn(slow, warmup=0, timed=5, abort_above=1e-6)
    assert math.isinf(t)
    # 1 forced warmup + 1 timed rep + 1 confirmation, 4 reps saved.
    assert len(calls) == 3
    calls.clear()
    t = time_fn(slow, warmup=0, timed=5, abort_above=1e9)
    assert math.isfinite(t) and len(calls) == 6  # survivor runs them all
    # A single slow blip does not abandon: first rep breaches, the
    # confirmation rep does not -> the candidate keeps measuring.
    calls.clear()
    # warmup rep, then a breaching first timed rep, then clean reps.
    durations = iter([0.0, 0.02] + [0.0] * 9)

    def blip():
        calls.append(1)
        _time.sleep(next(durations))

    t = time_fn(blip, warmup=0, timed=4, abort_above=5e-3)
    assert math.isfinite(t)  # survived the blip
    assert len(calls) == 6  # warmup + blip + confirmation + 3 further reps


def test_build_races_out_slow_candidates_on_suite_matrix():
    """Acceptance: cold-start build on a suite matrix abandons at least one
    survivor by racing (pruned-by-racing > 0), and the winner matches the
    un-raced search."""
    import math

    a = generate("cant", scale=1 / 256)
    raced = SparseOperator.build(a, cache=PlanCache(), warmup=0, timed=3,
                                 prune_factor=1e9, force_search=True)
    assert raced.plan.n_raced > 0  # cold-start search latency actually cut
    assert sum(math.isinf(t) for t in raced.measurements.values()) \
        == raced.plan.n_raced
    # The winner is a completed (finite) measurement — racing can only
    # abandon candidates at least RACE_FACTOR x slower than a finished one,
    # so the returned plan always carries a real median.
    assert math.isfinite(raced.plan.measured_s)
    assert raced.measurements[raced.plan.candidate.key()] == min(
        t for t in raced.measurements.values() if math.isfinite(t)
    )
    full = SparseOperator.build(a, cache=PlanCache(), warmup=0, timed=3,
                                prune_factor=1e9, force_search=True,
                                race=False)
    assert full.plan.n_raced == 0  # opt-out really disables racing
    assert all(math.isfinite(t) for t in full.measurements.values())


def test_plan_cache_concurrent_puts_do_not_clobber(tmp_path):
    """Satellite: two engines sharing the on-disk cache persist through the
    locked merge-then-replace — a second cache's put never clobbers a plan
    the first persisted after the second one loaded."""
    from repro.tune.plan import Plan

    path = tmp_path / "plans.json"

    def plan_for(fp, kind="spmv", k=1):
        return Plan(fingerprint=fp, kind=kind, fmt="csr", impl="vector",
                    params={}, est_cost=1.0, measured_s=1e-4,
                    n_candidates=1, n_measured=1, k=k, backend="cpu",
                    scale=[4, 4, 4])

    c1 = PlanCache(path)
    c2 = PlanCache(path)  # loaded BEFORE c1 persists anything (empty view)
    c1.put(plan_for("aaaa"))
    c2.put(plan_for("bbbb"))  # merge-on-put must pick up c1's entry
    reread = PlanCache(path)
    assert reread.get("aaaa", "spmv", 1) is not None
    assert reread.get("bbbb", "spmv", 1) is not None
    # Interleaved writes in the other direction survive too.
    c1.put(plan_for("cccc"))
    reread = PlanCache(path)
    assert {p for p in ("aaaa", "bbbb", "cccc")
            if reread.get(p, "spmv", 1) is not None} == {"aaaa", "bbbb", "cccc"}
    # The sidecar lock is left behind but never read as cache content.
    assert (tmp_path / "plans.json.lock").exists()


def test_aot_executable_matches_dispatch_and_supports_donation():
    """SparseOperator.aot lowers once to a persistent executable that agrees
    bitwise with the facade dispatch; donate_rhs consumes the operand."""
    d, a = small_csr(seed=31)
    op = SparseOperator.build(a, cache=PlanCache(), warmup=0, timed=1)
    x = jnp.asarray(np.random.default_rng(32)
                    .standard_normal(a.shape[1]).astype(np.float32))
    fn = op.aot()
    assert fn is op.aot()  # lowered once, cached
    assert np.array_equal(np.asarray(fn(x)), np.asarray(op @ x))
    # k>1 plan: the executable takes the (n, k) slab.
    op4 = SparseOperator.build(a, k=4, cache=PlanCache(), warmup=0, timed=1)
    X = jnp.asarray(np.random.default_rng(33)
                    .standard_normal((a.shape[1], 4)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(op4.aot()(X)),
                               np.asarray(op4 @ X), atol=0)
    # Donation-aware pin: the executable is pre-lowered and the donated
    # operand is consumed (deleted) after the call on backends that alias.
    cand = op4.plan.candidate
    opd = SparseOperator.from_candidate(a, cand, k=4, donate_rhs=True)
    Xd = jnp.asarray(np.asarray(X))
    y = opd.aot(donate_rhs=True)(Xd)
    np.testing.assert_allclose(np.asarray(y), np.asarray(op4 @ X), atol=1e-6)


# ---------------------------------------------------------------------------
# Transfer tuning: persisted features, prediction, prep-memo byte budget
# ---------------------------------------------------------------------------
def test_plan_features_persist_and_old_entries_load_cleanly(tmp_path):
    """Measured plans persist their feature vector (the transfer training
    set); a pre-PR-7 entry WITHOUT the field still loads — schema-additive,
    same PLAN_VERSION, treated as not-a-training-point rather than dropped."""
    import json

    from repro.tune import feature_vector

    path = tmp_path / "plans.json"
    d, a = small_csr(seed=40)
    op = SparseOperator.build(a, cache=PlanCache(path), warmup=0, timed=1)
    assert op.plan.features is not None
    assert feature_vector(op.plan.features) is not None

    # Round-trip through disk: features survive JSON.
    reread = PlanCache(path)
    plan = reread.get(fingerprint(a), "spmv", 1)
    assert plan is not None and plan.features == op.plan.features
    assert plan.predicted_from == ""  # measured plans never carry a source

    # Simulate a pre-PR-7 cache entry: strip the additive fields on disk.
    raw = json.loads(path.read_text())
    for v in raw.values():
        v.pop("features", None)
        v.pop("predicted_from", None)
    path.write_text(json.dumps(raw))
    legacy = PlanCache(path)
    old = legacy.get(fingerprint(a), "spmv", 1)
    assert old is not None  # loads cleanly: a cache HIT, not a re-search
    assert old.features is None and old.version == plan.version
    assert legacy.plans()  # and enumerates without crashing
    # ... it is simply unusable as a training point:
    from repro.tune import predict_candidate

    pred = predict_candidate(a, "spmv", 1, legacy)
    assert pred.source == "byte_model" and pred.n_neighbors == 0


def test_predict_transfers_within_radius_and_falls_back_beyond():
    from repro.tune import predict_candidate

    cache = PlanCache()
    d, a = small_csr(seed=41)
    op = SparseOperator.build(a, cache=cache, warmup=0, timed=1)
    _, b = small_csr(seed=42)  # same family: close in feature space

    pred = predict_candidate(b, "spmv", 1, cache)
    assert pred.confident and pred.source == fingerprint(a)
    assert pred.candidate.key() == op.plan.candidate.key()
    # Excluding the only neighbor forces the byte-model prior.
    alone = predict_candidate(b, "spmv", 1, cache,
                              exclude={fingerprint(a)})
    assert not alone.confident and alone.source == "byte_model"
    # A vanishing radius also rejects the neighbor (distance recorded).
    far = predict_candidate(b, "spmv", 1, cache, radius=0.0)
    assert not far.confident and far.source == "byte_model"
    assert np.isfinite(far.distance)


def test_build_predicted_never_persists_and_marks_provenance():
    cache = PlanCache()
    d, a = small_csr(seed=43)
    # Empty cache: byte-model fallback, nothing persisted.
    op = SparseOperator.build_predicted(a, cache=cache)
    assert op.plan.predicted_from == "byte_model"
    assert op.plan.measured_s == 0.0 and op.plan.n_measured == 0
    assert len(cache) == 0  # predicted plans NEVER enter the cache
    x = np.random.default_rng(44).standard_normal(a.shape[1]).astype(np.float32)
    np.testing.assert_allclose(np.asarray(op @ jnp.asarray(x)), d @ x,
                               atol=2e-3)

    # Train the cache, then: exact hit wins over prediction...
    measured = SparseOperator.build(a, cache=cache, warmup=0, timed=1)
    hit = SparseOperator.build_predicted(a, cache=cache)
    assert hit.from_cache and hit.predicted is None
    assert hit.plan.candidate == measured.plan.candidate
    # ... and a sibling fingerprint transfers with provenance recorded.
    _, b = small_csr(seed=45)
    sib = SparseOperator.build_predicted(b, cache=cache)
    assert sib.plan.predicted_from == fingerprint(a)
    assert sib.predicted is not None and sib.predicted.confident
    assert len(cache) == 1  # still only the measured plan


def test_prep_cache_byte_budget_evicts_lru_and_counts():
    from repro.tune import PrepCache, make, prep_nbytes, prepare

    d, a = small_csr(seed=46)
    cands = [make("csr", "vector"), make("csr", "gather"),
             make("sell", "ref", C=8, sigma=64)]
    preps = [prepare(a, c) for c in cands]
    per = [prep_nbytes(p) for p in preps]
    assert all(b > 0 for b in per)

    # Budget is one byte short of all three: inserting the third evicts
    # exactly the least-recently-used entry.
    pc = PrepCache(budget_bytes=per[0] + per[1] + per[2] - 1)
    for i, c in enumerate(cands[:2]):
        assert pc.get_or_build((fingerprint(a), i), lambda i=i: preps[i]) is preps[i]
    assert pc.stats()["misses"] == 2 and len(pc) == 2
    # Touch entry 0 so entry 1 is the least-recently-used.
    pc.get_or_build((fingerprint(a), 0), lambda: None)
    assert pc.stats()["hits"] == 1
    pc.get_or_build((fingerprint(a), 2), lambda: preps[2])
    s = pc.stats()
    assert s["evictions"] >= 1 and s["resident_bytes"] <= pc.budget_bytes
    assert pc.get_or_build((fingerprint(a), 0), lambda: "rebuilt") is preps[0]

    # An over-budget single prep is still served (never refused), and
    # evict_fp drops every entry of a fingerprint, returning bytes freed.
    tiny = PrepCache(budget_bytes=1)
    assert tiny.get_or_build(("fp", 0), lambda: preps[0]) is preps[0]
    assert len(tiny) == 1  # the just-inserted entry is never self-evicted
    freed = tiny.evict_fp("fp")
    assert freed == per[0] and len(tiny) == 0


def test_prepare_cached_respects_global_budget_counters():
    from repro.tune import make, prep_memo_stats, prepare_cached

    d, a = small_csr(seed=47)
    before = prep_memo_stats()
    c = make("csr", "gather")
    p1 = prepare_cached(a, c)
    p2 = prepare_cached(a, c)
    assert p1 is p2  # memo hit
    after = prep_memo_stats()
    assert after["hits"] >= before["hits"] + 1
    assert after["misses"] >= before["misses"]
    assert after["resident_bytes"] >= 0 and after["budget_bytes"] > 0


# ---------------------------------------------------------------------------
# Failures are recorded, never swallowed; the TPU space follows size rules
# ---------------------------------------------------------------------------
def test_search_records_failed_candidate_on_plan(tmp_path):
    """A candidate that raises in the measured search loses, but is warned
    about and recorded on the plan (and in the persisted cache entry) with
    its error, so a run can refuse a winner that only won by default."""
    from repro.tune import make

    _, a = small_csr(seed=5)
    broken = make("sell_blocked", "ref", C=8, sigma=64, n_slabs=2)  # SpMV-only
    cache = PlanCache(tmp_path / "plans.json")
    with pytest.warns(RuntimeWarning, match="sell_blocked/ref"):
        op = SparseOperator.build(
            a, k=8, cache=cache, warmup=0, timed=1,
            candidates=[make("csr", "vector"), broken],
        )
    assert op.plan.candidate.key() == "csr/vector"
    assert set(op.plan.failed) == {broken.key()}
    assert op.measurements[broken.key()] == float("inf")
    reloaded = PlanCache(tmp_path / "plans.json").get(
        op.plan.fingerprint, "spmm", 8
    )
    assert reloaded.failed == op.plan.failed


def test_tpu_enumeration_applies_kernel_size_rules(monkeypatch):
    """On TPU, Pallas candidates are enumerated only where their on-chip
    operands fit (and BCSR only with lane-dense (8, 128) blocks); the
    column-slab SELL tier is SpMV-only on every backend."""
    import dataclasses

    import jax

    from repro.tune.candidates import tpu_pallas_fits

    _, a = small_csr(seed=6)
    feats = extract(a)
    sparse_feats = extract(a, x_nnz=8)
    cpu = enumerate_candidates(feats)
    # From here on the enumeration sees a TPU backend.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tpu = enumerate_candidates(feats)
    assert [c for c in cpu if c.impl != "pallas"] == [
        c for c in tpu if c.impl != "pallas"
    ]
    tpu_pallas = {c.key() for c in tpu if c.impl == "pallas"}
    assert "bcsr/pallas[block=(8, 128)]" in tpu_pallas
    assert not any(k.startswith("bcsr/pallas[block=(8, 8)") for k in tpu_pallas)
    assert any(k.startswith("sell/pallas") for k in tpu_pallas)
    assert all(tpu_pallas_fits(c, feats) for c in tpu if c.impl == "pallas")
    sparse = enumerate_candidates(sparse_feats, "spmspv")
    assert any(c.key().startswith("spmspv/pallas") for c in sparse)

    # A 10M-row operand: its lane-dense vectors exceed the VMEM budget, so
    # no SELL kernel is enumerated (BCSR keeps only a band-sized x window,
    # until the band is as wide as the matrix), and a 5000-wide SELL tile
    # would not fit SMEM either.
    huge = dataclasses.replace(feats, m=10_000_000, n=10_000_000,
                               x_bytes=40_000_000, x_fits_vmem=False)
    big = enumerate_candidates(huge)
    assert {c.fmt for c in big if c.impl == "pallas"} == {"bcsr"}
    assert any(c.fmt == "sell_blocked" for c in big)
    banded = dataclasses.replace(huge, bandwidth=10_000_000)
    assert not [c for c in enumerate_candidates(banded)
                if c.impl == "pallas"]
    wide = dataclasses.replace(feats, nnz_row_max=5000)
    assert not any(c.fmt == "sell" and c.impl == "pallas"
                   for c in enumerate_candidates(wide))
    spmm = enumerate_candidates(huge, "spmm", k=16)
    assert not any(c.fmt == "sell_blocked" for c in spmm)


def test_tpu_cost_model_prices_lane_padding_of_narrow_xla_bcsr():
    """On TPU the XLA bcsr tier's (8, 8) blocks and (8, k) tiles are laid
    out in (8, 128) tiles; the byte model charges that padding there (it
    asked for 33 GB of a 16 GB chip on webbase-1M at k = 16), so the narrow
    blocks are pruned against merge/scan, while the CPU estimate is
    unchanged."""
    from repro.tune import make
    from repro.tune.candidates import estimate_cost, prune

    a = generate("webbase-1M", scale=0.01, seed=0)
    feats = extract(a)
    narrow = make("bcsr", "ref", block=(8, 8))
    merge = make("merge", "scan", chunk=2048)
    cpu = {c: estimate_cost(a, c, feats, k=16, on_cpu=True)
           for c in (narrow, merge)}
    tpu = {c: estimate_cost(a, c, feats, k=16, on_cpu=False)
           for c in (narrow, merge)}
    assert tpu[merge] == cpu[merge]
    assert tpu[narrow] > 10 * tpu[merge] and tpu[narrow] > cpu[narrow]
    assert prune(tpu) == [merge]


# ---------------------------------------------------------------------------
# Lane-row SELL in the search space
# ---------------------------------------------------------------------------
SELL_LR_KEY = "sell_lr/pallas[C=8,chunk_tile=16]"


def _on_tpu(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_sell_lr_enumerated_and_survives_prune_on_banded(monkeypatch):
    """On a banded FEM surrogate (ldoor's generator) the tier is enumerated
    at k = 1 for spmv and solver_step, and the TPU byte model, charging it
    by x accesses, keeps it through prune: there it is the cheapest."""
    from repro.tune.candidates import tpu_pallas_fits

    a = generate("ldoor", scale=0.01, seed=0)
    feats = extract(a)
    assert 1 <= feats.lanerow_max <= 8
    _on_tpu(monkeypatch)
    for kind in ("spmv", "solver_step"):
        cands = enumerate_candidates(feats, kind, k=1)
        assert SELL_LR_KEY in {c.key() for c in cands}, kind
    assert not any(c.fmt == "sell_lr" for c in
                   enumerate_candidates(feats, "spmm", k=16))
    assert not any(c.fmt == "sell_lr" for c in
                   enumerate_candidates(feats, "spmv", include_pallas=False))
    cands = enumerate_candidates(feats, "spmv")
    lr = next(c for c in cands if c.key() == SELL_LR_KEY)
    assert tpu_pallas_fits(lr, feats)
    costs = {c: estimate_cost(a, c, feats, on_cpu=False) for c in cands}
    assert lr in prune(costs)
    assert costs[lr] == min(costs.values())
    # Interpret mode on the CPU prices it out, like every Pallas tier.
    cpu = {c: estimate_cost(a, c, feats, on_cpu=True) for c in cands}
    assert lr not in prune(cpu)


def test_sell_lr_excluded_by_smem_rule_on_powerlaw(monkeypatch):
    """A power-law surrogate's longest rows touch hundreds of lane-rows, so
    a tile of seg_row would not fit SMEM: the tier is not enumerated on
    TPU, as sell/pallas is not."""
    from repro.tune.candidates import SMEM_BUDGET_BYTES

    a = generate("webbase-1M", scale=0.5, seed=0)
    feats = extract(a)
    assert 2 * 4 * 16 * 8 * feats.lanerow_max > SMEM_BUDGET_BYTES
    _on_tpu(monkeypatch)
    keys = {c.key() for c in enumerate_candidates(feats, "spmv")}
    assert SELL_LR_KEY not in keys
    assert not any(k.startswith("sell/pallas") for k in keys)


def test_sell_lr_excluded_by_hbm_rule_above_4gib(monkeypatch):
    """ldoor's shape: W_seg 8 needs 3.9 GB of value rows and is admitted;
    W_seg 24 (the pattern of A + A^T, as CG's SPD shift has) needs 11.7 GB,
    over HBM_BUDGET_BYTES, and is not, though its SMEM tile fits."""
    import dataclasses

    from repro.tune import make
    from repro.tune.candidates import (
        HBM_BUDGET_BYTES, SMEM_BUDGET_BYTES, tpu_pallas_fits,
    )

    _, a = small_csr(seed=62)
    ldoor = dataclasses.replace(extract(a), m=952_203, n=952_203,
                                x_bytes=952_203 * 4, lanerow_max=8)
    lr = make("sell_lr", "pallas", C=8, chunk_tile=16)
    assert tpu_pallas_fits(lr, ldoor)
    assert 119_040 * 8 * 8 * 512 <= HBM_BUDGET_BYTES
    wide = dataclasses.replace(ldoor, lanerow_max=20)
    assert 2 * 4 * 16 * 8 * 24 <= SMEM_BUDGET_BYTES
    assert 119_040 * 8 * 24 * 512 > HBM_BUDGET_BYTES
    assert not tpu_pallas_fits(lr, wide)
    _on_tpu(monkeypatch)
    assert SELL_LR_KEY in {c.key() for c in enumerate_candidates(ldoor)}
    assert SELL_LR_KEY not in {c.key() for c in enumerate_candidates(wide)}


# Sums of every estimate (TPU and CPU model, spmv at k = 1 and spmm at
# k = 16) of the tiers that were there before lane-row SELL, from the
# previous release's byte model: adding the tier changed none of them.
PRE_SELL_LR_ESTIMATE_SUMS = {
    ("cant", "spmv", False): (218297577.51698193, 19),
    ("cant", "spmv", True): (10000134473.516983, 19),
    ("cant", "spmm", False): (60105101.12626328, 12),
    ("cant", "spmm", True): (3345366157.1262636, 12),
    ("scircuit", "spmv", False): (304968675.65158826, 19),
    ("scircuit", "spmv", True): (10993250219.651588, 19),
    ("scircuit", "spmm", False): (146199248.44067705, 12),
    ("scircuit", "spmm", True): (4202971192.4406767, 12),
    ("webbase-1M", "spmv", False): (1030340347.5168276, 19),
    ("webbase-1M", "spmv", True): (20895212051.516827, 19),
    ("webbase-1M", "spmm", False): (864600409.6593407, 12),
    ("webbase-1M", "spmm", True): (12683728433.65934, 12),
}


@pytest.mark.parametrize("name", ["cant", "scircuit", "webbase-1M"])
def test_other_tiers_cost_estimates_unchanged_by_sell_lr(name):
    a = generate(name, scale=1 / 256, seed=0)
    feats = extract(a)
    for kind, k in (("spmv", 1), ("spmm", 16)):
        others = [c for c in enumerate_candidates(feats, kind, k=k)
                  if c.fmt != "sell_lr"]
        for on_cpu in (False, True):
            total = sum(estimate_cost(a, c, feats, k=k, on_cpu=on_cpu)
                        for c in others)
            want, count = PRE_SELL_LR_ESTIMATE_SUMS[(name, kind, on_cpu)]
            assert len(others) == count
            assert total == pytest.approx(want, rel=1e-12), (kind, on_cpu)


def test_plan_version_7_drops_v6_entries_and_rebuilds(tmp_path):
    """The v7 bump (lane-row SELL in the k = 1 space): a v6 plan never
    measured the tier, so it is dropped at load and the search runs again."""
    import json

    from repro.tune import PLAN_VERSION

    assert PLAN_VERSION == 7
    _, a = small_csr(seed=63)
    fp = fingerprint(a)
    path = tmp_path / "plans.json"
    v6_entry = {
        "fingerprint": fp, "kind": "spmv", "fmt": "sell", "impl": "pallas",
        "params": {"C": 8, "sigma": 1, "chunk_tile": 16}, "est_cost": 1.0,
        "measured_s": 1e-4, "n_candidates": 19, "n_measured": 9, "k": 1,
        "backend": "cpu", "scale": [a.shape[0], a.shape[1], a.nnz],
        "mesh_shape": [], "n_raced": 0, "features": None,
        "predicted_from": "", "failed": {}, "version": 6,
    }
    path.write_text(json.dumps({f"{fp}:spmv:k1": v6_entry}))
    cache = PlanCache(path)
    assert len(cache) == 0 and cache.get(fp, "spmv", 1, backend="cpu") is None
    op = SparseOperator.build(a, cache=cache, warmup=0, timed=1)
    assert not op.from_cache
    assert op.plan.features["lanerow_max"] == int(
        __import__("repro.kernels.ops", fromlist=["lanerow_counts"])
        .lanerow_counts(a).max())
    on_disk = json.loads(path.read_text())
    assert [e["version"] for e in on_disk.values()] == [7]
