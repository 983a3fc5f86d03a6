"""Edge cases for the kernel prep/dispatch layers, all against the dense
oracle: empty matrices and trailing empty rows (_rows_from_indptr), column
slabs that receive zero nonzeros (sell_prepare_blocked), all-empty block
rows (bcsr_prepare), pathological row-length distributions (empty rows, one
fully-dense row, power-law nnz) swept across every enumerated candidate
including the merge tier — plus regression tests that the vectorized
searchsorted slab split equals the original python row loop and that the
prepared CSR hot path carries no per-dispatch searchsorted."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import bcsr_from_csr, csr_from_dense
from repro.core.spmv import (
    _rows_from_indptr,
    csr_prepare,
    spmm_csr,
    spmv_csr,
    spmv_csr_scalar,
)
from repro.kernels import ops as kops
from repro.kernels.merge_spmv import merge_prepare, merge_spmm, merge_spmv


# ---------------------------------------------------------------------------
# _rows_from_indptr
# ---------------------------------------------------------------------------
def test_rows_from_indptr_empty_matrix():
    a = csr_from_dense(np.zeros((5, 7), np.float32))
    rows = _rows_from_indptr(jnp.asarray(a.indptr), 0, 5)
    assert rows.shape == (0,)
    x = np.ones(7, np.float32)
    for fn in (spmv_csr, spmv_csr_scalar):
        y = np.asarray(fn(a.device(), jnp.asarray(x), n_rows=5))
        np.testing.assert_allclose(y, np.zeros(5), err_msg=fn.__name__)


def test_rows_from_indptr_trailing_empty_rows():
    d = np.zeros((6, 4), np.float32)
    d[0, 1] = 2.0
    d[2, 3] = -1.0  # rows 1, 3, 4, 5 empty; trailing run of empties
    a = csr_from_dense(d)
    rows = np.asarray(_rows_from_indptr(jnp.asarray(a.indptr), a.nnz, 6))
    np.testing.assert_array_equal(rows, [0, 2])
    x = np.arange(1, 5, dtype=np.float32)
    for fn in (spmv_csr, spmv_csr_scalar):
        y = np.asarray(fn(a.device(), jnp.asarray(x), n_rows=6))
        np.testing.assert_allclose(y, d @ x, atol=1e-5, err_msg=fn.__name__)


# ---------------------------------------------------------------------------
# sell_prepare_blocked with empty slabs
# ---------------------------------------------------------------------------
def test_sell_blocked_slabs_with_zero_nonzeros():
    rng = np.random.default_rng(0)
    d = np.zeros((32, 64), np.float32)
    # All nonzeros in the first 16 columns -> slabs 2..4 of 4 are empty.
    d[:, :16] = ((rng.random((32, 16)) < 0.3)
                 * rng.standard_normal((32, 16))).astype(np.float32)
    a = csr_from_dense(d)
    x = rng.standard_normal(64).astype(np.float32)
    prep = kops.sell_prepare_blocked(a, n_slabs=4)
    y = np.asarray(kops.sell_spmv_blocked(prep, jnp.asarray(x)))
    np.testing.assert_allclose(y, d @ x, atol=1e-4)


def test_sell_blocked_fully_empty_matrix():
    a = csr_from_dense(np.zeros((16, 24), np.float32))
    prep = kops.sell_prepare_blocked(a, n_slabs=3)
    y = np.asarray(kops.sell_spmv_blocked(prep, jnp.ones(24, jnp.float32)))
    np.testing.assert_allclose(y, np.zeros(16))


# ---------------------------------------------------------------------------
# bcsr_prepare with all-empty block rows
# ---------------------------------------------------------------------------
def test_bcsr_prepare_all_empty_block_rows():
    d = np.zeros((16, 16), np.float32)
    a = csr_from_dense(d)
    b = bcsr_from_csr(a, (8, 8))
    assert b.n_blocks == 0
    prep = kops.bcsr_prepare(b)
    # Every block row got one explicit zero fill-in block, and the stream
    # is padded (with zero blocks) to whole kernel slabs.
    assert set(np.asarray(prep["block_rows"]).tolist()) == {0, 1}
    assert prep["blocks"].shape[0] == kops.BLOCK_TILE
    assert not np.asarray(prep["blocks"]).any()
    X = np.random.default_rng(1).standard_normal((16, 4)).astype(np.float32)
    out = np.asarray(kops.bcsr_spmm(prep, jnp.asarray(X)))
    np.testing.assert_allclose(out, np.zeros((16, 4)))


def test_bcsr_prepare_some_empty_block_rows_vs_dense():
    rng = np.random.default_rng(2)
    d = np.zeros((40, 24), np.float32)
    # Rows 8..15 and 32..39 stay all-zero -> block rows 1 and 4 empty (bm=8).
    for r0 in (0, 16, 24):
        d[r0 : r0 + 8] = ((rng.random((8, 24)) < 0.4)
                          * rng.standard_normal((8, 24))).astype(np.float32)
    a = csr_from_dense(d)
    b = bcsr_from_csr(a, (8, 8))
    gm, _ = b.grid_shape
    assert len(np.unique(b.block_rows)) < gm  # some block rows are empty
    prep = kops.bcsr_prepare(b)
    X = rng.standard_normal((24, 8)).astype(np.float32)
    out = np.asarray(kops.bcsr_spmm(prep, jnp.asarray(X)))
    np.testing.assert_allclose(out, d @ X, atol=1e-4)


# ---------------------------------------------------------------------------
# Vectorized slab split == original row loop
# ---------------------------------------------------------------------------
def test_sell_prepare_blocked_vectorized_matches_loop():
    rng = np.random.default_rng(3)
    d = ((rng.random((48, 96)) < 0.12) * rng.standard_normal((48, 96))).astype(
        np.float32
    )
    d[10:20] = 0.0  # a run of empty rows
    d[:, 60:] = 0.0  # empty trailing slabs
    a = csr_from_dense(d)
    for n_slabs in (1, 3, 5):
        fast = kops.sell_prepare_blocked(a, n_slabs, chunk_tile=8, C=8, sigma=16)
        slow = kops._sell_prepare_blocked_loop(a, n_slabs, chunk_tile=8, C=8,
                                               sigma=16)
        np.testing.assert_array_equal(fast["bounds"], slow["bounds"])
        assert fast["shape"] == slow["shape"]
        assert len(fast["slabs"]) == len(slow["slabs"])
        for s, (fs, ss) in enumerate(zip(fast["slabs"], slow["slabs"])):
            for key in ("cols", "vals", "row_perm"):
                np.testing.assert_array_equal(
                    np.asarray(fs[key]), np.asarray(ss[key]),
                    err_msg=f"slab {s} key {key} (n_slabs={n_slabs})",
                )


# ---------------------------------------------------------------------------
# Hoisted row map: no per-dispatch searchsorted on the prepared CSR path
# ---------------------------------------------------------------------------
def test_csr_prepare_hoists_row_map_out_of_dispatch():
    rng = np.random.default_rng(5)
    d = ((rng.random((48, 40)) < 0.15) * rng.standard_normal((48, 40))).astype(
        np.float32
    )
    a = csr_from_dense(d)
    prep = csr_prepare(a)
    np.testing.assert_array_equal(
        np.asarray(prep["rows"]),
        np.repeat(np.arange(48), np.diff(a.indptr)),
    )
    x = jnp.asarray(rng.standard_normal(40).astype(np.float32))
    X = jnp.asarray(rng.standard_normal((40, 4)).astype(np.float32))
    # The prepared-dict program must not re-derive the row map per dispatch.
    jpr_v = str(jax.make_jaxpr(lambda p, v: spmv_csr(p, v, n_rows=48))(prep, x))
    jpr_m = str(jax.make_jaxpr(lambda p, v: spmm_csr(p, v, n_rows=48))(prep, X))
    assert "searchsorted" not in jpr_v
    assert "searchsorted" not in jpr_m
    # Raw-dict callers keep working through the compat shim (which does).
    raw = a.device()
    jpr_raw = str(jax.make_jaxpr(lambda p, v: spmv_csr(p, v, n_rows=48))(raw, x))
    assert "searchsorted" in jpr_raw
    for fn, ref in ((spmv_csr, d @ np.asarray(x)),):
        np.testing.assert_allclose(
            np.asarray(fn(prep, x, n_rows=48)), ref, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(fn(raw, x, n_rows=48)), ref, atol=1e-4
        )
    np.testing.assert_allclose(
        np.asarray(spmm_csr(prep, X, n_rows=48)), d @ np.asarray(X), atol=1e-4
    )


# ---------------------------------------------------------------------------
# Merge tier edges
# ---------------------------------------------------------------------------
def test_merge_empty_matrix_and_oversized_chunk():
    a = csr_from_dense(np.zeros((6, 9), np.float32))
    prep = merge_prepare(a, chunk=4096)  # chunk >> nnz: one padded chunk
    y = np.asarray(merge_spmv(prep, jnp.ones(9, jnp.float32)))
    np.testing.assert_allclose(y, np.zeros(6))
    d = np.zeros((5, 4), np.float32)
    d[2, 1] = 3.0
    a2 = csr_from_dense(d)
    prep2 = merge_prepare(a2, chunk=1)  # chunk of one: all-boundary rows
    x = np.arange(1.0, 5.0, dtype=np.float32)
    np.testing.assert_allclose(
        np.asarray(merge_spmv(prep2, jnp.asarray(x))), d @ x, atol=1e-6
    )
    X = np.stack([x, 2 * x], axis=1)
    np.testing.assert_allclose(
        np.asarray(merge_spmm(prep2, jnp.asarray(X))), d @ X, atol=1e-6
    )


@pytest.mark.parametrize("chunk,k", [(64, 1), (64, 4), (2048, 1), (2048, 4)])
def test_merge_rows_are_accurate_elementwise_on_powerlaw(chunk, k):
    """Each row's error stays within float32 rounding of (|A| |x|)_i.

    Regression: rows were differences of one global prefix-sum table, so a
    short row late in the stream inherited the rounding of the whole
    prefix (off by 1e-2 of (|A| |x|)_i here, and by half of it at
    webbase-1M's full size).  chunk=64 makes the long rows straddle many
    chunks, exercising the carry."""
    import scipy.sparse as sp

    from repro.data.suite import generate

    a = generate("webbase-1M", scale=0.01, seed=0)
    a64 = sp.csr_matrix(
        (a.data.astype(np.float64), a.indices, a.indptr), shape=a.shape
    )
    X = np.random.default_rng(3).standard_normal((a.shape[1], k))
    X = X.astype(np.float32)
    got = np.asarray(merge_spmm(merge_prepare(a, chunk), jnp.asarray(X)))
    ref = a64 @ X.astype(np.float64)
    bound = abs(a64) @ np.abs(X.astype(np.float64))
    assert np.all(np.abs(got - ref) <= 1e-5 * bound + 1e-30)


# ---------------------------------------------------------------------------
# Pathological row distributions x every enumerated candidate
# ---------------------------------------------------------------------------
def _pathological(kind_, m=72, n=96, seed=0):
    rng = np.random.default_rng(seed)
    d = np.zeros((m, n), np.float32)
    if kind_ == "empty_rows":
        mask = rng.random((m, n)) < 0.1
        mask[::3] = False  # every third row empty, incl. leading/trailing runs
        mask[:4] = False
        mask[-4:] = False
        d = (mask * rng.standard_normal((m, n))).astype(np.float32)
    elif kind_ == "one_dense_row":
        d = ((rng.random((m, n)) < 0.03)
             * rng.standard_normal((m, n))).astype(np.float32)
        d[m // 2] = rng.standard_normal(n).astype(np.float32)  # fully dense
    elif kind_ == "powerlaw":
        lens = np.minimum((n / np.arange(1, m + 1) ** 1.2).astype(int) + 1, n)
        rng.shuffle(lens)
        for r, ln in enumerate(lens):
            cols = rng.choice(n, size=ln, replace=False)
            d[r, cols] = rng.standard_normal(ln).astype(np.float32)
    return d, csr_from_dense(d)


@pytest.mark.parametrize("dist", ["empty_rows", "one_dense_row", "powerlaw"])
def test_pathological_rows_every_candidate_matches_oracle(dist):
    from repro.tune import SparseOperator, enumerate_candidates, extract, make

    d, a = _pathological(dist)
    rng = np.random.default_rng(1)
    x = rng.standard_normal(a.shape[1]).astype(np.float32)
    X = rng.standard_normal((a.shape[1], 8)).astype(np.float32)

    spmv_cands = enumerate_candidates(extract(a))
    assert any(c.fmt == "merge" for c in spmv_cands)
    # The column-slab variants only self-enumerate when x exceeds VMEM;
    # force them in so the skew sweep covers the stacked pipeline kernel.
    spmv_cands += [
        make("sell_blocked", "ref", C=8, sigma=64, n_slabs=3),
        make("sell_blocked", "pallas", C=8, sigma=64, n_slabs=3),
    ]
    for cand in spmv_cands:
        op = SparseOperator.from_candidate(a, cand)
        got = np.asarray(op @ jnp.asarray(x))
        np.testing.assert_allclose(
            got, d @ x, atol=2e-3, err_msg=f"{dist}: {cand.key()}"
        )

    for cand in enumerate_candidates(extract(a, k=8), kind="spmm"):
        op = SparseOperator.from_candidate(a, cand, k=8)
        got = np.asarray(op @ jnp.asarray(X))
        np.testing.assert_allclose(
            got, d @ X, atol=5e-3, err_msg=f"{dist}: {cand.key()}"
        )


def test_merge_prepare_rejects_int32_overflowing_nnz():
    """Regression: indptr tails >= 2**31 used to WRAP through the int32
    astype into negative gather offsets (silently wrong late rows).  The
    guard must fire on a mocked indptr without allocating nnz-sized
    arrays, and must also catch padded sizes that cross 2**31."""
    import types

    big = types.SimpleNamespace(
        nnz=2**31,
        indptr=np.array([0, 2**30, 2**31], np.int64),
        indices=np.zeros(0, np.int32),
        data=np.zeros(0, np.float32),
        shape=(2, 2),
    )
    with pytest.raises(OverflowError, match="merge tier"):
        merge_prepare(big, 4096)
    # nnz just under the limit, but chunk padding crosses it: still rejected
    # (the slot offsets span the padded nnz).
    near = types.SimpleNamespace(
        nnz=2**31 - 1,
        indptr=np.array([0, 2**31 - 1], np.int64),
        indices=np.zeros(0, np.int32),
        data=np.zeros(0, np.float32),
        shape=(1, 2),
    )
    with pytest.raises(OverflowError, match="merge tier"):
        merge_prepare(near, 4096)
    # Far below the limit nothing changes.
    d = np.eye(3, dtype=np.float32)
    prep = merge_prepare(csr_from_dense(d), 4096)
    np.testing.assert_allclose(
        np.asarray(merge_spmv(prep, jnp.ones(3, jnp.float32))), np.ones(3)
    )


# ---------------------------------------------------------------------------
# PR 8: degenerate inputs must not poison ranking; spmspv edge cases
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(12, 16), (16, 12)])
def test_all_zero_matrix_builds_and_serves_zeros(shape):
    """nnz=0 / all-empty-rows: features and cost estimates must stay finite
    (no NaN ranking), build must land a deterministic plan, and both the
    dense and sparse-RHS kinds must serve exact zeros."""
    import math

    from repro.tune import (
        SparseOperator,
        enumerate_candidates,
        estimate_cost,
        extract,
    )

    m, n = shape
    a = csr_from_dense(np.zeros(shape, np.float32))
    feats = extract(a)
    assert all(np.isfinite(v) for v in feats.to_dict().values())
    for cand in enumerate_candidates(feats):
        est = estimate_cost(a, cand, feats)
        assert not math.isnan(est), cand.key()
    op = SparseOperator.build(a, warmup=0, timed=1, cache=None)
    y = np.asarray(op @ jnp.ones(n, jnp.float32))
    np.testing.assert_array_equal(y, np.zeros(m, np.float32))
    # sparse-RHS kind on the empty pattern
    sop = SparseOperator.build(a, x_nnz=4, warmup=0, timed=1, cache=None)
    idx = np.arange(4, dtype=np.int64)
    val = np.ones(4, np.float32)
    np.testing.assert_array_equal(
        np.asarray(sop.apply_sparse(idx, val)), np.zeros(m, np.float32)
    )


def test_prune_falls_back_deterministically_on_nonfinite_costs():
    """If every estimate is NaN/inf the pruner must not rank garbage: it
    returns the deterministic csr/vector fallback (or the first candidate
    when no csr/vector exists), never an empty or NaN-ordered list."""
    from repro.tune import prune
    from repro.tune.candidates import make

    cands = [make("csr", "scalar"), make("csr", "vector"), make("sell", "pallas")]
    costs = {c: float("nan") for c in cands}
    survivors = prune(costs, factor=2.0)
    assert [c.key() for c in survivors] == [make("csr", "vector").key()]
    costs_inf = {c: float("inf") for c in cands}
    assert [c.key() for c in prune(costs_inf, factor=2.0)] == [
        make("csr", "vector").key()
    ]
    # no csr/vector present: first enumerated candidate, still deterministic
    no_csr = {make("sell", "pallas"): float("nan"), make("ell", "ref"): float("nan")}
    assert [c.key() for c in prune(no_csr, factor=2.0)] == [
        make("sell", "pallas").key()
    ]
    # mixed: non-finite entries are simply excluded from the ranking
    mixed = {make("csr", "scalar"): float("inf"), make("csr", "vector"): 1.0}
    assert [c.key() for c in prune(mixed, factor=2.0)] == [
        make("csr", "vector").key()
    ]


def test_spmspv_zero_nnz_and_empty_bucket_edges():
    """All-zero sparse x (nnz(x)=0, the empty bucket) must return exact
    zeros through every spmspv path — ref, pallas, and pipelined pallas —
    not crash on a zero-length scatter."""
    from repro.kernels.spmspv import (
        pad_sparse_rhs,
        spmspv_bind,
        spmspv_prepare,
        work_bucket,
    )

    rng = np.random.default_rng(61)
    d = ((rng.random((24, 32)) < 0.2) * rng.standard_normal((24, 32))).astype(
        np.float32
    )
    a = csr_from_dense(d)
    prep = spmspv_prepare(a)
    bucket = 6
    xi, xv = pad_sparse_rhs(
        np.zeros(0, np.int64), np.zeros(0, np.float32), bucket, 32
    )
    for impl in ("ref", "pallas"):
        fn = spmspv_bind(prep, bucket, impl=impl)
        y = np.asarray(fn((jnp.asarray(xi), jnp.asarray(xv))))
        np.testing.assert_array_equal(y, np.zeros(24, np.float32))
    # work_bucket on the empty expansion stays positive and base-aligned
    from repro.kernels.spmspv import WORK_BUCKET_BASE

    g = work_bucket(0, a.nnz)
    assert g >= 1 and g % WORK_BUCKET_BASE == 0


def test_spmspv_scatter_pallas_pipelined_matches_ref():
    """The DMA-pipelined scatter path must agree with the ref expansion."""
    from repro.kernels.spmspv import (
        expand_products,
        pad_sparse_rhs,
        spmspv_prepare,
        spmspv_scatter_pallas,
        work_bucket,
    )

    rng = np.random.default_rng(62)
    d = ((rng.random((48, 64)) < 0.15) * rng.standard_normal((48, 64))).astype(
        np.float32
    )
    a = csr_from_dense(d)
    prep = spmspv_prepare(a)
    nx = 8
    idx = np.sort(rng.choice(64, size=nx, replace=False)).astype(np.int64)
    val = rng.standard_normal(nx).astype(np.float32)
    xi, xv = pad_sparse_rhs(idx, val, nx, 64)
    total = int(prep["col_len_np"][idx].sum())
    g = work_bucket(total, a.nnz)
    rows, prods = expand_products(prep, jnp.asarray(xi), jnp.asarray(xv), g)
    x_dense = np.zeros(64, np.float32)
    x_dense[idx] = val
    ref = d @ x_dense
    for pipelined in (False, True):
        y = np.asarray(
            spmspv_scatter_pallas(
                rows, prods, m=48, slab=g, interpret=True,
                pipelined=pipelined,
            )
        )
        np.testing.assert_allclose(y, ref, atol=1e-5)


# ---------------------------------------------------------------------------
# Lane-row SELL (sell_lr_prepare / sell_lr_spmv) against float64 SciPy and
# against the SELL kernel
# ---------------------------------------------------------------------------
def _csr_from_rows(n, rows):
    """CSR with the given (column list) per row; values from the columns."""
    from repro.core.formats import CSRMatrix

    indptr = np.zeros(len(rows) + 1, np.int32)
    indptr[1:] = np.cumsum([len(r) for r in rows])
    indices = np.concatenate([np.asarray(r, np.int32) for r in rows]
                             or [np.zeros(0, np.int32)])
    data = (np.cos(indices.astype(np.float64) * 0.37) + 1.5).astype(np.float32)
    return CSRMatrix((len(rows), n), indptr, indices, data)


def _lane_row_case(name):
    from repro.data.suite import generate

    rng = np.random.default_rng(61)
    if name == "banded_fem":  # ldoor's surrogate: runs of 6 within a band
        return generate("ldoor", scale=0.002, seed=0)
    if name == "run_crosses_lane_row":  # each run straddles 128k - 1 | 128k
        return _csr_from_rows(512, [
            list(range(128 * (1 + i % 3) - 3 - i % 4, 128 * (1 + i % 3) + 3))
            for i in range(40)])
    if name == "empty_rows":  # every third row empty, and a trailing run
        rows = [sorted(rng.choice(300, 5, replace=False)) if i % 3 else []
                for i in range(57)] + [[]] * 9
        return _csr_from_rows(300, rows)
    if name == "last_partial_lane_row":  # n = 300: lane-row 2 holds 44 cols
        return _csr_from_rows(300, [[0, 256 + i // 2, 299] if i % 2 else [299 - i]
                                    for i in range(44)])
    if name == "row_at_max_segments":  # one row touches all 20 lane-rows
        rows = [[5 * i] for i in range(70)]
        rows[33] = list(range(3, 2560, 128))
        return _csr_from_rows(2560, rows)
    if name == "chunk_count_padding":  # 13 rows: the last chunk is part pad
        return _csr_from_rows(200, [[i, 150 + i] for i in range(13)])
    if name == "unsorted_columns":  # CSR columns not ascending in a row
        rows = [list(rng.permutation(np.arange(i, 400, 37))) for i in range(30)]
        return _csr_from_rows(400, rows)
    raise ValueError(name)


@pytest.mark.parametrize("case", [
    "banded_fem", "run_crosses_lane_row", "empty_rows",
    "last_partial_lane_row", "row_at_max_segments", "chunk_count_padding",
    "unsorted_columns",
])
def test_sell_lr_matches_scipy_float64_and_sell(case):
    import scipy.sparse as sp

    from repro.core.formats import sell_from_csr

    a = _lane_row_case(case)
    m, n = a.shape
    x = np.random.default_rng(7).standard_normal(n).astype(np.float32)
    # Copies: SciPy may sort a matrix's indices in place.
    s = sp.csr_matrix((a.data.astype(np.float64), a.indices.copy(),
                       a.indptr.copy()), shape=a.shape)
    ref = s @ x.astype(np.float64)
    scale = abs(s) @ np.abs(x.astype(np.float64)) + 1e-30

    prep = kops.sell_lr_prepare(a, C=8, chunk_tile=16)
    counts = kops.lanerow_counts(a)
    assert prep["width"] == -(-max(int(counts.max(initial=0)), 1) // 8) * 8
    assert prep["seg_vals"].shape == (prep["seg_row"].shape[0], 128)
    y = np.asarray(kops.sell_lr_spmv(prep, jnp.asarray(x)))
    assert y.shape == (m,)
    assert np.all(np.abs(y - ref) <= 1e-6 * scale), case

    sell = kops.sell_prepare(sell_from_csr(a, C=8, sigma=1, width_align=8), 8)
    y_sell = np.asarray(kops.sell_spmv(sell, jnp.asarray(x)))
    assert np.all(np.abs(y - y_sell) <= 1e-6 * scale), case
    if case == "row_at_max_segments":
        assert prep["width"] == 24 and counts[33] == 20
