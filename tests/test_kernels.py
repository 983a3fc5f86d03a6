"""Pallas kernel allclose sweeps vs the pure-jnp oracles (interpret mode).

Per the assignment: for each kernel, sweep shapes/dtypes and
assert_allclose against the ref.py oracle.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcsr_from_csr, csr_from_dense, sell_from_csr
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.bcsr_spmm import bcsr_spmm_pallas
from repro.kernels.sell_spmv import sell_spmv_pallas


def rand_csr(rng, m, n, density, dtype=np.float32):
    d = ((rng.random((m, n)) < density) * rng.standard_normal((m, n))).astype(dtype)
    return d, csr_from_dense(d, dtype=dtype)


BCSR_CASES = [
    # (m, n, k, block, density)
    (64, 64, 16, (8, 8), 0.2),
    (96, 128, 32, (8, 16), 0.1),
    (128, 256, 64, (16, 16), 0.05),
    (100, 120, 128, (8, 16), 0.3),   # non-multiple m/n -> padding path
    (56, 72, 8, (8, 8), 0.9),        # near dense
]


@pytest.mark.parametrize("m,n,k,block,density", BCSR_CASES)
def test_bcsr_spmm_vs_oracle(m, n, k, block, density):
    rng = np.random.default_rng(m * 1000 + n)
    d, a = rand_csr(rng, m, n, density)
    b = bcsr_from_csr(a, block)
    prep = kops.bcsr_prepare(b)
    X = rng.standard_normal((n, k)).astype(np.float32)
    out = kops.bcsr_spmm(prep, jnp.asarray(X))
    # oracle 1: dense matmul
    np.testing.assert_allclose(np.asarray(out), d @ X, atol=5e-4, rtol=1e-4)
    # oracle 2: ref.py block loop
    gm, gn = b.grid_shape
    bm, bk = block
    xp = np.zeros((gn * bk, k), np.float32)
    xp[:n] = X
    ref = kref.bcsr_spmm_ref(
        jnp.asarray(prep["blocks"]),
        np.asarray(prep["block_rows"]),
        np.asarray(prep["block_cols"]),
        jnp.asarray(xp.reshape(gn, bk, k)),
        gm,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref).reshape(-1, k)[:m], atol=5e-4
    )


@pytest.mark.parametrize("dtype", [np.float32])
@pytest.mark.parametrize("m,n,density,C,sigma", [
    (64, 64, 0.1, 8, 16),
    (100, 80, 0.2, 8, 64),
    (256, 300, 0.05, 8, 32),
    (40, 500, 0.02, 8, 8),
])
def test_sell_spmv_vs_oracle(m, n, density, C, sigma, dtype):
    rng = np.random.default_rng(m + n)
    d, a = rand_csr(rng, m, n, density, dtype)
    s = sell_from_csr(a, C=C, sigma=sigma, width_align=8)
    prep = kops.sell_prepare(s)
    x = rng.standard_normal(n).astype(dtype)
    y = kops.sell_spmv(prep, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), d @ x, atol=5e-4, rtol=1e-4)
    # oracle: chunk-sum reference on the same packed arrays
    sums = kref.sell_spmv_ref(prep["cols"], prep["vals"], jnp.asarray(x))
    direct = sell_spmv_pallas(prep["cols"], prep["vals"], jnp.asarray(x),
                              interpret=True)
    np.testing.assert_allclose(np.asarray(direct), np.asarray(sums), atol=5e-4)


def test_bcsr_empty_rows_padded():
    """Rows with no blocks must still produce zero output (prepare pads)."""
    d = np.zeros((32, 32), np.float32)
    d[0, 0] = 1.0  # only the first block row is occupied
    a = csr_from_dense(d)
    b = bcsr_from_csr(a, (8, 8))
    prep = kops.bcsr_prepare(b)
    X = np.ones((32, 8), np.float32)
    out = np.asarray(kops.bcsr_spmm(prep, jnp.asarray(X)))
    np.testing.assert_allclose(out, d @ X, atol=1e-6)


def test_bcsr_bf16_inputs():
    rng = np.random.default_rng(7)
    d, a = rand_csr(rng, 64, 64, 0.2)
    b = bcsr_from_csr(a, (8, 8))
    prep = kops.bcsr_prepare(b)
    prep["blocks"] = prep["blocks"].astype(jnp.bfloat16)
    X = rng.standard_normal((64, 16)).astype(np.float32)
    out = kops.bcsr_spmm(prep, jnp.asarray(X).astype(jnp.bfloat16))
    np.testing.assert_allclose(np.asarray(out, np.float32), d @ X, atol=0.5, rtol=0.1)


def test_sell_spmv_cache_blocked():
    """Column-slab (cache-blocked) SELL equals the unblocked kernel — the
    paper's cited cache-blocking technique for x exceeding fast memory."""
    rng = np.random.default_rng(11)
    d, a = rand_csr(rng, 96, 400, 0.05)
    x = rng.standard_normal(400).astype(np.float32)
    prep1 = kops.sell_prepare(sell_from_csr(a, C=8, sigma=32, width_align=8))
    y1 = np.asarray(kops.sell_spmv(prep1, jnp.asarray(x)))
    for n_slabs in (2, 3, 5):
        prepb = kops.sell_prepare_blocked(a, n_slabs=n_slabs)
        yb = np.asarray(kops.sell_spmv_blocked(prepb, jnp.asarray(x)))
        np.testing.assert_allclose(yb, d @ x, atol=5e-4, rtol=1e-4)
        np.testing.assert_allclose(yb, y1, atol=5e-4, rtol=1e-4)


def test_slab_pipeline_dma_path_equals_direct_loads():
    """The double-buffered make_async_copy path must be numerically
    identical to the direct-load fallback (this interpreter models DMA
    semaphores, so the exact TPU-path slot/semaphore logic runs here) —
    for all four kernels built on kernels/pipeline.slab_pipeline."""
    from repro.kernels.sell_spmv import sell_spmv_blocked_pallas
    rng = np.random.default_rng(23)

    # SELL: resident-x kernel.
    d, a = rand_csr(rng, 96, 120, 0.1)
    s = sell_from_csr(a, C=8, sigma=32, width_align=8)
    prep = kops.sell_prepare(s)
    x = jnp.asarray(rng.standard_normal(120).astype(np.float32))
    outs = [
        np.asarray(sell_spmv_pallas(prep["cols"], prep["vals"], x,
                                    interpret=True, pipelined=p))
        for p in (False, True)
    ]
    np.testing.assert_array_equal(outs[0], outs[1])

    # Stacked column-slab SELL: x-slabs stream through the pipeline too.
    sprep = kops.sell_prepare_blocked_stacked(a, n_slabs=3)
    n_slabs, slab_n = sprep["cols"].shape[0], int(sprep["slab_n"])
    x_pad = jnp.zeros((n_slabs * slab_n,), jnp.float32).at[:120].set(x)
    outs = [
        np.asarray(sell_spmv_blocked_pallas(
            sprep["cols"], sprep["vals"], x_pad, slab_n=slab_n,
            interpret=True, pipelined=p))
        for p in (False, True)
    ]
    np.testing.assert_array_equal(outs[0], outs[1])

    # Lane-row SELL: seg_row tiles to SMEM and value-row slabs to VMEM.
    from repro.kernels.sell_spmv import sell_lr_spmv_pallas

    lprep = kops.sell_lr_prepare(a, chunk_tile=16)
    outs = [
        np.asarray(sell_lr_spmv_pallas(
            lprep["seg_row"], lprep["seg_vals"], x, width=lprep["width"],
            interpret=True, pipelined=p))
        for p in (False, True)
    ]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_allclose(outs[1][:96], d @ np.asarray(x), atol=1e-4)

    # BCSR: block stream slabs (n_blocks not a multiple of block_tile,
    # so the zero-block padding rides the DMA path as well).
    d, a = rand_csr(rng, 64, 72, 0.15)
    b = bcsr_from_csr(a, (8, 8))
    prep = kops.bcsr_prepare(b)
    gm, gn = b.grid_shape
    bm, bk = b.block_shape
    X = jnp.asarray(rng.standard_normal((gn * bk, 16)).astype(np.float32))
    outs = [
        np.asarray(bcsr_spmm_pallas(
            prep["block_rows"], prep["block_cols"], prep["blocks"],
            X.reshape(gn, bk, 16), n_block_rows=gm,
            interpret=True, pipelined=p))
        for p in (False, True)
    ]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_allclose(
        outs[1].reshape(gm * bm, 16)[:64],
        np.asarray(
            jnp.asarray(np.asarray(d)) @ X[:72]
        ),
        atol=5e-4,
    )
