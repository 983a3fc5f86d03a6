"""Quickstart: the paper's pipeline in ten lines of public API.

Build a matrix from the (synthesized) UFL suite, inspect its UCLD, reorder
with RCM, pack into SELL / BCSR, and multiply — SpMV (k=1) and SpMM (k=16)
— through both the XLA-vectorized tier and the Pallas kernels
(interpret-mode on CPU; MXU tiles on TPU).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core import (
    bcsr_from_csr,
    matrix_bandwidth,
    rcm,
    sell_from_csr,
    spmm_csr,
    spmv_csr,
    ucld,
    utd,
)
from repro.data.suite import generate
from repro.kernels import ops as kops
from repro.runtime.engine import SparseEngine
from repro.tune import PlanCache, SparseOperator


def main():
    # 1. a Table-1 matrix (pattern-faithful synthesis of `cant`)
    a = generate("cant", scale=1 / 64)
    m, n = a.shape
    print(f"cant @1/64: {m}x{n}, nnz={a.nnz}, nnz/row={a.nnz/m:.1f}")
    print(f"  UCLD={ucld(a):.3f}  UTD(8x128)={utd(a):.4f}  "
          f"bandwidth={matrix_bandwidth(a)}")

    # 2. RCM reordering (paper §4.4)
    ar = a.permuted(rcm(a))
    print(f"  after RCM: UCLD={ucld(ar):.3f} bandwidth={matrix_bandwidth(ar)}")

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    X = jnp.asarray(rng.standard_normal((n, 16)).astype(np.float32))

    # 3. SpMV / SpMM on the vectorized XLA tier
    y = spmv_csr(a.device(), x, n_rows=m)
    Y = spmm_csr(a.device(), X, n_rows=m)
    print(f"  SpMV |y|={float(jnp.linalg.norm(y)):.3f}   "
          f"SpMM |Y|={float(jnp.linalg.norm(Y)):.3f}")

    # 4. the Pallas kernels (vgatherd / register-blocking TPU adaptations)
    sell = kops.sell_prepare(sell_from_csr(a, C=8, sigma=64, width_align=8))
    y_k = kops.sell_spmv(sell, x)
    bcsr = kops.bcsr_prepare(bcsr_from_csr(a, (8, 16)))
    Y_k = kops.bcsr_spmm(bcsr, X)
    print(f"  kernels agree: SpMV {np.allclose(y, y_k, atol=1e-3)}, "
          f"SpMM {np.allclose(Y, Y_k, atol=1e-3)}")

    # 5. the autotuned facade: per-matrix kernel selection + plan cache
    cache = PlanCache()
    op = SparseOperator.build(a, cache=cache, warmup=1, timed=3)
    y_t = op @ x
    op2 = SparseOperator.build(a, cache=cache)  # same structure -> cache hit
    print(f"  autotuned plan: {op.plan.candidate.key()} "
          f"(timed {op.plan.n_measured}/{op.plan.n_candidates} candidates, "
          f"rebuild from cache: {op2.from_cache}); "
          f"agrees {np.allclose(y, y_t, atol=1e-3)}")

    # 6. the serving engine: pending SpMV requests aggregate into k-bucketed
    #    SpMM batches, each bucket running its own tuned plan (Fig 9 as a
    #    runtime decision)
    eng = SparseEngine(a, ks=(1, 4, 16), cache=cache, warmup=1, timed=3)
    reqs = [eng.submit(rng.standard_normal(n).astype(np.float32))
            for _ in range(9)]
    eng.drain()
    s = eng.stats.summary()
    print(f"  engine served {s['requests']} requests in {s['dispatches']} "
          f"dispatch(es) {s['by_bucket']} at occupancy {s['occupancy']:.2f}; "
          f"request 0 latency {reqs[0].latency_s * 1e3:.1f} ms")


if __name__ == "__main__":
    main()
