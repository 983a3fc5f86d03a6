"""The plain float64 reference, the bfloat16 control, and the limits.

The reference imports nothing of the program: it is SciPy's CSR product in
float64 over the benchmark's own matrix (``bench.lib.suite``).

Compared numbers, each with its limit (readings in ``PERF.md`` section 2):

* ``spmv_err``: the worst, over the checked requests and their rows, of
  ``|y - A x|_i / (|A| |x|)_i``, with ``A x`` in float64.  A float32 sum of
  a row's products is off by about ``sqrt(n) * 2**-24`` of ``(|A||x|)_i``;
  bfloat16 operands (the control) by about ``2**-9``.
* ``cg_residual``: the worst, over the solves, of the float64 true
  relative residual ``||b - A x|| / ||b||``.  The configuration's ``tol``
  is the solver's stopping rule on its own float32 residual, which rounding
  in the recurrence lets drift from the true one, so this limit too is set
  from readings.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

# Limits, each set between the program's readings on the chip (the lower)
# and the bfloat16 control's (the upper); PERF.md section 2 gives both.
SPMV_ERR_LIMIT = 1e-4
CG_RESIDUAL_LIMIT = 1e-4


def scipy_f64(a) -> sp.csr_matrix:
    return sp.csr_matrix(
        (a.data.astype(np.float64), a.indices, a.indptr), shape=a.shape)


def spmv_errors(a64: sp.csr_matrix, xs: np.ndarray, ys: np.ndarray,
                block: int = 32) -> np.ndarray:
    """Per request, the worst row error over ``(|A||x|)_i``.

    ``xs`` and ``ys`` are (requests, n); the products run a block of
    requests at a time as one sparse-times-dense product.
    """
    absa = abs(a64)
    out = np.empty(xs.shape[0])
    tiny = np.finfo(np.float64).tiny
    for i in range(0, xs.shape[0], block):
        x = xs[i:i + block].astype(np.float64).T
        ref = a64 @ x
        scale = absa @ np.abs(x)
        err = np.abs(ys[i:i + block].astype(np.float64).T - ref)
        out[i:i + block] = (err / np.maximum(scale, tiny)).max(axis=0)
    return out


def cg_residuals(a64: sp.csr_matrix, bs: np.ndarray, xs: np.ndarray) -> np.ndarray:
    """Per solve, ``||b - A x|| / ||b||`` in float64."""
    out = np.empty(bs.shape[0])
    for i, (b, x) in enumerate(zip(bs, xs)):
        b64 = b.astype(np.float64)
        out[i] = np.linalg.norm(b64 - a64 @ x.astype(np.float64)) / np.linalg.norm(b64)
    return out


# -- the control: the reference one precision step down --------------------
def _bf16(v: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(v, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def control_spmv(a, xs: np.ndarray) -> np.ndarray:
    """``A x`` with A and x rounded to bfloat16, summed in float32: what a
    bfloat16 matrix pass with float32 accumulation returns."""
    a16 = sp.csr_matrix((_bf16(a.data), a.indices, a.indptr), shape=a.shape)
    return np.asarray((a16 @ _bf16(xs).T).T, np.float32)


def control_cg(a, bs: np.ndarray, tol: float, maxiter: int) -> np.ndarray:
    """Conjugate gradients on A rounded to bfloat16, vectors in float32,
    stopped on its own residual at ``tol`` as the program's solver is."""
    a16 = sp.csr_matrix((_bf16(a.data), a.indices, a.indptr), shape=a.shape)
    out = []
    for b in bs:
        b = np.asarray(b, np.float32)
        x = np.zeros_like(b)
        r = b.copy()
        p = r.copy()
        rs = np.float32(r @ r)
        thresh = np.float32(tol * tol) * rs
        it = 0
        while it < maxiter and rs > thresh:
            ap = (a16 @ p).astype(np.float32)
            alpha = rs / np.float32(p @ ap)
            x = x + alpha * p
            r = r - alpha * ap
            rs_new = np.float32(r @ r)
            p = r + (rs_new / rs) * p
            rs = rs_new
            it += 1
        out.append(x)
    return np.stack(out)
