"""Per-matrix structural features driving candidate enumeration and pruning.

These are exactly the quantities the paper shows to predict kernel choice:
UCLD predicts the vgatherd/SELL win (Fig 5), block fill economics drive the
Table 2 register-blocking choice, nnz/row dispersion drives load balancing,
and the x-vector footprint against the VMEM budget decides whether the SELL
kernel needs column-slab cache blocking (Nishtala et al. in the paper's
references).  All are O(nnz) numpy on the host CSR.

Because plans persist these features alongside the winning candidate
(``Plan.features``), the plan cache doubles as a labelled dataset of
(structure -> winning plan); :mod:`repro.tune.predict` nearest-neighbors
over :func:`feature_vector` to transfer a plan to a *new* fingerprint
without paying the measured search.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping

import numpy as np

from repro.core.formats import CSRMatrix
from repro.core.metrics import matrix_bandwidth, ucld, utd

__all__ = ["MatrixFeatures", "extract", "FEATURE_NAMES", "feature_vector"]


@dataclasses.dataclass(frozen=True)
class MatrixFeatures:
    m: int
    n: int
    nnz: int
    nnz_row_mean: float
    nnz_row_cv: float  # std/mean of nnz per row (load-imbalance proxy)
    ucld: float  # paper Fig 5 predictor
    utd: float  # TPU tile generalization of UCLD
    bandwidth: int  # max |i - j| over nonzeros
    x_bytes: int  # footprint of the dense operand (k columns)
    x_fits_vmem: bool
    # Operand-density axis (PLAN_VERSION 6): nnz(x)/n for a sparse RHS, 1.0
    # for the dense-RHS kinds.  Drives the spmspv byte branch — the tuner
    # crosses over from dense-RHS tiers as x thins.  Trailing default keeps
    # positional construction of the dense-kind features unchanged.
    x_density: float = 1.0
    # Longest row: the SELL packing's width W (every chunk pads to it),
    # which sizes the SELL kernel's SMEM tiles.  Trailing default, like
    # x_density; not a FEATURE_NAMES axis.
    nnz_row_max: int = 0
    # Most 128-column lane-rows of x that one row touches: lane-row SELL's
    # width W_seg, which sizes that kernel's SMEM tiles and HBM value rows.
    # Trailing default, like nnz_row_max; not a FEATURE_NAMES axis.
    lanerow_max: int = 0

    def to_dict(self) -> dict[str, Any]:
        """Plain-python dict, safe for JSON persistence inside a Plan.

        numpy scalars (ucld/utd come back as np.float64) are coerced so the
        plan cache's ``json.dump`` never chokes on a feature value.
        """
        out: dict[str, Any] = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, (bool, np.bool_)):
                out[f.name] = bool(v)
            elif isinstance(v, (int, np.integer)):
                out[f.name] = int(v)
            else:
                out[f.name] = float(v)
        return out


# The embedding the transfer-tuning predictor measures distance in.  Size
# quantities enter log-scaled: a 2x-larger matrix of the same family should
# be a *near* neighbor (the paper's phenomena are per-row/per-tile densities,
# not absolute size), while the density/dispersion predictors (cv, ucld, utd)
# enter raw — they are already O(1) and they are what actually picks kernels.
FEATURE_NAMES = (
    "log_m",
    "log_n",
    "log_nnz",
    "log_nnz_row_mean",
    "nnz_row_cv",
    "ucld",
    "utd",
    "log_bandwidth",
    "x_fits_vmem",
    "x_density",
)


def feature_vector(
    feats: "MatrixFeatures | Mapping[str, Any]",
) -> np.ndarray | None:
    """Embed features (live or from ``Plan.features``) into FEATURE_NAMES
    order; None when a required key is missing (a cache entry written by a
    different feature schema must be skipped, never crash the predictor)."""
    d = feats.to_dict() if isinstance(feats, MatrixFeatures) else feats
    try:
        return np.array(
            [
                math.log10(max(float(d["m"]), 1.0)),
                math.log10(max(float(d["n"]), 1.0)),
                math.log10(max(float(d["nnz"]), 1.0)),
                math.log10(float(d["nnz_row_mean"]) + 1.0),
                float(d["nnz_row_cv"]),
                float(d["ucld"]),
                float(d["utd"]),
                math.log10(float(d["bandwidth"]) + 1.0),
                1.0 if d["x_fits_vmem"] else 0.0,
                # Schema-additive default: every pre-v6 measurement was a
                # dense-RHS one, so a missing key means x_density = 1.0.
                float(d.get("x_density", 1.0)),
            ],
            dtype=np.float64,
        )
    except (KeyError, TypeError, ValueError):
        return None


def extract(
    a: CSRMatrix, *, k: int = 1, val_bytes: int = 4, x_nnz: int | None = None
) -> MatrixFeatures:
    """Structural features; ``x_nnz`` sets the sparse-RHS density axis.

    Degenerate inputs (nnz = 0, all-empty rows, even m = 0) must come out
    finite: every downstream consumer ranks by these numbers, and one NaN
    here poisons the whole candidate ordering (see ``estimate_cost``).
    """
    from repro.kernels.ops import VMEM_BUDGET_BYTES, lanerow_counts

    m, n = a.shape
    lengths = np.diff(a.indptr).astype(np.float64)
    mean = float(lengths.mean()) if m else 0.0
    cv = float(lengths.std() / mean) if mean > 0 else 0.0
    x_bytes = int(n) * int(k) * val_bytes
    x_density = 1.0 if x_nnz is None else min(max(int(x_nnz), 0) / max(int(n), 1), 1.0)
    return MatrixFeatures(
        m=m,
        n=n,
        nnz=a.nnz,
        nnz_row_mean=mean,
        nnz_row_cv=cv,
        ucld=ucld(a),
        utd=utd(a),
        bandwidth=matrix_bandwidth(a),
        x_bytes=x_bytes,
        x_fits_vmem=x_bytes <= VMEM_BUDGET_BYTES,
        x_density=x_density,
        nnz_row_max=int(lengths.max(initial=0)),
        lanerow_max=int(lanerow_counts(a).max(initial=0)),
    )
