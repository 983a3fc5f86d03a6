"""Transfer tuning: predict a plan for a NEW fingerprint from the cache.

The plan cache plus :mod:`repro.tune.features` is already a labelled dataset
of (structural features -> winning candidate): every measured search persists
the features it extracted alongside the plan it picked.  The paper's central
serving-relevant finding (Table 2 / Fig 11) is that the winning configuration
is *per-matrix* — but matrices of the same structural family (banded FEM,
power-law graphs, stencils...) land on the same winner, which is what makes
the search's result *transferable*: a new fingerprint's plan can be read off
its nearest feature neighbors instead of re-measured.

:func:`predict_candidate` does exactly that:

* embed the request and every usable cache entry with
  :func:`repro.tune.features.feature_vector` (same kind, same k, same
  backend, same mesh topology — a point measurement transfers no further
  than it was taken);
* normalize each dimension by its spread over the training pool (so log-size
  and O(1)-density features weigh comparably) and take the RMS distance;
* if the nearest neighbor lies within ``radius``, serve its candidate —
  **confident** transfer;
* otherwise fall back to the byte-model argmin over the enumerated space —
  the tuner's own prior, the same estimate that drives pruning — flagged
  ``confident=False`` so callers know the background search matters more.

Predicted plans are served immediately and NEVER persisted: only measured
search results enter the cache, so prediction can never launder itself into
its own training set.  ``SparseFleet`` runs the real measured search in the
background and hot-swaps the executables when it lands.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable

import numpy as np

from repro.core.formats import CSRMatrix

from .candidates import (
    Candidate,
    enumerate_candidates,
    estimate_cost,
    fits_prep_budget,
)
from .features import MatrixFeatures, extract, feature_vector
from .plan import PlanCache

__all__ = ["PREDICT_RADIUS", "Prediction", "predict_candidate"]

# Confidence radius in normalized feature space (RMS over dimensions after
# per-dimension spread normalization, so the scale is ~"fraction of the
# training pool's spread").  Within it, same-family neighbors transfer their
# winner; beyond it the byte model is a better prior than a far neighbor.
PREDICT_RADIUS = 0.35

# Per-dimension normalization floor: a pool whose spread in some dimension
# is ~zero (e.g. every cached plan has x_fits_vmem=1) must not turn a tiny
# difference into a huge normalized distance.
_SPREAD_FLOOR = 0.05


@dataclasses.dataclass(frozen=True)
class Prediction:
    """One serve-now plan choice and the evidence behind it."""

    candidate: Candidate
    source: str  # neighbor fingerprint, or "byte_model" for the fallback
    distance: float  # normalized feature distance (inf for the fallback)
    confident: bool  # nearest neighbor was within the radius
    n_neighbors: int  # usable training points consulted


def _byte_model_argmin(
    a: CSRMatrix, feats: MatrixFeatures, kind: str, k: int,
    prep_budget: int | None = None,
) -> Candidate:
    """The fallback prior: cheapest byte-model estimate over the enumerated
    space — exactly the ranking the measured search prunes with, minus the
    measurement.  The scalar/interpret penalties already keep those tiers
    from ever being the argmin."""
    cands = enumerate_candidates(feats, kind, k=k, prep_budget=prep_budget)
    return min(cands, key=lambda c: estimate_cost(a, c, feats, k=k))


def predict_candidate(
    a: CSRMatrix,
    kind: str,
    k: int,
    cache: PlanCache,
    *,
    feats: MatrixFeatures | None = None,
    backend: str | None = None,
    mesh_shape: Iterable[int] = (),
    exclude: Iterable[str] = (),
    radius: float = PREDICT_RADIUS,
    prep_budget: int | None = None,
) -> Prediction:
    """Pick a serve-now candidate for ``a`` without a measured search.

    ``exclude`` drops training fingerprints (leave-one-out evaluation, or
    the request's own fingerprint); a neighbor's candidate whose prepared
    operand would not fit ``prep_budget`` bytes here is no training point.
    Always returns a candidate: the byte model is the floor, never an
    exception.
    """
    feats = extract(a, k=k) if feats is None else feats
    target = feature_vector(feats)
    mesh_shape = [int(s) for s in mesh_shape]
    exclude = set(exclude)

    pool: list[tuple[str, Candidate, np.ndarray]] = []
    if target is not None:
        for p in cache.plans():
            if p.kind != kind or int(p.k) != int(k):
                continue
            if p.fingerprint in exclude or not p.features:
                continue
            if backend is not None and p.backend != backend:
                continue
            if [int(s) for s in p.mesh_shape] != mesh_shape:
                continue
            vec = feature_vector(p.features)
            if vec is None:
                continue
            try:
                cand = p.candidate
            except Exception:
                continue  # params drifted: unusable as a training point
            if not fits_prep_budget(cand, feats, prep_budget):
                continue
            pool.append((p.fingerprint, cand, vec))

    if pool:
        mat = np.stack([v for _, _, v in pool])
        both = np.vstack([mat, target[None]])
        spread = np.maximum(
            both.max(axis=0) - both.min(axis=0),
            _SPREAD_FLOOR * (1.0 + np.abs(np.median(both, axis=0))),
        )
        dists = np.sqrt((((mat - target[None]) / spread) ** 2).mean(axis=1))
        i = int(np.argmin(dists))
        if float(dists[i]) <= radius:
            fp_n, cand, _ = pool[i]
            return Prediction(
                candidate=cand,
                source=fp_n,
                distance=float(dists[i]),
                confident=True,
                n_neighbors=len(pool),
            )
    return Prediction(
        candidate=_byte_model_argmin(a, feats, kind, k, prep_budget),
        source="byte_model",
        distance=float("inf") if not pool else float(np.min(dists)),
        confident=False,
        n_neighbors=len(pool),
    )
