"""The benchmark's own copy of the Table-1 matrix generator and ``spd_shift``.

The benchmark keeps its matrices under its own directory so that no change
to the program can change the yardstick.  Two rules tie the copy to the
program's ``repro.data.suite.generate`` and ``repro.core.spmv.spd_shift``,
and ``bench/tests/test_bench_suite.py`` holds both:

* The *pattern* (``indptr``, ``indices``) is the one ``generate`` makes at
  the configuration's structure seed.  Every family draws its values after
  its pattern from the same generator, so the pattern needs none of the
  value draws.  Duplicates are merged by sorting the row-major keys once,
  which gives the same pattern as ``csr_from_coo``'s lexsort and unique.
* ``spd_shift`` gives the same CSR, bit for bit, as the program's on the
  same input.  Its pattern and the positions every entry of A and of A^T
  land on depend on A's pattern alone, so they are worked out once and
  cached; per seed only the values are summed.

The pattern is the deployment: it comes from the configuration's fixed
structure seed, never from ``--seed``.  The values come from ``--seed``.
So the structure fingerprint the program's plan cache is keyed on stays
the same from seed to seed, and the pattern can be cached in the checkout.
"""
from __future__ import annotations

import dataclasses
import hashlib
import inspect
import json
import os
from pathlib import Path

import numpy as np

from . import registry

# Bumped whenever the code below could make a different pattern, so a
# pattern cached by an older copy is never read back.
PATTERN_VERSION = 1


@dataclasses.dataclass(frozen=True)
class Csr:
    """A host CSR matrix: float32 values, int32 indices."""

    shape: tuple
    indptr: np.ndarray
    indices: np.ndarray
    data: np.ndarray

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator for one use of ``--seed``.

    Any whole number is a valid seed, negative or above 2**63: it is folded
    to 64 bits, and each use (values, vectors, arrivals, sample) takes its
    own stream of it.
    """
    return np.random.default_rng([int(seed) % (1 << 64), int(stream)])


# -- pattern generators: copies of repro.data.suite's families, pattern only --
def _stencil5(t, scale, rng):
    side = max(int(round(np.sqrt(t["n_rows"] * scale))), 4)
    n = side * side
    idx = np.arange(n)
    r, c = idx // side, idx % side
    rows, cols = [idx], [idx]
    for dr, dc in ((0, 1), (0, -1), (1, 0), (-1, 0)):
        rr, cc = r + dr, c + dc
        ok = (rr >= 0) & (rr < side) & (cc >= 0) & (cc < side)
        rows.append(idx[ok])
        cols.append((rr * side + cc)[ok])
    return n, np.concatenate(rows), np.concatenate(cols)


def _banded_fem(t, scale, rng):
    n = max(int(t["n_rows"] * scale), 64)
    per_row = max(int(round(t["nnz"] / t["n_rows"])), 2)
    band = max(int((t.get("band") or 100) * np.sqrt(scale)), 8)
    run = 6
    n_runs = -(-per_row // run)
    r_idx = np.repeat(np.arange(n), n_runs)
    centers = rng.integers(-band, band, size=r_idx.shape[0])
    starts = np.clip(r_idx + centers, 0, n - 1)
    rows = np.repeat(r_idx, run)
    cols = np.clip(
        np.repeat(starts, run) + np.tile(np.arange(run), r_idx.shape[0]), 0, n - 1
    )
    return (n, np.concatenate([rows, np.arange(n)]),
            np.concatenate([cols, np.arange(n)]))


def _randsparse(t, scale, rng):
    n = max(int(t["n_rows"] * scale), 64)
    per_row = t["nnz"] / t["n_rows"]
    counts = rng.poisson(max(per_row - 1.0, 0.5), size=n)
    if t.get("max_row"):
        counts = np.minimum(counts, t["max_row"] - 1)
    rows = np.repeat(np.arange(n), counts)
    cols = rng.integers(0, n, size=rows.shape[0])
    return (n, np.concatenate([rows, np.arange(n)]),
            np.concatenate([cols, np.arange(n)]))


def _powerlaw(t, scale, rng):
    n = max(int(t["n_rows"] * scale), 64)
    target_nnz = int(t["nnz"] * scale)
    raw = rng.zipf(2.1, size=n).astype(np.float64)
    cap = (t.get("max_row") or n) * scale + 16
    raw = np.minimum(raw, cap)
    counts = np.maximum((raw / raw.sum() * target_nnz).astype(np.int64), 1)
    col_pop = rng.zipf(2.0, size=n).astype(np.float64)
    col_p = col_pop / col_pop.sum()
    rows = np.repeat(np.arange(n), counts)
    cols = rng.choice(n, size=rows.shape[0], p=col_p)
    return (n, np.concatenate([rows, np.arange(n)]),
            np.concatenate([cols, np.arange(n)]))


def _blockdense(t, scale, rng):
    n = max(int(t["n_rows"] * scale), 128)
    per_row = int(round(t["nnz"] / t["n_rows"]))
    cluster = max(min(per_row * 2, n // 4), 8)
    rows_l, cols_l = [], []
    for b in range(-(-n // cluster)):
        lo = b * cluster
        size = min(lo + cluster, n) - lo
        m_ = rng.random((size, size)) < min(per_row / max(size, 1), 1.0)
        np.fill_diagonal(m_, True)
        r, c = np.nonzero(m_)
        rows_l.append(r + lo)
        cols_l.append(c + lo)
    return n, np.concatenate(rows_l), np.concatenate(cols_l)


FAMILIES = {
    "stencil5": _stencil5,
    "banded_fem": _banded_fem,
    "randsparse": _randsparse,
    "powerlaw": _powerlaw,
    "blockdense": _blockdense,
}


def family(name: str, bench_dir: Path | None = None):
    """The pattern family ``name``: one of ``FAMILIES``, else the
    ``pattern`` function of ``<bench_dir>/families/<name>.py``."""
    if name in FAMILIES:
        return FAMILIES[name]
    return registry.load_module("families", name,
                                bench_dir or registry.BENCH_DIR).pattern


def make_pattern(table1: dict, scale: float, structure_seed: int,
                 bench_dir: Path | None = None):
    """``(n, indptr, indices)`` of the Table-1 matrix, duplicates merged."""
    rng = np.random.default_rng(int(structure_seed) * 1000 + int(table1["idx"]))
    n, rows, cols = family(table1["family"], bench_dir)(table1, scale, rng)
    keys = np.unique(rows.astype(np.int64) * n + cols.astype(np.int64))
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(keys // n, minlength=n), out=indptr[1:])
    return n, indptr.astype(np.int32), (keys % n).astype(np.int32)


def _spd_plan(n, indptr, indices):
    """Pattern of (A + A^T) and where each entry of A and of A^T lands."""
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    cols = indices.astype(np.int64)
    fwd_keys = rows * n + cols
    bwd_keys = cols * n + rows
    keys = np.unique(np.concatenate([fwd_keys, bwd_keys]))
    s_rows = keys // n
    s_indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(s_rows, minlength=n), out=s_indptr[1:])
    diag = np.searchsorted(keys, np.arange(n, dtype=np.int64) * (n + 1))
    # Every generator puts the whole diagonal into A, so the shift adds no
    # entry to the pattern, only to the diagonal's values.
    if not np.array_equal(keys[diag], np.arange(n, dtype=np.int64) * (n + 1)):
        raise ValueError("spd_shift needs A's whole diagonal in its pattern")
    return {
        "s_indptr": s_indptr.astype(np.int32),
        "s_indices": (keys % n).astype(np.int32),
        "fwd": np.searchsorted(keys, fwd_keys).astype(np.int32),
        "bwd": np.searchsorted(keys, bwd_keys).astype(np.int32),
        "diag": diag.astype(np.int32),
    }


class PatternStore:
    """A configuration's pattern, generated once and cached in ``cache_dir``.

    The cache file is named from the configuration and a digest of what
    made it (the Table-1 row, the scale, the structure seed and
    ``PATTERN_VERSION``; for a family of ``bench/families``, its file too),
    so an edited configuration never reads a stale pattern.
    ``cache_dir=None`` keeps everything in memory.
    """

    def __init__(self, name: str, table1: dict, scale: float,
                 structure_seed: int, cache_dir: Path | None,
                 bench_dir: Path | None = None):
        self.table1 = dict(table1)
        self.scale = float(scale)
        self.structure_seed = int(structure_seed)
        self.bench_dir = bench_dir
        key = [self.table1, self.scale, self.structure_seed, PATTERN_VERSION]
        fam = family(self.table1["family"], bench_dir)  # fails at once if none
        if self.table1["family"] not in FAMILIES:
            key.append(hashlib.sha256(
                Path(inspect.getfile(fam)).read_bytes()).hexdigest())
        what = json.dumps(key, sort_keys=True)
        digest = hashlib.sha256(what.encode()).hexdigest()[:12]
        self._base = None if cache_dir is None else Path(cache_dir) / f"{name}-{digest}"
        self.hits: dict[str, bool] = {}
        self._a = None
        self._spd = None

    def _load_or_make(self, tag: str, make):
        if self._base is None:
            self.hits[tag] = False
            return make()
        path = self._base.with_name(f"{self._base.name}.{tag}.npz")
        if path.exists():
            with np.load(path) as z:
                self.hits[tag] = True
                return {k: z[k] for k in z.files}
        out = make()
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp.npz")
        np.savez(tmp, **out)
        os.replace(tmp, path)
        self.hits[tag] = False
        return out

    def a_pattern(self) -> dict:
        if self._a is None:
            def make():
                n, indptr, indices = make_pattern(
                    self.table1, self.scale, self.structure_seed, self.bench_dir)
                return {"n": np.asarray(n), "indptr": indptr, "indices": indices}
            self._a = self._load_or_make("a", make)
        return self._a

    def spd_pattern(self) -> dict:
        if self._spd is None:
            p = self.a_pattern()
            self._spd = self._load_or_make(
                "spd", lambda: _spd_plan(int(p["n"]), p["indptr"], p["indices"]))
        return self._spd

    def matrix(self, values_seed: int) -> Csr:
        """A with float32 N(0, 1) values drawn from ``values_seed``."""
        p = self.a_pattern()
        n = int(p["n"])
        data = rng_for(values_seed, 0).standard_normal(
            p["indices"].shape[0], dtype=np.float32)
        return Csr((n, n), p["indptr"], p["indices"], data)


def spd_shift(a: Csr, plan: dict, margin: float = 1.0) -> Csr:
    """``repro.core.spmv.spd_shift(a)``, bit for bit, from a cached plan.

    Symmetrize: each entry of (A + A^T) / 2 sums at most two halves, one
    from A and one from A^T, in float64, then rounds to float32, as the
    program's duplicate-summing ``csr_from_coo`` does.  Shift: the largest
    off-diagonal absolute row sum, accumulated in float32 in CSR order
    (``np.add.at``, as the program does), plus ``margin``; the diagonal
    takes its absolute value plus the shift, summed in float64.
    """
    n = a.shape[0]
    half = (a.data * np.float32(0.5)).astype(np.float64)
    m = plan["s_indices"].shape[0]
    s = (np.bincount(plan["fwd"], weights=half, minlength=m)
         + np.bincount(plan["bwd"], weights=half, minlength=m)).astype(np.float32)
    rows = np.repeat(np.arange(n), np.diff(plan["s_indptr"]))
    off = rows != plan["s_indices"]
    row_abs = np.zeros(n, np.float32)
    np.add.at(row_abs, rows[off], np.abs(s[off]))
    shift = np.float32(row_abs.max(initial=0.0) + margin)
    data = np.where(off, s, np.abs(s))
    d = plan["diag"]
    data[d] = (np.abs(s[d]).astype(np.float64) + np.float64(shift)).astype(np.float32)
    return Csr(a.shape, plan["s_indptr"], plan["s_indices"], data)
