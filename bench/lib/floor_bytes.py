"""The least HBM traffic each measured operation needs: the roofline's floor.

A kernel's share of its HBM roofline is ``floor_bytes / peak_bandwidth``
over the device time it took.  The floor counts only what no
implementation can avoid moving, so that no format, fusion or layout can
read above 100%:

* Index bytes are not counted.  Column indices, row pointers, SELL slice
  tables and BCSR block maps differ by format, and a format that moves
  fewer of them (compressed indices, a stencil that computes them) must
  raise the share honestly instead of lowering the floor it is held to.
  This is why ``repro.core.metrics.spmm_app_bytes``, which counts CSR's
  index bytes, is not the floor: a format with fewer index bytes could
  read above 100% against it.
* Float32 values, 4 bytes each, as the program serves them.
"""
from __future__ import annotations

F32 = 4


def spmm_floor_bytes(nnz: int, n_rows: int, n_cols: int, k: int) -> int:
    """Y = A X with X (n_cols, k): the matrix values once (``nnz * 4``),
    X read once and Y written once (``(n_rows + n_cols) * k * 4``).
    SpMV is ``k = 1``."""
    return nnz * F32 + (n_rows + n_cols) * k * F32


def cg_iteration_floor_bytes(nnz: int, n: int) -> int:
    """One conjugate-gradient iteration: the matrix values once for A p
    (``nnz * 4``), and the three vectors it carries, x, r and p, each read
    once and written once (``6 * n * 4``).  A p and the dot products can
    stay on chip in a fused step, so they add nothing to the floor."""
    return nnz * F32 + 6 * n * F32
