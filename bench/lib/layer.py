"""The reductions behind the per-layer readers in ``bench/metrics``.

One quantity can move different end-to-end metrics in different cells
(idle device time costs the closed-loop caller latency and the open loop
throughput), so ``BENCHMARK.json`` names it once per end-to-end metric,
and each of those readers calls the one function here.  A function that finds nothing to
read returns None, never 0.
"""
from __future__ import annotations

from .floor_bytes import cg_iteration_floor_bytes, spmm_floor_bytes
from .peaks import peak

# The programs' names in the profiler's "XLA Modules" line: the engine's
# bucket programs and the solver's CG program are jitted by
# ``runtime.executable.hoisted_jit`` from a function named ``apply``.
PROGRAM_PREFIX = "jit_apply"


def padded_share(run):
    """% of the engine's dispatched columns that were zero padding, from
    ``EngineStats.padded_cols`` and ``occupied_cols`` over the window."""
    eng = run.counters.get("engine")
    if not eng:
        return None
    total = eng["padded_cols"] + eng["occupied_cols"]
    return 100.0 * eng["padded_cols"] / total if total else None


def idle_share(run):
    """% of the traced window in which no operation ran on the device."""
    if run.trace is None or run.trace.n_devices == 0:
        return None
    return 100.0 * run.trace.idle_share


def _program_seconds(run):
    _, seconds = run.trace.module_seconds(lambda n: n.startswith(PROGRAM_PREFIX))
    return seconds


def serve_roofline(run):
    """% of the HBM roofline the bucket programs reached: the floor bytes of
    every dispatch at its bucket's width, over the peak, over the programs'
    device seconds in the trace."""
    eng = run.counters.get("engine")
    if not eng or run.trace is None:
        return None
    seconds = _program_seconds(run)
    if seconds <= 0.0:
        return None
    floor = sum(count * spmm_floor_bytes(run.nnz, run.n_rows, run.n_cols, int(k))
                for k, count in eng["dispatched"].items())
    return 100.0 * floor / peak(run.device_kind)["hbm_bytes_per_s"] / seconds


def cg_roofline(run):
    """% of the HBM roofline the CG program reached: the floor bytes of one
    iteration times every solve's iterations, over the peak, over the
    program's device seconds in the trace."""
    cg = run.counters.get("cg")
    if not cg or run.trace is None:
        return None
    seconds = _program_seconds(run)
    iterations = sum(cg["iterations"])
    if seconds <= 0.0 or iterations == 0:
        return None
    floor = iterations * cg_iteration_floor_bytes(run.nnz, run.n_rows)
    return 100.0 * floor / peak(run.device_kind)["hbm_bytes_per_s"] / seconds


def cg_iterations(run):
    """Mean ``SolverResult.iterations`` per solve in the window."""
    cg = run.counters.get("cg")
    if not cg or not cg["iterations"]:
        return None
    return sum(cg["iterations"]) / len(cg["iterations"])
