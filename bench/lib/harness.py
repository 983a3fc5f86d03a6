"""Run one cell once: set up, measure for ``seconds``, check, reduce.

The result is the benchmark's last line (see ``bench/run.py``).  What the
run did goes to ``log`` on the way: the device, where the pattern and each
plan came from, requests offered, served and late, and every compared
number beside its limit.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import shutil
import time
from pathlib import Path

import numpy as np

from . import reference, registry, traffic
from .suite import PatternStore, rng_for, spd_shift
from .trace import TraceSummary, load_events, reduce_events, save_events


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class RunRecord:
    """What a per-layer reader may read: the run's counters and its trace."""

    n_rows: int
    n_cols: int
    nnz: int
    device_kind: str
    counters: dict
    trace: TraceSummary | None


def device_info(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips)}
    if require_tpu and (info["platform"] != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{info['platform']} device(s) ({info['kind']})")
    return devs[:chips], info


def device_vectors(seed: int, stream: int, count: int, n: int) -> list:
    """``count`` float32 N(0, 1) vectors of length ``n``, made on the device
    in one jitted call from ``seed``."""
    import jax
    import jax.numpy as jnp

    key = jax.random.key(int(rng_for(seed, stream).integers(0, 2**31 - 1)))
    make = jax.jit(lambda k: tuple(jax.random.normal(k, (count, n), jnp.float32)))
    return list(make(key))


def _p95(values: np.ndarray) -> float:
    """Nearest-rank 95th percentile."""
    v = np.sort(values)
    return float(v[max(0, math.ceil(0.95 * v.size) - 1)])


def _host(arrays) -> np.ndarray:
    return np.stack([np.asarray(x) for x in arrays])


def _csr(a):
    from repro.core.formats import CSRMatrix

    return CSRMatrix(a.shape, a.indptr, a.indices, a.data)


def _check(value, limit) -> dict:
    return {"value": None if value is None else float(value), "limit": float(limit)}


def check_stated(cfg: dict, a) -> None:
    """Refuse a matrix that is not the one its configuration says runs."""
    built = {"n_rows": a.shape[0], "nnz": a.nnz,
             "max_row": int(np.diff(a.indptr).max())}
    stated = {k: cfg[k] for k in built}
    if built != stated:
        raise ValueError(f"configuration {cfg['name']} states {stated}, but its "
                         f"generator builds {built}")


@dataclasses.dataclass
class Cell:
    """A cell set up and warmed: what the measured window drives."""

    name: str
    workload: dict
    config: dict
    mix: dict
    kind: str
    a: object  # the host matrix (bench.lib.suite.Csr)
    devs: list
    device: dict
    system: object  # SparseEngine or SparseSolver
    ops: dict
    pool: list  # device-resident request vectors or right-hand sides
    e2e_entries: list
    layer_entries: list
    cache_dir: Path
    bench_dir: Path
    setup_s: float


def build_cell(
    name: str,
    seed: int,
    *,
    bench_dir: Path = registry.BENCH_DIR,
    t_start: float | None = None,
    require_tpu: bool = True,
    scale: float | None = None,
    cache_dir: Path | None = None,
    log=print,
) -> Cell:
    """Load, build and warm up one cell; ``setup_s`` ends here."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    bench_dir = Path(bench_dir)
    bm = registry.load_benchmark(bench_dir.parent)
    w = registry.find_workload(bm, name)
    cfg = registry.load_config(w["config"], bench_dir)
    mix = registry.load_traffic(w["traffic"], bench_dir)
    e2e_entries, layer_entries = registry.cell_metrics(bm, name)
    devs, device = device_info(int(w["chips"]), require_tpu)
    log(f"device: {json.dumps(device)}")
    cache_dir = Path(cache_dir) if cache_dir is not None else bench_dir / ".cache"
    scale = float(cfg["scale"] if scale is None else scale)
    kind = "cg" if mix["request"] == "cg" else "serve"

    t = time.perf_counter()
    store = PatternStore(cfg["name"], cfg["generator"], scale,
                         cfg["structure_seed"], cache_dir / "patterns")
    a = store.matrix(seed)
    if scale == float(cfg["scale"]):
        check_stated(cfg, a)
    if kind == "cg":
        a = spd_shift(a, store.spd_pattern(), margin=cfg["cg"]["spd_margin"])
    n = a.shape[0]
    log(f"matrix {cfg['name']}@{scale:g}: {a.shape[0]}x{a.shape[1]}, "
        f"nnz={a.nnz}{' (spd_shift)' if kind == 'cg' else ''}; pattern from "
        f"cache: {store.hits}; {time.perf_counter() - t:.2f} s")

    from repro.runtime.engine import SparseEngine
    from repro.runtime.solver import SparseSolver
    from repro.tune import PlanCache

    plans = PlanCache(cache_dir / "plans" / f"{cfg['name']}.json")
    t = time.perf_counter()
    if kind == "serve":
        ks = tuple(int(k) for k in cfg["engine"]["ks"])
        system = SparseEngine(_csr(a), ks=ks, cache=plans)
        ops = system.ops
        pool = device_vectors(seed, 1, max(int(mix["x_pool"]), max(ks)), n)
    else:
        system = SparseSolver(_csr(a), cache=plans)
        ops = {1: system.op(1)}
        pool = device_vectors(seed, 2, int(mix["b_pool"]), n)
    for k, op in sorted(ops.items()):
        log(f"plan {op.plan.kind} k={k}: {op.plan.candidate.key()} from "
            f"{'the plan cache' if op.from_cache else 'a measured search'} "
            f"(measured {op.plan.measured_s * 1e3:.4f} ms)")
    log(f"plans ready in {time.perf_counter() - t:.2f} s")

    t = time.perf_counter()
    if kind == "serve":  # every bucket's program, and its result slices
        for k in ks:
            jax.block_until_ready(system.run(pool[:k]))
        system.stats = type(system.stats)()
    else:
        system.cg(pool[0], tol=float(cfg["cg"]["tol"]),
                  maxiter=int(cfg["cg"]["maxiter"]))
    log(f"warm-up {time.perf_counter() - t:.2f} s")
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.4f}")
    return Cell(name, w, cfg, mix, kind, a, devs, device, system, ops, pool,
                e2e_entries, layer_entries, cache_dir, bench_dir, setup_s)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             log=print, control: bool = False, dump_trace: Path | None = None,
             **build_kw) -> tuple[dict, dict]:
    """Build one cell and measure it once (see :func:`measure_cell`)."""
    cell = build_cell(name, seed, log=log, **build_kw)
    return measure_cell(cell, seed, seconds, trace, log=log, control=control,
                        dump_trace=dump_trace)


def measure_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
                 log=print, control: bool = False,
                 dump_trace: Path | None = None) -> tuple[dict, dict]:
    """Returns ``(result, extra)``: the last line's object, and readings
    that only ``bench/calibrate.py`` prints (the control's, when asked)."""
    import jax

    from repro.tune.operator import evict_prepared

    name, kind, mix, cfg, a = cell.name, cell.kind, cell.mix, cell.config, cell.a
    system, ops, pool, devs = cell.system, cell.ops, cell.pool, cell.devs
    device = dict(cell.device)
    n = a.shape[0]
    tol, maxiter = float(cfg["cg"]["tol"]), int(cfg["cg"]["maxiter"])
    cell.system = cell.ops = cell.pool = None  # this run frees them
    trace_dir = cell.cache_dir / "trace" / name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        if kind == "serve" and mix["loop"] == "open":
            out = traffic.serve_open(system, pool, mix, seconds, seed)
        elif kind == "serve":
            out = traffic.serve_closed(system, pool, mix, seconds, seed)
        else:
            out = traffic.cg_closed(system, pool, mix, seconds, seed, tol, maxiter)
    finally:
        if trace:
            jax.profiler.stop_trace()
    stats = (devs[0].memory_stats() or {})
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    counters: dict = {}
    e2e: dict = {"setup_s": cell.setup_s}
    extra: dict = {}
    if kind == "serve":
        st = system.stats
        counters["engine"] = {"padded_cols": st.padded_cols,
                              "occupied_cols": st.occupied_cols,
                              "dispatched": dict(st.dispatched)}
        summary = st.summary()
        log(f"engine stats: {json.dumps({k: summary[k] for k in ('dispatches', 'by_bucket', 'served_cols', 'padded_cols', 'retries', 'demotions', 'failed_batches')})}")
        late = out.lateness_s
        log(f"requests: offered {out.offered}, served {out.served}, failed or "
            f"never came {out.failed}; window {out.window_s:.4f} s; most pending "
            f"{out.max_pending}; generator late by mean "
            f"{(late.mean() if late.size else 0) * 1e3:.4f} ms, p95 "
            f"{(np.percentile(late, 95) if late.size else 0) * 1e3:.4f} ms, max "
            f"{(late.max() if late.size else 0) * 1e3:.4f} ms")
        if out.served:
            e2e["spmv_p95_ms"] = _p95(out.latencies_s) * 1e3
            e2e["spmv_rps"] = out.served / out.window_s
            log(f"latency ms: p50 {np.median(out.latencies_s) * 1e3:.4f}, p95 "
                f"{e2e['spmv_p95_ms']:.4f}, max {out.latencies_s.max() * 1e3:.4f}")
        idx = sorted(out.kept)
        ys = _host(out.kept[j] for j in idx) if idx else np.zeros((0, n), np.float32)
        xs = _host(pool)[[j % len(pool) for j in idx]] if idx else ys
        buckets = [out.kept_bucket[j] for j in idx]
        attempted, failed = out.offered, out.failed
        faults = system.supervisor.faults()
        system.close()
    else:
        its = out.iterations
        counters["cg"] = {"iterations": list(its)}
        log(f"solves: {out.solves} in {out.window_s:.4f} s, failed {out.failed}, "
            f"iterations {sorted(set(its))}, all converged {all(out.converged)}")
        if out.solves:
            e2e["cg_solve_s"] = out.window_s / out.solves
        idx = sorted(out.xs)
        xs = _host(out.xs[i] for i in idx) if idx else np.zeros((0, n), np.float32)
        bs = _host(pool)[[i % len(pool) for i in idx]] if idx else xs
        attempted = out.solves + out.failed
        failed = out.failed + sum(not c for c in out.converged)
        faults = system.supervisor.faults()
    if faults:
        log(f"supervisor faults: {faults}")
    for op in ops.values():
        evict_prepared(op.plan.fingerprint)
    del system, ops, pool, out
    gc.collect()

    t = time.perf_counter()
    a64 = reference.scipy_f64(a)
    if kind == "serve":
        errs = reference.spmv_errors(a64, xs, ys)
        for b in sorted(set(buckets), key=str):
            sel = [i for i, bb in enumerate(buckets) if bb == b]
            log(f"bucket {b}: {len(sel)} checked, worst spmv_err "
                f"{errs[sel].max():.4e}")
        checks = {
            "spmv_err": _check(errs.max() if errs.size else None,
                               reference.SPMV_ERR_LIMIT),
            "unanswered": _check(failed, 0),
        }
        if control:
            extra["control_spmv_err"] = float(
                reference.spmv_errors(a64, xs, reference.control_spmv(a, xs)).max())
        extra["spmv_err"] = errs.tolist()
    else:
        res = reference.cg_residuals(a64, bs, xs)
        checks = {
            "cg_residual": _check(res.max() if res.size else None,
                                  reference.CG_RESIDUAL_LIMIT),
            "unconverged": _check(failed, 0),
        }
        if control:
            ctrl = reference.control_cg(a, bs[:3], tol, maxiter)
            extra["control_cg_residual"] = reference.cg_residuals(a64, bs[:3], ctrl).tolist()
        extra["cg_residual"] = res.tolist()
    log(f"reference check {time.perf_counter() - t:.2f} s")
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    result: dict = {"correct": correct, "attempted": int(attempted),
                    "failed": int(failed)}
    if not trace:
        metrics = {}
        for m in cell.e2e_entries:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    else:
        summary = reduce_events(load_events(trace_dir))
        if dump_trace is not None:
            save_events(load_events(trace_dir), dump_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: window {summary.window_s:.4f} s, busy {summary.busy_s:.4f} s, "
            f"{summary.n_devices} device(s)")
        for mod, (cnt, sec) in sorted(summary.modules.items(), key=lambda kv: -kv[1][1])[:12]:
            log(f"trace program {mod}: {cnt} launches, {sec:.6f} s")
        log(f"trace idle by host span: {json.dumps(summary.gap_totals)}")
        record = RunRecord(a.shape[0], a.shape[1], a.nnz, device["kind"],
                           counters, summary)
        metrics = {}
        for m in cell.layer_entries:
            value = registry.load_reader(m["name"], cell.bench_dir)(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["device"] = device
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    return result, extra
