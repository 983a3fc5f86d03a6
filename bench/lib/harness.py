"""Run one cell once: set up, measure for ``seconds``, check, reduce.

The result is the benchmark's last line (see ``bench/run.py``).  The cell's
kind, ``bench/kinds/<request>.py`` as its traffic mix names it, builds the
system, drives the window, reduces what came back and checks it
(``bench/kinds/__init__.py``); the harness does what every kind shares:
the device, the profiler, the compile counter, the memory peak, the
reference's clock, the per-layer readers and the last line.  What the run
did goes to ``log`` on the way: the device, where the pattern and each
plan came from, what was offered, served and late, and every compared
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
from typing import Callable

import numpy as np

from . import reference, registry
from .spans import LONG_STEP_S, SpanSummary, compile_delta, reduce_spans
from .suite import PatternStore, rng_for
from .trace import TraceSummary, load_events, reduce_events, save_events


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class RunRecord:
    """What a per-layer reader may read: the run's counters, its trace, and
    the program's spans in it (``bench.lib.spans.reduce_spans``)."""

    n_rows: int
    n_cols: int
    nnz: int
    device_kind: str
    counters: dict
    trace: TraceSummary | None
    spans: SpanSummary | None = None


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


def p95(values: np.ndarray) -> float:
    """Nearest-rank 95th percentile."""
    v = np.sort(values)
    return float(v[max(0, math.ceil(0.95 * v.size) - 1)])


def host_stack(arrays) -> np.ndarray:
    return np.stack([np.asarray(x) for x in arrays])


def program_csr(a):
    """The benchmark's host matrix as the program's ``CSRMatrix``."""
    from repro.core.formats import CSRMatrix

    return CSRMatrix(a.shape, a.indptr, a.indices, a.data)


def compared(value, limit) -> dict:
    """One entry of ``checks``: a number beside its limit."""
    return {"value": None if value is None else float(value), "limit": float(limit)}


def check_stated(cfg: dict, a) -> None:
    """Refuse a matrix that is not the one its configuration says runs."""
    built = {"n_rows": a.shape[0], "nnz": a.nnz,
             "max_row": int(np.diff(a.indptr).max())}
    stated = {k: cfg[k] for k in built}
    if built != stated:
        raise ValueError(f"configuration {cfg['name']} states {stated}, but its "
                         f"generator builds {built}")


def compile_counts():
    """The program's compile counter now, or None for a program without one."""
    try:
        from repro.runtime.executable import compile_counts as counts
    except ImportError:
        return None
    return counts()


@dataclasses.dataclass
class Setup:
    """What a kind's ``build`` gets: the cell's files and where it runs."""

    config: dict
    mix: dict
    seed: int
    devs: list
    scale: float
    cache_dir: Path
    bench_dir: Path
    log: Callable

    def matrix(self):
        """``(store, a)``: the configuration's pattern, cached in
        ``cache_dir``, with float32 values from the seed; at the stated
        scale, refused unless it is the matrix the configuration states."""
        cfg = self.config
        store = PatternStore(cfg["name"], cfg["generator"], self.scale,
                             cfg["structure_seed"], self.cache_dir / "patterns",
                             self.bench_dir)
        a = store.matrix(self.seed)
        if self.scale == float(cfg["scale"]):
            check_stated(cfg, a)
        return store, a

    def log_matrix(self, store, a, t: float, note: str = "") -> None:
        self.log(f"matrix {self.config['name']}@{self.scale:g}: {a.shape[0]}x"
                 f"{a.shape[1]}, nnz={a.nnz}{note}; pattern from cache: "
                 f"{store.hits}; {time.perf_counter() - t:.2f} s")

    def plan_cache(self):
        """The program's plan cache for this configuration, in the checkout."""
        from repro.tune import PlanCache

        return PlanCache(self.cache_dir / "plans" / f"{self.config['name']}.json")

    def log_plans(self, ops: dict, t: float) -> None:
        for k, op in sorted(ops.items()):
            self.log(f"plan {op.plan.kind} k={k}: {op.plan.candidate.key()} from "
                     f"{'the plan cache' if op.from_cache else 'a measured search'} "
                     f"(measured {op.plan.measured_s * 1e3:.4f} ms)")
        self.log(f"plans ready in {time.perf_counter() - t:.2f} s")


@dataclasses.dataclass
class Reduced:
    """What a kind's ``reduce`` makes of a window's outcome."""

    e2e: dict  # end-to-end values under the benchmark's names, setup_s aside
    counters: dict  # for the per-layer readers (RunRecord.counters)
    attempted: int
    failed: int
    sample: object  # what the kind's ``check`` compares, on the host


@dataclasses.dataclass
class Cell:
    """A cell set up and warmed: what the measured window drives."""

    name: str
    workload: dict
    config: dict
    mix: dict
    kind: object  # the module bench/kinds/<request>.py
    a: object  # the host matrix (bench.lib.suite.Csr) RunRecord counts
    devs: list
    device: dict
    system: object
    ops: dict  # the prepared operators, by block width
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
    t_start = time.perf_counter() if t_start is None else t_start
    bench_dir = Path(bench_dir)
    bm = registry.load_benchmark(bench_dir.parent)
    w = registry.find_workload(bm, name)
    cfg = registry.load_config(w["config"], bench_dir)
    mix = registry.load_traffic(w["traffic"], bench_dir)
    kind = registry.load_kind(mix["request"], bench_dir)
    e2e_entries, layer_entries = registry.cell_metrics(bm, name)
    devs, device = device_info(int(w["chips"]), require_tpu)
    log(f"device: {json.dumps(device)}")
    cache_dir = Path(cache_dir) if cache_dir is not None else bench_dir / ".cache"
    scale = float(cfg["scale"] if scale is None else scale)
    system, ops, pool, a = kind.build(
        Setup(cfg, mix, seed, devs, scale, cache_dir, bench_dir, log))
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


def window(cell: Cell, seconds: float, seed: int, trace_dir: Path | None = None):
    """Drive one window of the cell's traffic, under the profiler when
    ``trace_dir`` is given.  Returns the kind's outcome and what the
    program's compile counter counted meanwhile (None without one)."""
    import jax

    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    before = compile_counts()
    try:
        out = cell.kind.drive(cell, seconds, seed)
    finally:
        after = compile_counts()
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return out, compile_delta(before, after)


def read_layers(cell: Cell, counters: dict, events: list,
                long_s: float = LONG_STEP_S) -> tuple[RunRecord, dict]:
    """The record the per-layer readers read, from one load of the trace's
    events, and what each of the cell's readers read from it (None where
    it found nothing)."""
    a = cell.a
    record = RunRecord(a.shape[0], a.shape[1], a.nnz, cell.device["kind"],
                       counters, reduce_events(events),
                       reduce_spans(events, long_s=long_s))
    return record, {m["name"]: registry.load_reader(m["name"], cell.bench_dir)(record)
                    for m in cell.layer_entries}


def measure_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
                 log=print, control: bool = False,
                 dump_trace: Path | None = None) -> tuple[dict, dict]:
    """Returns ``(result, extra)``: the last line's object, and the control's
    readings, which only ``bench/calibrate.py`` prints (when asked)."""
    from repro.tune.operator import evict_prepared

    kind = cell.kind
    device = dict(cell.device)
    trace_dir = cell.cache_dir / "trace" / cell.name if trace else None
    out, compiles = window(cell, seconds, seed, trace_dir)
    stats = (cell.devs[0].memory_stats() or {})
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    red = kind.reduce(cell, out, log)
    kind.close(cell)
    for op in cell.ops.values():
        evict_prepared(op.plan.fingerprint)
    cell.system = cell.ops = cell.pool = None  # this run frees them
    del out
    gc.collect()

    t = time.perf_counter()
    a64 = reference.scipy_f64(cell.a)
    checks = kind.check(cell, a64, red, log)
    extra = kind.control(cell, a64, red) if control else {}
    log(f"reference check {time.perf_counter() - t:.2f} s")
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    result: dict = {"correct": correct, "attempted": int(red.attempted),
                    "failed": int(red.failed)}
    if not trace:
        e2e = {"setup_s": cell.setup_s, **red.e2e}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in cell.e2e_entries if m["name"] in e2e}
        result["device"] = device
    else:
        t = time.perf_counter()
        events = load_events(trace_dir)
        if dump_trace is not None:
            save_events(events, dump_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        record, readings = read_layers(cell, {**red.counters, "runtime": compiles},
                                       events)
        log(f"trace read: {len(events)} events in {time.perf_counter() - t:.2f} s")
        del events
        summary = record.trace
        log(f"trace: window {summary.window_s:.4f} s, busy {summary.busy_s:.4f} s, "
            f"{summary.n_devices} device(s)")
        for mod, (cnt, sec) in sorted(summary.modules.items(), key=lambda kv: -kv[1][1])[:12]:
            log(f"trace program {mod}: {cnt} launches, {sec:.6f} s")
        log(f"trace idle by host span: {json.dumps(summary.gap_totals)}")
        result["metrics"] = {m["name"]: {"value": float(readings[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell.layer_entries
                             if readings[m["name"]] is not None}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["device"] = device
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    return result, extra
