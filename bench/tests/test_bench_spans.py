"""The reduction of the engine's and the solver's spans: on hand-made
events with known answers, on a chip trace recorded without them, and on
a trace the profiler writes here around a served engine."""
from pathlib import Path

import pytest

from bench.lib.spans import (
    OUTSIDE,
    compile_delta,
    engine_host_ms,
    engine_step_host_max_ms,
    engine_step_max_ms,
    reduce_spans,
    runtime_compiles,
    solver_host_ms,
)
from bench.lib.trace import Event, load_events, read_saved_events, reduce_events

DEV = "/device:TPU:0"
HOST = "/host:CPU"
DATA = Path(__file__).parent / "data"


def _ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def _serving_events():
    return [
        _ev(HOST, "python", "bench.window", 0, 1000),
        _ev(HOST, "python", "bench.submit", 40, 50),
        _ev(HOST, "python", "engine.submit", 50, 30),
        _ev(HOST, "python", "bench.step", 100, 300),
        _ev(HOST, "python", "engine.step", 100, 300),
        _ev(HOST, "python", "engine.launch", 120, 80),
        _ev(HOST, "python", "backend_compile_and_load", 130, 40),
        _ev(HOST, "python", "engine.retire", 250, 130),
        _ev(HOST, "python", "engine.device_wait", 260, 110),
        _ev(HOST, "python", "engine.step", 500, 60),
        _ev(HOST, "python", "engine.result", 600, 100),
        # After the window: neither a span nor a compile of it.
        _ev(HOST, "python", "engine.step", 1200, 100),
        _ev(HOST, "python", "backend_compile_and_load", 1100, 10),
        _ev(DEV, "XLA Modules", "jit_apply_engine_k16", 150, 300),
        _ev(DEV, "XLA Ops", "fusion", 150, 150),
        _ev(DEV, "XLA Ops", "fusion.1", 360, 90),
        _ev(DEV, "XLA Modules", "jit_apply_engine_k1", 800, 100),
        _ev(DEV, "XLA Ops", "sell_spmv_pallas", 800, 100),
    ]


def test_self_time_longest_and_idle_by_innermost_span():
    s = reduce_spans(_serving_events(), long_s=150e-9)
    ns = pytest.approx
    assert s.spans["engine.step"] == {"count": 2, "total_s": ns(360e-9),
                                      "self_s": ns(150e-9), "max_s": ns(300e-9)}
    assert s.spans["engine.launch"]["self_s"] == ns(80e-9)
    assert s.spans["engine.retire"]["self_s"] == ns(20e-9)
    assert s.spans["engine.device_wait"]["self_s"] == ns(110e-9)
    assert s.spans["engine.submit"]["self_s"] == ns(30e-9)
    assert s.spans["engine.result"]["total_s"] == ns(100e-9)
    # Idle: 0-150, 300-360, 450-800, 900-1000, each stretch put down to
    # the innermost program span on the host meanwhile.
    assert s.idle_by_span == {
        "engine.submit": ns(30e-9), "engine.step": ns(80e-9),
        "engine.launch": ns(30e-9), "engine.device_wait": ns(60e-9),
        "engine.result": ns(100e-9), OUTSIDE: ns(360e-9)}
    assert sum(s.idle_by_span.values()) == ns(660e-9)
    assert s.compiles == 1
    (step,) = s.long_steps
    assert step["seconds"] == ns(300e-9) and step["at_s"] == ns(100e-9)
    assert step["host_s"] == ns(190e-9)  # less its 110 ns device wait
    assert step["children"] == {
        "engine.device_wait": {"count": 1, "seconds": ns(110e-9)},
        "engine.launch": {"count": 1, "seconds": ns(80e-9)},
        "engine.retire": {"count": 1, "seconds": ns(130e-9)}}
    assert step["compiles"] == 1 and step["device_idle_s"] == ns(110e-9)
    # The engine's host time per launched batch: every span's self time
    # but the device wait's (150 + 80 + 20 + 30 + 100 ns), one launch.
    assert engine_host_ms(s) == ns(380e-9 * 1e3)
    assert engine_step_max_ms(s) == ns(300e-9 * 1e3)
    assert engine_step_host_max_ms(s) == ns(190e-9 * 1e3)
    assert solver_host_ms(s) is None
    # A step long only for its device wait is no stall.
    assert reduce_spans(_serving_events(), long_s=200e-9).long_steps == []


def test_solver_host_time_per_solve():
    events = [
        _ev(HOST, "python", "bench.window", 0, 1000),
        _ev(HOST, "python", "solver.call", 100, 400),
        _ev(HOST, "python", "solver.launch", 110, 40),
        _ev(HOST, "python", "solver.device_wait", 150, 330),
        _ev(HOST, "python", "solver.fetch", 500, 20),
        _ev(HOST, "python", "solver.call", 600, 300),
        _ev(HOST, "python", "solver.launch", 600, 50),
        _ev(HOST, "python", "solver.device_wait", 650, 240),
        _ev(HOST, "python", "solver.fetch", 900, 30),
        _ev(DEV, "XLA Ops", "while", 120, 370),
        _ev(DEV, "XLA Ops", "while", 620, 280),
    ]
    s = reduce_spans(events)
    # Self: calls 30 + 10, launches 40 + 50, fetches 20 + 30, over 2 solves.
    assert solver_host_ms(s) == pytest.approx(90e-9 * 1e3)
    assert s.spans["solver.device_wait"]["self_s"] == pytest.approx(570e-9)
    assert engine_host_ms(s) is None and engine_step_max_ms(s) is None
    assert engine_step_host_max_ms(s) is None
    assert s.idle_by_span["solver.launch"] == pytest.approx(30e-9)
    assert s.idle_by_span["solver.fetch"] == pytest.approx(50e-9)


def test_reduce_events_ignores_the_program_spans():
    events = _serving_events()
    without = [e for e in events if not e.name.startswith(("engine.", "solver."))]
    assert reduce_events(events) == reduce_events(without)


def test_chip_trace_without_program_spans_reads_nothing():
    """The recorded v5e trace comes from a program that had no spans: its
    idle time is all outside them, and every reader returns None."""
    events = read_saved_events(DATA / "ldoor_serve_open_v5e.events.json.gz")
    s = reduce_spans(events)
    assert s.spans == {} and s.long_steps == []
    idle = sum(sec for _, sec in reduce_events(events).gaps)
    assert s.idle_by_span == {OUTSIDE: pytest.approx(idle)}
    assert engine_host_ms(s) is None and engine_step_max_ms(s) is None
    assert solver_host_ms(s) is None


def test_compile_delta_and_its_reading():
    before = {"jaxpr_traces": 5, "jaxpr_trace_s": 1.0, "backend_compiles": 3,
              "backend_compile_s": 2.0, "programs": {"jit(apply)": 3}}
    after = {"jaxpr_traces": 6, "jaxpr_trace_s": 1.5, "backend_compiles": 4,
             "backend_compile_s": 2.25,
             "programs": {"jit(apply)": 3, "jit(apply_engine_k16)": 1}}
    d = compile_delta(before, after)
    assert d == {"jaxpr_traces": 1, "jaxpr_trace_s": 0.5, "backend_compiles": 1,
                 "backend_compile_s": 0.25,
                 "programs": {"jit(apply_engine_k16)": 1}}
    assert runtime_compiles({"runtime": d}) == 1.0
    assert runtime_compiles({"runtime": compile_delta(after, after)}) == 0.0
    assert compile_delta(None, after) is None
    assert runtime_compiles({}) is None


def test_profiler_trace_of_a_served_engine(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    from repro.core import csr_from_dense
    from repro.runtime.engine import SparseEngine
    from repro.tune import PlanCache

    rng = np.random.default_rng(0)
    d = ((rng.random((64, 64)) < 0.1) * rng.standard_normal((64, 64)))
    eng = SparseEngine(csr_from_dense(d.astype(np.float32)), ks=(1, 4),
                       cache=PlanCache(), warmup=0, timed=1)
    xs = [jnp.asarray(rng.standard_normal(64).astype(np.float32))
          for _ in range(5)]
    eng.run(xs)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation("bench.window"):
        reqs = [eng.submit(x) for x in xs]
        while eng.pending or eng.in_flight:
            with TraceAnnotation("bench.step"):
                eng.step()
        ys = [r.result() for r in reqs]
    jax.profiler.stop_trace()
    s = reduce_spans(load_events(tmp_path))
    assert s.spans["engine.submit"]["count"] == 5
    assert s.spans["engine.result"]["count"] == 5
    assert s.spans["engine.launch"]["count"] == 2  # 4 + 1
    assert s.spans["engine.retire"]["count"] == 2
    assert s.compiles == 0  # both buckets were warm
    host = engine_host_ms(s)
    assert host is not None and host > 0
    assert engine_step_max_ms(s) >= 1e3 * s.spans["engine.launch"]["max_s"]
    np.testing.assert_allclose(np.asarray(ys[0]),
                               d.astype(np.float32) @ np.asarray(xs[0]),
                               rtol=1e-4, atol=1e-4)
    eng.close()


@pytest.mark.parametrize("workload", ["ldoor.spmv_single", "ldoor.cg"])
def test_probe_reads_spans_and_compiles_on_a_small_cell(tmp_path, workload):
    from bench.lib.harness import build_cell
    from bench.probe_spans import probe

    cell = build_cell(workload, 2**31 + 5, require_tpu=False, scale=0.005,
                      cache_dir=tmp_path, log=lambda m: None)
    untraced, traced = probe(cell, [False, True], 0.5, 2**31 + 5,
                             log=lambda m: None)
    assert not untraced["traced"] and "spans" not in untraced
    for rec in (untraced, traced):
        assert rec["compiles"]["backend_compiles"] == 0  # set-up warmed all
        assert rec["runtime.compiles"] == 0.0
    layer = traced["per_layer"]
    if workload == "ldoor.cg":
        assert untraced["cg_solve_s"] > 0 and traced["attempted"] > 0
        assert layer["solver.host_ms"] > 0 and layer["engine.host_ms"] is None
        assert traced["spans"]["solver.call"]["count"] == traced["attempted"]
    else:
        assert untraced["spmv_rps"] > 0 and traced["spmv_p95_ms"] > 0
        assert layer["engine.host_ms"] > 0 and layer["engine.step_max_ms"] > 0
        assert traced["spans"]["engine.submit"]["count"] == traced["attempted"]
    assert traced["device_idle_pct"] is None  # no device plane on the CPU
    # The cell's accepted per-layer metrics, read as a traced run reads them.
    accepted = traced["accepted_per_layer"]
    if workload == "ldoor.cg":
        assert accepted["cg.iterations"] > 0
    else:
        assert set(accepted) == {"device_idle.serve", "kernel_hbm_roofline.serve"}
    cell.kind.close(cell)
