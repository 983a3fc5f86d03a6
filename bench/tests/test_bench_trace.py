"""The trace reduction: on hand-made events with known answers, on a small
trace recorded on a TPU v5e chip, and on a trace the profiler writes here."""
import glob
from pathlib import Path

import pytest

from bench.lib.trace import (
    UNATTRIBUTED,
    Event,
    load_events,
    read_saved_events,
    reduce_events,
)

DEV = "/device:TPU:0"
HOST = "/host:CPU"
DATA = Path(__file__).parent / "data"


def _ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def test_hand_made_trace():
    events = [
        _ev(HOST, "python", "bench.window", 100, 1000),
        _ev(HOST, "python", "bench.step", 100, 150),
        _ev(HOST, "python", "bench.wait", 600, 300),
        _ev(HOST, "python", "bench.submit", 950, 10),
        # Two launches of one program, its ops overlapping in places.
        _ev(DEV, "XLA Modules", "jit_apply", 150, 400),
        _ev(DEV, "XLA Ops", "gather", 150, 200),
        _ev(DEV, "XLA Ops", "fusion", 300, 150),   # 300-450: overlaps gather
        _ev(DEV, "XLA Modules", "jit_apply", 900, 300),
        _ev(DEV, "XLA Ops", "gather", 900, 300),   # clipped at 1100
        _ev(DEV, "XLA Ops", "outside", 2000, 50),  # after the window
    ]
    s = reduce_events(events)
    assert s.window_s == pytest.approx(1000e-9)
    # Busy: 150-450 and 900-1100, 500 ns of the 1000.
    assert s.busy_s == pytest.approx(500e-9)
    assert s.idle_share == pytest.approx(0.5)
    assert s.modules["jit_apply"] == [2, pytest.approx(600e-9)]
    assert s.ops["gather"] == pytest.approx(400e-9)
    assert "outside" not in s.ops
    # Gaps: 100-150 (under bench.step), 450-900 (bench.wait holds 300 of
    # its 450 ns), nothing after 1100.
    assert s.gaps == [("bench.wait", pytest.approx(450e-9)),
                      ("bench.step", pytest.approx(50e-9))]
    assert s.gap_totals["bench.wait"] == pytest.approx(450e-9)
    b = s.breakdown(top=1)
    assert b["device_ops"] == [["gather", pytest.approx(400e-9)]]
    assert b["idle_gaps"] == [["bench.wait", pytest.approx(450e-9)]]


def test_gap_with_no_harness_span_is_unattributed():
    events = [
        _ev(HOST, "python", "bench.window", 0, 100),
        _ev(DEV, "XLA Ops", "op", 0, 40),
    ]
    s = reduce_events(events)
    assert s.gaps == [(UNATTRIBUTED, pytest.approx(60e-9))]


def test_trace_without_window_is_refused():
    with pytest.raises(ValueError):
        reduce_events([_ev(DEV, "XLA Ops", "op", 0, 40)])


def test_profiler_trace_written_here(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones(1000)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = load_events(tmp_path)
    names = {e.name for e in events}
    assert {"bench.window", "bench.step"} <= names
    s = reduce_events(events)
    assert s.window_s > 0 and s.n_devices == 0  # no TPU plane on the CPU


def test_trace_recorded_on_the_chip():
    """2.5 s of an ldoor.serve_open window traced on one TPU v5e (seed 102):
    five k = 1 and five k = 16 launches of the engine's bucket programs."""
    events = read_saved_events(DATA / "ldoor_serve_open_v5e.events.json.gz")
    s = reduce_events(events)
    assert s.n_devices == 1 and s.window_s == pytest.approx(2.5)
    # Busy time, recomputed by a plain sweep over the clipped op intervals.
    w0 = next(e.start_ns for e in events if e.name == "bench.window")
    w1 = w0 + 2.5e9
    ivs = sorted((max(e.start_ns, w0), min(e.end_ns, w1)) for e in events
                 if e.line == "XLA Ops" and e.end_ns > w0 and e.start_ns < w1)
    busy, cur = 0.0, None
    for a, b in ivs:
        if cur is None or a > cur[1]:
            busy += 0.0 if cur is None else cur[1] - cur[0]
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    busy += cur[1] - cur[0]
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert s.busy_s + sum(sec for _, sec in s.gaps) == pytest.approx(s.window_s)
    launches, seconds = s.module_seconds(lambda n: n.startswith("jit_apply"))
    assert launches == 10 and 0 < seconds <= s.window_s
    assert {name for name, _ in s.gaps} <= {"bench.wait", "bench.step",
                                            "bench.submit", UNATTRIBUTED}
    ops = s.breakdown()["device_ops"]
    assert len(ops) == 10 and all(len(name) <= 120 for name, _ in ops)
