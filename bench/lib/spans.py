"""The engine's and the solver's own spans in a profiler trace.

The serving engine and the solver annotate their steps on the profiler's
clock (``repro.runtime.engine`` and ``repro.runtime.solver``):

* ``engine.submit``, ``engine.step``, ``engine.launch``, ``engine.retire``
  with its child ``engine.device_wait``, ``engine.recover`` and
  ``engine.result``;
* ``solver.call`` with its children ``solver.launch`` and
  ``solver.device_wait``, and ``solver.fetch``.

Spans nest by call on each host thread.  :func:`reduce_spans` runs over
the event list :func:`bench.lib.trace.load_events` returns, inside the
harness's ``bench.window`` span, and gives:

* for each span name: count, total, self time (duration less the part
  its child program spans cover) and the longest;
* the device's idle time, each stretch of it put down to the innermost
  program span running on the host meanwhile (``OUTSIDE`` where none
  is), so the idle share splits by what the program was doing;
* each ``engine.step``'s host time: its duration less the
  ``engine.device_wait`` under it (a step that retires a batch waits for
  the device, which is not the host's time); the longest, and every step
  whose host time exceeds ``long_s`` with its child spans by name, the
  backend compiles (``backend_compile_and_load`` host events) inside it,
  and the device's idle time under it.

The functions below it reduce a :class:`SpanSummary` to the per-layer
quantities the spans are there for.  Each returns None when nothing was
recorded (a program without these spans records none).
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict

import numpy as np

from .trace import DEVICE_PREFIX, OPS_LINE, WINDOW_SPAN, Event, _union

PROGRAM_PREFIXES = ("engine.", "solver.")
WAIT_SPANS = ("engine.device_wait", "solver.device_wait")
COMPILE_EVENT = "backend_compile_and_load"
OUTSIDE = "host:outside_program_spans"
LONG_STEP_S = 0.3


@dataclasses.dataclass
class SpanSummary:
    spans: dict  # name -> {"count", "total_s", "self_s", "max_s"}
    idle_by_span: dict  # innermost program span (or OUTSIDE) -> idle seconds
    step_host_max_s: float  # the most host time of one engine.step
    long_steps: list  # one dict per engine.step with host time over long_s
    compiles: int  # backend compiles that started in the window


def _idle_gaps(events: list[Event], w0: float, w1: float) -> np.ndarray:
    """(k, 2) intervals of the window in which the first device that ran
    anything ran no operation; empty when no device ran anything."""
    by_plane = defaultdict(list)
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX) and e.line == OPS_LINE:
            by_plane[e.plane].append((max(e.start_ns, w0), min(e.end_ns, w1)))
    for plane in sorted(by_plane):
        iv = np.asarray(by_plane[plane], dtype=np.float64).reshape(-1, 2)
        busy = _union(iv[iv[:, 1] > iv[:, 0]])
        if busy.size:
            starts = np.concatenate([[w0], busy[:, 1]])
            ends = np.concatenate([busy[:, 0], [w1]])
            keep = ends > starts
            return np.stack([starts[keep], ends[keep]], axis=1)
    return np.zeros((0, 2))


def _overlap(pieces: list, gaps: np.ndarray) -> dict:
    """Seconds of ``gaps`` that each named piece ``(start, end, name)``
    covers."""
    out: dict = defaultdict(float)
    if not gaps.size or not pieces:
        return out
    pieces = sorted(pieces)
    p_start = np.asarray([p[0] for p in pieces])
    p_end_max = np.maximum.accumulate([p[1] for p in pieces])
    for g0, g1 in gaps:
        lo = int(np.searchsorted(p_end_max, g0, side="right"))
        hi = int(np.searchsorted(p_start, g1))
        for s, e, name in pieces[lo:hi]:
            d = min(e, g1) - max(s, g0)
            if d > 0:
                out[name] += d * 1e-9
    return out


def reduce_spans(events: list[Event], long_s: float = LONG_STEP_S) -> SpanSummary:
    windows = [e for e in events if e.name == WINDOW_SPAN
               and not e.plane.startswith(DEVICE_PREFIX)]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    w0, w1 = windows[0].start_ns, windows[0].end_ns

    by_line = defaultdict(list)  # host thread -> clipped program spans
    compile_starts = []
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX):
            continue
        if e.name == COMPILE_EVENT:
            if w0 <= e.start_ns < w1:
                compile_starts.append(e.start_ns)
        elif e.name.startswith(PROGRAM_PREFIXES):
            s, t = max(e.start_ns, w0), min(e.end_ns, w1)
            if t > s or (t == s and w0 <= s < w1):
                by_line[(e.plane, e.line)].append((s, t, e.name))
    compile_starts = np.sort(np.asarray(compile_starts, dtype=np.float64))

    stats: dict = defaultdict(lambda: {"count": 0, "total_s": 0.0,
                                       "self_s": 0.0, "max_s": 0.0})
    pieces = []  # (start, end, name): each span less its children
    steps = []  # (start, end, host s, {child name -> [count, seconds]})
    step_host_max = 0.0
    for spans in by_line.values():
        spans.sort(key=lambda sp: (sp[0], -sp[1]))
        children = defaultdict(list)
        stack: list = []
        for i, (s, t, _) in enumerate(spans):
            while stack and spans[stack[-1]][1] < t:  # it ends before i does
                stack.pop()
            if stack:
                children[stack[-1]].append(i)
            stack.append(i)
        for i, (s, t, name) in enumerate(spans):
            st = stats[name]
            st["count"] += 1
            st["total_s"] += (t - s) * 1e-9
            st["max_s"] = max(st["max_s"], (t - s) * 1e-9)
            cur = s
            for c in children[i]:
                cs, ct, _ = spans[c]
                if cs > cur:
                    pieces.append((cur, cs, name))
                cur = max(cur, ct)
            if t > cur:
                pieces.append((cur, t, name))
            st["self_s"] += (t - s) * 1e-9 - sum(
                (spans[c][1] - spans[c][0]) * 1e-9 for c in children[i])
            if name == "engine.step":
                inner: dict = defaultdict(lambda: [0, 0.0])
                todo = list(children[i])
                while todo:
                    c = todo.pop()
                    inner[spans[c][2]][0] += 1
                    inner[spans[c][2]][1] += (spans[c][1] - spans[c][0]) * 1e-9
                    todo.extend(children[c])
                host = (t - s) * 1e-9 - inner.get("engine.device_wait",
                                                  (0, 0.0))[1]
                step_host_max = max(step_host_max, host)
                if host > long_s:
                    steps.append((s, t, host, dict(inner)))

    gaps = _idle_gaps(events, w0, w1)
    idle = _overlap(pieces, gaps)
    if gaps.size:
        covered = _union(np.asarray([p[:2] for p in pieces], dtype=np.float64)
                         .reshape(-1, 2))
        idle[OUTSIDE] = (float((gaps[:, 1] - gaps[:, 0]).sum()) * 1e-9
                         - sum(_overlap([(a, b, "") for a, b in covered],
                                        gaps).values()))
    long_steps = []
    for s, t, host, inner in sorted(steps):
        n_compiles = int(np.searchsorted(compile_starts, t)
                         - np.searchsorted(compile_starts, s))
        step_idle = _overlap([(s, t, "step")], gaps).get("step", 0.0)
        long_steps.append({
            "at_s": (s - w0) * 1e-9, "seconds": (t - s) * 1e-9, "host_s": host,
            "children": {k: {"count": v[0], "seconds": v[1]}
                         for k, v in sorted(inner.items())},
            "compiles": n_compiles, "device_idle_s": step_idle,
        })
    return SpanSummary(spans={k: dict(v) for k, v in sorted(stats.items())},
                       idle_by_span=dict(idle), step_host_max_s=step_host_max,
                       long_steps=long_steps,
                       compiles=int(compile_starts.size))


def _host_self_s(summary: SpanSummary, prefix: str) -> float:
    return sum(st["self_s"] for name, st in summary.spans.items()
               if name.startswith(prefix) and name not in WAIT_SPANS)


def engine_host_ms(summary: SpanSummary):
    """The engine's host time per dispatched batch, ms: the self time of
    every ``engine.*`` span but ``engine.device_wait``, over the
    ``engine.launch`` spans in the window."""
    launches = summary.spans.get("engine.launch", {}).get("count", 0)
    if not launches:
        return None
    return 1e3 * _host_self_s(summary, "engine.") / launches


def engine_step_max_ms(summary: SpanSummary):
    """The longest ``engine.step`` in the window, ms."""
    step = summary.spans.get("engine.step")
    return None if not step else 1e3 * step["max_s"]


def engine_step_host_max_ms(summary: SpanSummary):
    """The most host time one ``engine.step`` took in the window, ms: its
    duration less the device waits under it.  A stall of the host shows
    here; a step that waits out a batch on the device does not."""
    step = summary.spans.get("engine.step")
    return None if not step else 1e3 * summary.step_host_max_s


def solver_host_ms(summary: SpanSummary):
    """The solver's host time per solve, ms: the self time of every
    ``solver.*`` span but ``solver.device_wait``, over the ``solver.call``
    spans in the window."""
    calls = summary.spans.get("solver.call", {}).get("count", 0)
    if not calls:
        return None
    return 1e3 * _host_self_s(summary, "solver.") / calls


def compile_delta(before: dict | None, after: dict | None):
    """What ``repro.runtime.executable.compile_counts`` counted between
    two snapshots, or None when the program has no such counter."""
    if before is None or after is None:
        return None
    out = {k: after[k] - before[k] for k in after if k != "programs"}
    out["programs"] = {p: n - before["programs"].get(p, 0)
                       for p, n in after["programs"].items()
                       if n > before["programs"].get(p, 0)}
    return out


def runtime_compiles(counters: dict):
    """Backend compiles in the window (0 is a reading), from the compile
    counter's difference in ``counters["runtime"]``."""
    rt = counters.get("runtime")
    return None if rt is None else float(rt["backend_compiles"])
