"""From a JAX profiler trace to busy time, idle gaps and program times.

Two stages, so the second can be checked on a small recorded trace:

1. :func:`load_events` reads the ``.xplane.pb`` the profiler wrote into a
   flat list of :class:`Event` (plane, line, name, start, duration).
2. :func:`reduce_events` clips device events to the harness's
   ``bench.window`` span and reduces them:

   * busy time: the union of the intervals in which an operation ran on a
     device (the "XLA Ops" line of each ``/device:TPU:<i>`` plane),
     averaged over the devices that ran anything;
   * idle gaps: the complement of busy time in the window, each put down
     to the harness span (``bench.*``) on the host that overlaps it most,
     so a gap says what the host was doing while the device waited;
   * program times: the "XLA Modules" events (one per program launch) by
     name, which the per-layer readers match against the programs they
     measure;
   * operation times: the "XLA Ops" events by name, for the breakdown.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
from collections import defaultdict
from pathlib import Path

import numpy as np

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
UNATTRIBUTED = "host:outside_bench_spans"
_OP = re.compile(r"\s([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")


def short_op_name(hlo: str) -> str:
    """``%fusion.1 fusion f32[952203,16]`` for an HLO instruction's text."""
    name, eq, rhs = hlo.partition(" = ")
    op, shape = _OP.search(rhs), _SHAPE.search(rhs)
    if not eq or op is None:
        return hlo[:120]
    return f"{name} {op.group(1)} {shape.group(0) if shape else ''}".rstrip()


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(trace_dir: str | Path) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def save_events(events: list[Event], path: str | Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def read_saved_events(path: str | Path) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merged, sorted (k, 2) intervals."""
    if intervals.size == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over the devices that ran anything
    n_devices: int
    modules: dict  # program name -> [launches, seconds], in the window
    ops: dict  # operation name -> seconds, in the window
    gaps: list  # [(host span, seconds)], longest first
    gap_totals: dict  # host span -> seconds of idle device

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, match) -> tuple[int, float]:
        """Launches and device seconds of the programs ``match(name)`` accepts."""
        n, s = 0, 0.0
        for name, (cnt, sec) in self.modules.items():
            if match(name):
                n += cnt
                s += sec
        return n, s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[short_op_name(k), float(v)] for k, v in ops],
            "idle_gaps": [[k, float(v)] for k, v in self.gaps[:top]],
        }


def reduce_events(events: list[Event]) -> TraceSummary:
    windows = [e for e in events if e.name == WINDOW_SPAN
               and not e.plane.startswith(DEVICE_PREFIX)]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    w0, w1 = windows[0].start_ns, windows[0].end_ns

    def clipped(evs):
        iv = np.asarray([(max(e.start_ns, w0), min(e.end_ns, w1)) for e in evs],
                        dtype=np.float64).reshape(-1, 2)
        return iv[iv[:, 1] > iv[:, 0]]

    by_plane = defaultdict(lambda: defaultdict(list))
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX):
            by_plane[e.plane][e.line].append(e)

    busy_per_dev, merged_per_dev = [], []
    modules: dict = defaultdict(lambda: [0, 0.0])
    ops: dict = defaultdict(float)
    for lines in by_plane.values():
        busy_src = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        merged = _union(clipped(busy_src))
        if merged.size:
            busy_per_dev.append(float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9)
            merged_per_dev.append(merged)
        for e in lines.get(MODULES_LINE, []):
            d = min(e.end_ns, w1) - max(e.start_ns, w0)
            if d > 0:
                modules[e.name][0] += 1
                modules[e.name][1] += d * 1e-9
        for e in lines.get(OPS_LINE, []):
            d = min(e.end_ns, w1) - max(e.start_ns, w0)
            if d > 0:
                ops[e.name] += d * 1e-9

    # Idle gaps of the first device that ran anything (one chip per cell).
    gaps, totals = [], defaultdict(float)
    if merged_per_dev:
        m = merged_per_dev[0]
        starts = np.concatenate([[w0], m[:, 1]])
        ends = np.concatenate([m[:, 0], [w1]])
        keep = ends > starts
        spans = sorted(
            (e for e in events
             if e.name.startswith(HOST_SPAN_PREFIX) and e.name != WINDOW_SPAN
             and not e.plane.startswith(DEVICE_PREFIX)),
            key=lambda e: e.start_ns)
        s_start = np.asarray([e.start_ns for e in spans])
        # Every span before ``lo`` has ended by the gap's start.
        s_end_max = np.maximum.accumulate([e.end_ns for e in spans] or [0.0])
        for g0, g1 in zip(starts[keep], ends[keep]):
            lo = int(np.searchsorted(s_end_max, g0, side="right"))
            hi = int(np.searchsorted(s_start, g1))
            overlap = defaultdict(float)
            for e in spans[lo:hi]:
                overlap[e.name] += max(0.0, min(e.end_ns, g1) - max(e.start_ns, g0))
            best = max(overlap, key=overlap.get) if overlap else UNATTRIBUTED
            if best != UNATTRIBUTED and overlap[best] <= 0.0:
                best = UNATTRIBUTED
            sec = (g1 - g0) * 1e-9
            gaps.append((best, float(sec)))
            totals[best] += float(sec)
        gaps.sort(key=lambda g: -g[1])

    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=float(np.mean(busy_per_dev)) if busy_per_dev else 0.0,
        n_devices=len(busy_per_dev),
        modules={k: list(v) for k, v in modules.items()},
        ops=dict(ops),
        gaps=gaps,
        gap_totals=dict(totals),
    )
