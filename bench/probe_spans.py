#!/usr/bin/env python3
"""Read the engine's and the solver's own spans on the chip, window by window.

    python bench/probe_spans.py --workload ldoor.serve_open --seed 7 \
        --seconds 40 --windows 0,1,1,1 [--until-long-step]

Sets a cell up once (as ``bench/run.py`` does) and drives its traffic for
one window per entry of ``--windows``: ``0`` untraced, ``1`` under the
profiler, every window with the same seed.  Each window prints one line,
``window {...}``, with the end-to-end numbers and the attempted and
failed counts its kind's ``reduce`` gives (``bench/kinds``), and what the
program's compile counter (``repro.runtime.executable.compile_counts``)
counted in it (``runtime.compiles``: backend compiles).  A traced window
adds the device's idle share, the programs of the "XLA Modules" line, the
cell's accepted per-layer metrics as ``bench/run.py --trace 1`` reads them, the
spans of ``bench.lib.spans`` reduced (count, total, self time and longest
per name; idle time by innermost span) and the per-layer readings they
give: ``engine.host_ms``, ``engine.step_max_ms``,
``engine.step_host_max_ms``, ``solver.host_ms``.  Every ``engine.step``
whose host time (less its device waits) exceeds ``--long`` seconds is
printed with its child spans, the compiles inside it, and the host events
that took most of it; so is the device's longest idle gap.  ``--until-long-step`` ends the run after the first
traced window that had one.

So an untraced and a traced window on one set-up give what tracing costs
end to end.  A program without the spans or the counter (an older
checkout under these benchmark files) reads None for them.  Nothing is
checked against the reference here: ``bench/run.py`` does that.
"""
import argparse
import json
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(msg, flush=True)


def _host_events_in(events, t0: float, t1: float, top: int = 8) -> dict:
    """Seconds of each host event name (spans included) inside [t0, t1],
    largest first."""
    from bench.lib.trace import DEVICE_PREFIX

    total = defaultdict(float)
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX):
            continue
        d = min(e.end_ns, t1) - max(e.start_ns, t0)
        if d > 0:
            total[e.name] += d * 1e-9
    return dict(sorted(total.items(), key=lambda kv: -kv[1])[:top])


def _traced_readings(cell, counters: dict, events, long_s: float) -> dict:
    from bench.lib import spans
    from bench.lib.harness import read_layers
    from bench.lib.trace import WINDOW_SPAN

    record, accepted = read_layers(cell, counters, events, long_s)
    summary, sp = record.trace, record.spans
    window = next(e for e in events if e.name == WINDOW_SPAN)
    w0 = window.start_ns
    for step in sp.long_steps:
        t0 = w0 + step["at_s"] * 1e9
        step["host_events"] = _host_events_in(events, t0, t0 + step["seconds"] * 1e9)
    gaps = spans._idle_gaps(events, w0, window.end_ns)
    longest_idle = None
    if gaps.size:
        g0, g1 = gaps[(gaps[:, 1] - gaps[:, 0]).argmax()]
        longest_idle = {"at_s": (g0 - w0) * 1e-9, "seconds": (g1 - g0) * 1e-9,
                        "host_events": _host_events_in(events, g0, g1)}
    return {
        "device_idle_pct": (100.0 * summary.idle_share
                            if summary.n_devices else None),
        "accepted_per_layer": accepted,
        "modules": {k: v for k, v in sorted(summary.modules.items(),
                                            key=lambda kv: -kv[1][1])[:12]},
        "per_layer": {
            "engine.host_ms": spans.engine_host_ms(sp),
            "engine.step_max_ms": spans.engine_step_max_ms(sp),
            "engine.step_host_max_ms": spans.engine_step_host_max_ms(sp),
            "solver.host_ms": spans.solver_host_ms(sp)},
        "spans": sp.spans,
        "idle_by_program_span": sp.idle_by_span,
        "compiles_in_trace": sp.compiles,
        "long_steps": sp.long_steps,
        "longest_idle": longest_idle,
    }


def probe(cell, windows: list, seconds: float, seed: int, *,
          long_s: float = 0.3, until_long_step: bool = False, log=log) -> list:
    """Drive ``cell`` for one window per entry of ``windows`` (True: under
    the profiler); returns the windows' records, each also logged."""
    from bench.lib.harness import window
    from bench.lib.spans import runtime_compiles
    from bench.lib.trace import load_events

    trace_dir = cell.cache_dir / "trace" / f"probe-{cell.name}"
    records = []
    for i, traced in enumerate(windows):
        t = time.perf_counter()
        out, compiles = window(cell, seconds, seed, trace_dir if traced else None)
        rec = {"workload": cell.name, "window": i, "traced": traced,
               "seed": seed, "seconds": time.perf_counter() - t}
        red = cell.kind.reduce(cell, out, log)
        del out
        counters = {**red.counters, "runtime": compiles}
        rec.update(red.e2e, attempted=red.attempted, failed=red.failed,
                   compiles=compiles)
        rec["runtime.compiles"] = runtime_compiles(counters)
        if traced:
            t = time.perf_counter()
            rec.update(_traced_readings(cell, counters, load_events(trace_dir),
                                        long_s))
            shutil.rmtree(trace_dir, ignore_errors=True)
            rec["reduce_s"] = time.perf_counter() - t
        log("window " + json.dumps(rec))
        records.append(rec)
        if traced and until_long_step and rec["long_steps"]:
            break
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--windows", default="0,1",
                    help="one entry per window: 0 untraced, 1 traced")
    ap.add_argument("--long", type=float, default=0.3,
                    help="print every engine.step with more host time than "
                    "this many seconds")
    ap.add_argument("--until-long-step", action="store_true")
    ap.add_argument("--cache-dir", help="plan and pattern caches (default "
                    "bench/.cache)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from repro.core.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    from bench.lib.harness import NoAccelerator, build_cell

    try:
        cell = build_cell(args.workload, args.seed, log=log, cache_dir=(
            Path(args.cache_dir) if args.cache_dir else None))
    except NoAccelerator as exc:
        print(f"probe: {exc}; nothing was run", file=sys.stderr)
        return 3
    probe(cell, [w == "1" for w in args.windows.split(",")], args.seconds,
          args.seed, long_s=args.long, until_long_step=args.until_long_step)
    cell.kind.close(cell)
    return 0


if __name__ == "__main__":
    sys.exit(main())
