#!/usr/bin/env python3
"""Readings that set the benchmark's rates and limits, taken on the chip.

    python bench/calibrate.py sweep --workload ldoor.serve_open \
        --rates 10,15,20,25,30 --seconds 20 --seed 5
    python bench/calibrate.py readings --workload ldoor.cg \
        --seeds 11,12,13 --seconds 10 [--control]
    python bench/calibrate.py stalls --workload ldoor.serve_open \
        --windows 6 --seconds 40 --seed 5 --over 0.6

``sweep`` sets a serving cell up once and offers its open loop at each
rate in turn, printing offered and served requests, how long the queue
took to drain after the last arrival, the latency quantiles and the mix of
buckets: the knee is the highest rate at which served keeps up with
offered and the drain stays flat.

``readings`` runs the cell once per seed, in this one process, with the
window given, and prints each compared number; ``--control`` also prints
the bfloat16 control's reading on the same inputs (``bench.lib.reference``),
and ``--dump-trace DIR`` traces the window and keeps its events.
The benchmark's own runs (``bench/run.py``) never run the control.

``stalls`` sets a serving cell up once and runs its open loop for
``--windows`` windows, timing every ``submit``, ``step``, launch and
retirement of the engine.  A watchdog thread wakes every millisecond and
records how late it woke, the process's CPU time and the main thread's
innermost frames.  Every call longer than ``--over`` seconds is printed
with what the watchdog saw meanwhile: a watchdog that woke on time while
the main thread used no CPU means the main thread waited with the
interpreter free (on the device or the runtime); one that woke late by as
much means the whole process, or the interpreter lock, was held.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(msg, flush=True)


def sweep(args) -> int:
    import numpy as np

    from bench.lib import traffic
    from bench.lib.harness import build_cell

    cell = build_cell(args.workload, args.seed, log=log)
    for rate in (float(r) for r in args.rates.split(",")):
        before = dict(cell.system.stats.dispatched)
        pad0 = cell.system.stats.padded_cols
        mix = {**cell.mix, "rate_per_s": rate}
        out = traffic.serve_open(cell.system, cell.pool, mix, args.seconds, args.seed)
        lat = np.sort(out.latencies_s)
        by = {k: v - before.get(k, 0) for k, v in cell.system.stats.dispatched.items()}
        log("sweep " + json.dumps({
            "rate_per_s": rate, "offered": out.offered, "served": out.served,
            "window_s": out.window_s,
            "drain_s": out.window_s - traffic.arrival_times(rate, args.seconds, args.seed)[-1],
            "served_per_s": out.served / out.window_s,
            "p50_ms": float(np.median(lat)) * 1e3 if lat.size else None,
            "p95_ms": float(lat[int(np.ceil(0.95 * lat.size)) - 1]) * 1e3 if lat.size else None,
            "max_pending": out.max_pending, "by_bucket": by,
            "padded_cols": cell.system.stats.padded_cols - pad0,
            "late_p95_ms": float(np.percentile(out.lateness_s, 95)) * 1e3,
        }))
    return 0


def readings(args) -> int:
    from bench.lib.harness import run_cell

    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        dump = None
        if args.dump_trace:
            dump = Path(args.dump_trace) / f"{args.workload}.{seed}.events.json.gz"
            dump.parent.mkdir(parents=True, exist_ok=True)
        result, extra = run_cell(args.workload, seed, args.seconds, dump is not None,
                                 log=log, control=args.control, dump_trace=dump)
        log("reading " + json.dumps({
            "workload": args.workload, "seed": seed, "correct": result["correct"],
            "checks": result["checks"], "metrics": result["metrics"],
            **{k: v for k, v in extra.items() if k.startswith("control")},
            "seconds": time.perf_counter() - t}))
    return 0


TICK_S = 0.001


def stalls(args) -> int:
    import threading
    from collections import Counter

    import numpy as np

    from bench.lib import traffic
    from bench.lib.harness import build_cell

    cell = build_cell(args.workload, args.seed, log=log)
    eng = cell.system
    main_id = threading.get_ident()
    main_cpu = time.pthread_getcpuclockid(main_id)
    calls: list = []  # (name, start, end, main thread's CPU seconds)

    def timed(name, fn):
        def wrapper(*a, **kw):
            t0, c0 = time.perf_counter(), time.clock_gettime(main_cpu)
            try:
                return fn(*a, **kw)
            finally:
                calls.append((name, t0, time.perf_counter(),
                              time.clock_gettime(main_cpu) - c0))
        return wrapper

    for name in ("submit", "step", "_launch", "_retire_one"):
        setattr(eng, name, timed(name, getattr(eng, name)))
    mix = {**cell.mix, "rate_per_s": args.rate} if args.rate else cell.mix
    ticks: list = []  # (time, woke late by, process CPU, main's frames)

    def watch(stop):
        while not stop.is_set():
            t = time.perf_counter()
            time.sleep(TICK_S)
            now = time.perf_counter()
            frame, where = sys._current_frames().get(main_id), []
            while frame is not None and len(where) < 3:
                where.append(f"{frame.f_code.co_name}:{frame.f_lineno}")
                frame = frame.f_back
            ticks.append((now, now - t - TICK_S, time.process_time(), "<".join(where)))

    for w in range(args.windows):
        calls.clear()
        ticks.clear()
        stop = threading.Event()
        th = threading.Thread(target=watch, args=(stop,), daemon=True)
        th.start()
        out = traffic.serve_open(eng, cell.pool, mix, args.seconds, args.seed + w)
        stop.set()
        th.join()
        lat = np.sort(out.latencies_s)
        late = np.array([tk[1] for tk in ticks])
        log("window " + json.dumps({
            "window": w, "served": out.served, "offered": out.offered,
            "p95_ms": float(lat[int(np.ceil(0.95 * lat.size)) - 1]) * 1e3,
            "generator_late_max_ms": float(out.lateness_s.max()) * 1e3,
            "watchdog_late_ms": {"p50": float(np.median(late)) * 1e3,
                                 "p99.9": float(np.quantile(late, 0.999)) * 1e3,
                                 "max": float(late.max()) * 1e3},
            "calls_ms": {n: [float(np.median(d)) * 1e3, float(d.max()) * 1e3]
                         for n in ("submit", "step", "_launch", "_retire_one")
                         if (d := np.array([c[2] - c[1] for c in calls if c[0] == n])).size},
        }))
        for name, t0, t1, cpu in calls:
            if t1 - t0 <= args.over or name == "step":
                continue
            inside = [tk for tk in ticks if t0 <= tk[0] <= t1 + TICK_S]
            log("stall " + json.dumps({
                "window": w, "call": name, "at_s": t0 - calls[0][1],
                "seconds": t1 - t0, "main_cpu_s": cpu,
                "process_cpu_s": inside[-1][2] - inside[0][2] if len(inside) > 1 else None,
                "watchdog_ticks": len(inside),
                "watchdog_late_max_s": max((tk[1] for tk in inside), default=None),
                "main_frames": Counter(tk[3] for tk in inside).most_common(3),
            }))
        long_steps = [c for c in calls if c[0] == "step" and c[2] - c[1] > args.over]
        log(f"window {w}: {len(long_steps)} step() calls over {args.over} s")
    eng.close()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--rates", required=True)
    s.add_argument("--seconds", type=float, default=20.0)
    s.add_argument("--seed", type=int, default=0)
    r = sub.add_parser("readings")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", required=True)
    r.add_argument("--seconds", type=float, default=10.0)
    r.add_argument("--control", action="store_true")
    r.add_argument("--dump-trace", metavar="DIR",
                   help="trace the window and save its events under DIR")
    t = sub.add_parser("stalls")
    t.add_argument("--workload", required=True)
    t.add_argument("--windows", type=int, default=6)
    t.add_argument("--seconds", type=float, default=40.0)
    t.add_argument("--seed", type=int, default=0)
    t.add_argument("--rate", type=float, help="offer this rate instead of the mix's")
    t.add_argument("--over", type=float, default=0.6,
                   help="print every call longer than this many seconds")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from repro.core.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    return {"sweep": sweep, "readings": readings, "stalls": stalls}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
