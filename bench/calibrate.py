#!/usr/bin/env python3
"""Readings that set the benchmark's rates and limits, taken on the chip.

    python bench/calibrate.py sweep --workload ldoor.serve_open \
        --rates 10,15,20,25,30 --seconds 20 --seed 5
    python bench/calibrate.py readings --workload ldoor.cg \
        --seeds 11,12,13 --seconds 10 [--control]

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
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from repro.core.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    return {"sweep": sweep, "readings": readings}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
