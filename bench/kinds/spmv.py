"""Dense SpMV requests to a ``SparseEngine``, from open-loop or
closed-loop callers (``mix["loop"]``).

The engine holds the configuration's matrix with the buckets
``config["engine"]["ks"]``; each request is one x from a pool of
``max(mix["x_pool"], max(ks))`` vectors (stream 1 of the seed).  End to
end: ``spmv_p95_ms``, ``spmv_rps``; counters: ``engine`` (padded and
occupied columns, dispatches by bucket).  Checked: ``spmv_err`` of every
kept answer, and no request unanswered.
"""
import json
import time

import jax
import numpy as np

from bench.lib import reference, traffic
from bench.lib.harness import (Reduced, compared, device_vectors, host_stack,
                               p95, program_csr)
from repro.runtime.engine import SparseEngine


def build(setup):
    t = time.perf_counter()
    store, a = setup.matrix()
    setup.log_matrix(store, a, t)
    ks = tuple(int(k) for k in setup.config["engine"]["ks"])
    t = time.perf_counter()
    system = SparseEngine(program_csr(a), ks=ks, cache=setup.plan_cache())
    pool = device_vectors(setup.seed, 1, max(int(setup.mix["x_pool"]), max(ks)),
                          a.shape[0])
    setup.log_plans(system.ops, t)
    t = time.perf_counter()
    for k in ks:  # every bucket's program, and its result slices
        jax.block_until_ready(system.run(pool[:k]))
    setup.log(f"warm-up {time.perf_counter() - t:.2f} s")
    return system, system.ops, pool, a


def drive(cell, seconds, seed):
    eng = cell.system
    eng.stats = type(eng.stats)()  # count this window's dispatches only
    run = traffic.serve_open if cell.mix["loop"] == "open" else traffic.serve_closed
    return run(eng, cell.pool, cell.mix, seconds, seed)


def reduce(cell, out, log):
    st = cell.system.stats
    counters = {"engine": {"padded_cols": st.padded_cols,
                           "occupied_cols": st.occupied_cols,
                           "dispatched": dict(st.dispatched)}}
    summary = st.summary()
    log(f"engine stats: {json.dumps({k: summary[k] for k in ('dispatches', 'by_bucket', 'served_cols', 'padded_cols', 'retries', 'demotions', 'failed_batches')})}")
    late = out.lateness_s
    log(f"requests: offered {out.offered}, served {out.served}, failed or "
        f"never came {out.failed}; window {out.window_s:.4f} s; most pending "
        f"{out.max_pending}; generator late by mean "
        f"{(late.mean() if late.size else 0) * 1e3:.4f} ms, p95 "
        f"{(np.percentile(late, 95) if late.size else 0) * 1e3:.4f} ms, max "
        f"{(late.max() if late.size else 0) * 1e3:.4f} ms")
    e2e = {}
    if out.served:
        e2e["spmv_p95_ms"] = p95(out.latencies_s) * 1e3
        e2e["spmv_rps"] = out.served / out.window_s
        log(f"latency ms: p50 {np.median(out.latencies_s) * 1e3:.4f}, p95 "
            f"{e2e['spmv_p95_ms']:.4f}, max {out.latencies_s.max() * 1e3:.4f}")
    pool, n = cell.pool, cell.a.shape[0]
    idx = sorted(out.kept)
    ys = host_stack(out.kept[j] for j in idx) if idx else np.zeros((0, n), np.float32)
    xs = host_stack(pool)[[j % len(pool) for j in idx]] if idx else ys
    buckets = [out.kept_bucket[j] for j in idx]
    if faults := cell.system.supervisor.faults():
        log(f"supervisor faults: {faults}")
    return Reduced(e2e, counters, out.offered, out.failed, (xs, ys, buckets))


def close(cell):
    cell.system.close()


def check(cell, a64, red, log):
    xs, ys, buckets = red.sample
    errs = reference.spmv_errors(a64, xs, ys)
    for b in sorted(set(buckets), key=str):
        sel = [i for i, bb in enumerate(buckets) if bb == b]
        log(f"bucket {b}: {len(sel)} checked, worst spmv_err "
            f"{errs[sel].max():.4e}")
    return {"spmv_err": compared(errs.max() if errs.size else None,
                                 reference.SPMV_ERR_LIMIT),
            "unanswered": compared(red.failed, 0)}


def control(cell, a64, red):
    xs = red.sample[0]
    return {"control_spmv_err": float(reference.spmv_errors(
        a64, xs, reference.control_spmv(cell.a, xs)).max())}
