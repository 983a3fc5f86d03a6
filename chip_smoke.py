#!/usr/bin/env python3
"""Smoke run of the system's main paths on the accelerator.

    python chip_smoke.py [--matrix ldoor --scale 1.0 --seed 0]   # one chip
    python chip_smoke.py --mesh 4                                # four chips

Phases, all in this one process (it starts no children):

1. Device: print the platform, device kind and count.  Without a TPU the
   script exits non-zero before touching the matrix (``--rehearse`` runs
   the phases anyway, at a small ``--scale``, and still exits non-zero).
2. Tuned serving: ``SparseEngine(a, ks=(1, 16))`` over a Table-1 suite
   matrix made from ``--seed`` by ``repro.data.suite.generate``, with a
   fresh in-memory plan cache and ``force_search=True`` so every surviving
   candidate is compiled and measured.  ``--requests`` SpMV requests go
   through ``submit``/``result``, a quarter one at a time and the rest in
   one burst, so every k-bucket serves; each result, and each bucket's
   warm-up output, is checked against a float64 SciPy ``A @ x``.
3. Fused solve: ``SparseSolver(spd_shift(a)).cg(b, tol=1e-5)``; the
   returned x is checked by its float64 true residual.

``--mesh P`` runs only the mesh path instead: a 1-D P-chip mesh, the tuned
allgather/ring schedule per bucket serving the same requests, and a mesh
CG, both against the same float64 oracle.

The run fails on any candidate lost to an exception, any supervisor fault
(retry, demotion, failed batch or solver attempt), a k-bucket that served
no request, and any result outside tolerance.  The last line of standard
output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from pathlib import Path

# Elementwise SpMV tolerance against the float64 oracle, relative to
# (|A| @ |x|)_i.  A float32 sum of a row's n products is off by about
# sqrt(n) * 2**-24 of that, n * 2**-24 at worst (n = 49 on ldoor: < 3e-6;
# every tier, the merge tier included, sums a row's own products only).
# A wrong column, a dropped row, a sum polluted by other rows' rounding or
# a bfloat16 MXU pass (2**-8) lands far above it.
SPMV_RTOL = 1e-4
# CG stops on its recursive float32 residual at tol * ||b||; the true
# residual, recomputed in float64, may drift above that by rounding in the
# recurrence.  A tenfold margin covers the drift on the well-conditioned
# spd_shift systems; a solve that did not converge misses it by far.
CG_TOL = 1e-5
CG_TRUE_RESIDUAL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def scipy_csr(a):
    import numpy as np
    import scipy.sparse as sp

    return sp.csr_matrix(
        (a.data.astype(np.float64), a.indices, a.indptr), shape=a.shape
    )


def check_spmv(a64, absa64, xs, ys) -> float:
    """Worst ``|y - A x| / (|A| |x|)`` over the requests; raises past SPMV_RTOL."""
    import numpy as np

    worst = 0.0
    for i, (x, y) in enumerate(zip(xs, ys)):
        x64 = np.asarray(x, np.float64)
        ref = a64 @ x64
        scale = absa64 @ np.abs(x64)
        err = np.abs(np.asarray(y, np.float64) - ref)
        ratio = float(np.max(err / np.maximum(scale, np.finfo(np.float64).tiny)))
        worst = max(worst, ratio)
        if ratio > SPMV_RTOL:
            raise AssertionError(
                f"request {i}: error {ratio:.3e} x (|A||x|) exceeds {SPMV_RTOL:g}"
            )
    return worst


def report_plans(ops) -> int:
    """Print each bucket's plan and every candidate's outcome; returns how
    many Pallas candidates were compiled and measured."""
    import math

    n_pallas = 0
    for k, op in sorted(ops.items()):
        p = op.plan
        log(
            f"plan k={k}: {p.candidate.key()} measured {p.measured_s * 1e3:.4f} ms "
            f"({p.n_candidates} enumerated, {p.n_measured} measured, "
            f"{p.n_raced} raced, {len(p.failed)} failed)"
        )
        for key, t in sorted(op.measurements.items(), key=lambda kv: kv[1]):
            if key in p.failed:
                log(f"  candidate {key}: FAILED {p.failed[key]}")
                continue
            what = "raced out" if math.isinf(t) else f"{t * 1e3:.4f} ms"
            log(f"  candidate {key}: {what}")
            n_pallas += "/pallas" in key
    return n_pallas


def serve_phase(a, a64, absa64, *, ks, n_req, seed, mesh=None):
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime.engine import SparseEngine
    from repro.tune import PlanCache

    t0 = time.perf_counter()
    eng = SparseEngine(
        a, ks=ks, cache=PlanCache(), force_search=True, mesh=mesh,
    )
    log(f"engine built (measured search, all buckets) in "
        f"{time.perf_counter() - t0:.2f} s")
    n_pallas = report_plans(eng.ops)
    log(f"pallas candidates compiled and measured: {n_pallas}")

    rng = np.random.default_rng(seed + 1)
    xs = [rng.standard_normal(a.shape[1]).astype(np.float32)
          for _ in range(n_req)]
    dev_xs = [jnp.asarray(x) for x in xs]
    for k in ks:  # first dispatch of each bucket compiles its executable
        for rep in ("first dispatch (compile + run)", "second dispatch"):
            t0 = time.perf_counter()
            ys = eng.run(dev_xs[:k])
            log(f"bucket {k} {rep}: {time.perf_counter() - t0:.3f} s")
        worst = check_spmv(a64, absa64, xs[:k], ys)
        log(f"bucket {k} warm-up vs float64 oracle: worst error "
            f"{worst:.3e} x (|A||x|)")
    eng.stats = type(eng.stats)()

    # A quarter of the requests one at a time (each alone in the queue, so
    # the narrowest bucket serves it), the rest as one burst (the widest):
    # every bucket's plan serves checked requests.
    n_single = n_req // 4
    t0 = time.perf_counter()
    ys = [eng.submit(x).result() for x in dev_xs[:n_single]]
    reqs = [eng.submit(x) for x in dev_xs[n_single:]]
    ys += [r.result() for r in reqs]
    dt = time.perf_counter() - t0
    log(f"served {len(ys)} requests ({n_single} one at a time, "
        f"{len(reqs)} in a burst) in {dt:.4f} s (host clock, compile "
        f"excluded)")
    log(f"engine stats: {json.dumps(eng.stats.summary())}")
    worst = check_spmv(a64, absa64, xs, ys)
    log(f"spmv vs float64 oracle: worst error {worst:.3e} x (|A||x|) "
        f"<= {SPMV_RTOL:g}")
    s = eng.stats.summary()
    bad = {key: s[key] for key in ("failed_batches", "failed_requests",
                                   "retries", "demotions") if s[key]}
    unserved = [k for k in ks if not s["by_bucket"].get(k)]
    if unserved:
        bad["buckets_not_served"] = unserved
    failed = {k: op.plan.failed for k, op in eng.ops.items() if op.plan.failed}
    eng.close()
    return eng.supervisor.faults(), bad, failed


def cg_phase(spd, spd64, *, seed, mesh=None):
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime.solver import SparseSolver
    from repro.tune import PlanCache

    rng = np.random.default_rng(seed + 2)
    b = rng.standard_normal(spd.shape[0]).astype(np.float32)
    t0 = time.perf_counter()
    solver = SparseSolver(spd, cache=PlanCache(), force_search=True, mesh=mesh)
    op = solver.op(1)
    log(f"solver plan searched in {time.perf_counter() - t0:.2f} s")
    report_plans({1: op})
    for rep in ("first solve (compile + run)", "second solve"):
        t0 = time.perf_counter()
        res = solver.cg(jnp.asarray(b), tol=CG_TOL)
        log(f"cg {rep}: {time.perf_counter() - t0:.3f} s")
    x64 = np.asarray(res.x, np.float64)
    b64 = b.astype(np.float64)
    true_rel = float(np.linalg.norm(b64 - spd64 @ x64) / np.linalg.norm(b64))
    log(f"cg: plan {res.plan}, {res.iterations} iterations, converged="
        f"{res.converged}, recursive residual {res.residual:.4e}, "
        f"float64 true relative residual {true_rel:.4e}")
    if not res.converged:
        raise AssertionError("cg did not converge")
    if not true_rel <= CG_TRUE_RESIDUAL:
        raise AssertionError(
            f"cg true residual {true_rel:.3e} exceeds {CG_TRUE_RESIDUAL:g}"
        )
    return solver.supervisor.faults(), op.plan.failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--matrix", default="ldoor", help="Table-1 suite matrix")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="row-count scale of the suite matrix (1.0 = Table 1)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--mesh", type=int, default=0, metavar="P",
                    help="run only the mesh path on a 1-D mesh of P chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases without a TPU (never ends in ok)")
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.core.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {json.dumps(device)}")
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 1

    from repro.core.spmv import spd_shift
    from repro.data.suite import generate

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_spmm_mesh

        mesh = make_spmm_mesh(args.mesh)
        device["count"] = args.mesh
        log(f"mesh: {args.mesh} devices on axis {mesh.axis_names[0]!r}")

    t0 = time.perf_counter()
    a = generate(args.matrix, scale=args.scale, seed=args.seed)
    spd = spd_shift(a)
    a64, spd64 = scipy_csr(a), scipy_csr(spd)
    absa64 = abs(a64)
    log(f"matrix {args.matrix}@{args.scale:g} seed {args.seed}: "
        f"{a.shape[0]}x{a.shape[1]}, nnz={a.nnz}; spd_shift nnz={spd.nnz} "
        f"(generated in {time.perf_counter() - t0:.1f} s)")

    problems = []
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always", RuntimeWarning)
        faults, bad, failed = serve_phase(
            a, a64, absa64, ks=(1, 16), n_req=args.requests, seed=args.seed,
            mesh=mesh,
        )
        cg_faults, cg_failed = cg_phase(spd, spd64, seed=args.seed, mesh=mesh)
    if faults or cg_faults:
        problems.append(f"supervisor faults: engine {faults}, cg {cg_faults}")
    if bad:
        problems.append(f"engine stats: {bad}")
    if failed or cg_failed:
        problems.append(f"candidates lost to exceptions: {failed} {cg_failed}")
    tuner = [str(w.message) for w in caught if "tuner:" in str(w.message)]
    if tuner:
        problems.append(f"tuner warnings: {tuner}")
    for p in problems:
        log(f"FAIL: {p}")
    if problems:
        return 1
    if not on_tpu:
        log("rehearsal passed; not a TPU run, so no result")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
