"""Back-to-back CG solves on the configuration's ``spd_shift``, by the
program's fused solver (``SparseSolver.cg``), one caller.

Right-hand sides come from a pool of ``mix["b_pool"]`` vectors (stream 2
of the seed), each solved to ``config["cg"]["tol"]`` within ``maxiter``.
End to end: ``cg_solve_s``; counters: ``cg`` (iterations per solve).
Checked: the float64 true residual of each of the first ``b_pool``
solves, and no solve unconverged.
"""
import time

import numpy as np

from bench.lib import reference, traffic
from bench.lib.harness import Reduced, compared, device_vectors, host_stack, program_csr
from bench.lib.suite import spd_shift
from repro.runtime.solver import SparseSolver


def _stop(cfg):
    return float(cfg["cg"]["tol"]), int(cfg["cg"]["maxiter"])


def build(setup):
    cfg = setup.config
    t = time.perf_counter()
    store, a = setup.matrix()
    a = spd_shift(a, store.spd_pattern(), margin=cfg["cg"]["spd_margin"])
    setup.log_matrix(store, a, t, " (spd_shift)")
    t = time.perf_counter()
    system = SparseSolver(program_csr(a), cache=setup.plan_cache())
    ops = {1: system.op(1)}
    pool = device_vectors(setup.seed, 2, int(setup.mix["b_pool"]), a.shape[0])
    setup.log_plans(ops, t)
    t = time.perf_counter()
    tol, maxiter = _stop(cfg)
    system.cg(pool[0], tol=tol, maxiter=maxiter)
    setup.log(f"warm-up {time.perf_counter() - t:.2f} s")
    return system, ops, pool, a


def drive(cell, seconds, seed):
    return traffic.cg_closed(cell.system, cell.pool, cell.mix, seconds, seed,
                             *_stop(cell.config))


def reduce(cell, out, log):
    its = out.iterations
    log(f"solves: {out.solves} in {out.window_s:.4f} s, failed {out.failed}, "
        f"iterations {sorted(set(its))}, all converged {all(out.converged)}")
    e2e = {"cg_solve_s": out.window_s / out.solves} if out.solves else {}
    pool, n = cell.pool, cell.a.shape[0]
    idx = sorted(out.xs)
    xs = host_stack(out.xs[i] for i in idx) if idx else np.zeros((0, n), np.float32)
    bs = host_stack(pool)[[i % len(pool) for i in idx]] if idx else xs
    if faults := cell.system.supervisor.faults():
        log(f"supervisor faults: {faults}")
    return Reduced(e2e, {"cg": {"iterations": list(its)}}, out.solves + out.failed,
                   out.failed + sum(not c for c in out.converged), (bs, xs))


def close(cell):
    """The solver starts no thread and holds no queue."""


def check(cell, a64, red, log):
    bs, xs = red.sample
    res = reference.cg_residuals(a64, bs, xs)
    return {"cg_residual": compared(res.max() if res.size else None,
                                    reference.CG_RESIDUAL_LIMIT),
            "unconverged": compared(red.failed, 0)}


def control(cell, a64, red):
    bs = red.sample[0][:3]
    ctrl = reference.control_cg(cell.a, bs, *_stop(cell.config))
    return {"control_cg_residual": reference.cg_residuals(a64, bs, ctrl).tolist()}
