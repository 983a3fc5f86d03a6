"""The one traffic generator: every mix file is parameters for it.

A mix (``bench/traffic/<mix>.json``) names its loop and its request:

* ``{"loop": "open", "request": "spmv", "rate_per_s": R, "x_pool": P,
  "checked": C}``: independent callers.  Requests are due on a Poisson-like
  schedule at ``R`` per second for the whole window, whatever the engine
  does; each is one dense x from a pool of ``P`` distinct device-resident
  vectors.  Latency runs from when a request was *due* to when its result
  was ready, so a stall pays for the requests it delays.
* ``{"loop": "closed", "request": "spmv", "x_pool": P, "check_every": C}``:
  one caller that submits its next request after the previous
  ``result()``; every C-th answer is checked.  Latency runs from ``submit``.
* ``{"loop": "closed", "request": "cg", "b_pool": P}``: back-to-back solves,
  each with the next right-hand side of the pool.

Steadiness: an open loop's request count is ``round(R * seconds)`` and its
gaps are the same set of exponential quantiles for every seed, shuffled
by the seed, so seeds change the order of the work and never its amount.

The engine has no thread of its own, so the open loop drives it: each
turn submits every arrival that is due, calls ``step()`` (which dispatches
pending requests and retires finished batches) and stamps the requests
that came back.  Its spans (``bench.submit``, ``bench.step``,
``bench.wait``, ``bench.solve``, ``bench.result``) label the device's idle
gaps in a trace.
"""
from __future__ import annotations

import dataclasses
import time
from collections import Counter, deque

import numpy as np
from jax.profiler import TraceAnnotation

from .suite import rng_for

# Idle turns of the open loop sleep at most this long, so a finished batch
# is stamped within about half a millisecond of being ready.
POLL_S = 0.0005
# Every bucket that serves gets at least this many of its first requests
# checked, on top of the seeded sample.
FIRST_PER_BUCKET = 4
# A request not back this long after the last one was due never came.
GIVE_UP_S = 60.0


def arrival_times(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of an open loop's requests."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng_for(seed, 3).shuffle(gaps)
    return np.cumsum(gaps)


def checked_sample(n: int, count: int, seed: int) -> set:
    k = min(n, int(count))
    return set(rng_for(seed, 4).choice(n, size=k, replace=False).tolist())


@dataclasses.dataclass
class ServeOutcome:
    offered: int
    served: int
    failed: int
    latencies_s: np.ndarray  # per served request
    lateness_s: np.ndarray  # submit time minus due time, per submitted request
    window_s: float  # window start to the last response
    kept: dict  # request index -> y (device array), the checked requests
    kept_bucket: dict  # request index -> the bucket that served it
    max_pending: int


def serve_open(eng, pool: list, mix: dict, seconds: float, seed: int) -> ServeOutcome:
    due = arrival_times(float(mix["rate_per_s"]), seconds, seed)
    n, p = len(due), len(pool)
    check = checked_sample(n, mix.get("checked", 64), seed)
    lat = np.full(n, np.nan)
    late = np.full(n, np.nan)
    kept, kept_bucket, per_bucket = {}, {}, Counter()
    outstanding: deque = deque()
    i = max_pending = 0
    t_last = None
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        due_abs = t0 + due
        give_up = due_abs[-1] + GIVE_UP_S
        while i < n or outstanding:
            now = time.perf_counter()
            if i < n and due_abs[i] <= now:
                with TraceAnnotation("bench.submit"):
                    while i < n and due_abs[i] <= now:
                        outstanding.append((i, eng.submit(pool[i % p])))
                        late[i] = time.perf_counter() - due_abs[i]
                        i += 1
            max_pending = max(max_pending, eng.pending)
            if eng.pending or eng.in_flight:
                with TraceAnnotation("bench.step"):
                    eng.step()
            now = time.perf_counter()
            came_back = False
            while outstanding and outstanding[0][1].done:
                j, req = outstanding.popleft()
                came_back = True
                if req.failed:
                    continue
                lat[j] = now - due_abs[j]
                t_last = now
                if j in check or per_bucket[req.bucket] < FIRST_PER_BUCKET:
                    per_bucket[req.bucket] += 1
                    kept[j] = req.result()
                    kept_bucket[j] = req.bucket
            if now > give_up:
                break
            if not came_back and not (i < n and due_abs[i] <= now):
                wait = POLL_S if i >= n else min(POLL_S, due_abs[i] - now)
                if wait > 0:
                    with TraceAnnotation("bench.wait"):
                        time.sleep(wait)
    served = int(np.isfinite(lat).sum())
    return ServeOutcome(
        offered=n, served=served, failed=n - served,
        latencies_s=lat[np.isfinite(lat)], lateness_s=late[np.isfinite(late)],
        window_s=(t_last if t_last is not None else time.perf_counter()) - t0,
        kept=kept, kept_bucket=kept_bucket, max_pending=max_pending,
    )


def serve_closed(eng, pool: list, mix: dict, seconds: float, seed: int) -> ServeOutcome:
    check_every = max(1, int(mix.get("check_every", 8)))
    lats, kept, kept_bucket = [], {}, {}
    failed = i = 0
    p = len(pool)
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            t = time.perf_counter()
            with TraceAnnotation("bench.result"):
                req = eng.submit(pool[i % p])
                try:
                    y = req.result()
                except Exception:  # the engine failed this request's future
                    failed += 1
                    i += 1
                    continue
            lats.append(time.perf_counter() - t)
            if i % check_every == 0:
                kept[i] = y
                kept_bucket[i] = req.bucket
            i += 1
        t_end = time.perf_counter()
    return ServeOutcome(
        offered=i, served=len(lats), failed=failed,
        latencies_s=np.asarray(lats), lateness_s=np.zeros(0),
        window_s=t_end - t0, kept=kept, kept_bucket=kept_bucket, max_pending=1,
    )


@dataclasses.dataclass
class SolveOutcome:
    solves: int
    failed: int
    window_s: float
    iterations: list
    converged: list
    xs: dict  # solve index -> x (device array)


def cg_closed(solver, pool: list, mix: dict, seconds: float, seed: int,
              tol: float, maxiter: int) -> SolveOutcome:
    its, conv, xs = [], [], {}
    failed = 0
    p = len(pool)
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            i = len(its) + failed
            with TraceAnnotation("bench.solve"):
                try:
                    res = solver.cg(pool[i % p], tol=tol, maxiter=maxiter)
                except Exception:  # the solver's supervisor gave up
                    failed += 1
                    res = None
            if res is not None:
                its.append(res.iterations)
                conv.append(res.converged)
                if i < p:  # each right-hand side is checked once
                    xs[i] = res.x
            if time.perf_counter() - t0 >= seconds:
                break
        t_end = time.perf_counter()
    return SolveOutcome(solves=len(its), failed=failed, window_s=t_end - t0,
                        iterations=its, converged=conv, xs=xs)
