"""Serving launcher: batched LM decode, or the batch-aggregating SparseEngine.

LM decode over a reduced or full config:

  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-3-4b \
      --reduced --requests 8 --slots 4

Sparse workload: serve SpMV requests over a Table-1 suite matrix through
``repro.runtime.engine.SparseEngine`` — pending requests are aggregated into
k-bucketed SpMM batches (Fig 9's amortization applied to serving), each
bucket dispatching the plan ``repro.tune`` measured for that width.  The
first launch searches every bucket; plans persist in the on-disk plan cache
(~/.cache/repro_tune, override with $REPRO_TUNE_CACHE), so a restarted
engine reloads the whole k-indexed plan table without re-searching:

  PYTHONPATH=src python -m repro.launch.serve --sparse cant --requests 64 \
      --k-buckets 1,4,16,64 [--shards 4] [--mesh-shards 4] [--max-wait-ms 5]

``--mesh-shards P`` serves over a real device mesh: A is partitioned over a
1-D mesh axis and each k-bucket's plan picks between the allgather and ring
collective schedules through the tuner (plans are cached per topology, so
restarting on the same mesh skips the search).  ``--max-wait-ms`` enables
admission control: a partial bucket dispatches once its oldest request has
waited that long instead of waiting for the bucket to fill.

Multi-tenant fleet: serve SEVERAL suite matrices at once through
``repro.runtime.fleet.SparseFleet`` — per-tenant plan tables come from the
transfer predictor (cache hit / nearest-neighbor / byte model; no measured
search before the first result) while the background retune searches and
hot-swaps off the hot path:

  PYTHONPATH=src python -m repro.launch.serve --fleet cant,scircuit \
      --requests 64 --max-wait-ms 5 [--stats-json stats.json]

``--stats-json PATH`` (both sparse modes) dumps the run's stats summary —
``EngineStats.summary()`` plus throughput, or the fleet-wide
``FleetStats.summary()`` — as JSON for dashboards and CI artifacts.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from pathlib import Path

from repro.configs import ARCH_IDS, get_config, get_reduced


def _overload_kwargs(args) -> dict:
    """Map the overload CLI flags onto SparseEngine/SparseFleet kwargs
    (both take the same names, so one helper serves both launchers)."""
    kw: dict = {}
    if args.max_queue > 0:
        kw["max_queue"] = args.max_queue
        kw["overload_policy"] = args.overload_policy
    if args.shed_after_ms > 0:
        kw["shed_after_s"] = args.shed_after_ms / 1e3
    if args.brownout:
        from repro.runtime.overload import BrownoutController

        kw["brownout"] = BrownoutController()
    return kw


def serve_sparse(args) -> None:
    import jax.numpy as jnp

    from repro.data.suite import SUITE, generate
    from repro.runtime.engine import SparseEngine

    names = [s.name for s in SUITE]
    if args.sparse not in names:
        raise SystemExit(
            f"unknown suite matrix {args.sparse!r}; choose from: {', '.join(names)}"
        )
    ks = tuple(int(k) for k in args.k_buckets.split(","))
    a = generate(args.sparse, scale=args.scale)
    max_wait_s = args.max_wait_ms / 1e3 if args.max_wait_ms else None
    overload_kw = _overload_kwargs(args)
    t0 = time.perf_counter()
    if args.mesh_shards > 1:
        if args.shards > 1:
            raise SystemExit("--shards and --mesh-shards are mutually "
                             "exclusive (single-device vmap vs device mesh)")
        from repro.launch.mesh import make_spmm_mesh
        from repro.launch.shardspecs import sparse_rhs_sharding

        mesh = make_spmm_mesh(args.mesh_shards)
        eng = SparseEngine(a, ks=ks, mesh=mesh, max_wait_s=max_wait_s,
                           async_depth=args.async_depth, **overload_kw)
    else:
        mesh = None
        eng = SparseEngine(a, ks=ks, n_shards=args.shards,
                           max_wait_s=max_wait_s,  # on-disk plan cache
                           async_depth=args.async_depth, **overload_kw)
    t_build = time.perf_counter() - t0
    rng = np.random.default_rng(0)
    xs = [
        jnp.asarray(rng.standard_normal(a.shape[1]).astype(np.float32))
        for _ in range(args.requests)
    ]
    if mesh is not None:
        # Pre-place request vectors row-sharded on the mesh so ingest is
        # paid once, outside the dispatch hot path.
        import jax

        x_sharding = sparse_rhs_sharding(mesh, eng.axis)
        if a.shape[1] % args.mesh_shards == 0:
            xs = [jax.device_put(x, x_sharding) for x in xs]
    eng.run(xs[: min(len(xs), max(ks))])  # compile outside the timed window
    eng.stats = type(eng.stats)()  # measure the steady state only
    from repro.runtime.overload import OverloadError

    t0 = time.perf_counter()
    reqs, refused = [], 0
    for x in xs:  # offered load: all pending at once
        try:
            reqs.append(eng.submit(x))
        except OverloadError:
            refused += 1  # typed refusal — an open-loop caller backs off
    if max_wait_s is None:
        eng.drain()
    else:
        # Serve through the admission gate: full buckets dispatch at once,
        # the partial tail waits out its SLO (observable as a ~max_wait_ms
        # latency floor on the last batch) instead of being force-flushed.
        while eng.pending:
            if eng.step() == 0:
                time.sleep(min(max_wait_s / 4, 1e-3))
        eng.flush()  # retire the async in-flight window
    dt = time.perf_counter() - t0
    served = [r for r in reqs if not r.failed]
    shed = len(reqs) - len(served)
    flops = 2 * a.nnz * len(served)
    s = eng.stats.summary()
    plans = {k: op.plan.candidate.key() for k, op in eng.ops.items()}
    if args.mesh_shards > 1:
        hit = "plan table from cache" if eng.from_cache else (
            f"schedules searched in {t_build:.1f}s")
        src = (f"mesh-sharded over {args.mesh_shards} devices "
               f"(collective schedules per bucket; {hit})")
    elif args.shards > 1:
        src = f"row-partitioned stacked dispatch over {args.shards} shards"
    elif eng.from_cache:
        src = "k-indexed plan table from cache"
    else:
        src = f"searched in {t_build:.1f}s"
    lat = sorted(r.latency_s for r in served) or [0.0]
    raced = sum(op.plan.n_raced for op in eng.ops.values())
    overload = (
        f" [overload: refused={refused} shed={shed}]"
        if refused or shed else ""
    )
    print(
        f"served {len(served)}/{len(xs)} spmv requests on "
        f"{args.sparse}@{args.scale:g} "
        f"({a.shape[0]}x{a.shape[1]}, nnz={a.nnz}) in {dt:.3f}s "
        f"({len(served) / dt:.1f} req/s, {flops / dt / 1e9:.2f} GF/s, "
        f"async_depth={eng.async_depth}){overload}\n"
        f"  dispatches={s['dispatches']} by_bucket={s['by_bucket']} "
        f"occupancy={s['occupancy']:.2f} "
        f"(padding {s['padded_occupancy']:.2f} — not served work) "
        f"latency mean/p50/p99 = {s['latency_mean_ms']:.2f}/"
        f"{lat[len(lat) // 2] * 1e3:.2f}/{s['latency_p99_ms']:.2f} ms\n"
        f"  plans={plans}\n"
        f"  ({src}; {raced} candidates pruned by racing)"
    )
    for k, op in sorted(eng.ops.items()):
        for cand, err in sorted(op.plan.failed.items()):
            print(f"  bucket {k}: candidate {cand} failed: {err}")
    if args.stats_json:
        _dump_stats(
            args.stats_json,
            {
                "mode": "sparse",
                "matrix": args.sparse,
                "scale": args.scale,
                "requests": len(xs),
                "served": len(served),
                "refused": refused,
                "elapsed_s": round(dt, 6),
                "req_per_s": round(len(served) / dt, 3),
                "gflops": round(flops / dt / 1e9, 4),
                "plans": plans,
                "engine": s,
            },
        )
    faults = eng.supervisor.faults()
    if faults:
        # A demoted bucket or a failed batch served (or refused) requests
        # off the tuned plans: the run is degraded, not a success.
        raise SystemExit(f"degraded run: supervisor recorded {faults}")


def _dump_stats(path: str, payload: dict) -> None:
    p = Path(path)
    if p.parent != Path("."):
        p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    print(f"  stats written to {p}")


def serve_fleet(args) -> None:
    import jax.numpy as jnp

    from repro.data.suite import SUITE, generate
    from repro.runtime.fleet import SparseFleet

    names = [s.name for s in SUITE]
    tenants = [t for t in args.fleet.split(",") if t]
    for t in tenants:
        if t not in names:
            raise SystemExit(
                f"unknown suite matrix {t!r}; choose from: {', '.join(names)}"
            )
    ks = tuple(int(k) for k in args.k_buckets.split(","))
    max_wait_s = args.max_wait_ms / 1e3 if args.max_wait_ms else None
    fleet = SparseFleet(ks=ks, max_wait_s=max_wait_s,
                        async_depth=args.async_depth,
                        **_overload_kwargs(args))
    rng = np.random.default_rng(0)
    mats = {}
    t0 = time.perf_counter()
    for t in tenants:
        mats[t] = generate(t, scale=args.scale)
        fleet.add_tenant(t, mats[t])
    t_admit = time.perf_counter() - t0
    xs = {
        t: [
            jnp.asarray(rng.standard_normal(mats[t].shape[1], ).astype(np.float32))
            for _ in range(args.requests)
        ]
        for t in tenants
    }
    from repro.runtime.overload import OverloadError

    t0 = time.perf_counter()
    reqs, refused = [], 0
    for i in range(args.requests):  # interleave tenants: shared-device load
        for t in tenants:
            try:
                reqs.append(fleet.submit(t, xs[t][i]))
            except OverloadError:
                refused += 1  # typed refusal — the caller backs off
                fleet.step()  # ...and drains a batch before the next offer
    # ``done`` (result OR exception) — a shed/deadline-failed future never
    # gets a ``_ys``, so polling that would spin forever.
    while not all(r.done for r in reqs):
        if fleet.step() == 0:
            fleet.flush()
            if max_wait_s:
                time.sleep(min(max_wait_s / 4, 1e-3))
    fleet.flush()
    dt = time.perf_counter() - t0
    fleet.wait_retunes(timeout=args.retune_wait_s)
    fleet.close()
    summary = fleet.stats().summary()
    served = sum(1 for r in reqs if not r.failed)
    total = len(reqs)
    overload = (
        f" [overload: refused={refused} shed={total - served}]"
        if refused or served < total else ""
    )
    print(
        f"fleet served {served}/{total + refused} requests over "
        f"{len(tenants)} tenants "
        f"({', '.join(tenants)}) in {dt:.3f}s "
        f"({served / dt:.1f} req/s){overload}; "
        f"admitted in {t_admit:.3f}s "
        f"(cache={summary['cache_admissions']} "
        f"predicted={summary['predicted_admissions']}; "
        f"transferred_buckets={summary['transferred_buckets']} "
        f"byte_model_buckets={summary['byte_model_buckets']})\n"
        f"  retunes done={summary['retunes_done']} "
        f"failed={summary['retunes_failed']} "
        f"swaps_applied={summary['swaps_applied']}; "
        f"resident {summary['resident_bytes']}/{summary['budget_bytes']} B, "
        f"evictions={summary['evictions']}"
    )
    if args.stats_json:
        _dump_stats(
            args.stats_json,
            {
                "mode": "fleet",
                "tenants": tenants,
                "scale": args.scale,
                "requests": total,
                "served": served,
                "refused": refused,
                "elapsed_s": round(dt, 6),
                "req_per_s": round(served / dt, 3),
                "admit_s": round(t_admit, 6),
                "fleet": summary,
            },
        )


def serve_lm(args) -> None:
    from repro.models.lm import init_model
    from repro.runtime.server import BatchedServer, Request

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    params, _ = init_model(cfg, 0)
    srv = BatchedServer(cfg, params, batch_slots=args.slots, max_seq=args.max_seq)
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32),
                max_new=args.max_new)
        for i in range(args.requests)
    ]
    for r in reqs:
        srv.submit(r)
    t0 = time.perf_counter()
    srv.run_until_drained()
    dt = time.perf_counter() - t0
    done = sum(r.done for r in reqs)
    toks = sum(len(r.out) for r in reqs)
    lats = sorted(r.latency_s for r in reqs if r.done)
    lat_txt = (f", request latency p50 {lats[len(lats) // 2]:.2f}s "
               f"p99 {lats[int(len(lats) * 0.99)]:.2f}s" if lats else "")
    print(f"served {done}/{len(reqs)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s, {srv.steps} decode steps, "
          f"{srv.prefills} prefills, "
          f"batch occupancy {srv.occupancy * args.slots:.2f}/{args.slots}"
          f"{lat_txt})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default=None)
    ap.add_argument("--sparse", default=None, metavar="MATRIX",
                    help="serve autotuned SpMV over this suite matrix "
                         "instead of an LM")
    ap.add_argument("--fleet", default=None, metavar="M1,M2,...",
                    help="serve several suite matrices as SparseFleet "
                         "tenants (transfer-tuned admission + background "
                         "retune)")
    ap.add_argument("--stats-json", default=None, metavar="PATH",
                    help="write the run's stats summary as JSON "
                         "(EngineStats.summary() / FleetStats.summary())")
    ap.add_argument("--retune-wait-s", type=float, default=60.0,
                    help="--fleet: how long to wait for background retunes "
                         "before reporting (0 = don't wait)")
    ap.add_argument("--scale", type=float, default=1 / 64,
                    help="suite matrix scale for --sparse")
    ap.add_argument("--k-buckets", default="1,4,16,64",
                    help="tuned batch widths for the sparse engine")
    ap.add_argument("--shards", type=int, default=1,
                    help="row-partition the matrix and dispatch shards "
                         "under one batched vmap (core.distributed)")
    ap.add_argument("--mesh-shards", type=int, default=1,
                    help="serve over a real device mesh: shard A over a 1-D "
                         "mesh axis and tune a collective schedule "
                         "(allgather/ring) per k-bucket")
    ap.add_argument("--max-wait-ms", type=float, default=0.0,
                    help="admission control: dispatch a partial bucket once "
                         "its oldest request has waited this long "
                         "(0 = dispatch immediately)")
    ap.add_argument("--async-depth", type=int, default=2,
                    help="in-flight dispatch window (0 = fully synchronous; "
                         "2 = double-buffered: batch t+1 assembles while "
                         "batch t computes)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission: cap the pending queue at this "
                         "many requests (per tenant under --fleet; "
                         "0 = unbounded, the pre-PR-10 behavior)")
    ap.add_argument("--overload-policy", default="reject",
                    choices=("reject", "shed-oldest", "block"),
                    help="what submit() does at a full queue: reject fast "
                         "with a typed OverloadError, evict the oldest "
                         "queued request, or block (bounded) for space")
    ap.add_argument("--shed-after-ms", type=float, default=0.0,
                    help="deadline-aware shedding: fail queued requests "
                         "typed (DeadlineExceededError) once they have "
                         "waited this long at a dispatch boundary "
                         "(0 = never shed)")
    ap.add_argument("--brownout", action="store_true",
                    help="arm a BrownoutController (default watermarks): "
                         "under sustained pressure serving degrades "
                         "gracefully (widest-bucket dispatch, paused "
                         "retune/repair, SHED refusals) and recovers")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args()
    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.fleet is not None:
        serve_fleet(args)
        return
    if args.sparse is not None:
        serve_sparse(args)
        return
    if args.arch is None:
        ap.error("one of --arch, --sparse or --fleet is required")
    serve_lm(args)


if __name__ == "__main__":
    main()
