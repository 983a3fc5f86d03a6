"""Paper Fig 7: strong scaling of SpMV application bandwidth.

The Phi sweep (cores x threads) maps to shard-count scaling of the
distributed SpMM.  Two parts:

  model: per-shard x-traffic (allgather vs on-demand) for 1..64 shards —
         the distributed version of Fig 7's saturation analysis;
  measured: ring vs allgather SpMM on 8 fake CPU devices (a subprocess
         held to the CPU, so the benchmark process keeps its own device;
         a failing child fails the run).
"""
import os
import subprocess
import sys
import textwrap

from repro.core.traffic import shard_vector_access
from .common import row, suite

SCALE = 1 / 64
MATS = ["cant", "webbase-1M", "mesh_2048"]


def main(lines: list):
    mats = suite(SCALE)
    for name in MATS:
        a = mats[name]
        for p in (2, 8, 32):
            s = shard_vector_access(a, p)
            lines.append(row(
                f"fig7_model_{name}_p{p}", 0.0,
                f"allgatherB={s['allgather_bytes']:.0f};"
                f"ondemandB={s['ondemand_bytes']:.0f};headroom={s['ratio']:.2f}"))
    out = _measure_8dev()
    lines.extend(out)


def _measure_8dev():
    code = textwrap.dedent("""
        import time, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import csr_from_dense
        from repro.core.formats import CSRMatrix
        from repro.core.partition import stack_csr_shards
        from repro.core.compat import make_mesh
        from repro.core.distributed import allgather_spmm
        from repro.data.suite import generate
        a = generate("cant", scale=1/64)
        n = a.shape[0] - a.shape[0] % 8
        for P_ in (2, 4, 8):
            mesh = make_mesh((P_,), ("x",), devices=jax.devices()[:P_])
            bounds = np.linspace(0, n, P_ + 1).astype(int)
            shards = []
            for s in range(P_):
                lo, hi = bounds[s], bounds[s+1]
                ip = (a.indptr[lo:hi+1] - a.indptr[lo]).astype(a.indptr.dtype)
                sl = slice(a.indptr[lo], a.indptr[hi])
                shards.append(CSRMatrix((hi-lo, a.shape[1]), ip,
                              a.indices[sl].copy(), a.data[sl].copy()))
            st = {k: jax.device_put(jnp.asarray(v), NamedSharding(mesh, P("x")))
                  for k, v in stack_csr_shards(shards).items() if k != "n_rows"}
            X = jax.device_put(
                jnp.asarray(np.random.default_rng(0).standard_normal(
                    (a.shape[1], 8)).astype(np.float32))[:n//P_*P_].reshape(n//P_*P_, 8)[:n],
                NamedSharding(mesh, P("x")))
            def run():
                return allgather_spmm(mesh, "x", st, X)
            run(); jax.block_until_ready(run())
            ts = []
            for _ in range(5):
                t0 = time.perf_counter(); jax.block_until_ready(run())
                ts.append(time.perf_counter() - t0)
            t = float(np.median(ts))
            gf = 2 * a.nnz * 8 / t / 1e9
            print(f"fig7_measured_cant_p{P_},{t*1e6:.1f},{gf:.2f}GF")
    """)
    env = dict(os.environ)
    # The child measures 8 fake CPU devices.  It must never reach for the
    # accelerator: a parent that has touched JAX holds the chip, and a
    # child that asks for it then fails or hangs.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(
            f"fig7 8-device child failed (rc={out.returncode}):\n"
            f"{out.stderr[-2000:]}"
        )
    return [l for l in out.stdout.splitlines() if l.startswith("fig7")]
