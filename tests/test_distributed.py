"""Distributed paths under 8 fake devices (subprocess so the main pytest
process keeps its single-device jax initialization)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_ring_and_allgather_spmm_match_dense():
    print(run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import csr_from_dense
        from repro.core.formats import CSRMatrix
        from repro.core.partition import grid_2d, stack_csr_shards
        from repro.core.distributed import allgather_spmm, ring_spmm
        from repro.core.compat import make_mesh
        mesh = make_mesh((4,), ("x",))
        rng = np.random.default_rng(2)
        n, k = 64, 8
        d = ((rng.random((n, n)) < 0.1) * rng.standard_normal((n, n))).astype(np.float32)
        a = csr_from_dense(d)
        X = rng.standard_normal((n, k)).astype(np.float32)
        bounds = np.arange(0, n + 1, 16)
        shards = []
        for s in range(4):
            lo, hi = bounds[s], bounds[s+1]
            ip = (a.indptr[lo:hi+1] - a.indptr[lo]).astype(a.indptr.dtype)
            sl = slice(a.indptr[lo], a.indptr[hi])
            shards.append(CSRMatrix((hi-lo, n), ip, a.indices[sl].copy(), a.data[sl].copy()))
        stacked = {kk: jax.device_put(jnp.asarray(v), NamedSharding(mesh, P("x")))
                   for kk, v in stack_csr_shards(shards).items() if kk != "n_rows"}
        Xs = jax.device_put(jnp.asarray(X), NamedSharding(mesh, P("x")))
        Y = np.asarray(allgather_spmm(mesh, "x", stacked, Xs)).reshape(n, k)
        assert np.allclose(Y, d @ X, atol=1e-4), "allgather mismatch"
        grid = grid_2d(a, (4, 4))
        slabs = [stack_csr_shards(grid[i]) for i in range(4)]
        maxr = max(s["indptr"].shape[1] for s in slabs) - 1
        maxn = max(s["indices"].shape[1] for s in slabs)
        def pad(s):
            P_, r1 = s["indptr"].shape
            ip = np.zeros((P_, maxr + 1), s["indptr"].dtype); ip[:, :r1] = s["indptr"]; ip[:, r1:] = s["indptr"][:, -1:]
            idx = np.zeros((P_, maxn), s["indices"].dtype); idx[:, :s["indices"].shape[1]] = s["indices"]
            dat = np.zeros((P_, maxn), s["data"].dtype); dat[:, :s["data"].shape[1]] = s["data"]
            return {"indptr": ip, "indices": idx, "data": dat}
        gs = {kk: np.stack([pad(s)[kk] for s in slabs]) for kk in ("indptr","indices","data")}
        gd = {kk: jax.device_put(jnp.asarray(v), NamedSharding(mesh, P("x"))) for kk, v in gs.items()}
        Yr = np.asarray(ring_spmm(mesh, "x", gd, Xs)).reshape(-1, k)[:n]
        assert np.allclose(Yr, d @ X, atol=1e-4), "ring mismatch"
        print("distributed spmm OK")
    """))


def test_ef_compressed_psum_reduces_and_feeds_back_error():
    print(run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp, functools
        from jax.sharding import PartitionSpec as P
        from repro.core.compat import make_mesh, shard_map
        from repro.optim.compress import ef_compressed_psum
        mesh = make_mesh((8,), ("d",))
        @functools.partial(shard_map, mesh=mesh, in_specs=(P("d"), P("d")),
                           out_specs=(P("d"), P("d")))
        def allred(g, e):
            out, e2 = ef_compressed_psum(g[0], e[0], "d")
            return out[None], e2[None]
        rng = np.random.default_rng(0)
        g = rng.standard_normal((8, 128)).astype(np.float32)
        e = np.zeros((8, 128), np.float32)
        out, err = allred(jnp.asarray(g), jnp.asarray(e))
        true = g.sum(axis=0)
        got = np.asarray(out)[0]
        rel = np.abs(got - true).max() / (np.abs(true).max() + 1e-9)
        assert rel < 0.05, f"int8 allreduce too lossy: {rel}"
        # error feedback: the residual equals what quantization dropped
        assert np.abs(np.asarray(err)).max() > 0
        print("ef psum OK rel", rel)
    """))


def test_sharded_train_step_on_2x4_mesh():
    """End-to-end pjit train step on a (data=2, model=4) mesh."""
    print(run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.lm import ModelConfig, init_model
        from repro.models.common import default_rules, set_active_rules
        from repro.optim.adamw import OptimConfig, adamw_init
        from repro.runtime.trainer import make_train_step, shardings_for
        from repro.launch.shardspecs import param_shardings, batch_shardings
        from repro.core.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = default_rules(False)
        set_active_rules(rules)
        cfg = ModelConfig(arch_id="t", family="dense", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=4, d_ff=128, vocab=512,
                          dtype=jnp.float32, remat="none", attn_chunk=16)
        params, axes = init_model(cfg, 0)
        shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        p_sh = param_shardings(mesh, rules, axes, shapes)
        params = jax.tree.map(jax.device_put, params, p_sh)
        opt_cfg = OptimConfig()
        opt = adamw_init(params, opt_cfg)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, 512, (4, 32)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, 512, (4, 32)), jnp.int32)}
        b_shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
        b_sh = batch_shardings(mesh, cfg, b_shapes)
        batch = jax.tree.map(jax.device_put, batch, b_sh)
        step = jax.jit(make_train_step(cfg, opt_cfg, 2), donate_argnums=(0, 1))
        with mesh:
            p2, o2, m = step(params, opt, batch)
        assert np.isfinite(float(m["loss"])), m
        # compare against single-device reference
        cfg2 = cfg
        params_ref, _ = init_model(cfg2, 0)
        opt_ref = adamw_init(params_ref, opt_cfg)
        from repro.runtime.trainer import make_train_step as mts
        batch_host = jax.tree.map(lambda x: jax.device_put(np.asarray(x), jax.devices()[0]), batch)
        p_ref, _, m_ref = mts(cfg2, opt_cfg, 2)(params_ref, opt_ref, batch_host)
        assert abs(float(m["loss"]) - float(m_ref["loss"])) < 1e-3, (float(m["loss"]), float(m_ref["loss"]))
        print("sharded train step OK", float(m["loss"]))
    """))
