"""The roofline's HLO analyzer: loop multiplication + collective accounting."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import analyze_hlo, _parse_op_line


def test_scan_flops_match_unrolled():
    def f_scan(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        return jax.lax.scan(body, x, w)[0]

    def f_unroll(x, w):
        c = x
        for i in range(8):
            c = jnp.tanh(c @ w[i])
        return c

    x = jnp.zeros((64, 64))
    w = jnp.zeros((8, 64, 64))
    cost_s = analyze_hlo(jax.jit(f_scan).lower(x, w).compile().as_text(), 1)
    cost_u = analyze_hlo(jax.jit(f_unroll).lower(x, w).compile().as_text(), 1)
    true_dot_flops = 8 * 2 * 64 ** 3
    assert abs(cost_s.flops - cost_u.flops) / cost_u.flops < 0.05
    assert cost_s.flops >= true_dot_flops
    assert cost_s.flops < true_dot_flops * 1.2


def test_nested_scan_multiplies():
    def f(x, w):
        def outer(c, wi):
            def inner(c2, _):
                return jnp.tanh(c2 @ wi), None
            c2, _ = jax.lax.scan(inner, c, jnp.arange(3))
            return c2, None
        return jax.lax.scan(outer, x, w)[0]

    x = jnp.zeros((32, 32))
    w = jnp.zeros((4, 32, 32))
    cost = analyze_hlo(jax.jit(f).lower(x, w).compile().as_text(), 1)
    true_flops = 4 * 3 * 2 * 32 ** 3
    assert cost.flops >= true_flops and cost.flops < true_flops * 1.3


def test_parse_op_line_tuple_with_comments():
    line = ('  %while.30 = (s32[], f32[4,2]{1,0}, /*index=5*/f32[2,4]{1,0}) '
            'while(%tuple.1), condition=%cond.1, body=%body.1')
    parsed = _parse_op_line(line)
    assert parsed is not None
    name, type_str, op, args, attrs = parsed
    assert name == "%while.30" and op == "while"
    assert "condition=%cond.1" in attrs


def test_collective_bytes_under_spmd():
    code = """
        import jax, jax.numpy as jnp, sys
        sys.path.insert(0, {src!r})
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.core.compat import make_mesh
        mesh = make_mesh((8,), ("model",))
        w_sh = NamedSharding(mesh, P(None, "model"))
        x_sh = NamedSharding(mesh, P(None, None))
        def f(x, w):
            return (x @ w) @ w.T
        comp = jax.jit(f, in_shardings=(x_sh, w_sh), out_shardings=x_sh).lower(
            jax.ShapeDtypeStruct((64, 512), jnp.float32),
            jax.ShapeDtypeStruct((512, 512), jnp.float32)).compile()
        c = analyze_hlo(comp.as_text(), 8)
        exp_flops = 2 * 64 * 512 * 512 / 8 * 2
        assert abs(c.flops - exp_flops) / exp_flops < 0.05, c.flops
        exp_ar = 2 * (7 / 8) * 64 * 512 * 4
        assert abs(c.collective_bytes - exp_ar) / exp_ar < 0.05, c.collective_bytes
        print("OK", c.flops, c.collective_bytes)
    """
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code.format(src=src))],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
